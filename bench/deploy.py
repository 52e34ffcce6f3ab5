"""A configuration file (``bench/configs/<config>.json``) made into the
program's objects and the deployment's data."""
from __future__ import annotations

from bench.datagen import clustered_vectors, seed_key


def build_config(config: dict):
    from repro.core.rnn_descent import RNNDescentConfig

    return RNNDescentConfig(metric=config["metric"], **config["build"])


def search_config(config: dict):
    from repro.core.search import SearchConfig

    return SearchConfig(metric=config["metric"], **config["search"])


def make_data(config: dict, seed: int, queries: int | None = None):
    """(key, x, q): the corpus and queries of the deployment, on the
    device, from ``seed``."""
    import jax

    key = seed_key(seed)
    g = config["generator"]
    x, q = clustered_vectors(key, config["rows"], config["dim"],
                             config["queries"] if queries is None else queries,
                             g["n_clusters"], g["cluster_std"])
    return key, *jax.block_until_ready((x, q))


def build_index(config: dict, seed: int):
    """(index, x, q): the deployment's corpus built into a ``StreamingANN``
    with the configuration's build, the corpus and the query set (on the
    device), all from ``seed``."""
    import jax

    from repro.streaming import StreamingANN, StreamingConfig

    key, x, q = make_data(config, seed)
    ann = StreamingANN.from_corpus(
        x, StreamingConfig(build=build_config(config)),
        key=jax.random.fold_in(key, 1))
    jax.block_until_ready(ann.store)
    return ann, x, q
