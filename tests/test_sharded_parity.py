"""Sharded (shard_map) construction + serving vs the single-device oracles.

The contract under test (core/shard.py + core/search.py ``mesh=``): sharded
results are **exactly equal** — same int32 neighbor ids, same uint32
dist_keys, same flags — to the single-device build/search with the same
config. No tolerance, no canonicalization.

These tests run on whatever devices exist: under plain tier-1 (one CPU
device) they exercise the complete sharded code path — row padding,
destination-bucketed (n_pad/D, B) scatter blocks, the ring ppermute
exchange, the corpus-sharded beam — on a 1-device mesh; the CI mesh job
re-runs them with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so
the exchange really crosses 8 shards. The corpus size (700) is deliberately
not divisible by 2, 4, or 8, so multi-device runs always exercise the inert
row padding.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as G
from repro.core import nn_descent as nnd
from repro.core import nsg_style
from repro.core import rnn_descent as rd
from repro.core import search as S
from repro.core import shard
from repro.data.synthetic import VectorDatasetSpec, clustered_vectors
from repro.distributed import sharding as SH
from repro.launch.mesh import make_mesh

N = 700                    # 700 % 8 == 4: row padding always active at 8 dev
METRICS = ("l2", "ip", "cos")
KEY = jax.random.PRNGKey(1)


def _rnn_cfg(metric):
    return rd.RNNDescentConfig(s=8, r=16, t1=2, t2=2, capacity=24,
                               chunk=128, metric=metric)


def _nn_cfg(metric):
    return nnd.NNDescentConfig(k=16, s=8, iters=3, chunk=96, metric=metric)


def _nsg_cfg(metric):
    return nsg_style.NSGStyleConfig(r=8, c=24, metric=metric,
                                    knn=_nn_cfg(metric))


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((jax.device_count(),), ("data",))


@pytest.fixture(scope="module")
def corpus():
    x, q = clustered_vectors(
        jax.random.PRNGKey(0),
        VectorDatasetSpec("shard", n=N, d=24, n_queries=101, n_clusters=8),
    )
    return x, q


@pytest.fixture(scope="module")
def rnn_graph(corpus):
    x, _ = corpus
    return rd.build(x, _rnn_cfg("l2"), KEY)


def assert_graph_bitwise_equal(a: G.Graph, b: G.Graph):
    assert np.array_equal(np.asarray(a.neighbors), np.asarray(b.neighbors))
    # distances compared as uint32 dist_keys: bit-exact, inf-safe
    assert np.array_equal(np.asarray(G.dist_key(a.dists)),
                          np.asarray(G.dist_key(b.dists)))
    assert np.array_equal(np.asarray(a.flags), np.asarray(b.flags))


# ------------------------------------------------------------- construction
@pytest.mark.parametrize("metric", METRICS)
def test_rnn_descent_sharded_parity(corpus, mesh, metric):
    x, _ = corpus
    cfg = _rnn_cfg(metric)
    assert_graph_bitwise_equal(
        rd.build(x, cfg, KEY), rd.build(x, cfg, KEY, mesh=mesh))


@pytest.mark.parametrize("metric", METRICS)
def test_nn_descent_sharded_parity(corpus, mesh, metric):
    x, _ = corpus
    cfg = _nn_cfg(metric)
    assert_graph_bitwise_equal(
        nnd.build(x, cfg, KEY), nnd.build(x, cfg, KEY, mesh=mesh))


@pytest.mark.parametrize("metric", METRICS)
def test_nsg_style_sharded_parity(corpus, mesh, metric):
    x, _ = corpus
    cfg = _nsg_cfg(metric)
    assert_graph_bitwise_equal(
        nsg_style.build(x, cfg, KEY), nsg_style.build(x, cfg, KEY, mesh=mesh))


def test_divisible_row_count_parity(mesh):
    """n an exact multiple of the shard count: no padding path at all."""
    n = 16 * jax.device_count()
    x = jax.random.normal(jax.random.PRNGKey(3), (n, 16))
    cfg = rd.RNNDescentConfig(s=6, r=10, t1=2, t2=2, capacity=16, chunk=64)
    assert_graph_bitwise_equal(
        rd.build(x, cfg, KEY), rd.build(x, cfg, KEY, mesh=mesh))


def test_sharded_build_requires_bucketed_merge(corpus, mesh):
    x, _ = corpus
    cfg = rd.RNNDescentConfig(s=8, r=16, t1=2, t2=2, capacity=24, merge="sort")
    with pytest.raises(ValueError, match="bucketed"):
        rd.build(x, cfg, KEY, mesh=mesh)


def test_explicit_axis_mesh_rejected(corpus, rnn_graph):
    """jax.make_mesh defaults to Explicit axes under JAX 0.9; the sharded
    build and search say so instead of failing deep inside a reshape."""
    x, q = corpus
    explicit = jax.make_mesh((jax.device_count(),), ("data",))
    with pytest.raises(ValueError, match="launch.mesh.make_mesh"):
        rd.build(x, _rnn_cfg("l2"), KEY, mesh=explicit)
    with pytest.raises(ValueError, match="launch.mesh.make_mesh"):
        S.search_tiled(x, rnn_graph, q, 0, S.SearchConfig(), mesh=explicit)


def test_mesh_resolves_ann_axes(mesh):
    """RULES must route both ANN logical axes onto the mesh."""
    assert SH.axis_count(mesh, "rows") == jax.device_count()
    assert SH.axis_count(mesh, "queries") == jax.device_count()
    assert shard.row_axes(mesh) == ("data",)


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="the 8-shard exchange needs the CI mesh job "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
def test_exchange_really_crosses_eight_shards(mesh):
    assert shard.n_shards(mesh) == 8


# ------------------------------------------------------------------ serving
@pytest.mark.parametrize("visited", ("hashed", "dense"))
@pytest.mark.parametrize("use_pallas", (False, True))
def test_search_tiled_sharded_parity(corpus, mesh, rnn_graph, visited,
                                     use_pallas):
    """Sharded query-tile serving == unsharded, ids and dist bits, for both
    visited modes and both beam inner-loop implementations. The query count
    (101) divides neither tile_b nor the device count."""
    x, q = corpus
    cfg = S.SearchConfig(l=16, k=12, max_iters=48, topk=5,
                         visited=visited, use_pallas=use_pallas)
    ep = S.default_entry_point(x)
    ids_1, d_1 = S.search_tiled(x, rnn_graph, q, ep, cfg, tile_b=16)
    ids_m, d_m = S.search_tiled(x, rnn_graph, q, ep, cfg, tile_b=16,
                                mesh=mesh)
    assert np.array_equal(np.asarray(ids_1), np.asarray(ids_m))
    assert np.array_equal(np.asarray(G.dist_key(d_1)),
                          np.asarray(G.dist_key(d_m)))


def test_search_sharded_multi_entry(corpus, mesh, rnn_graph):
    x, q = corpus
    cfg = S.SearchConfig(l=16, k=12, max_iters=48, topk=3)
    eps = jnp.broadcast_to(
        S.default_entry_points(x, n_entries=3)[None, :], (q.shape[0], 3))
    ids_1, d_1 = S.search_tiled(x, rnn_graph, q, eps, cfg, tile_b=32)
    ids_m, d_m = S.search_tiled(x, rnn_graph, q, eps, cfg, tile_b=32,
                                mesh=mesh)
    assert np.array_equal(np.asarray(ids_1), np.asarray(ids_m))
    assert np.array_equal(np.asarray(G.dist_key(d_1)),
                          np.asarray(G.dist_key(d_m)))


def test_search_sharded_tiny_batch(corpus, mesh, rnn_graph):
    """Batch smaller than one tile per device: heavy pad, results intact."""
    x, q = corpus
    cfg = S.SearchConfig(l=8, k=8, max_iters=24, topk=2)
    qq = q[:3]
    ep = S.default_entry_point(x)
    ids_1, _ = S.search_tiled(x, rnn_graph, qq, ep, cfg, tile_b=64)
    ids_m, _ = S.search_tiled(x, rnn_graph, qq, ep, cfg, tile_b=64, mesh=mesh)
    assert np.array_equal(np.asarray(ids_1), np.asarray(ids_m))


def test_search_sharded_no_padding_blowup(corpus, mesh, rnn_graph):
    """The query-tile shrink: b=101 on D devices must not launch more
    (tiles x lanes x iters) than the single-device run, while the per-lane
    beam work (iterations of live lanes) stays bitwise identical."""
    x, q = corpus
    cfg = S.SearchConfig(l=16, k=12, max_iters=48, topk=5)
    ep = S.default_entry_point(x)
    *_, st_1 = S.search_tiled(x, rnn_graph, q, ep, cfg, tile_b=256,
                              with_stats=True)
    *_, st_m = S.search_tiled(x, rnn_graph, q, ep, cfg, tile_b=256,
                              mesh=mesh, with_stats=True)
    assert int(st_1["work"]) == int(st_m["work"])
    assert int(st_1["visited_lost"]) == int(st_m["visited_lost"])
    assert int(st_m["launched"]) <= int(st_1["launched"])
    # lanes bounded by one ceil-division tile per device
    d = jax.device_count()
    assert st_m["tiles"] * st_m["tile_lanes"] <= d * max(2, -(-101 // d))


# -------------------------------------------------- corpus-sharded serving
@pytest.mark.parametrize("visited", ("hashed", "dense"))
def test_search_corpus_sharded_parity(corpus, mesh, rnn_graph, visited):
    """shard="corpus" — x and adjacency rows partitioned over the mesh,
    frontier gathers routed through owner-contribute collectives — must be
    bitwise equal to the single-device beam: same ids, same uint32 dist
    bits, same per-lane work and lost visited-table inserts. The batch (101)
    divides neither the tile nor the device count."""
    x, q = corpus
    cfg = S.SearchConfig(l=16, k=12, max_iters=48, topk=5, visited=visited)
    ep = S.default_entry_point(x)
    ids_1, d_1, st_1 = S.search_tiled(x, rnn_graph, q, ep, cfg, tile_b=16,
                                      with_stats=True)
    ids_m, d_m, st_m = S.search_tiled(x, rnn_graph, q, ep, cfg, tile_b=16,
                                      mesh=mesh, shard="corpus",
                                      with_stats=True)
    assert np.array_equal(np.asarray(ids_1), np.asarray(ids_m))
    assert np.array_equal(np.asarray(G.dist_key(d_1)),
                          np.asarray(G.dist_key(d_m)))
    assert int(st_1["work"]) == int(st_m["work"])
    assert int(st_1["visited_lost"]) == int(st_m["visited_lost"])
    # no lane blowup: the super-tiles launch no more lanes than the
    # single-device tiling of the same batch
    assert st_m["tiles"] * st_m["tile_lanes"] <= st_1["tiles"] * st_1["tile_lanes"]


@pytest.mark.parametrize("mode", ("int8", "pq"))
def test_search_corpus_sharded_quant_parity(corpus, mesh, rnn_graph, mode):
    """Quantized scoring against row-sharded codes: int8 rows and pq codes
    live with their owner; scale/zero/codebooks replicate."""
    from repro.quant import Quantization, encode_corpus
    x, q = corpus
    quant = (Quantization(mode="int8") if mode == "int8"
             else Quantization(mode="pq", m=6))
    cfg = S.SearchConfig(l=16, k=12, max_iters=48, topk=5, quant=quant)
    qx = encode_corpus(x, quant)
    ep = S.default_entry_point(x)
    ids_1, d_1 = S.search_tiled(x, rnn_graph, q, ep, cfg, tile_b=16, qx=qx)
    ids_m, d_m = S.search_tiled(x, rnn_graph, q, ep, cfg, tile_b=16, qx=qx,
                                mesh=mesh, shard="corpus")
    assert np.array_equal(np.asarray(ids_1), np.asarray(ids_m))
    assert np.array_equal(np.asarray(G.dist_key(d_1)),
                          np.asarray(G.dist_key(d_m)))


def test_search_corpus_sharded_tiny_batch(corpus, mesh, rnn_graph):
    """b=3 on up to 8 devices: lane blocks floor at 2 so per-block scoring
    keeps batch >= 2 (XLA:CPU's batch-1 einsum rounds differently)."""
    x, q = corpus
    cfg = S.SearchConfig(l=8, k=8, max_iters=24, topk=2)
    ep = S.default_entry_point(x)
    ids_1, d_1 = S.search_tiled(x, rnn_graph, q[:3], ep, cfg, tile_b=64)
    ids_m, d_m = S.search_tiled(x, rnn_graph, q[:3], ep, cfg, tile_b=64,
                                mesh=mesh, shard="corpus")
    assert np.array_equal(np.asarray(ids_1), np.asarray(ids_m))
    assert np.array_equal(np.asarray(G.dist_key(d_1)),
                          np.asarray(G.dist_key(d_m)))


def test_search_corpus_sharded_multi_entry_and_valid(corpus, mesh, rnn_graph):
    """Multi-entry seeding + tombstone mask through the corpus-sharded path."""
    x, q = corpus
    cfg = S.SearchConfig(l=16, k=12, max_iters=48, topk=3)
    eps = jnp.broadcast_to(
        S.default_entry_points(x, n_entries=3)[None, :], (q.shape[0], 3))
    valid = jnp.arange(N) % 7 != 0
    ids_1, d_1 = S.search_tiled(x, rnn_graph, q, eps, cfg, tile_b=32,
                                valid=valid)
    ids_m, d_m = S.search_tiled(x, rnn_graph, q, eps, cfg, tile_b=32,
                                valid=valid, mesh=mesh, shard="corpus")
    assert np.array_equal(np.asarray(ids_1), np.asarray(ids_m))
    assert np.array_equal(np.asarray(G.dist_key(d_1)),
                          np.asarray(G.dist_key(d_m)))


def test_search_tiled_rejects_unknown_shard(corpus, mesh, rnn_graph):
    x, q = corpus
    cfg = S.SearchConfig(l=8, k=8, max_iters=8, topk=2)
    ep = S.default_entry_point(x)
    with pytest.raises(ValueError, match="unknown shard mode"):
        S.search_tiled(x, rnn_graph, q[:4], ep, cfg, tile_b=4, mesh=mesh,
                       shard="rows")
    with pytest.raises(ValueError, match="requires mesh"):
        S.search_tiled(x, rnn_graph, q[:4], ep, cfg, tile_b=4, shard="corpus")


def test_default_entry_points_rejects_oversized(corpus):
    x, _ = corpus
    with pytest.raises(ValueError, match="exceeds the corpus size"):
        S.default_entry_points(x, n_entries=N + 1)
