"""Filtered search: a per-query label predicate checked inside the beam,
against the plain brute-force reference (``eval.filtered_ground_truth``),
and the label words the streaming store carries with each row."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import eval as E
from repro.core import rnn_descent as rd
from repro.core import search as S

N, D, NQ, BITS = 1000, 16, 64, 8
BUILD = dict(s=8, r=24, t1=2, t2=3, capacity=32, chunk=256)
RECALL_FLOOR = 0.9   # recall@10 against the exact filtered top 10
TOPK = 10


def _cfg(metric="l2", **kw):
    return S.SearchConfig(**{**dict(l=32, k=24, max_iters=512, metric=metric,
                                    topk=TOPK), **kw})


@functools.lru_cache(maxsize=None)
def _corpus(metric: str, seed: int):
    """Gaussian corpus and queries, its graph, and per row a random bitset
    of BITS labels (each bit set with probability 1/2)."""
    kx, kq, kb = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (N, D), jnp.float32)
    q = jax.random.normal(kq, (NQ, D), jnp.float32)
    g = rd.build(x, rd.RNNDescentConfig(metric=metric, **BUILD), kb)
    rng = np.random.default_rng(seed)
    labels = ((rng.random((N, BITS)) < 0.5) @ (1 << np.arange(BITS))
              ).astype(np.uint32)
    return x, q, g, labels


def _masks(seed: int, bits: int) -> np.ndarray:
    rng = np.random.default_rng(1000 + seed)
    return np.array([np.sum(1 << rng.choice(BITS, bits, replace=False))
                     for _ in range(NQ)], np.uint32)


def _search(x, g, q, labels, masks, cfg, **kw):
    ep = S.default_entry_point(x, cfg.metric, valid=kw.get("valid"))
    ids, dists, stats = S.search_tiled(
        x, g, q, ep, cfg, tile_b=32, labels=jnp.asarray(labels),
        filters=jnp.asarray(masks), with_stats=True, **kw)
    return np.asarray(ids), np.asarray(dists), stats


def _recall(ids, ref_ids) -> float:
    return float(np.mean([len(np.intersect1d(a[a >= 0], b[b >= 0]))
                          / max(int(np.sum(b >= 0)), 1)
                          for a, b in zip(ids, ref_ids)]))


def _assert_sound(x, q, labels, masks, ids, dists, metric, valid=None):
    """Every returned row passes (and is live), lists are sorted and
    duplicate-free, and each distance is the pair's float64 distance."""
    for i in range(ids.shape[0]):
        got = ids[i][ids[i] >= 0]
        assert np.all((labels[got] & masks[i]) == masks[i]), i
        if valid is not None:
            assert np.all(np.asarray(valid)[got]), i
        assert len(np.unique(got)) == len(got), i
        assert np.all(np.diff(dists[i][ids[i] >= 0]) >= 0), i
        assert np.all(np.isinf(dists[i][ids[i] < 0])), i
        one = np.zeros(N, np.uint32)          # the pair's reference distance
        one[got] = 1
        ref_d, ref_i = E.filtered_ground_truth(x, q[i:i + 1], one,
                                               np.ones(1, np.uint32),
                                               k=len(got), metric=metric)
        ref = dict(zip(ref_i[0].tolist(), ref_d[0].tolist()))
        want = np.array([ref[j] for j in got.tolist()])
        gap = np.abs(dists[i][ids[i] >= 0] - want) / np.maximum(
            np.abs(want), 1.0)
        assert np.all(gap <= 1e-5), (i, gap.max())


# ------------------------------------------------ against the reference
@pytest.mark.parametrize("bits", [1, 2, 4])
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("metric", ["l2", "ip", "cos"])
def test_filtered_matches_reference(metric, seed, bits):
    """Masks of 1, 2 and 4 labels pass about 1/2, 1/4 and 1/16 of the rows.
    The beam keeps walking through rows that fail, so recall@10 holds
    against the exact filtered neighbours at every selectivity."""
    x, q, g, labels = _corpus(metric, seed)
    masks = _masks(seed, bits)
    cfg = _cfg(metric)
    ids, dists, stats = _search(x, g, q, labels, masks, cfg)
    _assert_sound(x, q, labels, masks, ids, dists, metric)
    _, ref_ids = E.filtered_ground_truth(x, q, labels, masks, TOPK, metric)
    assert _recall(ids, ref_ids) >= RECALL_FLOOR
    assert int(stats["capped"]) == 0
    assert 0 < int(stats["work_max"]) < cfg.max_iters
    assert 0 < int(stats["filter_passed"]) < int(stats["filter_scored"])


def test_all_pass_mask_meets_recall_floor():
    """The empty mask passes every row: the filtered traversal then
    answers the plain k-NN query."""
    x, q, g, labels = _corpus("l2", 0)
    masks = np.zeros(NQ, np.uint32)
    ids, dists, stats = _search(x, g, q, labels, masks, _cfg())
    _assert_sound(x, q, labels, masks, ids, dists, "l2")
    _, ref_ids = E.filtered_ground_truth(x, q, labels, masks, TOPK)
    assert _recall(ids, ref_ids) >= RECALL_FLOOR
    assert int(stats["filter_passed"]) == int(stats["filter_scored"])


def test_tombstones_and_predicate_combine():
    """A result must be live and pass: tombstoned rows (``valid=``) are
    traversed but never returned, whatever their labels."""
    x, q, g, labels = _corpus("l2", 1)
    masks = _masks(1, 2)
    valid = jnp.asarray(np.random.default_rng(7).random(N) > 0.3)
    ids, dists, _ = _search(x, g, q, labels, masks, _cfg(), valid=valid)
    _assert_sound(x, q, labels, masks, ids, dists, "l2", valid=valid)
    _, ref_ids = E.filtered_ground_truth(x, q, labels, masks, TOPK,
                                         valid=np.asarray(valid))
    assert _recall(ids, ref_ids) >= RECALL_FLOOR


def test_filtered_search_matches_search_tiled_pallas_and_mesh():
    """``search`` and ``search_tiled`` agree on a filtered query; the fused
    Pallas kernel and a one-device query mesh return the same ids."""
    from repro.launch.mesh import make_mesh

    x, q, g, labels = _corpus("l2", 2)
    masks = _masks(2, 2)
    ids, dists, _ = _search(x, g, q, labels, masks, _cfg())
    ep = S.default_entry_point(x, "l2")
    i2, d2 = S.search(x, g, q, ep, _cfg(), labels=jnp.asarray(labels),
                      filters=jnp.asarray(masks))
    np.testing.assert_array_equal(ids, np.asarray(i2))
    np.testing.assert_array_equal(dists, np.asarray(d2))
    i3, _, _ = _search(x, g, q, labels, masks, _cfg(use_pallas=True))
    np.testing.assert_array_equal(ids, i3)
    i4, d4, st4 = _search(x, g, q, labels, masks, _cfg(),
                          mesh=make_mesh((1,), ("data",)))
    np.testing.assert_array_equal(ids, i4)
    np.testing.assert_array_equal(dists, d4)


def test_dense_visited_filtered_matches_reference():
    x, q, g, labels = _corpus("l2", 0)
    masks = _masks(0, 4)
    ids, dists, stats = _search(x, g, q, labels, masks,
                                _cfg(visited="dense"))
    _assert_sound(x, q, labels, masks, ids, dists, "l2")
    _, ref_ids = E.filtered_ground_truth(x, q, labels, masks, TOPK)
    assert _recall(ids, ref_ids) >= RECALL_FLOOR
    assert int(stats["visited_lost"]) == 0


def test_beam_cut_short_loses_recall_and_counts_capped_lanes():
    x, q, g, labels = _corpus("l2", 0)
    masks = _masks(0, 4)
    ids, _, stats = _search(x, g, q, labels, masks, _cfg(max_iters=4))
    _, ref_ids = E.filtered_ground_truth(x, q, labels, masks, TOPK)
    assert _recall(ids, ref_ids) < 0.5
    assert int(stats["capped"]) == NQ
    assert int(stats["work_max"]) == 4


def test_small_table_stays_sound():
    """A table far under the one sized from ``max_iters`` x k loses
    inserts (a full window, or two fresh candidates of a lane in one bucket
    in one step). A lost vertex may be scored and expanded again, but the
    exact dedup against both lists keeps every result list sound."""
    x, q, g, labels = _corpus("l2", 1)
    masks = _masks(1, 2)
    small = _cfg(slots=4096)
    assert S.resolve_slots(small) < S.resolve_slots(_cfg())
    ids, dists, stats = _search(x, g, q, labels, masks, small)
    assert int(stats["visited_lost"]) > 0
    _assert_sound(x, q, labels, masks, ids, dists, "l2")
    _, ref_ids = E.filtered_ground_truth(x, q, labels, masks, TOPK)
    assert _recall(ids, ref_ids) >= RECALL_FLOOR


# ------------------------------------------ the unfiltered program stays
def _jaxpr(x, g, q, labels, masks=None, pass_labels=True):
    cfg = _cfg(max_iters=16)

    def f(x, g, q, lab, m):
        return S.search_tiled(x, g, q, 0, cfg, tile_b=32, with_stats=True,
                              labels=lab if pass_labels else None,
                              filters=m)
    return str(jax.make_jaxpr(f)(x, g, q, jnp.asarray(labels), masks))


def test_unfiltered_program_has_no_filter_ops():
    """``filters=None`` is a static branch: handing over labels changes
    nothing in the traced program, which never reads them (the label
    array appears once, as the unused argument), and holds one top_k in
    its loop body (the beam's) where the filtered program merges two
    lists."""
    x, q, g, labels = _corpus("l2", 0)
    plain = _jaxpr(x, g, q, labels)
    assert _jaxpr(x, g, q, labels, pass_labels=False) == plain
    assert plain.count("u32[1000]") == 1
    filtered = _jaxpr(x, g, q, labels, jnp.asarray(_masks(0, 1)))
    assert filtered.count("u32[1000]") > 1
    assert filtered.count("top_k") == plain.count("top_k") + 2


def test_obs_counts_filter_work():
    from repro.obs import metrics
    from repro.obs import trace as T

    x, q, g, labels = _corpus("l2", 0)
    metrics.REGISTRY.reset()
    with T.enabled_scope():
        *_, st = _search(x, g, q, labels, _masks(0, 2), _cfg())
    snap = metrics.REGISTRY.snapshot()
    metrics.REGISTRY.reset()

    def total(name):
        return sum(s["value"] for s in snap[name]["samples"])

    assert total("search_filter_scored_total") == int(st["filter_scored"])
    assert total("search_filter_passed_total") == int(st["filter_passed"])
    assert total("search_lane_work_total") == int(st["work"])


# ----------------------------------------------- what is not supported
def test_unsupported_combinations_raise():
    from repro.launch.mesh import make_mesh
    from repro.quant import Quantization, encode_corpus

    x, q, g, labels = _corpus("l2", 0)
    lab, masks = jnp.asarray(labels), jnp.asarray(_masks(0, 1))
    cfg = _cfg()
    with pytest.raises(ValueError, match="labels="):
        S.search_tiled(x, g, q, 0, cfg, filters=masks)
    with pytest.raises(ValueError, match="corpus"):
        S.search_tiled(x, g, q, 0, cfg, labels=lab, filters=masks,
                       mesh=make_mesh((1,), ("rows",)), shard="corpus")
    qcfg = _cfg(quant=Quantization(mode="int8"))
    with pytest.raises(ValueError, match="quant"):
        S.search_tiled(x, g, q, 0, qcfg, labels=lab, filters=masks,
                       qx=encode_corpus(x, qcfg.quant))
    with pytest.raises(ValueError, match="quant"):
        S.search(x, g, q, 0, qcfg, labels=lab, filters=masks)
    with pytest.raises(ValueError, match="labels has shape"):
        S.search_tiled(x, g, q, 0, cfg, labels=lab[:10], filters=masks)
    with pytest.raises(ValueError, match="filters has shape"):
        S.search_tiled(x, g, q, 0, cfg, labels=lab, filters=masks[:3])


def test_serving_frontend_refuses_filters():
    from repro.serving import ServingFrontend
    from repro.streaming import StreamingANN, StreamingConfig

    x, q, _, labels = _corpus("l2", 0)
    ann = StreamingANN.from_corpus(
        x[:200], StreamingConfig(build=rd.RNNDescentConfig(**BUILD),
                                 seed_k=16),
        key=jax.random.PRNGKey(0), labels=labels[:200])
    fe = ServingFrontend(ann)
    with pytest.raises(ValueError, match="filtered"):
        fe.submit(np.asarray(q[0]), filters=np.uint32(1))


# ----------------------------------------- labels in the streaming store
def test_streaming_store_keeps_labels_with_rows(tmp_path):
    """insert / delete / grow / compact / save-restore keep each row's
    label word with its vector, and ``StreamingANN.search(filters=)`` only
    returns live rows that pass."""
    from repro.streaming import StreamingANN, StreamingConfig

    x, _, _, labels = _corpus("l2", 1)
    n0 = 500
    ann = StreamingANN.from_corpus(
        x[:n0], StreamingConfig(build=rd.RNNDescentConfig(**BUILD),
                                seed_k=16),
        key=jax.random.PRNGKey(3), labels=labels[:n0])
    assert ann.store.labels.dtype == jnp.uint32
    assert ann.capacity == 512
    ids = ann.insert(x[n0:n0 + 100], labels=labels[n0:n0 + 100])  # grows
    assert ann.capacity == 1024
    row_vec = {int(r): i for r, i in zip(ids, range(n0, n0 + 100))}
    row_vec.update({i: i for i in range(n0)})
    gone = np.array([3, 77, int(ids[5]), int(ids[50])])
    ann.delete(gone)

    def check(store, rows_to_vec):
        lab = np.asarray(store.labels)
        xs = np.asarray(store.x)
        for r, i in rows_to_vec.items():
            assert lab[r] == labels[i]
            np.testing.assert_array_equal(xs[r], np.asarray(x[i]))
        free = ~np.asarray(store.occupied)
        assert np.all(lab[free] == 0)

    live = {r: i for r, i in row_vec.items() if r not in set(gone.tolist())}
    check(ann.store, live)
    masks = _masks(1, 2)[:40]
    cfg = _cfg()
    got, _ = ann.search(x[:40], cfg, filters=masks)
    got = np.asarray(got)
    assert not np.isin(got, gone).any()
    lab = np.asarray(ann.store.labels)
    for i, row in enumerate(got):
        row = row[row >= 0]
        assert np.all((lab[row] & masks[i]) == masks[i])

    remap = ann.compact()
    check(ann.store, {int(remap[r]): i for r, i in live.items()})
    ann.save(str(tmp_path))
    back = StreamingANN.restore(str(tmp_path))
    np.testing.assert_array_equal(np.asarray(back.store.labels),
                                  np.asarray(ann.store.labels))
    # rows inserted without labels carry 0: only the empty mask finds them
    new = ann.insert(x[900:904])
    assert np.all(np.asarray(ann.store.labels)[new] == 0)


def test_unlabelled_store_holds_no_label_array(tmp_path):
    """A store given no labels carries none through insert, grow, compact
    and a checkpoint; its first labelled insert gives it one, 0 in the rows
    that came before."""
    from repro.streaming import StreamingANN, StreamingConfig

    x, _, _, labels = _corpus("l2", 1)
    ann = StreamingANN.from_corpus(
        x[:500], StreamingConfig(build=rd.RNNDescentConfig(**BUILD),
                                 seed_k=16),
        key=jax.random.PRNGKey(3))
    assert ann.store.labels is None
    ann.insert(x[500:600])                                    # grows
    ann.delete(np.array([1, 2]))
    ann.compact()
    ann.save(str(tmp_path))
    assert StreamingANN.restore(str(tmp_path)).store.labels is None
    assert ann.store.labels is None
    ids = ann.insert(x[600:610], labels=labels[600:610])
    lab = np.asarray(ann.store.labels)
    np.testing.assert_array_equal(lab[ids], labels[600:610])
    assert lab.shape == (ann.capacity,)
    assert np.all(np.delete(lab, ids) == 0)
