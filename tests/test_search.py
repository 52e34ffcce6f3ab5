"""Serving-path tests: hashed-visited beam search vs the dense-bitmask oracle,
the tiled driver, entry-point validation, and the visited-memory contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import eval as E
from repro.core import rnn_descent as rd
from repro.core import search as S


BUILD_CFG = dict(s=6, r=12, t1=2, t2=3, capacity=16, chunk=128)


def _corpus(metric="l2", seed=0, n=400, d=24, nq=24):
    key = jax.random.PRNGKey(seed)
    kx, kq = jax.random.split(key)
    x = jax.random.normal(kx, (n, d), jnp.float32)
    q = jax.random.normal(kq, (nq, d), jnp.float32)
    g = rd.build(x, rd.RNNDescentConfig(metric=metric, **BUILD_CFG),
                 jax.random.PRNGKey(seed + 1))
    return x, q, g


# ------------------------------------------------- hashed vs dense equivalence
@pytest.mark.parametrize("seed, metric, slots, probes", [
    *[pytest.param(seed, metric, None, 8, id=f"{seed}-{metric}")
      for seed in (0, 1) for metric in ("l2", "ip", "cos")],
    # under 128 slots one bucket row (W = slots) holds the lane's table
    pytest.param(0, "l2", 64, 8, id="0-l2-slots64"),
    # 16 slots probed twice: windows fill and inserts are lost
    pytest.param(1, "l2", 16, 2, id="1-l2-overflow"),
])
def test_hashed_matches_dense_oracle(seed, metric, slots, probes):
    """With a generous iteration budget the hashed table's only failure mode
    (lost insertions -> re-scoring) cannot change the converged beam, so
    results must match the exact dense bitmask bit-for-bit, with the same
    beam expansions."""
    x, q, g = _corpus(metric=metric, seed=seed)
    ep = S.default_entry_point(x, metric)
    base = dict(l=16, k=12, max_iters=128, metric=metric, topk=5)
    hashed = S.SearchConfig(visited="hashed", slots=slots, probes=probes,
                            **base)
    dense = S.SearchConfig(visited="dense", **base)
    ids_h, d_h = S.search(x, g, q, ep, hashed)
    ids_d, d_d = S.search(x, g, q, ep, dense)
    np.testing.assert_array_equal(np.asarray(ids_h), np.asarray(ids_d))
    np.testing.assert_allclose(np.asarray(d_h), np.asarray(d_d), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(d_h).view(np.uint32),
                                  np.asarray(d_d).view(np.uint32))
    *_, st_h = S.search_tiled(x, g, q, ep, hashed, tile_b=q.shape[0],
                              with_stats=True)
    *_, st_d = S.search_tiled(x, g, q, ep, dense, tile_b=q.shape[0],
                              with_stats=True)
    assert int(st_h["work"]) == int(st_d["work"])
    assert int(st_d["visited_lost"]) == 0
    if slots == 16:
        assert int(st_h["visited_lost"]) > 0


# ----------------------------------------------- bucket-row table, directly
_visited_lookup_insert = jax.jit(S._visited_lookup_insert,
                                 static_argnames="probes")


def _lookup_insert(table, ids, want=None, probes=8):
    ids = jnp.asarray(ids, jnp.int32)
    want = jnp.ones(ids.shape, bool) if want is None else jnp.asarray(want)
    return _visited_lookup_insert(table, ids, want, jnp.arange(ids.shape[0]),
                                  probes=probes)


def _table(lanes, slots):
    w = min(128, slots)
    return jnp.full((lanes * slots // w, w), -1, jnp.int32)


@pytest.mark.parametrize("slots, probes", [(256, 8), (64, 2), (8, 8)])
def test_visited_table_no_false_positive(slots, probes):
    """Inserted ids are found again unless their insert was lost; ids never
    inserted are never reported seen, however full the table gets."""
    rng = np.random.default_rng(slots)
    lanes, c = 4, 16
    pool = rng.permutation(1 << 20)[: 2 * 8 * c * lanes].reshape(2, lanes, -1)
    inserted, absent = pool[0], pool[1]
    table, lost = _table(lanes, slots), 0
    for step in range(8):
        ids = inserted[:, step * c:(step + 1) * c]
        seen, table, n_lost = _lookup_insert(table, ids, probes=probes)
        assert not np.asarray(seen).any()    # fresh ids: never seen before
        lost += int(np.sum(np.asarray(n_lost)))
    seen_in, _, _ = _lookup_insert(table, inserted, want=np.zeros(
        inserted.shape, bool), probes=probes)
    seen_out, _, _ = _lookup_insert(table, absent, want=np.zeros(
        absent.shape, bool), probes=probes)
    assert not np.asarray(seen_out).any()
    stored = np.asarray(table)
    assert int(np.asarray(seen_in).sum()) == int((stored >= 0).sum())
    assert int(np.asarray(seen_in).sum()) + lost == inserted.size


def test_visited_table_reinsert_found():
    table = _table(2, 4096)
    ids = [[7, 1 << 20], [12345, 3]]
    seen, table, lost = _lookup_insert(table, ids)
    assert not np.asarray(seen).any() and int(np.sum(lost)) == 0
    seen, table2, lost = _lookup_insert(table, ids)
    assert np.asarray(seen).all() and int(np.sum(lost)) == 0
    np.testing.assert_array_equal(np.asarray(table2), np.asarray(table))
    # lanes are separate tables: lane 1 has not seen lane 0's ids
    seen, _, _ = _lookup_insert(table, [[12345, 3], [7, 1 << 20]],
                                want=np.zeros((2, 2), bool))
    assert not np.asarray(seen).any()


def test_visited_table_full_window_drops_and_counts():
    """A probe window with no empty slot drops the insert, counts it lost
    and leaves the table as it was."""
    table = jnp.arange(100, 108, dtype=jnp.int32)[None, :]   # 1 lane, 8 slots
    seen, table2, lost = _lookup_insert(table, [[5, 6]], probes=3)
    assert not np.asarray(seen).any()
    assert np.asarray(lost).tolist() == [2]
    np.testing.assert_array_equal(np.asarray(table2), np.asarray(table))
    # a candidate that was already seen, or not wanted, is never lost
    seen, _, lost = _lookup_insert(table, [[100, 5]], want=[[True, False]],
                                   probes=8)
    assert np.asarray(seen).tolist() == [[True, False]]
    assert np.asarray(lost).tolist() == [0]


def test_visited_table_same_bucket_writes_keep_earlier_entries():
    """Two fresh ids landing in one bucket in one step: one row write wins,
    the other insert is counted lost, and no earlier entry is erased."""
    table = _table(1, 8)                                      # one bucket
    for earlier in (41, 42):
        _, table, lost = _lookup_insert(table, [[earlier]])
        assert np.asarray(lost).tolist() == [0]
    seen, table, lost = _lookup_insert(table, [[43, 44]])
    assert not np.asarray(seen).any()
    assert np.asarray(lost).tolist() == [1]
    seen, _, _ = _lookup_insert(table, [[41, 42, 43, 44]],
                                want=np.zeros((1, 4), bool))
    seen = np.asarray(seen)[0]
    assert seen[:2].all() and seen[2:].sum() == 1


def test_hashed_tiny_table_still_sorted_unique():
    """Even a deliberately undersized table (lots of lost insertions) must
    yield sorted, duplicate-free, valid top-k results."""
    x, q, g = _corpus()
    ep = S.default_entry_point(x)
    cfg = S.SearchConfig(l=16, k=12, max_iters=128, topk=8, slots=32, probes=2)
    ids, dists = S.search(x, g, q, ep, cfg)
    ids, dists = np.asarray(ids), np.asarray(dists)
    assert (ids >= 0).all()
    assert (np.diff(dists, axis=1) >= 0).all()
    for row in ids:
        assert len(set(row.tolist())) == len(row)


# ------------------------------------------------------------- tiled driver
def test_search_tiled_matches_search():
    x, q, g = _corpus(nq=50)
    ep = S.default_entry_point(x)
    cfg = S.SearchConfig(l=16, k=12, max_iters=128, topk=4)
    ids_full, d_full = S.search(x, g, q, ep, cfg)
    for tile_b in (16, 50, 64):  # padded, exact, oversized
        ids_t, d_t = S.search_tiled(x, g, q, ep, cfg, tile_b=tile_b)
        np.testing.assert_array_equal(np.asarray(ids_t), np.asarray(ids_full))
        np.testing.assert_allclose(np.asarray(d_t), np.asarray(d_full), rtol=1e-6)


def test_tiled_recall_close_to_oracle(small_dataset):
    """Acceptance: hashed recall@1 within 0.01 of the dense oracle at equal L."""
    x, q, gt = small_dataset
    g = rd.build(x, rd.RNNDescentConfig(s=8, r=24, t1=3, t2=4, capacity=32,
                                        chunk=256), jax.random.PRNGKey(1))
    ep = S.default_entry_point(x)
    base = dict(l=32, k=24, max_iters=128)
    r_h = E.recall_at_k(S.search_tiled(
        x, g, q, ep, S.SearchConfig(visited="hashed", **base), tile_b=32)[0], gt)
    r_d = E.recall_at_k(S.search(
        x, g, q, ep, S.SearchConfig(visited="dense", **base))[0], gt)
    assert abs(r_h - r_d) <= 0.01


# ------------------------------------------------------ entry-point handling
def test_entry_point_validation():
    x, q, g = _corpus(nq=8)
    cfg = S.SearchConfig(l=8, k=8, max_iters=32)
    with pytest.raises(ValueError):  # wrong-length 1-D: used to truncate silently
        S.search(x, g, q, jnp.zeros((5,), jnp.int32), cfg)
    with pytest.raises(ValueError):  # batch mismatch on 2-D
        S.search(x, g, q, jnp.zeros((5, 2), jnp.int32), cfg)
    with pytest.raises(ValueError):  # more seeds than beam slots
        S.search(x, g, q, jnp.zeros((8, 9), jnp.int32), cfg)
    with pytest.raises(ValueError):  # bogus rank
        S.search(x, g, q, jnp.zeros((8, 2, 2), jnp.int32), cfg)
    # accepted forms: scalar, (B,), (B, E)
    for ep in (jnp.int32(0), jnp.zeros((8,), jnp.int32), jnp.zeros((8, 4), jnp.int32)):
        ids, _ = S.search(x, g, q, ep, cfg)
        assert ids.shape == (8, 1)


def test_empty_query_batch():
    x, _, g = _corpus(nq=8)
    q0 = jnp.zeros((0, x.shape[1]), jnp.float32)
    cfg = S.SearchConfig(l=8, k=8, max_iters=16, topk=2)
    ids, dists = S.search_tiled(x, g, q0, jnp.int32(0), cfg, tile_b=64)
    assert ids.shape == (0, 2) and dists.shape == (0, 2)


def test_default_entry_points_distinct():
    x = jax.random.normal(jax.random.PRNGKey(0), (50, 8))
    for seed in range(5):
        eps = np.asarray(S.default_entry_points(
            x, n_entries=8, key=jax.random.PRNGKey(seed)))
        assert len(set(eps.tolist())) == 8, eps


def test_multi_entry_seeding():
    x, q, g = _corpus(nq=16)
    eps = S.default_entry_points(x, n_entries=4)
    assert eps.shape == (4,)
    eps_b = jnp.broadcast_to(eps[None, :], (16, 4))
    cfg = S.SearchConfig(l=16, k=12, max_iters=96, topk=4)
    ids, dists = S.search(x, g, q, eps_b, cfg)
    assert ids.shape == (16, 4)
    ids = np.asarray(ids)
    for row in ids:
        assert len(set(row.tolist())) == len(row)
    # duplicate seeds in a lane are inert, not duplicated results
    dup = jnp.zeros((16, 4), jnp.int32)
    ids2, _ = S.search(x, g, q, dup, cfg)
    for row in np.asarray(ids2):
        assert len(set(row.tolist())) == len(row)


def test_multi_entry_not_worse_than_single(small_dataset):
    x, q, gt = small_dataset
    g = rd.build(x, rd.RNNDescentConfig(s=8, r=24, t1=3, t2=4, capacity=32,
                                        chunk=256), jax.random.PRNGKey(1))
    cfg = S.SearchConfig(l=32, k=24, max_iters=128)
    ep1 = S.default_entry_point(x)
    eps = jnp.broadcast_to(S.default_entry_points(x, 4)[None, :], (q.shape[0], 4))
    r1 = E.recall_at_k(S.search(x, g, q, ep1, cfg)[0], gt)
    r4 = E.recall_at_k(S.search(x, g, q, eps, cfg)[0], gt)
    assert r4 >= r1 - 0.02


# --------------------------------------------------------- memory contract
def test_visited_bytes_independent_of_n():
    cfg = S.SearchConfig(l=32, k=16, max_iters=64)
    assert S.visited_state_bytes(cfg, n=1_000, lanes=256) == \
        S.visited_state_bytes(cfg, n=100_000_000, lanes=256)
    dense = S.SearchConfig(l=32, k=16, max_iters=64, visited="dense")
    assert S.visited_state_bytes(dense, n=200_000, lanes=256) > \
        S.visited_state_bytes(dense, n=1_000, lanes=256)


def test_resolve_slots_power_of_two():
    for l, k, it in [(8, 8, 16), (64, 32, 256), (128, 64, 512)]:
        slots = S.resolve_slots(S.SearchConfig(l=l, k=k, max_iters=it))
        assert slots & (slots - 1) == 0
        assert slots >= l + it * k  # holds every possible visited vertex
    assert S.resolve_slots(S.SearchConfig(slots=1024)) == 1024


def test_config_validation():
    # all config rejections are ValueError with a message (PR 3 turned the
    # old bare asserts into clear errors; full matrix in test_beam_score.py)
    with pytest.raises(ValueError, match="topk"):
        S.SearchConfig(l=8, topk=9)
    with pytest.raises(ValueError, match="visited"):
        S.SearchConfig(visited="bloom")
    with pytest.raises(ValueError, match="power of two"):
        S.SearchConfig(slots=1000)  # not a power of two


# ------------------------------------------------------- build regression
def test_build_jit_matches_build_second_seed():
    """build() vs build_jit() regression on a fresh seed/config (the serving
    path assumes either build produces the identical graph)."""
    x = jax.random.normal(jax.random.PRNGKey(11), (256, 16), jnp.float32)
    cfg = rd.RNNDescentConfig(s=5, r=10, t1=2, t2=2, capacity=12, chunk=64)
    g_eager = rd.build(x, cfg, jax.random.PRNGKey(12))
    g_scan = rd.build_jit(x, cfg, jax.random.PRNGKey(12))
    np.testing.assert_array_equal(np.asarray(g_eager.neighbors),
                                  np.asarray(g_scan.neighbors))
