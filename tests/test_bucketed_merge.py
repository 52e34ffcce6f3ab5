"""Scatter-bucketed merge vs. the sort oracle (graph.py merge="bucketed").

With ``n_buckets >= next_pow2(n)`` the bucket slot hash is injective, so the
bucketed path must reproduce the lexsort oracle *exactly* — neighbors, dists,
and flags — for every metric (including the negative-distance ``ip``). With
tiny buckets it may drop edges (collision losses) but must never corrupt a
row or violate a degree cap.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # degrades to skip without hypothesis

from repro.core import distances as D
from repro.core import graph as G

METRICS = ("l2", "ip", "cos")


def _canon(g):
    """Per-row canonical multiset of (dist, id, flag) — merge paths may order
    equal-distance entries differently, content must match."""
    nbrs, dists, flags = np.asarray(g.neighbors), np.asarray(g.dists), np.asarray(g.flags)
    return [
        sorted(
            (float(dists[i, j]), int(nbrs[i, j]), int(flags[i, j]))
            for j in range(nbrs.shape[1]) if nbrs[i, j] >= 0
        )
        for i in range(nbrs.shape[0])
    ]


def _check_row_invariant(g):
    nbrs, dists = np.asarray(g.neighbors), np.asarray(g.dists)
    for i in range(nbrs.shape[0]):
        valid = nbrs[i] >= 0
        k = valid.sum()
        assert valid[:k].all(), f"row {i}: valid entries not a prefix"
        assert np.all(np.isinf(dists[i, k:]))
        assert np.all(np.diff(dists[i, :k]) >= 0), f"row {i}: not sorted"
        assert len(set(nbrs[i, :k].tolist())) == k, f"row {i}: duplicate neighbor"
        assert nbrs[i, :k].max(initial=-1) < nbrs.shape[0]
        assert i not in nbrs[i, :k], f"row {i}: self loop"


def _rand_graph(key, x, m, metric):
    """Valid graph with real distances (dist is a function of (src, dst), as
    in the builders — required for oracle/bucketed dedup ties to agree) and a
    random NEW/OLD flag mix to exercise flag recovery."""
    n = x.shape[0]
    k1, k2 = jax.random.split(key)
    ids = jax.random.randint(k1, (n, m), -2, n, dtype=jnp.int32)
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    ids = jnp.where(ids == rows, -1, ids)
    ids = G.dedup_row_ids(jnp.where(ids < 0, -1, ids))
    dist = D.gather_dists(
        x, jnp.broadcast_to(rows, ids.shape).reshape(-1), ids.reshape(-1), metric
    ).reshape(n, m)
    flags = jax.random.randint(k2, (n, m), 0, 2).astype(jnp.uint8)
    return G.sort_rows(G.Graph(
        ids, jnp.where(ids >= 0, dist, jnp.inf), jnp.where(ids >= 0, flags, G.OLD)
    ))


def _setup(seed, metric, n=48, m=6, d=16, n_cand=150):
    key = jax.random.PRNGKey(seed)
    kx, kg, ks, kd = jax.random.split(key, 4)
    x = jax.random.normal(kx, (n, d))
    g = _rand_graph(kg, x, m, metric)
    src = jax.random.randint(ks, (n_cand,), -1, n, dtype=jnp.int32)
    dst = jax.random.randint(kd, (n_cand,), -1, n, dtype=jnp.int32)
    dist = D.gather_dists(x, src, dst, metric)
    return x, g, src, dst, dist


def test_dist_key_monotone_and_bijective():
    vals = np.array(
        [-np.inf, -3.4e38, -2.5, -1.0, -1e-20, -0.0, 0.0, 1e-20, 1e-3, 1.0,
         2.5, 1e10, 3.4e38, np.inf], np.float32)
    keys = np.asarray(G.dist_key(jnp.asarray(vals))).astype(np.uint64)
    assert np.all(np.diff(keys.astype(np.int64)) >= 0)
    strict = vals[:-1] < vals[1:]          # -0.0 == 0.0 may share order only
    assert np.all(np.diff(keys.astype(np.int64))[strict] > 0)
    back = np.asarray(G.key_dist(jnp.asarray(keys.astype(np.uint32))))
    assert np.array_equal(back.view(np.uint32), vals.view(np.uint32))


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_merge_candidates_matches_sort_oracle(metric, seed):
    _, g, src, dst, dist = _setup(seed, metric)
    out_s = G.merge_candidate_edges(g, src, dst, dist, merge="sort")
    out_b = G.merge_candidate_edges(g, src, dst, dist, merge="bucketed", n_buckets=64)
    _check_row_invariant(out_b)
    assert _canon(out_s) == _canon(out_b)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_add_reverse_matches_sort_oracle(metric, seed):
    _, g, _, _, _ = _setup(seed, metric)
    for r in (3, 8):
        out_s = G.add_reverse_edges(g, r, merge="sort")
        out_b = G.add_reverse_edges(g, r, merge="bucketed", n_buckets=64)
        _check_row_invariant(out_b)
        assert _canon(out_s) == _canon(out_b)


@pytest.mark.parametrize("metric", METRICS)
def test_merge_with_cap_matches_sort_oracle(metric):
    _, g, src, dst, dist = _setup(7, metric)
    out_s = G.merge_candidate_edges(g, src, dst, dist, cap=3, merge="sort")
    out_b = G.merge_candidate_edges(g, src, dst, dist, cap=3, merge="bucketed",
                                    n_buckets=64)
    assert _canon(out_s) == _canon(out_b)
    assert int(G.out_degrees(out_b).max()) <= 3


def test_existing_edge_beats_candidate_copy():
    """Re-offered existing edges must keep their stored flag and distance
    (paper Alg. 4: no insertion if the edge exists) — even when the candidate
    copy's distance is (numerically) smaller."""
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    g = _rand_graph(jax.random.PRNGKey(1), x, 4, "l2")
    nbrs = np.asarray(g.neighbors)
    i = next(i for i in range(16) if (nbrs[i] >= 0).any())
    j = int(nbrs[i][nbrs[i] >= 0][0])
    d_stored = float(np.asarray(g.dists)[i, 0])
    f_stored = int(np.asarray(g.flags)[i, 0])
    cand_d = jnp.asarray([d_stored * 0.5], jnp.float32)
    out = G.merge_candidate_edges(
        g, jnp.asarray([i], jnp.int32), jnp.asarray([j], jnp.int32), cand_d,
        merge="bucketed", n_buckets=16)
    row = list(np.asarray(out.neighbors)[i])
    assert j in row
    slot = row.index(j)
    assert int(np.asarray(out.flags)[i, slot]) == f_stored
    assert float(np.asarray(out.dists)[i, slot]) == d_stored


@pytest.mark.parametrize("n_buckets", [2, 4, 8])
def test_tiny_buckets_never_corrupt(n_buckets):
    """Overflowing buckets may *drop* candidates but must never break the row
    invariant, exceed a degree cap, or fabricate edges."""
    for seed in (0, 1):
        x, g, src, dst, dist = _setup(seed, "l2", n=32, m=6, n_cand=400)
        out = G.merge_candidate_edges(
            g, src, dst, dist, cap=4, merge="bucketed", n_buckets=n_buckets)
        _check_row_invariant(out)
        assert int(G.out_degrees(out).max()) <= 4
        rev = G.add_reverse_edges(g, 3, merge="bucketed", n_buckets=n_buckets)
        _check_row_invariant(rev)
        assert int(G.out_degrees(rev).max()) <= 3
        assert int(G.in_degrees(rev).max()) <= 3
        # every surviving edge of the reverse pass existed in E ∪ reverse(E)
        allowed = set()
        nbrs, dists = np.asarray(g.neighbors), np.asarray(g.dists)
        for u in range(g.n):
            for v, w in zip(nbrs[u], dists[u]):
                if v >= 0:
                    allowed.add((u, int(v))), allowed.add((int(v), u))
        out_n = np.asarray(rev.neighbors)
        for u in range(rev.n):
            for v in out_n[u][out_n[u] >= 0]:
                assert (u, int(v)) in allowed


def test_builders_bucketed_by_default():
    from repro.core import nn_descent as nnd
    from repro.core import nsg_style
    from repro.core import rnn_descent as rd

    assert rd.RNNDescentConfig().merge == "bucketed"
    assert nnd.NNDescentConfig().merge == "bucketed"
    assert nsg_style.NSGStyleConfig().merge == "bucketed"


@pytest.mark.parametrize("builder", ["rnn", "nnd"])
def test_build_bucketed_tracks_sort_oracle_recall(builder, small_dataset):
    """End-to-end: a bucketed build must serve recall within noise of the
    sort-oracle build on the same corpus."""
    from repro.core import eval as E
    from repro.core import nn_descent as nnd
    from repro.core import rnn_descent as rd
    from repro.core import search as S

    x, q, gt = small_dataset
    x, q, gt = x[:1000], q[:50], gt[:50]
    _, gt = E.ground_truth(x, q, k=1)
    recalls = {}
    for merge in ("sort", "bucketed"):
        if builder == "rnn":
            cfg = rd.RNNDescentConfig(s=8, r=16, t1=2, t2=3, capacity=24,
                                      chunk=256, merge=merge)
            g = rd.build(x, cfg, jax.random.PRNGKey(5))
        else:
            cfg = nnd.NNDescentConfig(k=16, s=8, iters=4, chunk=256, merge=merge)
            g = nnd.build(x, cfg, jax.random.PRNGKey(5))
        ep = S.default_entry_point(x)
        ids, _ = S.search(x, g, q, ep, S.SearchConfig(l=32, k=16, max_iters=128))
        recalls[merge] = E.recall_at_k(ids, gt)
    assert recalls["bucketed"] >= recalls["sort"] - 0.05, recalls


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(4, 24),
    m=st.integers(2, 8),
    n_cand=st.integers(1, 40),
    n_buckets=st.sampled_from([2, 8, 32]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_bucketed_merge_never_breaks_invariant(n, m, n_cand, n_buckets, seed):
    key = jax.random.PRNGKey(seed)
    kx, kg, ks, kd = jax.random.split(key, 4)
    x = jax.random.normal(kx, (n, 8))
    g = _rand_graph(kg, x, m, "l2")
    src = jax.random.randint(ks, (n_cand,), -1, n, dtype=jnp.int32)
    dst = jax.random.randint(kd, (n_cand,), -1, n, dtype=jnp.int32)
    dist = D.gather_dists(x, src, dst, "l2")
    out = G.merge_candidate_edges(g, src, dst, dist, merge="bucketed",
                                  n_buckets=n_buckets)
    _check_row_invariant(out)
    assert int(G.out_degrees(out).max()) <= m
    # exact-width buckets reproduce the oracle
    if n_buckets >= n:
        oracle = G.merge_candidate_edges(g, src, dst, dist, merge="sort")
        assert _canon(oracle) == _canon(out)


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(4, 20),
    m=st.integers(2, 8),
    r=st.integers(1, 8),
    n_buckets=st.sampled_from([2, 8, 32]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_bucketed_reverse_caps(n, m, r, n_buckets, seed):
    key = jax.random.PRNGKey(seed)
    kx, kg = jax.random.split(key)
    x = jax.random.normal(kx, (n, 8))
    g = _rand_graph(kg, x, m, "l2")
    out = G.add_reverse_edges(g, r, merge="bucketed", n_buckets=n_buckets)
    _check_row_invariant(out)
    assert int(G.out_degrees(out).max()) <= min(r, m)
    assert int(G.in_degrees(out).max()) <= r
    if n_buckets >= n:
        oracle = G.add_reverse_edges(g, r, merge="sort")
        assert _canon(oracle) == _canon(out)


@pytest.mark.parametrize("metric", ["l2", "ip", "cos"])
def test_gather_dists_blocks_match_unblocked(metric):
    """Above GATHER_BLOCK pairs gather_dists scores block by block; every
    pair's distance is bitwise the one an unblocked call gives."""
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (3000, 16))
    p = D.GATHER_BLOCK + 4097            # one full block + a padded tail
    u = jax.random.randint(jax.random.PRNGKey(4), (p,), -1, 3000)
    v = jax.random.randint(jax.random.PRNGKey(5), (p,), -1, 3000)
    got = np.asarray(D.gather_dists(x, u, v, metric))
    step = D.GATHER_BLOCK // 2
    want = np.concatenate([
        np.asarray(D.gather_dists(x, u[i:i + step], v[i:i + step], metric))
        for i in range(0, p, step)])
    assert got.shape == (p,)
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    assert np.all(np.isinf(got[(np.asarray(u) < 0) | (np.asarray(v) < 0)]))
