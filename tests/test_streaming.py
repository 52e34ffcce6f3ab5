"""Streaming subsystem: incremental insert/delete with tombstone-aware
serving (src/repro/streaming/).

Covers the dynamic-index contracts:
  * inserted points become searchable (each finds itself as its own NN) and
    insert seeds ride the current graph, not a rebuild;
  * deleted ids are tombstoned — never surface in top-k, but their rows stay
    traversable bridges until compact();
  * tombstone-aware search (``search_tiled(valid=)``) and masked entry-point
    selection (``default_entry_points(valid=)``), including the padded-row
    case the streaming store creates;
  * capacity growth (power-of-two re-pad) preserves the graph;
  * epoch-snapshot serving: a snapshot taken before an update keeps serving
    the old graph bit-for-bit;
  * compact() renumbers survivors, drops tombstones, and preserves quality;
  * sharded streaming updates are **bitwise equal** to single-device — on
    the mesh over every visible device (1 under plain tier-1; 8 in the CI
    mesh job, where the frontier exchange really crosses shards);
  * churn end-to-end: after interleaved inserts (>=30%) and deletes (>=20%)
    recall@10 on survivors is within 0.02 of a from-scratch rebuild.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import eval as E
from repro.core import graph as G
from repro.core import rnn_descent as rd
from repro.core import search as S
from repro.data.synthetic import VectorDatasetSpec, clustered_vectors
from repro.launch.mesh import make_mesh
from repro.streaming import StreamingANN, StreamingConfig
from repro.streaming import store as ST
from repro.streaming import updates as U

CFG = StreamingConfig(
    build=rd.RNNDescentConfig(s=8, r=16, t1=2, t2=3, capacity=24, chunk=128),
    seed_l=32, seed_k=12, seed_iters=64, batch_k=4, sweeps=2, splice_k=6,
)
SCFG = S.SearchConfig(l=32, k=16, max_iters=96, topk=10)


@pytest.fixture(scope="module")
def corpus():
    x, q = clustered_vectors(
        jax.random.PRNGKey(0),
        VectorDatasetSpec("stream", n=700, d=24, n_queries=60, n_clusters=8),
    )
    return x, q


@pytest.fixture(scope="module")
def base_ann(corpus):
    x, _ = corpus
    return StreamingANN.from_corpus(x[:500], CFG, key=jax.random.PRNGKey(1))


def _stores_equal(a: ST.Store, b: ST.Store):
    assert np.array_equal(np.asarray(a.x), np.asarray(b.x))
    assert np.array_equal(np.asarray(a.graph.neighbors),
                          np.asarray(b.graph.neighbors))
    assert np.array_equal(np.asarray(G.dist_key(a.graph.dists)),
                          np.asarray(G.dist_key(b.graph.dists)))
    assert np.array_equal(np.asarray(a.graph.flags), np.asarray(b.graph.flags))
    assert np.array_equal(np.asarray(a.occupied), np.asarray(b.occupied))
    assert np.array_equal(np.asarray(a.tombstone), np.asarray(b.tombstone))


# ---------------------------------------------------------------- store layer
def test_store_padding_and_counts(corpus):
    x, _ = corpus
    g = rd.build(x[:500], CFG.build, jax.random.PRNGKey(1))
    st = ST.from_built(x[:500], g)
    assert st.capacity == 512 and st.capacity == ST.next_capacity(500)
    assert ST.occupied_count(st) == 500 and ST.live_count(st) == 500
    assert ST.free_count(st) == 12
    # padded rows are inert: zero vectors, empty adjacency
    assert np.all(np.asarray(st.x)[500:] == 0.0)
    assert np.all(np.asarray(st.graph.neighbors)[500:] == -1)
    g2 = ST.grow(st, 600)
    assert g2.capacity == 1024
    assert np.array_equal(np.asarray(g2.graph.neighbors)[:512],
                          np.asarray(st.graph.neighbors))
    assert ST.grow(st, 100).capacity == 512  # never shrinks


# ------------------------------------------------------------- insert/delete
def test_insert_makes_points_searchable(corpus, base_ann):
    x, _ = corpus
    ann = StreamingANN(store=base_ann.store, cfg=CFG)   # fresh handle
    new_ids = ann.insert(x[500:700])
    assert new_ids.shape == (200,) and ann.live == 700
    # every inserted point finds itself as its own nearest neighbor
    ids, dists = ann.search(x[500:700], SCFG)
    self_hit = np.mean(np.asarray(ids[:, 0]) == new_ids)
    assert self_hit >= 0.95, self_hit
    # and the old points still resolve
    ids_old, _ = ann.search(x[:64], SCFG)
    assert np.mean(np.asarray(ids_old[:, 0]) == np.arange(64)) >= 0.95


def test_insert_requires_free_rows(corpus, base_ann):
    x, _ = corpus
    with pytest.raises(ValueError, match="free rows"):
        U.insert(base_ann.store, x[500:700], CFG)  # 12 free < 200


def test_insert_growth_preserves_results(corpus, base_ann):
    x, q = corpus
    ann = StreamingANN(store=base_ann.store, cfg=CFG)
    assert ann.capacity == 512
    ann.insert(x[500:700])                  # forces a grow to 1024
    assert ann.capacity == 1024
    ids, _ = ann.search(q, SCFG)
    gt_d, gt_i = E.ground_truth(x[:700], q, k=10)
    assert E.recall_topk(ids, gt_i) > 0.85


def test_delete_tombstones_never_surface(corpus, base_ann):
    x, q = corpus
    ann = StreamingANN(store=base_ann.store, cfg=CFG)
    gt_d, gt_i = E.ground_truth(x[:500], q, k=3)
    hot = np.unique(np.asarray(gt_i).ravel())[:60]   # ids queries actually hit
    ann.delete(hot)
    st = ann.store
    assert int(jnp.sum(st.tombstone)) == len(hot)
    # tombstoned rows keep their out-edges (traversable bridges)
    assert np.any(np.asarray(st.graph.neighbors)[hot] >= 0)
    ids, dists = ann.search(q, SCFG)
    leaked = np.intersect1d(np.asarray(ids).ravel(), hot)
    assert leaked.size == 0, leaked
    # quality on the survivors holds (repair spliced around the deletions)
    valid = np.ones(500, bool); valid[hot] = False
    gt_v_d, gt_v_i = E.ground_truth(
        x[:500], q, k=10, valid=jnp.asarray(valid))
    pad = jnp.zeros((ann.capacity - 500,), bool)
    r = E.recall_topk(ids, gt_v_i,
                      valid=jnp.concatenate([jnp.asarray(valid), pad]))
    assert r > 0.85, r


def test_delete_is_idempotent_and_bounds_checked(base_ann):
    st = base_ann.store
    st1 = U.delete(st, np.array([3, 3, 5]), CFG)
    st2 = U.delete(st1, np.array([3, 5, -7, 10**6]), CFG)  # junk ids skipped
    assert int(jnp.sum(st2.tombstone)) == 2
    assert st2.epoch == st1.epoch  # no-op delete does not bump the epoch


# ------------------------------------------------- tombstone-aware search API
def test_search_valid_mask_unit(corpus):
    x, q = corpus
    g = rd.build(x[:500], CFG.build, jax.random.PRNGKey(1))
    ep = S.default_entry_point(x[:500])
    ids0, d0 = S.search_tiled(x[:500], g, q, ep, SCFG, tile_b=32)
    # masking the top hit promotes the runner-up, everywhere
    valid = jnp.ones((500,), bool).at[ids0[:, 0]].set(False)
    ids1, d1 = S.search_tiled(x[:500], g, q, ep, SCFG, tile_b=32, valid=valid)
    assert not np.any(np.isin(np.asarray(ids1), np.asarray(ids0[:, 0])))
    # each lane's new top-1 is its previous first *unmasked* result (the
    # mask is the union of every query's old top-1, so rank-2 can be masked
    # for some other lane's sake too)
    v_np, i0_np = np.asarray(valid), np.asarray(ids0)
    expect = np.array([row[v_np[row]][0] for row in i0_np])
    assert np.array_equal(np.asarray(ids1[:, 0]), expect)
    # an all-true mask returns the unmasked results bit for bit
    ids2, d2 = S.search_tiled(x[:500], g, q, ep, SCFG, tile_b=32,
                              valid=jnp.ones((500,), bool))
    assert np.array_equal(np.asarray(ids2), np.asarray(ids0))
    assert np.array_equal(np.asarray(G.dist_key(d2)), np.asarray(G.dist_key(d0)))
    # all-masked: nothing surfaces, (-1, +inf) padding
    ids3, d3 = S.search_tiled(x[:500], g, q, ep, SCFG, tile_b=32,
                              valid=jnp.zeros((500,), bool))
    assert np.all(np.asarray(ids3) == -1) and np.all(np.isinf(np.asarray(d3)))


def test_default_entry_points_skip_masked(corpus):
    x, _ = corpus
    xp = jnp.pad(x[:500], ((0, 100), (0, 0)))      # padded rows = zeros
    valid = jnp.arange(600) < 500
    # the zero rows sit at the centroid — without the mask one of them wins
    # (the historical bug: a padded row handed out as a seed)
    masked_center = S.default_entry_point(xp, valid=valid)
    assert int(masked_center) < 500
    eps = S.default_entry_points(xp, n_entries=8,
                                 key=jax.random.PRNGKey(3), valid=valid)
    assert eps.shape == (8,)
    assert np.all(np.asarray(eps) < 500)
    assert len(set(np.asarray(eps).tolist())) == 8
    # tombstoned rows are skipped the same way
    tomb_valid = valid & (jnp.arange(600) >= 10)
    eps2 = S.default_entry_points(xp, n_entries=8,
                                  key=jax.random.PRNGKey(3), valid=tomb_valid)
    assert np.all(np.asarray(eps2) >= 10) and np.all(np.asarray(eps2) < 500)
    # degenerate: fewer live rows than entries -> duplicates of the centroid
    # seed (inert in-beam), never a masked row
    tiny = jnp.zeros((600,), bool).at[7].set(True).at[12].set(True)
    eps3 = np.asarray(S.default_entry_points(xp, n_entries=4, valid=tiny))
    assert set(eps3.tolist()) <= {7, 12}


def test_recall_topk_valid_mask_semantics():
    valid = jnp.array([True, True, False, True])
    gt = jnp.array([[0, 2, 3]])          # gt column 2 is deleted
    pred_hit = jnp.array([[0, 3, 1]])    # finds both surviving gt ids
    pred_dead = jnp.array([[0, 2, 2]])   # "finds" the deleted id
    assert E.recall_topk(pred_hit, gt, valid=valid) == 1.0
    assert E.recall_topk(pred_dead, gt, valid=valid) == 0.5
    # unmasked semantics unchanged
    assert E.recall_topk(pred_hit, gt) == pytest.approx(2 / 3)


# --------------------------------------------------------- epochs & snapshots
def test_epoch_snapshot_serves_old_graph(corpus, base_ann):
    x, q = corpus
    ann = StreamingANN(store=base_ann.store, cfg=CFG)
    epoch0, snap = ann.snapshot()
    ids0, d0 = ann.search(q, SCFG)
    ann.insert(x[500:560])
    ann.delete(np.arange(40))
    assert ann.epoch == epoch0 + 2
    # the snapshot still serves the pre-update graph bit for bit
    valid = ST.active_mask(snap)
    ep = S.default_entry_point(snap.x, SCFG.metric, valid=valid)
    ids1, d1 = S.search_tiled(snap.x, snap.graph, q, ep, SCFG, tile_b=64,
                              valid=valid)
    assert np.array_equal(np.asarray(ids0), np.asarray(ids1))
    assert np.array_equal(np.asarray(G.dist_key(d0)), np.asarray(G.dist_key(d1)))
    # while the live index reflects the updates
    ids2, _ = ann.search(q, SCFG)
    assert not np.array_equal(np.asarray(ids0), np.asarray(ids2))


# ------------------------------------------------------------------- compact
def test_compact_drops_tombstones_and_renumbers(corpus, base_ann):
    x, q = corpus
    ann = StreamingANN(store=base_ann.store, cfg=CFG)
    ann.insert(x[500:600])
    ann.delete(np.arange(0, 150))
    remap = ann.compact()
    st = ann.store
    assert ann.live == 450 and st.capacity == 512
    assert int(jnp.sum(st.tombstone)) == 0
    assert np.all(remap[:150] == -1)
    kept = remap[150:600]
    assert np.array_equal(np.sort(kept), np.arange(450))
    # vectors moved with their ids
    assert np.array_equal(np.asarray(st.x)[kept[0]], np.asarray(x[150]))
    # no edge points at a dropped row and the row invariant holds
    nb = np.asarray(st.graph.neighbors)
    assert nb.max() < 450
    live_rows = nb[:450]
    d = np.asarray(st.graph.dists)[:450]
    d_cmp = np.where(np.isfinite(d), d, np.finfo(np.float32).max)
    assert np.all(np.diff(d_cmp, axis=1) >= 0)   # valid-first, ascending
    assert np.all((live_rows >= 0) == np.isfinite(d))
    # quality after compact (bridges removed, repair sweep re-knit)
    gt_d, gt_i = E.ground_truth(st.x, q, k=10,
                                valid=ST.active_mask(st))
    ids, _ = ann.search(q, SCFG)
    assert E.recall_topk(ids, gt_i, valid=ST.active_mask(st)) > 0.85


# ------------------------------------------------------------ sharded parity
def test_sharded_streaming_updates_bitwise_equal(corpus):
    """Insert + delete through the mesh over every visible device must be
    bitwise equal to single-device (frontier bucket exchange = the PR-4
    min-fold; delete repair is per-row). 1-wide under plain tier-1 (still
    the full shard_map path), 8-wide in the CI mesh job."""
    x, _ = corpus
    mesh = make_mesh((jax.device_count(),), ("data",))
    g = rd.build(x[:420], CFG.build, jax.random.PRNGKey(1))
    st = ST.from_built(x[:420], g, capacity=700)

    s1, slots1 = U.insert(st, x[420:560], CFG)
    s8, slots8 = U.insert(st, x[420:560], CFG, mesh=mesh)
    assert np.array_equal(slots1, slots8)
    _stores_equal(s1, s8)

    d1 = U.delete(s1, np.arange(50, 140), CFG)
    d8 = U.delete(s8, np.arange(50, 140), CFG, mesh=mesh)
    _stores_equal(d1, d8)

    # serving through the mesh matches too (valid mask composes with the
    # query-tile sharding)
    q = x[560:620]
    valid = ST.active_mask(d1)
    ep = S.default_entry_point(d1.x, SCFG.metric, valid=valid)
    i1, dd1 = S.search_tiled(d1.x, d1.graph, q, ep, SCFG, tile_b=16,
                             valid=valid)
    i8, dd8 = S.search_tiled(d8.x, d8.graph, q, ep, SCFG, tile_b=16,
                             mesh=mesh, valid=valid)
    assert np.array_equal(np.asarray(i1), np.asarray(i8))
    assert np.array_equal(np.asarray(G.dist_key(dd1)),
                          np.asarray(G.dist_key(dd8)))


# ------------------------------------------------------------- churn quality
def test_churn_recall_within_rebuild_floor(corpus):
    """The acceptance schedule: insert >=30% new points, delete >=20% of the
    originals, interleaved; survivors' recall@10 within 0.02 of a
    from-scratch rebuild."""
    x, q = corpus
    n0 = 500
    ann = StreamingANN.from_corpus(x[:n0], CFG, key=jax.random.PRNGKey(1))
    ann.insert(x[n0:n0 + 80])                        # +16%
    ann.delete(np.arange(0, 60))                     # -12% of originals
    ann.insert(x[n0 + 80:n0 + 160])                  # +32% total
    ann.delete(np.arange(60, 110))                   # -22% of originals
    st = ann.store
    valid = ST.active_mask(st)
    assert ann.live == n0 + 160 - 110

    gt_d, gt_i = E.ground_truth(st.x, q, k=10, valid=valid)
    ids, _ = ann.search(q, SCFG)
    r_stream = E.recall_topk(ids, gt_i, valid=valid)

    surv = np.asarray(st.x)[np.asarray(valid)]
    g_reb = rd.build(jnp.asarray(surv), CFG.build, jax.random.PRNGKey(2),
                     )
    ep = S.default_entry_point(jnp.asarray(surv))
    ids_r, _ = S.search_tiled(jnp.asarray(surv), g_reb, q, ep, SCFG,
                              tile_b=64)
    gt_rd, gt_ri = E.ground_truth(jnp.asarray(surv), q, k=10)
    r_rebuild = E.recall_topk(ids_r, gt_ri)
    assert r_stream >= r_rebuild - 0.02, (r_stream, r_rebuild)
