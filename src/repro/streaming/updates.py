"""Incremental index maintenance: batched insert and delete with localized
RNN-Descent repair.

RNN-Descent builds search-ready graphs *directly* — no ANNS bootstrap — which
is exactly what makes it incrementally maintainable: splicing a batch of new
points in needs only (a) somewhere to seed their candidate lists from, which
the current graph itself provides via beam search, and (b) a few localized
prune/merge sweeps over the touched rows, which are the same
``rnn_descent.prune_rows`` + ``graph`` bucket-scatter/merge primitives the
batch builder runs globally.

Insert (one batch of B points)
------------------------------
1. **Seed.** Beam-search the *current* graph for each new point
   (``search_tiled``, tombstone-aware so only live vertices surface) —
   its ``seed_k`` results become the new row's out-edges, plus ``batch_k``
   brute-force nearest neighbors *within* the batch (two new points in the
   same unexplored region cannot find each other through the old graph).
2. **Frontier.** The touched row set = the B new rows ∪ every seeded
   candidate: a fixed-size sorted-unique id buffer of F = B * (1 + seed_k)
   slots (capacity-sentinel padded), so every jitted shape depends on the
   *batch*, never the corpus.
3. **Reverse repair + localized sweeps.** Each candidate v gets the reverse
   offer (v -> new) — that is what makes new points discoverable — and
   ``sweeps`` RNN-Descent sweeps run restricted to the frontier: gather the
   frontier rows, fused RNG prune (``prune_rows``), scatter the replacement
   edges (w -> v) into *frontier-local* bucket tables
   (``bucket_scatter_tables(row_ids=frontier)`` — table row f is vertex
   frontier[f]), and merge each frontier row with its bucket
   (``merge_rows_with_buckets``). Replacement edges whose destination row
   fell outside the frontier are dropped — the locality that keeps insert
   cost O(F), verified against corpus size in BENCH_streaming.json.

Sharded inserts (``mesh=``) ride the same exchange as the batch build:
*frontier* rows partition across the mesh's "rows" axis, each shard prunes
its slice and scatters one destination block at a time into (F/D, B)
partial tables, and ``shard.exchange_scatter`` (ring ppermute + pairwise
staged lexicographic-min fold) hands each shard the combined block for its
rows without ever materializing a full-height (F, B) table. Per-row work
is identical and the fold is exact, so sharded updates are **bitwise
equal** to single-device (tests/test_streaming.py) — the same argument as
the sharded batch build.

Delete (one batch of ids)
-------------------------
Rows are tombstoned, not erased: their vector and out-edges stay resident so
they keep serving as traversable bridges (search masks them out of results
via ``valid=``). Repair then splices each deleted vertex v out of the live
topology: every live in-neighbor u of v is offered v's ``splice_k`` nearest
out-neighbors as candidates (d(u, w) computed fresh), merged into u's row and
re-capped under the RNG prune — so u keeps a direct path into the region v
covered even after ``store.compact()`` physically removes v. The affected
rows are found with one adjacency scan and repaired under a fixed budget of
``delete_fanout`` rows per deleted id (overflow rows keep their tombstone
bridges until a later batch or compact — dropped work is bounded staleness,
never corruption). Per-affected-row work is independent, so the sharded path
just partitions the affected block (no exchange needed) and is bitwise equal
by construction.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distances as D
from repro.core import graph as G
from repro.core import rnn_descent as rd
from repro.core import search as S
from repro.core import shard
from repro.streaming.store import Store, active_mask, free_count, row_labels

NEW = G.NEW


@dataclasses.dataclass(frozen=True)
class StreamingConfig:
    """Knobs for incremental maintenance. ``build`` carries the shared
    RNN-Descent parameters (metric, adjacency capacity M, prune chunking,
    merge path) — streaming stores must be built and repaired under one
    config so the localized sweeps speak the same dialect as the batch
    builder."""

    build: rd.RNNDescentConfig = rd.RNNDescentConfig()
    seed_l: int = 64        # beam width of the insert seeding search
    seed_k: int = 24        # candidates harvested per inserted point
    seed_iters: int = 96    # max beam expansions during seeding
    search_k: int = 32      # Eq. 4 prefix limit during the seeding search
    batch_k: int = 8        # brute-force intra-batch neighbors per new point
    sweeps: int = 2         # localized RNN-Descent sweeps per insert batch
    splice_k: int = 8       # out-neighbors spliced per deleted vertex
    delete_fanout: int = 32  # repaired in-neighbor rows budget per deleted id

    def __post_init__(self):
        if not (1 <= self.seed_k <= self.seed_l):
            raise ValueError(
                f"seed_k={self.seed_k} must be in [1, seed_l={self.seed_l}]")
        if self.seed_k > self.build.capacity:
            raise ValueError(
                f"seed_k={self.seed_k} exceeds adjacency capacity "
                f"M={self.build.capacity}")
        if self.sweeps < 1:
            raise ValueError(f"sweeps must be >= 1, got {self.sweeps}")
        if min(self.seed_iters, self.search_k, self.splice_k,
               self.delete_fanout) < 1:
            raise ValueError(
                "seed_iters, search_k, splice_k and delete_fanout must be "
                ">= 1")
        if self.batch_k < 0:
            raise ValueError(f"batch_k must be >= 0, got {self.batch_k}")

    @property
    def metric(self) -> str:
        return self.build.metric

    def seed_search_cfg(self) -> S.SearchConfig:
        return S.SearchConfig(
            l=self.seed_l, k=min(self.search_k, self.build.capacity),
            max_iters=self.seed_iters, metric=self.metric, topk=self.seed_k)


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _gather_rows(g: G.Graph, idx: jnp.ndarray, cap: int) -> G.Graph:
    """Gather adjacency rows for a sentinel-padded id buffer (idx == cap
    marks padding; padded rows come back empty/inert)."""
    cl = jnp.minimum(idx, cap - 1)
    live = (idx < cap)[:, None]
    return G.Graph(
        neighbors=jnp.where(live, g.neighbors[cl], -1),
        dists=jnp.where(live, g.dists[cl], jnp.inf),
        flags=jnp.where(live, g.flags[cl], G.OLD),
    )


def _scatter_rows(g: G.Graph, idx: jnp.ndarray, blk: G.Graph) -> G.Graph:
    """Write a row block back (sentinel ids dropped)."""
    return G.Graph(
        neighbors=g.neighbors.at[idx].set(blk.neighbors, mode="drop"),
        dists=g.dists.at[idx].set(blk.dists, mode="drop"),
        flags=g.flags.at[idx].set(blk.flags, mode="drop"),
    )


def _frontier_ids(slots: jnp.ndarray, cand_ids: jnp.ndarray, cap: int,
                  f_pad: int) -> jnp.ndarray:
    """Sorted-unique frontier buffer: new slots ∪ seeded candidates,
    duplicates and invalid entries pushed to the ``cap`` sentinel tail."""
    raw = jnp.concatenate([
        slots.astype(jnp.int32),
        jnp.where(cand_ids.reshape(-1) >= 0, cand_ids.reshape(-1), cap)
        .astype(jnp.int32),
    ])
    f = jnp.sort(raw)
    dup = jnp.concatenate([jnp.zeros((1,), bool), f[1:] == f[:-1]])
    f = jnp.sort(jnp.where(dup | (f >= cap), cap, f))
    return jnp.pad(f, (0, f_pad - f.shape[0]), constant_values=cap)


def _local_rows(frontier: jnp.ndarray, ids: jnp.ndarray,
                f_pad: int) -> jnp.ndarray:
    """Global vertex ids -> frontier-local row positions (f_pad = dropped)."""
    pos = jnp.clip(jnp.searchsorted(frontier, ids), 0, f_pad - 1)
    ok = (ids >= 0) & (frontier[pos] == ids)
    return jnp.where(ok, pos, f_pad).astype(jnp.int32)


def _frontier_sweep_block(x, g, f_slice, f_full, ex_rows, ex_ids, ex_d,
                          cfg: StreamingConfig, axes, n_dev: int,
                          f_pad: int, n_buckets: int) -> G.Graph:
    """One localized RNN-Descent sweep over (this shard's slice of) the
    frontier: fused RNG prune, replacement edges routed into frontier-local
    bucket tables, bucket merge. ``ex_*`` carries extra candidate offers
    (the reverse edges v -> new on the first sweep; empty afterwards) —
    replicated across shards, exact under the idempotent min-fold."""
    cap, m = g.neighbors.shape
    blk = _gather_rows(g, f_slice, cap)
    keep, red_w, red_d = rd.prune_rows(x, blk.neighbors, blk.dists, blk.flags,
                                       cfg.build)
    pruned = G.sort_rows(G.Graph(
        neighbors=jnp.where(keep, blk.neighbors, -1),
        dists=jnp.where(keep, blk.dists, jnp.inf),
        flags=jnp.zeros_like(blk.flags),
    ))
    # replacement edges (w -> v): destination w is any graph vertex; only
    # frontier destinations merge (out-of-frontier edges are dropped — the
    # locality bound that keeps insert cost batch-sized)
    rw = red_w.reshape(-1)
    rv = jnp.where(red_w >= 0, blk.neighbors, -1).reshape(-1)
    rows_cat = jnp.concatenate([_local_rows(f_full, rw, f_pad), ex_rows])
    ids_cat = jnp.concatenate([rv, ex_ids])
    d_cat = jnp.concatenate([red_d.reshape(-1), ex_d])
    flags_cat = jnp.full(ids_cat.shape, NEW)

    def scatter_block(lo, f_blk):
        return G.bucket_scatter_tables(
            rows_cat - lo, ids_cat, d_cat, flags_cat, f_blk, n_buckets,
            row_ids=jax.lax.dynamic_slice(f_full, (lo,), (f_blk,)))

    _, kt, it, ft = shard.exchange_scatter(axes, n_dev, f_pad, scatter_block)
    b_ids, b_d, b_f = G.decode_bucket_tables(kt, it, ft)
    return G.merge_rows_with_buckets(pruned, b_ids, b_d, b_f, m, m)


def _sweep(x, g, frontier, ex_rows, ex_ids, ex_d, cfg: StreamingConfig,
           mesh) -> G.Graph:
    """Run one frontier sweep (single-device or shard_map over the mesh's
    "rows" axis) and scatter the updated rows back into the graph."""
    f_pad = frontier.shape[0]
    n_buckets = cfg.build.n_buckets or G.default_buckets(
        g.neighbors.shape[1])
    if mesh is None:
        blk = _frontier_sweep_block(x, g, frontier, frontier, ex_rows, ex_ids,
                                    ex_d, cfg, (), 1, f_pad, n_buckets)
    else:
        from jax.sharding import PartitionSpec as P

        from repro.distributed import sharding as SH

        axes = shard.row_axes(mesh)
        n_dev = shard.n_shards(mesh)
        fspec = SH.pspec(mesh, shard.ROWS)
        gspec = SH.pspec(mesh, shard.ROWS, None)
        rep = G.Graph(P(), P(), P())

        def body(xx, gg, fs, ff, er, ei, ed):
            return _frontier_sweep_block(xx, gg, fs, ff, er, ei, ed, cfg,
                                         axes, n_dev, f_pad, n_buckets)

        blk = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), rep, fspec, P(), P(), P(), P()),
            out_specs=G.Graph(gspec, gspec, gspec),
            check_vma=False,
        )(x, g, frontier, frontier, ex_rows, ex_ids, ex_d)
    return _scatter_rows(g, frontier, blk)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "f_pad"))
def _graft(x, g: G.Graph, occupied, new_x, slots, cand_ids, cand_d,
           cfg: StreamingConfig, mesh, f_pad: int):
    """Jitted insert body: write the new rows, then reverse-repair + sweep
    the frontier. All shapes depend on (capacity, batch) only."""
    cap, m = g.neighbors.shape
    b, k = cand_ids.shape
    x2 = x.at[slots].set(new_x)
    occ2 = occupied.at[slots].set(True)

    # intra-batch brute-force neighbors: new points in the same unexplored
    # region can't reach each other through the old graph
    bk = min(cfg.batch_k, b - 1)
    if bk > 0:
        bb = D.pairwise(new_x, new_x, cfg.metric)
        bb = jnp.where(jnp.eye(b, dtype=bool), jnp.inf, bb)
        neg_bd, bidx = jax.lax.top_k(-bb, bk)
        batch_ids = slots[bidx].astype(jnp.int32)            # (B, bk) global
        batch_d = -neg_bd
    else:
        batch_ids = jnp.zeros((b, 0), jnp.int32)
        batch_d = jnp.zeros((b, 0), jnp.float32)

    # new rows: seeded candidates + batch neighbors, capped to M under the
    # row invariant (all flagged NEW — the first sweep RNG-prunes them)
    row_ids = jnp.concatenate([cand_ids.astype(jnp.int32), batch_ids], axis=1)
    row_d = jnp.concatenate(
        [jnp.where(cand_ids >= 0, cand_d, jnp.inf), batch_d], axis=1)
    row_ids, row_d, row_f = G.row_topk(
        row_ids, row_d, jnp.full(row_ids.shape, NEW), m, m)
    g2 = _scatter_rows(g, slots, G.Graph(row_ids, row_d, row_f))

    frontier = _frontier_ids(slots, cand_ids, cap, f_pad)

    # reverse offers: candidate v -> new slot (discoverability of the new
    # points), and batch neighbor j -> i to make intra-batch edges mutual
    off_rows = jnp.concatenate([
        _local_rows(frontier, cand_ids.reshape(-1), f_pad),
        _local_rows(frontier, batch_ids.reshape(-1), f_pad),
    ])
    off_ids = jnp.concatenate([
        jnp.broadcast_to(slots[:, None], (b, k)).reshape(-1),
        jnp.broadcast_to(slots[:, None], (b, bk)).reshape(-1),
    ]).astype(jnp.int32)
    off_d = jnp.concatenate([
        jnp.where(cand_ids >= 0, cand_d, jnp.inf).reshape(-1),
        batch_d.reshape(-1),
    ])

    empty_r = jnp.zeros((0,), jnp.int32)
    empty_d = jnp.zeros((0,), jnp.float32)
    for t in range(cfg.sweeps):
        if t == 0:
            g2 = _sweep(x2, g2, frontier, off_rows, off_ids, off_d, cfg, mesh)
        else:
            g2 = _sweep(x2, g2, frontier, empty_r, empty_r, empty_d, cfg,
                        mesh)
    return x2, g2, occ2


def insert(store: Store, new_x, cfg: StreamingConfig,
           mesh=None, labels=None) -> tuple[Store, np.ndarray]:
    """Insert a batch of vectors; returns ``(new_store, row_ids)``.
    ``labels``: optional (B,) uint32 label words of the new rows (0 when
    None; the store gains a label array the first time labels are given).

    The store must have ``free_count(store) >= len(new_x)`` — capacity
    growth is the :class:`repro.streaming.index.StreamingANN` layer's job
    (it is a host-level shape change). The input store is untouched
    (functional update), so snapshots taken before the call keep serving
    the previous epoch."""
    new_x = jnp.asarray(new_x, jnp.float32)
    b = int(new_x.shape[0])
    if b == 0:
        return store, np.zeros((0,), np.int32)
    if free_count(store) < b:
        raise ValueError(
            f"store has {free_count(store)} free rows < batch {b}: grow the "
            "store first (StreamingANN.insert does this automatically)")
    if labels is not None:
        labels = jnp.asarray(labels).astype(jnp.uint32)
    if labels is not None and labels.shape != (b,):
        raise ValueError(
            f"labels has shape {labels.shape} but the batch has {b} rows")
    slots = np.flatnonzero(~np.asarray(store.occupied))[:b].astype(np.int32)

    active = active_mask(store)
    eps = S.default_entry_point(store.x, cfg.metric, valid=active)
    cand_ids, cand_d = S.search_tiled(
        store.x, store.graph, new_x, eps, cfg.seed_search_cfg(),
        tile_b=min(256, b), mesh=mesh, valid=active)

    n_dev = 1 if mesh is None else shard.n_shards(mesh)
    f_pad = _round_up(b * (1 + cfg.seed_k), max(n_dev, 1))
    x2, g2, occ2 = _graft(store.x, store.graph, store.occupied, new_x,
                          jnp.asarray(slots), cand_ids, cand_d, cfg, mesh,
                          f_pad)
    qx2 = store.qx
    if qx2 is not None:
        # encode into the *frozen* code space (scale/zero/codebooks trained
        # at quantize time) — no retraining per batch, so build-side and
        # serve-side codes for a row never depend on when it arrived. Points
        # outside the trained int8 range clip; retrain via
        # store.quantize_store after heavy drift.
        from repro.quant import encode_rows
        qx2 = qx2._replace(
            codes=qx2.codes.at[jnp.asarray(slots)].set(
                encode_rows(new_x, qx2)))
    labels2 = store.labels
    if labels is not None:
        labels2 = row_labels(store).at[jnp.asarray(slots)].set(labels)
    elif labels2 is not None:
        labels2 = labels2.at[jnp.asarray(slots)].set(0)
    return Store(x=x2, graph=g2, occupied=occ2, tombstone=store.tombstone,
                 epoch=store.epoch + 1, qx=qx2, remap=store.remap,
                 labels=labels2), slots


# ------------------------------------------------------------------- delete
def _repair_block(x, g: G.Graph, tomb, a_slice,
                  cfg: StreamingConfig) -> G.Graph:
    """Splice repair for (this shard's slice of) the affected rows: drop
    edges into tombstones, offer each dropped vertex's ``splice_k`` nearest
    out-neighbors instead, re-cap under the RNG prune."""
    cap, m = g.neighbors.shape
    a_loc = a_slice.shape[0]
    blk = _gather_rows(g, a_slice, cap)
    nb = blk.neighbors
    dead = (nb >= 0) & tomb[jnp.maximum(nb, 0)]
    kept = G.sort_rows(G.Graph(
        neighbors=jnp.where(dead, -1, nb),
        dists=jnp.where(dead, jnp.inf, blk.dists),
        flags=jnp.where(dead, G.OLD, blk.flags),
    ))
    sk = min(cfg.splice_k, m)
    # v's out-neighbor prefix (rows are distance-sorted, so [:sk] is its sk
    # nearest) — gathered from the pre-sliced (cap, sk) view to keep the
    # materialized block (A, M, sk), not (A, M, M)
    spl = g.neighbors[:, :sk][jnp.maximum(nb, 0)]             # (A, M, sk)
    spl = jnp.where(dead[:, :, None], spl, -1)
    spl = jnp.where((spl >= 0) & ~tomb[jnp.maximum(spl, 0)], spl, -1)
    row_g = jnp.broadcast_to(a_slice[:, None, None], spl.shape)
    ds = D.gather_dists(x, row_g.reshape(-1), spl.reshape(-1),
                        cfg.metric).reshape(a_loc, -1)
    rows_loc = jnp.broadcast_to(jnp.arange(a_loc, dtype=jnp.int32)[:, None],
                                (a_loc, m * sk))
    n_buckets = cfg.build.n_buckets or G.default_buckets(m)
    b_ids, b_d, b_f = G.bucket_scatter(
        rows_loc.reshape(-1), spl.reshape(-1), ds.reshape(-1),
        jnp.full((a_loc * m * sk,), NEW), a_loc, n_buckets, row_ids=a_slice)
    merged = G.merge_rows_with_buckets(kept, b_ids, b_d, b_f, m, m)
    keep, _, _ = rd.prune_rows(x, merged.neighbors, merged.dists,
                               merged.flags, cfg.build)
    return G.sort_rows(G.Graph(
        neighbors=jnp.where(keep, merged.neighbors, -1),
        dists=jnp.where(keep, merged.dists, jnp.inf),
        flags=jnp.zeros_like(merged.flags),
    ))


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"))
def _repair(x, g: G.Graph, tomb, a_idx, cfg: StreamingConfig,
            mesh) -> G.Graph:
    if mesh is None:
        blk = _repair_block(x, g, tomb, a_idx, cfg)
    else:
        from jax.sharding import PartitionSpec as P

        from repro.distributed import sharding as SH

        fspec = SH.pspec(mesh, shard.ROWS)
        gspec = SH.pspec(mesh, shard.ROWS, None)
        rep = G.Graph(P(), P(), P())

        def body(xx, gg, tt, aa):
            return _repair_block(xx, gg, tt, aa, cfg)

        blk = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), rep, P(), fspec),
            out_specs=G.Graph(gspec, gspec, gspec),
            check_vma=False,
        )(x, g, tomb, a_idx)
    return _scatter_rows(g, a_idx, blk)


def delete(store: Store, ids, cfg: StreamingConfig, mesh=None) -> Store:
    """Tombstone a batch of row ids and splice-repair their live
    in-neighbors; returns the new store (input untouched).

    Ids that are out of range, unoccupied, or already tombstoned are
    silently skipped (delete is idempotent). The repair budget is
    ``delete_fanout`` affected rows per deleted id — overflow rows keep
    routing through the tombstone bridges until a later delete batch or
    :func:`repro.streaming.store.compact` (bounded staleness, never a
    dangling edge: tombstoned vectors stay resident)."""
    cap = store.capacity
    ids_np = np.unique(np.asarray(ids).astype(np.int32).reshape(-1))
    ids_np = ids_np[(ids_np >= 0) & (ids_np < cap)]
    occ = np.asarray(store.occupied)
    tomb0 = np.asarray(store.tombstone)
    ids_np = ids_np[occ[ids_np] & ~tomb0[ids_np]]
    bd = int(ids_np.shape[0])
    if bd == 0:
        return store
    tomb_new = store.tombstone.at[jnp.asarray(ids_np)].set(True)

    nbrs = store.graph.neighbors
    newly = jnp.zeros((cap,), bool).at[jnp.asarray(ids_np)].set(True)
    affected = (jnp.any((nbrs >= 0) & newly[jnp.maximum(nbrs, 0)], axis=1)
                & store.occupied & ~tomb_new)
    aff_np = np.flatnonzero(np.asarray(affected))

    n_dev = 1 if mesh is None else shard.n_shards(mesh)
    budget = _round_up(min(cap, max(bd * cfg.delete_fanout, 1)),
                       max(n_dev, 1))
    take = min(aff_np.shape[0], budget)
    a_idx = np.full((budget,), cap, np.int32)
    a_idx[:take] = aff_np[:take]

    g2 = _repair(store.x, store.graph, tomb_new, jnp.asarray(a_idx), cfg,
                 mesh)
    return store._replace(graph=g2, tombstone=tomb_new,
                          epoch=store.epoch + 1)
