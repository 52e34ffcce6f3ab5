"""Plain reference of one RNN-Descent sweep and one reverse-edge pass over a
fixed-capacity adjacency, written from the paper (Algorithms 4 and 5) and
the configuration's merge rule, independent of the program.

A graph is three (n, M) arrays: neighbour ids (-1 = empty slot), distances
(+inf in empty slots) and flags (1 = "new", 0 = "old"); each row holds its
valid entries first, in ascending distance.

Sweep (Algorithm 4), per row u:
  * the RNG prune walks u's neighbours v_i in row order and drops v_i when
    some already kept v_j (j < i, not both "old") has
    d(v_i, v_j) <= d(u, v_i); a dropped v_i is offered to the first such
    v_j as the edge (v_j -> v_i) with distance d(v_i, v_j);
  * kept entries stay, flagged "old"; offered edges are merged into their
    source rows flagged "new", an edge already in the row keeps the row's
    copy, and each row keeps its M shortest edges.
Reverse pass (Algorithm 5): E := E u reverse(E) (reversed copies "new", an
original edge beats a reversed copy of itself), keep the R shortest
in-edges of every vertex, then the R shortest out-edges of every vertex.

Merge rule (``merge="bucketed"``, the configuration's): candidate edges of
a row are hashed into B slots by their other endpoint (B the least power of
two >= max(2 * cap, 128), cap = M in the sweep and R in the reverse pass),
slot = (id * 2654435761 mod 2^32) mod B, and one edge survives per slot:
the least by (priority, distance, id), with the largest flag among copies
of that edge. Distances compare through their order-preserving uint32 key.
Rows then keep their shortest edges, ties by (distance, slot) in the
reverse pass and by (distance, id) in the sweep.

Pair distances for the prune are squared l2 in float32 at the matmul's
highest precision, computed on the device block by block; everything else
is exact integer and float comparison on the host in numpy. ``edge_dists``
gives every stored edge's true distance, to hold the distances a graph
carries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SLOT_MULT = np.uint64(2654435761)
NEW, OLD = np.uint8(1), np.uint8(0)


# ------------------------------------------------------------------ keys
def dist_key(d: np.ndarray) -> np.ndarray:
    """float32 -> uint32 with the same order (sign-flip transform)."""
    b = np.ascontiguousarray(d, np.float32).view(np.uint32)
    return np.where(b >> np.uint32(31) == 1, ~b, b | np.uint32(0x80000000))


def key_dist(k: np.ndarray) -> np.ndarray:
    b = np.where(k >> np.uint32(31) == 0, ~k, k & np.uint32(0x7FFFFFFF))
    return np.ascontiguousarray(b, np.uint32).view(np.float32)


def n_slots_for(cap: int) -> int:
    """Slots per row: the least power of two >= max(2 * cap, 128)."""
    b = 128
    while b < 2 * cap:
        b *= 2
    return b


def slot_of(ids: np.ndarray, n_slots: int) -> np.ndarray:
    return ((ids.astype(np.uint64) * SLOT_MULT)
            & np.uint64(n_slots - 1)).astype(np.int64)


# ---------------------------------------------------------------- prune
def _pair_l2(v: jax.Array) -> jax.Array:
    sq = jnp.sum(v * v, axis=-1)
    dot = jnp.einsum("cid,cjd->cij", v, v,
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(sq[:, :, None] + sq[:, None, :] - 2.0 * dot, 0.0)


@functools.partial(jax.jit, static_argnames=("block",))
def _prune_device(x, nbr, dist, flag, block: int):
    n, m = nbr.shape
    pad = (-n) % block
    nbr = jnp.pad(nbr, ((0, pad), (0, 0)), constant_values=-1)
    dist = jnp.pad(dist, ((0, pad), (0, 0)), constant_values=jnp.inf)
    flag = jnp.pad(flag, ((0, pad), (0, 0)))

    def one_block(args):
        ids, d, f = args
        valid = ids >= 0
        pair = _pair_l2(x[jnp.maximum(ids, 0)])
        pair = jnp.where(valid[:, :, None] & valid[:, None, :], pair,
                         jnp.inf)
        old = f == 0
        both_old = old[:, :, None] & old[:, None, :]
        rows = jnp.arange(ids.shape[0])

        def step(i, carry):
            keep, red_w, red_d = carry
            drops = (keep & ~both_old[:, i, :]
                     & (pair[:, i, :] <= d[:, i][:, None]))
            dropped = jnp.any(drops, axis=1) & valid[:, i]
            j = jnp.argmax(drops, axis=1)
            keep = keep.at[:, i].set(valid[:, i] & ~dropped)
            red_w = red_w.at[:, i].set(jnp.where(dropped, ids[rows, j], -1))
            red_d = red_d.at[:, i].set(
                jnp.where(dropped, pair[rows, i, j], jnp.inf))
            return keep, red_w, red_d

        init = (jnp.zeros(ids.shape, bool), jnp.full(ids.shape, -1, jnp.int32),
                jnp.full(ids.shape, jnp.inf, jnp.float32))
        return jax.lax.fori_loop(0, m, step, init)

    shaped = tuple(a.reshape(-1, block, m) for a in (nbr, dist, flag))
    out = jax.lax.map(one_block, shaped)
    return tuple(a.reshape(-1, m)[:n] for a in out)


def prune(x, nbr, dist, flag, block: int = 256):
    """(keep, redirect ids, redirect distances), each (n, M), on the host."""
    out = _prune_device(jnp.asarray(x), jnp.asarray(nbr), jnp.asarray(dist),
                        jnp.asarray(flag), block)
    return tuple(np.asarray(a) for a in out)


@functools.partial(jax.jit, static_argnames=("block",))
def _edge_dists_device(x, nbr, block: int):
    n, m = nbr.shape
    pad = (-n) % block
    rows = jnp.arange(n + pad).reshape(-1, block)
    nbr = jnp.pad(nbr, ((0, pad), (0, 0)), constant_values=-1)

    def one_block(args):
        r, ids = args
        diff = x[jnp.maximum(ids, 0)] - x[jnp.minimum(r, n - 1)][:, None, :]
        return jnp.where(ids >= 0, jnp.sum(diff * diff, axis=-1), jnp.nan)

    return jax.lax.map(one_block, (rows, nbr.reshape(-1, block, m))) \
        .reshape(-1, m)[:n]


def edge_dists(x, nbr, block: int = 1024) -> np.ndarray:
    """(n, M) squared l2 of every edge (u, nbr[u, i]) as sum((x_u - x_v)^2)
    in float32 on the device, which no cancellation rounds; nan in empty
    slots."""
    return np.asarray(_edge_dists_device(jnp.asarray(x), jnp.asarray(nbr),
                                         block))


# ---------------------------------------------------------------- merge
def _bucket_winners(rows, ids, dist, flag, n, n_slots, prio=None):
    """One surviving edge per (row, slot): the least by (prio, key, id),
    flag = max over copies of that winner. Returns flat arrays
    (row, id, dist, flag, slot) of the winners."""
    valid = (ids >= 0) & (rows >= 0) & (rows < n) & (ids != rows) \
        & ~np.isnan(dist)
    rows, ids, dist, flag = rows[valid], ids[valid], dist[valid], flag[valid]
    prio = np.zeros(rows.shape, np.int64) if prio is None else prio[valid]
    slot = slot_of(ids, n_slots)
    cell = rows.astype(np.int64) * n_slots + slot
    key = dist_key(dist)
    order = np.lexsort((ids, key, prio, cell))
    cell, ids, key, prio, flag, slot = (a[order] for a in
                                        (cell, ids, key, prio, flag, slot))
    first = np.ones(cell.shape, bool)
    first[1:] = cell[1:] != cell[:-1]
    head = np.maximum.accumulate(np.where(first, np.arange(cell.size), 0))
    same = ((ids == ids[head]) & (key == key[head]) & (prio == prio[head]))
    fmax = np.zeros(cell.size, np.uint8)
    np.maximum.at(fmax, head[same], flag[same])
    w = np.nonzero(first)[0]
    return (cell[w] // n_slots, ids[w], key_dist(key[w]), fmax[w], slot[w])


def _rows_from_sorted(rows, ids, dist, flag, n, width, cap):
    """Scatter edges already sorted by (row, order) into (n, width) rows,
    keeping the first ``cap`` of each row."""
    start = np.ones(rows.shape, bool)
    start[1:] = rows[1:] != rows[:-1]
    head = np.maximum.accumulate(np.where(start, np.arange(rows.size), 0))
    rank = np.arange(rows.size) - head
    ok = rank < min(cap, width)
    out_i = np.full((n, width), -1, np.int32)
    out_d = np.full((n, width), np.inf, np.float32)
    out_f = np.zeros((n, width), np.uint8)
    r, k = rows[ok], rank[ok]
    out_i[r, k] = ids[ok]
    out_d[r, k] = dist[ok]
    out_f[r, k] = flag[ok]
    return out_i, out_d, out_f


def sweep(x, nbr, dist, flag):
    """Reference of one ``update_neighbors`` sweep; returns (ids, dists,
    flags) of the swept graph."""
    n, m = nbr.shape
    n_slots = n_slots_for(m)
    keep, red_w, red_d = prune(x, nbr, dist, flag)
    # kept entries, all "old"
    kr, kc = np.nonzero(keep)
    k_rows, k_ids, k_dist = kr, nbr[kr, kc], dist[kr, kc]
    # offered edges (w -> v), "new", one per (w, slot(v))
    cr, cc = np.nonzero(red_w >= 0)
    b_rows, b_ids, b_dist, b_flag, _ = _bucket_winners(
        red_w[cr, cc].astype(np.int64), nbr[cr, cc].astype(np.int64),
        red_d[cr, cc], np.full(cr.size, NEW), n, n_slots)
    rows = np.concatenate([k_rows, b_rows])
    ids = np.concatenate([k_ids, b_ids]).astype(np.int64)
    dists = np.concatenate([k_dist, b_dist]).astype(np.float32)
    flags = np.concatenate([np.full(kr.size, OLD), b_flag])
    from_bucket = np.concatenate([np.zeros(kr.size, np.int8),
                                  np.ones(b_rows.size, np.int8)])
    # an edge already in the row keeps the row's copy
    order = np.lexsort((from_bucket, ids, rows))
    rows, ids, dists, flags = (a[order] for a in (rows, ids, dists, flags))
    dup = np.zeros(rows.shape, bool)
    dup[1:] = (rows[1:] == rows[:-1]) & (ids[1:] == ids[:-1])
    rows, ids, dists, flags = (a[~dup] for a in (rows, ids, dists, flags))
    order = np.lexsort((ids, dists, rows))
    return _rows_from_sorted(rows[order], ids[order], dists[order],
                             flags[order], n, m, m)


def reverse(nbr, dist, flag, r: int):
    """Reference of one ``add_reverse_edges`` pass with in- and out-degree
    cap ``r``; returns (ids, dists, flags)."""
    n, m = nbr.shape
    n_slots = n_slots_for(r)
    er, ec = np.nonzero(nbr >= 0)
    u = er.astype(np.int64)
    v = nbr[er, ec].astype(np.int64)
    d = dist[er, ec]
    f = flag[er, ec]
    # in-edges of every vertex: originals (u -> v) and reversed (v -> u)
    in_row = np.concatenate([v, u])
    in_src = np.concatenate([u, v])
    in_d = np.concatenate([d, d])
    in_f = np.concatenate([f, np.full(f.size, NEW)])
    prio = np.concatenate([np.zeros(u.size, np.int64),
                           np.ones(u.size, np.int64)])
    rows, ids, ds, fs, slot = _bucket_winners(in_row, in_src, in_d, in_f, n,
                                              n_slots, prio)
    order = np.lexsort((slot, ds, rows))
    rows, ids, ds, fs = (a[order] for a in (rows, ids, ds, fs))
    start = np.ones(rows.shape, bool)
    start[1:] = rows[1:] != rows[:-1]
    rank = np.arange(rows.size) - np.maximum.accumulate(
        np.where(start, np.arange(rows.size), 0))
    ok = rank < r
    # surviving edges (src -> row), capped per source
    rows, ids, ds, fs, slot = _bucket_winners(ids[ok], rows[ok], ds[ok],
                                              fs[ok], n, n_slots)
    order = np.lexsort((slot, ds, rows))
    return _rows_from_sorted(rows[order], ids[order], ds[order], fs[order],
                             n, m, min(r, m))
