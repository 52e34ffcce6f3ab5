"""Evaluation utilities: brute-force ground truth, recall, degree stats."""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distances as D
from repro.core import graph as G


def ground_truth(
    x: jnp.ndarray, queries: jnp.ndarray, k: int = 1, metric: str = "l2",
    tile: int = 1024, use_pallas: bool = False,
    valid: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact top-k via tiled brute force (optionally the Pallas distance tile).

    ``valid``: optional (n,) bool mask — masked rows (tombstones, capacity
    padding in a streaming store) are excluded from the ground truth, so
    churn benchmarks measure recall against the *surviving* corpus. When
    fewer than k rows are valid the tail pads with (+inf, -1)."""
    if use_pallas:
        from repro.kernels.pairwise_l2 import ops as pl2
        d = pl2.pairwise_l2(queries, x)
        if valid is not None:
            d = jnp.where(valid[None, :], d, jnp.inf)
        neg, idx = jax.lax.top_k(-d, k)
        return -neg, jnp.where(neg > -jnp.inf, idx, -1)
    if valid is not None:
        # masked fused tile-top-k (mirrors pairwise_tiled's k-path): only one
        # (tile, n) distance block is ever live, never the full (Q, n) matrix
        # — churn evaluation stays feasible at the corpus sizes the streaming
        # store targets
        nq = queries.shape[0]
        pad = (-nq) % tile
        q_tiles = jnp.pad(queries, ((0, pad), (0, 0))).reshape(
            -1, tile if nq else 1, queries.shape[1])

        def tile_topk(t):
            d = jnp.where(valid[None, :], D.pairwise(t, x, metric), jnp.inf)
            neg, idx = jax.lax.top_k(-d, k)
            return -neg, jnp.where(neg > -jnp.inf, idx, -1)

        d, idx = jax.lax.map(tile_topk, q_tiles)
        return d.reshape(-1, k)[:nq], idx.reshape(-1, k)[:nq]
    return D.pairwise_tiled(queries, x, metric, tile_a=tile, k=k)


def filtered_ground_truth(
    x, queries, labels, filters, k: int = 1, metric: str = "l2",
    valid=None,
) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k under a per-query label predicate: the plain reference of
    ``search(labels=, filters=)``. Brute force on the host in float64, one
    query at a time, over the rows whose label word holds every bit of the
    query's mask (``(label & mask) == mask``) and, with ``valid``, that are
    live. No graph, tiles or batching. Returns (dists, ids), (B, k) numpy,
    nearest first, padded (+inf, -1) when fewer than k rows pass."""
    x = np.asarray(x, np.float64)
    q = np.asarray(queries, np.float64)
    lab = np.asarray(labels).astype(np.uint32)
    masks = np.asarray(filters).astype(np.uint32)
    live = np.ones(x.shape[0], bool) if valid is None else np.asarray(valid)
    if metric == "cos":
        x = x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)
        q = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
    out_d = np.full((q.shape[0], k), np.inf)
    out_i = np.full((q.shape[0], k), -1, np.int64)
    for i, (qi, m) in enumerate(zip(q, masks)):
        if metric == "l2":
            d = np.sum((x - qi) ** 2, axis=1)
        elif metric == "ip":
            d = -(x @ qi)
        elif metric == "cos":
            d = 1.0 - x @ qi
        else:
            raise ValueError(f"unknown metric {metric!r}")
        d = np.where(((lab & m) == m) & live, d, np.inf)
        order = np.argsort(d, kind="stable")[:k]
        ok = np.isfinite(d[order])
        out_d[i, :ok.sum()] = d[order][ok]
        out_i[i, :ok.sum()] = order[ok]
    return out_d, out_i


def recall_at_k(pred_ids: jnp.ndarray, gt_ids: jnp.ndarray) -> float:
    """Fraction of queries whose true NN (gt column 0) appears in pred."""
    hit = jnp.any(pred_ids == gt_ids[:, :1], axis=1)
    return float(jnp.mean(hit))


def recall_topk(
    pred_ids: jnp.ndarray, gt_ids: jnp.ndarray,
    valid: jnp.ndarray | None = None,
) -> float:
    """Set recall: mean fraction of the true top-k (all gt columns) present in
    pred — the paper's recall@k, as opposed to :func:`recall_at_k`'s
    1-NN-in-top-k.

    ``valid``: optional (n,) bool mask for churned corpora — deleted /
    padded ids are excluded from both sides: a masked gt column leaves the
    denominator (the true top-k over survivors may be shorter than k), and a
    masked prediction can never score a hit. Queries with no valid gt column
    drop out of the mean entirely."""
    if valid is None:
        hit = jnp.any(pred_ids[:, :, None] == gt_ids[:, None, :], axis=1)
        return float(jnp.mean(jnp.mean(hit, axis=1)))
    gt_ok = (gt_ids >= 0) & valid[jnp.maximum(gt_ids, 0)]
    pred_ok = (pred_ids >= 0) & valid[jnp.maximum(pred_ids, 0)]
    match = (pred_ids[:, :, None] == gt_ids[:, None, :]) & pred_ok[:, :, None]
    hit = jnp.any(match, axis=1) & gt_ok
    denom = jnp.sum(gt_ok, axis=1)
    per_q = jnp.sum(hit, axis=1) / jnp.maximum(denom, 1)
    any_gt = denom > 0
    return float(jnp.sum(jnp.where(any_gt, per_q, 0.0))
                 / jnp.maximum(jnp.sum(any_gt), 1))


def evaluate_search(
    x: jnp.ndarray,
    g: G.Graph,
    queries: jnp.ndarray,
    gt_ids: jnp.ndarray,
    cfg,
    entry_points: jnp.ndarray | None = None,
    tile_b: int = 256,
    repeats: int = 2,
    valid: jnp.ndarray | None = None,
) -> dict:
    """Recall@k + QPS over the tiled serving driver (``search_tiled``).

    Returns recall, queries/sec (best of ``repeats``, compile excluded by the
    warmup repeat), the peak visited-state footprint of one query tile — the
    number that is now independent of the corpus size in hashed mode — and
    which beam inner-loop implementation served (``cfg.use_pallas`` selects
    the fused Pallas gather+score kernel; results are bitwise-identical
    either way).

    ``valid``: optional (n,) tombstone/padding mask for churned corpora —
    threads through serving (masked ids traverse but never surface), seeds
    the default entry point from live rows only, and scores recall with the
    masked :func:`recall_at_k` semantics (pass gt computed with the same
    mask via :func:`ground_truth`)."""
    from repro.core import search as S

    if entry_points is None:
        entry_points = S.default_entry_point(x, cfg.metric, valid=valid)
    sec, (ids, _) = timed(
        S.search_tiled, x, g, queries, entry_points, cfg, tile_b=tile_b,
        valid=valid, repeats=repeats)
    lanes = min(tile_b, queries.shape[0])
    return {
        "recall_at_1": recall_at_k(ids, gt_ids),
        "recall_topk": recall_topk(ids, gt_ids, valid=valid),
        "qps": queries.shape[0] / sec,
        "visited_mode": cfg.visited,
        "visited_bytes_per_tile": S.visited_state_bytes(cfg, x.shape[0], lanes),
        "search_path": "pallas-fused" if cfg.use_pallas else "jnp-ref",
    }


def degree_stats(g: G.Graph) -> dict:
    out_d = np.asarray(G.out_degrees(g))
    in_d = np.asarray(G.in_degrees(g))
    return {
        "avg_out_degree": float(out_d.mean()),
        "max_out_degree": int(out_d.max()),
        "avg_in_degree": float(in_d.mean()),
        "max_in_degree": int(in_d.max()),
        "out_degree_hist": np.bincount(out_d, minlength=1).tolist(),
    }


def connectivity_lower_bound(g: G.Graph, entry: int, iters: int = 64) -> float:
    """Fraction of vertices reachable from ``entry`` within ``iters`` BFS
    frontier expansions (vectorized dense BFS — exact for small graphs)."""
    n = g.n
    reach = jnp.zeros((n,), bool).at[entry].set(True)

    def body(_, reach):
        nbrs = jnp.where(g.neighbors >= 0, g.neighbors, 0)
        frontier = reach[:, None] & (g.neighbors >= 0)
        marks = jnp.zeros((n,), bool).at[nbrs.reshape(-1)].max(frontier.reshape(-1))
        return reach | marks

    reach = jax.lax.fori_loop(0, iters, body, reach)
    return float(jnp.mean(reach))


def timed(fn: Callable, *args, repeats: int = 1, **kw) -> tuple[float, object]:
    """Wall-clock a blocking call (best of ``repeats``); returns (sec, result).
    Each repeat lands on the obs trace as an ``eval/timed`` span when
    tracing is on (repro.obs.trace.timed measures unconditionally)."""
    from repro.obs import trace

    name = getattr(fn, "__name__", type(fn).__name__)
    best, out = float("inf"), None
    for _ in range(repeats):
        with trace.timed("eval/timed", fn=name) as tm:
            out = jax.block_until_ready(fn(*args, **kw))
        best = min(best, tm.seconds)
    return best, out
