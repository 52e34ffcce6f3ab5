"""Plain reference of k-nearest-neighbour search: exact squared l2 in
float64 on the host, by blocks of queries."""
from __future__ import annotations

import numpy as np


def pair_dists(x: np.ndarray, q: np.ndarray, ids: np.ndarray,
               block: int = 512) -> np.ndarray:
    """(b, k) float64 squared l2 between query row i and corpus rows
    ids[i]; nan where ids is out of range."""
    n = x.shape[0]
    out = np.full(ids.shape, np.nan)
    for s in range(0, ids.shape[0], block):
        i = ids[s:s + block]
        ok = (i >= 0) & (i < n)
        v = x[np.where(ok, i, 0)].astype(np.float64)
        d = np.sum((v - q[s:s + block].astype(np.float64)[:, None, :]) ** 2,
                   axis=-1)
        out[s:s + block] = np.where(ok, d, np.nan)
    return out


def exact_topk(x: np.ndarray, q: np.ndarray, k: int,
               block: int = 256) -> np.ndarray:
    """(b, k) ids of each query's k nearest corpus rows, nearest first."""
    x64 = x.astype(np.float64)
    xx = np.sum(x64 * x64, axis=1)
    out = []
    for s in range(0, q.shape[0], block):
        qb = q[s:s + block].astype(np.float64)
        d = xx[None, :] - 2.0 * (qb @ x64.T)
        part = np.argpartition(d, k, axis=1)[:, :k]
        order = np.argsort(np.take_along_axis(d, part, 1), axis=1)
        out.append(np.take_along_axis(part, order, 1))
    return np.concatenate(out)


def bad_ids(ids: np.ndarray, n: int) -> int:
    """Returned entries that are no corpus row, or repeat within a row."""
    bad = (ids < 0) | (ids >= n)
    s = np.sort(ids, axis=1)
    rep = np.zeros_like(bad)
    rep[:, 1:] = s[:, 1:] == s[:, :-1]
    return int(bad.sum() + rep.sum())


def recall(ids: np.ndarray, exact: np.ndarray) -> float:
    """Mean share of each query's exact neighbours among its results."""
    k = exact.shape[1]
    hits = [len(np.intersect1d(a[:k], b)) for a, b in zip(ids, exact)]
    return float(np.mean(hits)) / k
