"""Plain reference of filtered k-nearest-neighbour search: for each query,
exact squared l2 in float64 on the host over the corpus rows whose label
word holds every bit of the query's mask, by blocks of queries."""
from __future__ import annotations

import numpy as np


def passes(labels: np.ndarray, masks: np.ndarray, ids: np.ndarray
           ) -> np.ndarray:
    """(b, k) bool: corpus row ids[i, j] passes query i's mask
    (``(label & mask) == mask``); False where ids is out of range."""
    n = labels.shape[0]
    ok = (ids >= 0) & (ids < n)
    lab = labels.astype(np.uint32)[np.where(ok, ids, 0)]
    m = masks.astype(np.uint32)[:, None]
    return ok & ((lab & m) == m)


def violations(labels: np.ndarray, masks: np.ndarray, ids: np.ndarray
               ) -> int:
    """Returned corpus rows (ids >= 0) that fail their query's mask."""
    return int(np.sum((ids >= 0) & ~passes(labels, masks, ids)))


def exact_topk(x: np.ndarray, q: np.ndarray, labels: np.ndarray,
               masks: np.ndarray, k: int, block: int = 128) -> np.ndarray:
    """(b, k) ids of each query's k nearest passing rows, nearest first
    (-1 where fewer than k rows pass)."""
    x64 = x.astype(np.float64)
    xx = np.sum(x64 * x64, axis=1)
    lab = labels.astype(np.uint32)
    out = []
    for s in range(0, q.shape[0], block):
        qb = q[s:s + block].astype(np.float64)
        m = masks.astype(np.uint32)[s:s + block, None]
        d = xx[None, :] - 2.0 * (qb @ x64.T)
        d = np.where((lab[None, :] & m) == m, d, np.inf)
        part = np.argpartition(d, k, axis=1)[:, :k]
        pd = np.take_along_axis(d, part, 1)
        order = np.argsort(pd, axis=1)
        ids = np.take_along_axis(part, order, 1)
        out.append(np.where(np.isfinite(np.take_along_axis(pd, order, 1)),
                            ids, -1))
    return np.concatenate(out)
