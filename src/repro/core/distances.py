"""Distance computation — the compute hot spot of every graph-ANN algorithm.

All routines operate on fp32 (configurable) and express pairwise distances as
GEMMs so that XLA maps them onto the MXU:  ||a-b||^2 = ||a||^2 + ||b||^2 - 2ab.
Tiled variants bound the materialized distance block.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

Metric = str  # "l2" (squared), "ip" (negative inner product), "cos"

# Every distance dot runs at full f32 precision. The TPU default for an f32
# matmul rounds its inputs to bfloat16, which at ||a||^2 + ||b||^2 - 2ab
# cancels most of the distance between near neighbours; the CPU ignores
# the setting, so CPU results are unchanged.
HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.matmul(a, b, precision=HIGHEST)


def _sqnorm(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(x * x, axis=-1)


def pairwise(a: jnp.ndarray, b: jnp.ndarray, metric: Metric = "l2") -> jnp.ndarray:
    """Dense (na, nb) distance matrix. Smaller is closer for every metric."""
    if metric == "l2":
        # max(., 0) guards tiny negative values from cancellation.
        d = _sqnorm(a)[:, None] + _sqnorm(b)[None, :] - 2.0 * _mm(a, b.T)
        return jnp.maximum(d, 0.0)
    if metric == "ip":
        return -_mm(a, b.T)
    if metric == "cos":
        an = a / jnp.maximum(jnp.linalg.norm(a, axis=-1, keepdims=True), 1e-12)
        bn = b / jnp.maximum(jnp.linalg.norm(b, axis=-1, keepdims=True), 1e-12)
        return 1.0 - _mm(an, bn.T)
    raise ValueError(f"unknown metric {metric!r}")


def point_to_points(q: jnp.ndarray, xs: jnp.ndarray, metric: Metric = "l2") -> jnp.ndarray:
    """Distances from a single query (d,) to a set (m, d) -> (m,)."""
    return pairwise(q[None, :], xs, metric)[0]


def batched_gram(vecs: jnp.ndarray, metric: Metric = "l2") -> jnp.ndarray:
    """(..., m, d) -> (..., m, m) pairwise distances within each group.

    This is the inner kernel of the RNG-prune scan: each vertex's gathered
    neighbor block forms a small Gram matrix that lives in VMEM on TPU.
    """
    if metric == "l2":
        # f32 accumulation regardless of input dtype (bf16 inputs halve the
        # gather/Gram HBM traffic; the MXU accumulates f32 natively)
        sq = jnp.sum(jnp.square(vecs), axis=-1, dtype=jnp.float32)
        g = jnp.einsum("...md,...nd->...mn", vecs, vecs,
                       preferred_element_type=jnp.float32, precision=HIGHEST)
        return jnp.maximum(sq[..., :, None] + sq[..., None, :] - 2.0 * g, 0.0)
    if metric == "ip":
        return -jnp.einsum("...md,...nd->...mn", vecs, vecs,
                           precision=HIGHEST)
    if metric == "cos":
        n = vecs / jnp.maximum(jnp.linalg.norm(vecs, axis=-1, keepdims=True), 1e-12)
        return 1.0 - jnp.einsum("...md,...nd->...mn", n, n,
                                precision=HIGHEST)
    raise ValueError(f"unknown metric {metric!r}")


def pairwise_tiled(
    a: jnp.ndarray,
    b: jnp.ndarray,
    metric: Metric = "l2",
    tile_a: int = 1024,
    reduce_fn: Callable[[jnp.ndarray, jnp.ndarray], tuple[jnp.ndarray, ...]] | None = None,
    k: int | None = None,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray]:
    """Tiled pairwise distances; optionally fused row-top-k to avoid the
    (na, nb) materialization (brute-force ground truth at scale).

    Returns the full matrix when ``k is None`` else ``(dists, idx)`` of shape
    (na, k) with ascending distances.
    """
    na = a.shape[0]
    pad = (-na) % tile_a
    a_pad = jnp.pad(a, ((0, pad), (0, 0)))
    a_tiles = a_pad.reshape(-1, tile_a, a.shape[1])

    if k is None:
        out = jax.lax.map(lambda t: pairwise(t, b, metric), a_tiles)
        return out.reshape(-1, b.shape[0])[:na]

    def tile_topk(t):
        d = pairwise(t, b, metric)
        neg_d, idx = jax.lax.top_k(-d, k)
        return -neg_d, idx

    d, idx = jax.lax.map(tile_topk, a_tiles)
    return d.reshape(-1, k)[:na], idx.reshape(-1, k)[:na]


# Pair block of gather_dists: the gathered rows of one block take
# 2 * 2^18 * d * 4 bytes (256 MiB at d=128 f32), whatever the pair count.
# The whole-array gather of RandomGraph(S=20) at 1M rows would hold two
# (20M, 128) f32 copies of x (19 GB), more than a 16 GB chip.
GATHER_BLOCK = 1 << 18


def _pair_dists(x: jnp.ndarray, u: jnp.ndarray, v: jnp.ndarray,
                metric: Metric) -> jnp.ndarray:
    xu = x[jnp.maximum(u, 0)]
    xv = x[jnp.maximum(v, 0)]
    if metric == "l2":
        diff = xu - xv
        d = jnp.sum(diff * diff, axis=-1)
    elif metric == "ip":
        d = -jnp.sum(xu * xv, axis=-1)
    elif metric == "cos":
        nu = xu / jnp.maximum(jnp.linalg.norm(xu, axis=-1, keepdims=True), 1e-12)
        nv = xv / jnp.maximum(jnp.linalg.norm(xv, axis=-1, keepdims=True), 1e-12)
        d = 1.0 - jnp.sum(nu * nv, axis=-1)
    else:
        raise ValueError(f"unknown metric {metric!r}")
    return jnp.where((u < 0) | (v < 0), jnp.inf, d)


@functools.partial(jax.jit, static_argnames=("metric",))
def gather_dists(x: jnp.ndarray, u: jnp.ndarray, v: jnp.ndarray, metric: Metric = "l2") -> jnp.ndarray:
    """Distances between row pairs (x[u[i]], x[v[i]]). Invalid (-1) ids -> +inf.

    More than ``GATHER_BLOCK`` pairs are scored block by block (each pair's
    distance is computed alone, so the blocking changes no result)."""
    p = u.shape[0]
    if p <= GATHER_BLOCK:
        return _pair_dists(x, u, v, metric)
    pad = (-p) % GATHER_BLOCK
    blocks = tuple(jnp.pad(a, (0, pad), constant_values=-1)
                   .reshape(-1, GATHER_BLOCK) for a in (u, v))
    d = jax.lax.map(lambda uv: _pair_dists(x, *uv, metric), blocks)
    return d.reshape(-1)[:p]
