"""Pure-jnp oracle for the fused gather+score beam kernel.

:func:`score_block` is the single source of truth for the scoring math: the
Pallas kernel body imports and calls it on its VMEM tile, so the fused path
and this oracle execute the *same* op sequence (same einsum contraction, same
clamps) on f32 inputs. That is what makes the bitwise id/key parity asserted
in tests/test_beam_score.py an equality, not a tolerance.

``gram_dtype`` follows the rng_prune convention: ``"bf16"`` means the
neighbor vectors are *gathered* in bfloat16 (halving gather HBM traffic);
everything is upcast to f32 before any arithmetic, so accumulation precision
is unchanged and only the stored-vector precision differs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# full-f32 dots (the TPU default rounds f32 matmul inputs to bfloat16;
# core/distances.py says why that breaks near-neighbour distances)
HIGHEST = jax.lax.Precision.HIGHEST


def _sqsum(a: jnp.ndarray) -> jnp.ndarray:
    """(..., d) -> (...) squared norms as one batched dot. Each row is its
    own (1, d) x (1, d) batch entry: Mosaic lowers no dot without a
    non-contracting dim on each side, nor one with more than one batch
    dim, so the batch dims are flattened into one."""
    rows = a.reshape(-1, 1, a.shape[-1])
    return jnp.einsum("nxd,nyd->nxy", rows, rows,
                      preferred_element_type=jnp.float32,
                      precision=HIGHEST).reshape(a.shape[:-1])


def score_block(vecs: jnp.ndarray, q: jnp.ndarray, metric: str) -> jnp.ndarray:
    """(..., K, d) gathered neighbor block x (..., d) queries -> (..., K) f32
    distances (smaller is closer for every metric). Inputs are upcast to f32
    before any arithmetic."""
    v = vecs.astype(jnp.float32)
    qq = q.astype(jnp.float32)
    # every d-reduction is an einsum/dot_general: XLA keeps dot reduction
    # order fixed across fusion contexts, where a fused jnp.sum(v*v) does
    # not — and the Pallas-interpret and pure-jnp paths must agree bitwise
    # (asserted in tests/test_beam_score.py), not just to tolerance.
    sqsum = _sqsum
    if metric == "l2":
        dot = jnp.einsum("...kd,...d->...k", v, qq,
                         preferred_element_type=jnp.float32,
                         precision=HIGHEST)
        return jnp.maximum(sqsum(qq)[..., None] + sqsum(v) - 2.0 * dot, 0.0)
    if metric == "ip":
        return -jnp.einsum("...kd,...d->...k", v, qq,
                           preferred_element_type=jnp.float32,
                           precision=HIGHEST)
    if metric == "cos":
        vn = v / jnp.maximum(jnp.sqrt(sqsum(v))[..., None], 1e-12)
        qn = qq / jnp.maximum(jnp.sqrt(sqsum(qq))[..., None], 1e-12)
        return 1.0 - jnp.einsum("...kd,...d->...k", vn, qn,
                                preferred_element_type=jnp.float32,
                                precision=HIGHEST)
    raise ValueError(f"unknown metric {metric!r}")


def beam_score_ref(
    x: jnp.ndarray,
    neighbors: jnp.ndarray,
    u: jnp.ndarray,
    queries: jnp.ndarray,
    k: int,
    metric: str = "l2",
    gram_dtype: str = "f32",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Gather + score one beam expansion step, pure jnp.

    ``u`` (B,) frontier vertex ids -> for each lane, its first ``k``
    out-neighbors from ``neighbors`` (n, M) are gathered from ``x`` and scored
    against ``queries`` (B, d). Returns ``(ids, dists, keys)`` each (B, k):
    int32 neighbor ids (-1 for padded slots), f32 distances (+inf for padded
    slots), and the monotone uint32 sort key of each distance
    (:func:`repro.core.graph.dist_key` — ready for key-ordered merge or the
    hashed visited-table probe).
    """
    # Deferred: core.search imports this package, so a module-level
    # core.graph import would make the package order-sensitive to load.
    from repro.core import graph as G

    if gram_dtype == "bf16":
        x = x.astype(jnp.bfloat16)
    nbrs = neighbors[u][:, :k]                       # Eq. 4 prefix slice
    vecs = x[jnp.maximum(nbrs, 0)]                   # (B, k, d)
    d = score_block(vecs, queries, metric)
    valid = nbrs >= 0
    d = jnp.where(valid, d, jnp.inf)
    ids = jnp.where(valid, nbrs, -1)
    return ids, d, G.dist_key(d)


def beam_score_int8_ref(
    codes: jnp.ndarray,
    scale: jnp.ndarray,
    zero: jnp.ndarray,
    neighbors: jnp.ndarray,
    u: jnp.ndarray,
    queries: jnp.ndarray,
    k: int,
    metric: str = "l2",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """int8 oracle: gather *code* rows (a quarter of the f32 gather bytes)
    and score through :func:`repro.quant.int8_score_block` — the same
    function the fused kernel body calls, so parity is bitwise."""
    from repro.core import graph as G
    from repro.quant import int8_score_block

    nbrs = neighbors[u][:, :k]
    blk = codes[jnp.maximum(nbrs, 0)]                # (B, k, d) int8
    d = int8_score_block(blk, scale, zero, queries, metric)
    valid = nbrs >= 0
    d = jnp.where(valid, d, jnp.inf)
    ids = jnp.where(valid, nbrs, -1)
    return ids, d, G.dist_key(d)


def beam_score_pq_ref(
    codes: jnp.ndarray,
    neighbors: jnp.ndarray,
    u: jnp.ndarray,
    lut_a: jnp.ndarray,
    lut_b: jnp.ndarray,
    qsq: jnp.ndarray,
    k: int,
    metric: str = "l2",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """PQ oracle: gather (B, k, m) uint8 code rows and score them against
    the per-query LUT from :func:`repro.quant.pq_lut` via
    :func:`repro.quant.pq_score_codes` — shared with the kernel body."""
    from repro.core import graph as G
    from repro.quant import pq_score_codes

    nbrs = neighbors[u][:, :k]
    blk = codes[jnp.maximum(nbrs, 0)]                # (B, k, m) uint8
    d = pq_score_codes(blk, lut_a, lut_b, qsq, metric)
    valid = nbrs >= 0
    d = jnp.where(valid, d, jnp.inf)
    ids = jnp.where(valid, nbrs, -1)
    return ids, d, G.dist_key(d)
