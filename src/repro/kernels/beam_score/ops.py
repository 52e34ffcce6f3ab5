"""Jit'd wrapper: pad + kernel dispatch for the fused gather+score beam step."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.beam_score.kernel import (
    beam_score_int8_tiles,
    beam_score_pq_tiles,
    beam_score_tiles,
    block_layout,
    block_layout_int8,
    block_layout_pq,
)
from repro.kernels.beam_score.ref import (
    beam_score_int8_ref,
    beam_score_pq_ref,
    beam_score_ref,
)


def _prefix(neighbors, u, k, tile_b):
    """Eq. 4 prefix ``neighbors[u][:, :k]`` of each lane, padded to a whole
    number of lane tiles (padded lanes read row 0; they are sliced off)."""
    b = u.shape[0]
    tile_b = max(1, min(tile_b, b))
    pad = (-b) % tile_b
    u_p = jnp.pad(u.astype(jnp.int32), (0, pad))
    return neighbors[u_p][:, :k], tile_b, pad


def _tile_rows(a):
    """Pad a packed-dtype corpus (bf16, int8, uint8) to whole (sublane,
    lane) tiles of rows: the kernel reads such rows by loading their aligned
    tile, which must lie inside the array (see ``kernel._load_row``)."""
    per_tile = 32 // a.dtype.itemsize
    pad = (-a.shape[0]) % per_tile
    return jnp.pad(a, ((0, pad), (0, 0))) if pad else a


def _finish(nbrs, dist, b):
    """Mask padded adjacency slots to (-1, +inf) and attach the uint32 key;
    ``dists`` is decoded back from the key (the exact inverse transform)."""
    from repro.core import graph as G  # deferred: core imports this package

    nbrs, dist = nbrs[:b], dist[:b]
    valid = nbrs >= 0
    keys = G.dist_key(jnp.where(valid, dist, jnp.inf))
    return jnp.where(valid, nbrs, -1), G.key_dist(keys), keys


@functools.partial(jax.jit, static_argnames=("k", "metric", "tile_b",
                                             "interpret", "gram_dtype"))
def beam_score(
    x: jnp.ndarray,
    neighbors: jnp.ndarray,
    u: jnp.ndarray,
    queries: jnp.ndarray,
    k: int,
    metric: str = "l2",
    tile_b: int = 64,
    interpret: bool | None = None,
    gram_dtype: str = "f32",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused one-step beam expansion: gather ``neighbors[u][:, :k]``, gather
    their vectors from ``x``, score against ``queries``.

    Returns ``(ids, dists, keys)``, each (B, k): int32 neighbor ids (-1 for
    padded adjacency slots), f32 distances (+inf for padded slots), and the
    monotone uint32 sort key per candidate. ``dists`` is decoded from ``keys``
    via the exact inverse transform, so it is bitwise-equal to the oracle's
    f32 distances.

    ``gram_dtype="bf16"`` gathers the neighbor vectors in bfloat16 (the
    rng_prune convention — halves the gather traffic; the kernel upcasts to
    f32 before scoring). ``tile_b`` sizes the kernel's lane tile: VMEM holds
    a (tile_b, k, d) f32 gathered block per grid step.
    """
    b = u.shape[0]
    k = min(k, neighbors.shape[1])
    if gram_dtype == "bf16":
        x = x.astype(jnp.bfloat16)
    nbrs, tile_b, pad = _prefix(neighbors, u, k, tile_b)
    q_p = jnp.pad(queries, ((0, pad), (0, 0)))
    dist = beam_score_tiles(nbrs, q_p, _tile_rows(x), metric=metric,
                            tile_b=tile_b,
                            interpret=interpret)
    return _finish(nbrs, dist, b)


@functools.partial(jax.jit, static_argnames=("k", "metric", "tile_b",
                                             "interpret"))
def beam_score_int8(
    codes: jnp.ndarray,
    scale: jnp.ndarray,
    zero: jnp.ndarray,
    neighbors: jnp.ndarray,
    u: jnp.ndarray,
    queries: jnp.ndarray,
    k: int,
    metric: str = "l2",
    tile_b: int = 64,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused beam expansion over an int8 corpus held in VMEM (a quarter of
    the f32 corpus's bytes): gathers (tile_b, k, d) *code* rows and
    dequantizes in-register
    inside the shared ``repro.quant.int8_score_block``. Same contract and
    return shape as :func:`beam_score`; bitwise-equal to
    :func:`beam_score_int8_ref`."""
    b = u.shape[0]
    k = min(k, neighbors.shape[1])
    nbrs, tile_b, pad = _prefix(neighbors, u, k, tile_b)
    q_p = jnp.pad(queries, ((0, pad), (0, 0)))
    dist = beam_score_int8_tiles(nbrs, q_p, _tile_rows(codes), scale[None, :],
                                 zero[None, :], metric=metric, tile_b=tile_b,
                                 interpret=interpret)
    return _finish(nbrs, dist, b)


@functools.partial(jax.jit, static_argnames=("k", "metric", "tile_b",
                                             "interpret"))
def beam_score_pq(
    codes: jnp.ndarray,
    neighbors: jnp.ndarray,
    u: jnp.ndarray,
    lut_a: jnp.ndarray,
    lut_b: jnp.ndarray,
    qsq: jnp.ndarray,
    k: int,
    metric: str = "l2",
    tile_b: int = 64,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused beam expansion over a PQ corpus: the caller computes the
    query-to-centroid LUT once per query batch (``repro.quant.pq_lut`` —
    it is loop-invariant across beam iterations) and the kernel scores the
    gathered (tile_b, k, m) uint8 code block by pure gather-accumulate
    (``repro.quant.pq_score_codes``, shared with
    :func:`beam_score_pq_ref`). Same contract as :func:`beam_score`.

    Interpret mode only: Mosaic does not lower the in-kernel LUT gather, so
    on a compiled backend this raises ValueError (use ``use_pallas=False``,
    the XLA path)."""
    if interpret is None:
        interpret = default_interpret()
    if not interpret:
        raise ValueError(
            "beam_score_pq does not compile for TPU: Mosaic lowers no 3-D "
            "gather (the PQ lookup-table read); search with "
            "SearchConfig(use_pallas=False) for PQ corpora")
    b = u.shape[0]
    k = min(k, neighbors.shape[1])
    nbrs, tile_b, pad = _prefix(neighbors, u, k, tile_b)
    lut_a_p = jnp.pad(lut_a, ((0, pad), (0, 0), (0, 0)))
    qsq_p = jnp.pad(qsq, (0, pad))[:, None]
    dist = beam_score_pq_tiles(nbrs, lut_a_p, lut_b, qsq_p, _tile_rows(codes),
                               metric=metric, tile_b=tile_b,
                               interpret=interpret)
    return _finish(nbrs, dist, b)


def _spec(name, ins, outs, shapes, grid, tiles_fn, low_precision=()):
    from repro.kernels.spec import BlockMeta, KernelSpec

    meta = lambda trips: tuple(
        BlockMeta(nm, shapes[nm][0], bs, shapes[nm][1], im)
        for nm, bs, im in trips)

    def trace():
        args = [jax.ShapeDtypeStruct(*shapes[nm]) for nm, _, _ in ins]
        return jax.make_jaxpr(tiles_fn)(*args)

    return KernelSpec(name=name, grid=grid, inputs=meta(ins),
                      outputs=meta(outs), trace=trace,
                      low_precision_inputs=low_precision)


def kernel_spec(*, b: int = 128, n: int = 1024, d: int = 64, k: int = 16,
                tile_b: int = 64, metric: str = "l2", gram_dtype: str = "f32"):
    """Static :class:`repro.kernels.spec.KernelSpec` for one problem size —
    consumed by ``repro.analysis.kernel_check`` (VMEM bound, index-map
    in-bounds proof, f32-accumulator rule under ``gram_dtype="bf16"``)."""
    xdt = jnp.bfloat16 if gram_dtype == "bf16" else jnp.float32
    ins, outs = block_layout(b, n, d, k, tile_b)
    shapes = {
        "nbrs": ((b, k), jnp.int32),
        "queries": ((b, d), jnp.float32),
        "x": ((n, d), xdt),
        "dist": ((b, k), jnp.float32),
    }
    return _spec(
        f"beam_score[{metric},{gram_dtype}]", ins, outs, shapes,
        (b // tile_b,), functools.partial(
            beam_score_tiles, metric=metric, tile_b=tile_b,
            interpret=True,  # repo-lint: allow-interpret (abstract trace only)
        ), ("x",) if gram_dtype == "bf16" else ())


def kernel_spec_int8(*, b: int = 256, n: int = 2048, d: int = 128,
                     k: int = 32, tile_b: int = 64, metric: str = "l2"):
    """Spec for the int8 decode+score variant. ``codes`` is declared a
    low-precision input: the checker proves the body upcasts to the f32
    accumulator (the in-register dequantize) before any arithmetic."""
    ins, outs = block_layout_int8(b, n, d, k, tile_b)
    shapes = {
        "nbrs": ((b, k), jnp.int32),
        "queries": ((b, d), jnp.float32),
        "codes": ((n, d), jnp.int8),
        "scale": ((1, d), jnp.float32),
        "zero": ((1, d), jnp.float32),
        "dist": ((b, k), jnp.float32),
    }
    return _spec(
        f"beam_score_int8[{metric}]", ins, outs, shapes, (b // tile_b,),
        functools.partial(
            beam_score_int8_tiles, metric=metric, tile_b=tile_b,
            interpret=True,  # repo-lint: allow-interpret (abstract trace only)
        ), ("codes",))


def kernel_spec_pq(*, b: int = 256, n: int = 2048, mq: int = 32, k: int = 32,
                   tile_b: int = 64, metric: str = "l2"):
    """Spec for the PQ LUT-gather variant. ``codes`` are table *indices*
    (uint8 -> int32 for the gather, never to a float): no arithmetic ever
    touches them, so no low-precision input is declared and the checker's
    dot rules see only the f32 LUT reductions."""
    ins, outs = block_layout_pq(b, n, mq, k, tile_b)
    shapes = {
        "nbrs": ((b, k), jnp.int32),
        "lut_a": ((b, mq, 256), jnp.float32),
        "lut_b": ((mq, 256), jnp.float32),
        "qsq": ((b, 1), jnp.float32),
        "codes": ((n, mq), jnp.uint8),
        "dist": ((b, k), jnp.float32),
    }
    return _spec(
        f"beam_score_pq[{metric}]", ins, outs, shapes, (b // tile_b,),
        functools.partial(
            beam_score_pq_tiles, metric=metric, tile_b=tile_b,
            interpret=True,  # repo-lint: allow-interpret (abstract trace only)
        ))


def default_specs():
    """Representative spec instances checked in CI: the docstring's VMEM
    budget point (tile_b=64, K=32, d=128) in both gram dtypes and metrics,
    plus the int8 and PQ decode variants at the same point (PQ at the
    d=128 -> m=32 compression the acceptance table records)."""
    return [
        kernel_spec(b=256, n=2048, d=128, k=32, tile_b=64, metric="l2",
                    gram_dtype="f32"),
        kernel_spec(b=256, n=2048, d=128, k=32, tile_b=64, metric="cos",
                    gram_dtype="bf16"),
        kernel_spec(b=64, n=512, d=32, k=8, tile_b=64, metric="ip",
                    gram_dtype="f32"),
        kernel_spec_int8(b=256, n=2048, d=128, k=32, tile_b=64, metric="l2"),
        kernel_spec_int8(b=64, n=512, d=32, k=8, tile_b=64, metric="ip"),
        kernel_spec_pq(b=256, n=2048, mq=32, k=32, tile_b=64, metric="l2"),
        kernel_spec_pq(b=256, n=2048, mq=32, k=32, tile_b=64, metric="cos"),
    ]


__all__ = [
    "beam_score", "beam_score_ref", "beam_score_int8", "beam_score_int8_ref",
    "beam_score_pq", "beam_score_pq_ref", "kernel_spec", "kernel_spec_int8",
    "kernel_spec_pq", "default_specs",
]
