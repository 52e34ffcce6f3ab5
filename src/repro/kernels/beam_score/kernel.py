"""Pallas TPU kernel: fused gather + score for the beam-search inner loop.

Per lane tile: each lane's Eq. 4 neighbor prefix (tb, K) arrives in SMEM
(the caller slices ``neighbors[u][:, :K]`` — B*K int32, negligible next to
the vector rows), each neighbor id selects its vector row from ``x`` with a
``pl.ds`` ref load into a (tb, K, d) VMEM scratch, and the gathered block is
scored against the query tile (tb, d) — so the candidate block never
round-trips to HBM between the gather and the distance evaluation. The
kernel returns raw f32 distances; the wrapper masks padded slots to +inf
and encodes the monotone uint32 key (``graph.dist_key`` sign-flip
transform), which decodes back to the exact f32 distance.

Scoring calls :func:`repro.kernels.beam_score.ref.score_block` — the same
function the pure-jnp oracle uses — so fused and oracle paths share one op
sequence and the parity tests can assert bitwise equality.

VMEM budget per tile (fp32): ``x`` is a whole-array block, so the kernel
targets corpora whose vectors fit VMEM alongside the (tb, K, d) gathered
block — tb=64, K=32, d=128 -> gathered block 1 MiB. For corpora beyond VMEM
the search keeps the pure-jnp path (XLA row gathers stream from HBM);
streaming rows from HBM under this kernel is the follow-up recorded in
ROADMAP.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.beam_score.ref import score_block


def _load_row(src_ref, vid, dtype):
    """Row ``vid`` of a VMEM ref as a (1, w) block of ``dtype``. A packed
    dtype (bf16 holds 16 rows per (sublane, lane) tile, 8-bit types 32)
    cannot be sliced at an arbitrary row, so its aligned tile is loaded and
    the row picked by a one-hot max, which returns the element exactly;
    32-bit rows load directly."""
    per_tile = 8 * 4 // src_ref.dtype.itemsize
    if per_tile == 8:
        return src_ref[pl.ds(vid, 1), :].astype(dtype)
    base = pl.multiple_of(vid // per_tile * per_tile, per_tile)
    blk = src_ref[pl.ds(base, per_tile), :].astype(dtype)
    fill = (-jnp.inf if jnp.issubdtype(dtype, jnp.floating)
            else jnp.iinfo(dtype).min)
    sub = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
    return jnp.max(jnp.where(sub == vid % per_tile, blk, fill), axis=0,
                   keepdims=True)


def _gather_rows(nbrs_ref, src_ref, out_ref, dtype):
    """``out_ref[lane, j] = src_ref[max(nbrs[lane, j], 0)]`` for the whole
    lane tile: scalar ids read from SMEM address one-row ``pl.ds`` loads
    (Mosaic cannot index a vector register by a traced value). Rows are
    cast to the scratch ``dtype`` on the way in."""
    tb, k = nbrs_ref.shape

    def lane_body(lane, carry):
        def j_body(j, carry):
            vid = jnp.maximum(nbrs_ref[lane, j], 0)
            out_ref[lane, pl.ds(j, 1), :] = _load_row(src_ref, vid, dtype)
            return carry

        return jax.lax.fori_loop(0, k, j_body, carry)

    jax.lax.fori_loop(0, tb, lane_body, 0)


def _beam_score_body(nbrs_ref, q_ref, x_ref, dist_ref, vec_ref, *,
                     metric: str):
    _gather_rows(nbrs_ref, x_ref, vec_ref, jnp.float32)
    dist_ref[...] = score_block(vec_ref[...], q_ref[...], metric)


def _beam_score_int8_body(nbrs_ref, q_ref, codes_ref, scale_ref, zero_ref,
                          dist_ref, code_ref, *, metric: str):
    """int8 variant: the resident corpus is int8 codes (a quarter of the
    f32 corpus's VMEM, so four times the rows fit). The gather is not
    cheaper: each code row is read by loading its whole packed (32, 128)
    tile and selecting one row, and lands as exact f32 values in the
    (tb, k, d) scratch, since one-row stores into 8-bit VMEM tiles pack
    four rows per sublane. Dequantization happens in-register inside
    :func:`repro.quant.int8_score_block` — shared with the jnp oracle, so
    fused-vs-oracle parity is bitwise."""
    from repro.quant import int8_score_block

    _gather_rows(nbrs_ref, codes_ref, code_ref, jnp.float32)
    dist_ref[...] = int8_score_block(code_ref[...], scale_ref[...],
                                     zero_ref[...], q_ref[...], metric)


def _beam_score_pq_body(nbrs_ref, luta_ref, lutb_ref, qsq_ref, codes_ref,
                        dist_ref, code_ref, *, metric: str):
    """PQ variant: the query tile arrives pre-expanded into its
    query-to-centroid LUT (``pq_lut`` — computed once per tile, outside the
    beam loop), so scoring is a pure gather-accumulate over the (tb, k, m)
    gathered code block. No arithmetic ever touches the codes — they are
    table indices — hence no dequantize step and no low-precision-input
    declaration in the spec. The LUT lookup is a 3-D gather, which Mosaic
    does not lower: this variant runs in interpret mode only (ops.py
    refuses it on a compiled backend)."""
    from repro.quant import pq_score_codes

    _gather_rows(nbrs_ref, codes_ref, code_ref, jnp.int32)
    dist_ref[...] = pq_score_codes(code_ref[...], luta_ref[...],
                                   lutb_ref[...], qsq_ref[...][:, 0], metric)


def _block_specs(trips):
    """BlockSpecs from layout triples; the neighbor-id block lives in SMEM
    (its values are row addresses)."""
    return [pl.BlockSpec(bs, im, memory_space=pltpu.SMEM) if nm == "nbrs"
            else pl.BlockSpec(bs, im) for nm, bs, im in trips]


def block_layout(b: int, n: int, d: int, k: int, tile_b: int):
    """(inputs, outputs) block layout: ``(name, block_shape, index_map)``
    triples — the single source consumed by both ``pallas_call`` below and
    the exported spec metadata (``ops.kernel_spec``), so the statically
    checked index maps are the ones the kernel actually runs with. The lane
    tile strides over queries; the corpus is a whole-array block (the
    VMEM-resident-corpus contract in the module docstring)."""
    inputs = (
        ("nbrs", (tile_b, k), lambda i: (i, 0)),
        ("queries", (tile_b, d), lambda i: (i, 0)),
        ("x", (n, d), lambda i: (0, 0)),
    )
    return inputs, (("dist", (tile_b, k), lambda i: (i, 0)),)


def block_layout_int8(b: int, n: int, d: int, k: int, tile_b: int):
    """int8 layout: as :func:`block_layout` but the corpus block is the
    (n, d) int8 code array plus whole-block (1, d) scale / zero rows."""
    inputs = (
        ("nbrs", (tile_b, k), lambda i: (i, 0)),
        ("queries", (tile_b, d), lambda i: (i, 0)),
        ("codes", (n, d), lambda i: (0, 0)),
        ("scale", (1, d), lambda i: (0, 0)),
        ("zero", (1, d), lambda i: (0, 0)),
    )
    return inputs, (("dist", (tile_b, k), lambda i: (i, 0)),)


def block_layout_pq(b: int, n: int, mq: int, k: int, tile_b: int):
    """PQ layout: the query tile is replaced by its LUT tile
    (tile_b, mq, 256) + the query-independent (mq, 256) centroid-norm table
    + (tile_b, 1) query norms; the corpus block is the (n, mq) uint8 codes."""
    inputs = (
        ("nbrs", (tile_b, k), lambda i: (i, 0)),
        ("lut_a", (tile_b, mq, 256), lambda i: (i, 0, 0)),
        ("lut_b", (mq, 256), lambda i: (0, 0)),
        ("qsq", (tile_b, 1), lambda i: (i, 0)),
        ("codes", (n, mq), lambda i: (0, 0)),
    )
    return inputs, (("dist", (tile_b, k), lambda i: (i, 0)),)


def _call(body, ins, outs, scratch, b, tile_b, interpret, *args):
    if b % tile_b != 0:
        raise ValueError(
            f"batch {b} is not a multiple of tile_b={tile_b} (ops.py pads "
            "before dispatching here)")
    if interpret is None:
        from repro.kernels import default_interpret
        interpret = default_interpret()
    return pl.pallas_call(
        body,
        grid=(b // tile_b,),
        in_specs=_block_specs(ins),
        out_specs=_block_specs(outs)[0],
        out_shape=jax.ShapeDtypeStruct((b, outs[0][1][1]), jnp.float32),
        scratch_shapes=[scratch],
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("metric", "tile_b", "interpret"))
def beam_score_tiles(
    nbrs: jnp.ndarray,      # (B, k) int32, B % tile_b == 0, -1 padded
    queries: jnp.ndarray,   # (B, d)
    x: jnp.ndarray,         # (n, d)
    metric: str, tile_b: int, interpret: bool | None = None,
) -> jnp.ndarray:
    """Returns the (B, k) f32 distances of every gathered row (padded
    slots score row 0; the caller masks them)."""
    (b, k), (n, d) = nbrs.shape, x.shape
    ins, outs = block_layout(b, n, d, k, tile_b)
    return _call(functools.partial(_beam_score_body, metric=metric), ins,
                 outs, pltpu.VMEM((tile_b, k, d), jnp.float32), b, tile_b,
                 interpret, nbrs, queries, x)


@functools.partial(jax.jit, static_argnames=("metric", "tile_b", "interpret"))
def beam_score_int8_tiles(
    nbrs: jnp.ndarray,      # (B, k) int32, B % tile_b == 0
    queries: jnp.ndarray,   # (B, d)
    codes: jnp.ndarray,     # (n, d) int8
    scale: jnp.ndarray,     # (1, d) f32
    zero: jnp.ndarray,      # (1, d) f32
    metric: str, tile_b: int, interpret: bool | None = None,
) -> jnp.ndarray:
    """Returns the (B, k) f32 distances (see :func:`beam_score_tiles`)."""
    (b, k), (n, d) = nbrs.shape, codes.shape
    ins, outs = block_layout_int8(b, n, d, k, tile_b)
    return _call(functools.partial(_beam_score_int8_body, metric=metric), ins,
                 outs, pltpu.VMEM((tile_b, k, d), jnp.float32), b, tile_b,
                 interpret, nbrs, queries, codes, scale, zero)


@functools.partial(jax.jit, static_argnames=("metric", "tile_b", "interpret"))
def beam_score_pq_tiles(
    nbrs: jnp.ndarray,      # (B, k) int32, B % tile_b == 0
    lut_a: jnp.ndarray,     # (B, mq, 256) f32
    lut_b: jnp.ndarray,     # (mq, 256) f32
    qsq: jnp.ndarray,       # (B, 1) f32
    codes: jnp.ndarray,     # (n, mq) uint8
    metric: str, tile_b: int, interpret: bool | None = None,
) -> jnp.ndarray:
    """Returns the (B, k) f32 distances (see :func:`beam_score_tiles`)."""
    (b, k), (n, mq) = nbrs.shape, codes.shape
    ins, outs = block_layout_pq(b, n, mq, k, tile_b)
    return _call(functools.partial(_beam_score_pq_body, metric=metric), ins,
                 outs, pltpu.VMEM((tile_b, k, mq), jnp.int32), b, tile_b,
                 interpret, nbrs, lut_a, lut_b, qsq, codes)
