"""Pallas TPU kernel: fused candidate-Gram + triangular RNG prune (Alg. 4 core).

Per vertex tile: the gathered neighbor block (tc, M, d) enters VMEM once; the
(tc, M, M) candidate-pair distance Gram is produced on the MXU and consumed
*in place* by the sequential keep/redirect scan — it never reaches HBM. This
is the TPU-native rethink of the paper's per-pair scalar distance evaluations:
the CPU code's early-exit saves distance computations; on TPU distances are
effectively free on the MXU and the win is avoiding HBM traffic for the Gram.

VMEM budget per tile (fp32): tc=8, M=128, d=960 -> vecs 3.9 MiB (7.9 MiB
double-buffered) + pair scratch 0.5 MiB + scan state < 16 MiB.

The neighbor gather itself stays outside the kernel (XLA's native gather is
already bandwidth-optimal on TPU for row gathers; Pallas adds nothing there).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _prune_scan(ids, dists, flags, vecs, pair_ref):
    """Shared Gram + keep/redirect scan over an f32 (tc, M, d) candidate
    block — the body tail for both the f32/bf16 and the int8-decode
    variants (int8 only changes how ``vecs`` got into registers).

    Written for Mosaic: the candidate-pair block goes to the VMEM scratch
    ``pair_ref`` (tc, M, M) and the scan reads row i with a ref load; every
    per-column read, write and gather along the candidate (lane) axis is a
    one-hot ``iota`` select reduced by max/min, which picks one element
    exactly. ``flags`` and the returned ``keep`` are int32 (an 8-row 8-bit
    block would sit below the 8-bit (32, 128) tiling)."""
    tc, m = ids.shape
    sq = jnp.broadcast_to(jnp.sum(vecs * vecs, axis=-1, keepdims=True),
                          (tc, m, m))                    # [c, i, j] = |v_i|^2
    gram = jax.lax.dot_general(                          # (tc, M, M) on the MXU
        vecs, vecs, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST,
    )
    pair = jnp.maximum(sq + jnp.swapaxes(sq, 1, 2) - 2.0 * gram, 0.0)
    valid = ids >= 0

    def both(mask):                                      # mask_i & mask_j
        v = mask.astype(jnp.int32)
        return (v[:, :, None] * v[:, None, :]) > 0

    big = jnp.float32(3.4e38)                           # +inf stand-in (VMEM-safe)
    pair = jnp.where(both(valid), pair, big)
    # old-old pairs are exempt: NaN never satisfies `<=`, so an exempt pair
    # can never fail (and a failing pair, the only kind read back, is real)
    pair_ref[...] = jnp.where(both(flags == 0), jnp.nan, pair)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tc, m), 1)
    valid_i32 = valid.astype(jnp.int32)
    i32_min = jnp.int32(jnp.iinfo(jnp.int32).min)

    def pick(onehot, v, fill):
        return jnp.max(jnp.where(onehot, v, fill), axis=1, keepdims=True)

    def body(i, carry):
        keep, red_w, red_d = carry
        row = pair_ref[:, i, :]                          # pair[:, i, :]
        col_i = lane == i
        d_i = pick(col_i, dists, -jnp.inf)               # dists[:, i]
        valid_i = pick(col_i, valid_i32, 0) > 0          # valid[:, i]
        fail = (keep > 0) & (row <= d_i)
        any_fail = (jnp.max(fail.astype(jnp.int32), axis=1, keepdims=True)
                    > 0) & valid_i
        first_j = jnp.min(jnp.where(fail, lane, m), axis=1, keepdims=True)
        at_j = lane == first_j
        w = jnp.where(any_fail, pick(at_j, ids, i32_min), jnp.int32(-1))
        dw = jnp.where(any_fail, pick(at_j, row, -jnp.inf), big)
        keep = jnp.where(col_i, (valid_i & ~any_fail).astype(jnp.int32), keep)
        red_w = jnp.where(col_i, w, red_w)
        red_d = jnp.where(col_i, dw, red_d)
        return keep, red_w, red_d

    init = (
        jnp.zeros((tc, m), jnp.int32),
        jnp.full((tc, m), -1, jnp.int32),
        jnp.full((tc, m), big, jnp.float32),
    )
    keep, red_w, red_d = jax.lax.fori_loop(0, m, body, init)
    return keep, red_w, jnp.where(red_d >= big, jnp.inf, red_d)


def _rng_prune_body(ids_ref, dists_ref, flags_ref, vecs_ref, keep_ref,
                    redw_ref, redd_ref, pair_ref):
    vecs = vecs_ref[...].astype(jnp.float32)            # (tc, M, d)
    keep, red_w, red_d = _prune_scan(ids_ref[...], dists_ref[...],
                                     flags_ref[...], vecs, pair_ref)
    keep_ref[...] = keep
    redw_ref[...] = red_w
    redd_ref[...] = red_d


def _rng_prune_int8_body(ids_ref, dists_ref, flags_ref, codes_ref, scale_ref,
                         zero_ref, keep_ref, redw_ref, redd_ref, pair_ref):
    """int8 variant: the gathered candidate block arrives as (tc, M, d)
    int8 codes (4x less HBM->VMEM traffic) and dequantizes in-register via
    the shared ``repro.quant.int8_decode`` before the same Gram + scan.
    Decode is elementwise, so decode-after-gather here is bitwise-equal to
    the oracle's gather-after-decode."""
    from repro.quant import int8_decode

    vecs = int8_decode(codes_ref[...], scale_ref[...], zero_ref[...])
    keep, red_w, red_d = _prune_scan(ids_ref[...], dists_ref[...],
                                     flags_ref[...], vecs, pair_ref)
    keep_ref[...] = keep
    redw_ref[...] = red_w
    redd_ref[...] = red_d


def block_layout(n: int, m: int, d: int, tile_c: int):
    """(inputs, outputs) ``(name, block_shape, index_map)`` triples — single
    source for both ``pallas_call`` and the exported spec metadata
    (``ops.kernel_spec``). Everything tiles over vertex rows."""
    row = lambda i: (i, 0)
    inputs = (
        ("ids", (tile_c, m), row),
        ("dists", (tile_c, m), row),
        ("flags", (tile_c, m), row),
        ("vecs", (tile_c, m, d), lambda i: (i, 0, 0)),
    )
    outputs = (
        ("keep", (tile_c, m), row),
        ("red_w", (tile_c, m), row),
        ("red_d", (tile_c, m), row),
    )
    return inputs, outputs


def block_layout_int8(n: int, m: int, d: int, tile_c: int):
    """int8 layout: the gathered candidate block is (tile_c, M, d) int8
    codes plus whole-block (1, d) scale / zero rows."""
    row = lambda i: (i, 0)
    inputs = (
        ("ids", (tile_c, m), row),
        ("dists", (tile_c, m), row),
        ("flags", (tile_c, m), row),
        ("codes", (tile_c, m, d), lambda i: (i, 0, 0)),
        ("scale", (1, d), lambda i: (0, 0)),
        ("zero", (1, d), lambda i: (0, 0)),
    )
    outputs = (
        ("keep", (tile_c, m), row),
        ("red_w", (tile_c, m), row),
        ("red_d", (tile_c, m), row),
    )
    return inputs, outputs


@functools.partial(jax.jit, static_argnames=("tile_c", "interpret"))
def rng_prune_int8_tiles(
    ids: jnp.ndarray, dists: jnp.ndarray, flags: jnp.ndarray,
    codes: jnp.ndarray, scale: jnp.ndarray, zero: jnp.ndarray,
    tile_c: int = 8, interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """ids/dists/flags (n, M) + gathered codes (n, M, d) int8 + scale/zero
    (1, d) -> keep/red_w/red_d."""
    if interpret is None:
        from repro.kernels import default_interpret
        interpret = default_interpret()
    n, m = ids.shape
    d = codes.shape[-1]
    if n % tile_c != 0:
        raise ValueError(
            f"row count {n} is not a multiple of tile_c={tile_c} "
            "(ops.rng_prune_int8 pads before dispatching here)")
    grid = (n // tile_c,)
    ins, outs = block_layout_int8(n, m, d, tile_c)
    return pl.pallas_call(
        _rng_prune_int8_body,
        grid=grid,
        in_specs=[pl.BlockSpec(bs, im) for _, bs, im in ins],
        out_specs=[pl.BlockSpec(bs, im) for _, bs, im in outs],
        out_shape=[
            jax.ShapeDtypeStruct((n, m), jnp.int32),
            jax.ShapeDtypeStruct((n, m), jnp.int32),
            jax.ShapeDtypeStruct((n, m), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((tile_c, m, m), jnp.float32)],
        interpret=interpret,
    )(ids, dists, flags, codes, scale, zero)


@functools.partial(jax.jit, static_argnames=("tile_c", "interpret"))
def rng_prune_tiles(
    ids: jnp.ndarray, dists: jnp.ndarray, flags: jnp.ndarray, vecs: jnp.ndarray,
    tile_c: int = 8, interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """ids/dists/flags (n, M) + gathered vecs (n, M, d) -> keep/red_w/red_d."""
    if interpret is None:
        from repro.kernels import default_interpret
        interpret = default_interpret()
    n, m = ids.shape
    d = vecs.shape[-1]
    if n % tile_c != 0:
        raise ValueError(
            f"row count {n} is not a multiple of tile_c={tile_c} "
            "(ops.rng_prune pads before dispatching here)")
    grid = (n // tile_c,)
    ins, outs = block_layout(n, m, d, tile_c)
    return pl.pallas_call(
        _rng_prune_body,
        grid=grid,
        in_specs=[pl.BlockSpec(bs, im) for _, bs, im in ins],
        out_specs=[pl.BlockSpec(bs, im) for _, bs, im in outs],
        out_shape=[
            jax.ShapeDtypeStruct((n, m), jnp.int32),
            jax.ShapeDtypeStruct((n, m), jnp.int32),
            jax.ShapeDtypeStruct((n, m), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((tile_c, m, m), jnp.float32)],
        interpret=interpret,
    )(ids, dists, flags, vecs)
