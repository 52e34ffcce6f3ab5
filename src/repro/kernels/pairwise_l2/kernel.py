"""Pallas TPU kernel: tiled pairwise squared-L2 distance.

The distance tile is THE compute hot spot of every stage of the paper
(random-init distances, brute-force ground truth, beam-search scoring). The
kernel streams (tile_m, d) of A and (tile_n, d) of B through VMEM and runs
the -2AB^T contraction on the MXU; the (tile_m, tile_n) output block never
round-trips through HBM in expanded form.

Tiling rules (TPU v5e):
  * tile_m/tile_n multiples of 128 -> MXU-aligned matmul dims;
  * full-d blocks: all assigned corpora have d <= 1024, so an fp32 A-tile is
    at most 256*1024*4 = 1 MiB; A+B+out fit comfortably in 16 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pairwise_l2_body(a_ref, b_ref, o_ref):
    a = a_ref[...].astype(jnp.float32)           # (tm, d)
    b = b_ref[...].astype(jnp.float32)           # (tn, d)
    an = jnp.sum(a * a, axis=-1, keepdims=True)  # (tm, 1)
    bn = jnp.sum(b * b, axis=-1, keepdims=True)  # (tn, 1)
    dot = jax.lax.dot_general(                   # (tm, tn) on the MXU
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    o_ref[...] = jnp.maximum(an + bn.T - 2.0 * dot, 0.0)


def block_layout(na: int, nb: int, d: int, tile_m: int, tile_n: int):
    """(inputs, outputs) ``(name, block_shape, index_map)`` triples — single
    source for both ``pallas_call`` and ``ops.kernel_spec``. A strides the
    row axis, B the column axis, full-d blocks per the tiling rules above."""
    inputs = (
        ("a", (tile_m, d), lambda i, j: (i, 0)),
        ("b", (tile_n, d), lambda i, j: (j, 0)),
    )
    outputs = (
        ("out", (tile_m, tile_n), lambda i, j: (i, j)),
    )
    return inputs, outputs


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n", "interpret"))
def pairwise_l2_tiles(
    a: jnp.ndarray, b: jnp.ndarray,
    tile_m: int = 256, tile_n: int = 256, interpret: bool | None = None,
) -> jnp.ndarray:
    """(na, d) x (nb, d) -> (na, nb); na/nb must be tile multiples (ops.py pads)."""
    if interpret is None:
        from repro.kernels import default_interpret
        interpret = default_interpret()
    na, d = a.shape
    nb = b.shape[0]
    if na % tile_m != 0 or nb % tile_n != 0:
        raise ValueError(
            f"shapes ({na}, {nb}) are not multiples of tiles "
            f"({tile_m}, {tile_n}) (ops.pairwise_l2 pads before dispatching "
            "here)")
    grid = (na // tile_m, nb // tile_n)
    ins, outs = block_layout(na, nb, d, tile_m, tile_n)
    return pl.pallas_call(
        _pairwise_l2_body,
        grid=grid,
        in_specs=[pl.BlockSpec(bs, im) for _, bs, im in ins],
        out_specs=pl.BlockSpec(outs[0][1], outs[0][2]),
        out_shape=jax.ShapeDtypeStruct((na, nb), jnp.float32),
        interpret=interpret,
    )(a, b)
