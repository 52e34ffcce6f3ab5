"""Jit'd wrapper: gather + pad + kernel dispatch for the fused RNG prune."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.rng_prune.kernel import (
    block_layout,
    block_layout_int8,
    rng_prune_int8_tiles,
    rng_prune_tiles,
)
from repro.kernels.rng_prune.ref import rng_prune_ref


@functools.partial(jax.jit, static_argnames=("tile_c", "interpret", "gram_dtype"))
def rng_prune(
    x: jnp.ndarray,
    ids: jnp.ndarray,
    dists: jnp.ndarray,
    flags: jnp.ndarray | None = None,
    tile_c: int = 8,
    interpret: bool | None = None,
    gram_dtype: str = "f32",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (keep bool, redirect_w int32, redirect_d f32), shapes (n, M).

    ``flags=None`` means plain Algorithm 3 (everything "new" -> no exemption).
    ``gram_dtype="bf16"`` gathers the neighbor vectors in bfloat16, halving
    the gather + kernel-input HBM traffic (the kernel upcasts to f32 before
    the Gram, so accumulation precision is unchanged).
    """
    if interpret is None:
        interpret = default_interpret()
    n, m = ids.shape
    flags = (jnp.ones((n, m), jnp.int32) if flags is None
             else flags.astype(jnp.int32))
    if gram_dtype == "bf16":
        x = x.astype(jnp.bfloat16)
    pad = (-n) % tile_c
    ids_p = jnp.pad(ids, ((0, pad), (0, 0)), constant_values=-1)
    dists_p = jnp.pad(dists, ((0, pad), (0, 0)), constant_values=jnp.inf)
    flags_p = jnp.pad(flags, ((0, pad), (0, 0)))
    vecs = x[jnp.maximum(ids_p, 0)]
    keep, red_w, red_d = rng_prune_tiles(
        ids_p, dists_p, flags_p, vecs, tile_c=tile_c, interpret=interpret
    )
    return keep[:n].astype(bool), red_w[:n], red_d[:n]


@functools.partial(jax.jit, static_argnames=("tile_c", "interpret"))
def rng_prune_int8(
    codes: jnp.ndarray,
    scale: jnp.ndarray,
    zero: jnp.ndarray,
    ids: jnp.ndarray,
    dists: jnp.ndarray,
    flags: jnp.ndarray | None = None,
    tile_c: int = 8,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """int8-corpus RNG prune: gathers candidate *code* rows (4x less
    gather traffic than f32) and dequantizes in-register inside the kernel
    before the shared Gram + keep/redirect scan. Same contract as
    :func:`rng_prune`; bitwise-equal to running :func:`rng_prune` over the
    decoded corpus ``x_hat`` (decode commutes with the row gather)."""
    if interpret is None:
        interpret = default_interpret()
    n, m = ids.shape
    flags = (jnp.ones((n, m), jnp.int32) if flags is None
             else flags.astype(jnp.int32))
    pad = (-n) % tile_c
    ids_p = jnp.pad(ids, ((0, pad), (0, 0)), constant_values=-1)
    dists_p = jnp.pad(dists, ((0, pad), (0, 0)), constant_values=jnp.inf)
    flags_p = jnp.pad(flags, ((0, pad), (0, 0)))
    cvecs = codes[jnp.maximum(ids_p, 0)]                 # (n_pad, M, d) int8
    keep, red_w, red_d = rng_prune_int8_tiles(
        ids_p, dists_p, flags_p, cvecs, scale[None, :], zero[None, :],
        tile_c=tile_c, interpret=interpret
    )
    return keep[:n].astype(bool), red_w[:n], red_d[:n]


def kernel_spec(*, n: int = 64, m: int = 32, d: int = 64, tile_c: int = 8,
                gram_dtype: str = "f32"):
    """Static :class:`repro.kernels.spec.KernelSpec` for one problem size —
    consumed by ``repro.analysis.kernel_check``. Under ``gram_dtype="bf16"``
    the gathered ``vecs`` arrive low-precision and the checker enforces that
    the in-kernel Gram still accumulates in f32."""
    from repro.kernels.spec import BlockMeta, KernelSpec

    vdt = jnp.bfloat16 if gram_dtype == "bf16" else jnp.float32
    ins, outs = block_layout(n, m, d, tile_c)
    shapes = {
        "ids": ((n, m), jnp.int32),
        "dists": ((n, m), jnp.float32),
        "flags": ((n, m), jnp.int32),
        "vecs": ((n, m, d), vdt),
        "keep": ((n, m), jnp.int32),
        "red_w": ((n, m), jnp.int32),
        "red_d": ((n, m), jnp.float32),
    }
    meta = lambda trips: tuple(
        BlockMeta(nm, shapes[nm][0], bs, shapes[nm][1], im)
        for nm, bs, im in trips)

    def trace():
        args = [jax.ShapeDtypeStruct(*shapes[nm]) for nm, _, _ in ins]
        return jax.make_jaxpr(functools.partial(
            rng_prune_tiles, tile_c=tile_c,
            interpret=True,  # repo-lint: allow-interpret (abstract trace only)
        ))(*args)

    return KernelSpec(
        name=f"rng_prune[{gram_dtype}]",
        grid=(n // tile_c,),
        inputs=meta(ins),
        outputs=meta(outs),
        trace=trace,
        low_precision_inputs=("vecs",) if gram_dtype == "bf16" else (),
    )


def kernel_spec_int8(*, n: int = 64, m: int = 128, d: int = 960,
                     tile_c: int = 8):
    """Spec for the int8-decode variant: the gathered ``codes`` block is a
    declared low-precision input, so the checker proves the body upcasts
    to the f32 accumulator (the in-register dequantize) before the Gram."""
    from repro.kernels.spec import BlockMeta, KernelSpec

    ins, outs = block_layout_int8(n, m, d, tile_c)
    shapes = {
        "ids": ((n, m), jnp.int32),
        "dists": ((n, m), jnp.float32),
        "flags": ((n, m), jnp.int32),
        "codes": ((n, m, d), jnp.int8),
        "scale": ((1, d), jnp.float32),
        "zero": ((1, d), jnp.float32),
        "keep": ((n, m), jnp.int32),
        "red_w": ((n, m), jnp.int32),
        "red_d": ((n, m), jnp.float32),
    }
    meta = lambda trips: tuple(
        BlockMeta(nm, shapes[nm][0], bs, shapes[nm][1], im)
        for nm, bs, im in trips)

    def trace():
        args = [jax.ShapeDtypeStruct(*shapes[nm]) for nm, _, _ in ins]
        return jax.make_jaxpr(functools.partial(
            rng_prune_int8_tiles, tile_c=tile_c,
            interpret=True,  # repo-lint: allow-interpret (abstract trace only)
        ))(*args)

    return KernelSpec(
        name="rng_prune[int8]",
        grid=(n // tile_c,),
        inputs=meta(ins),
        outputs=meta(outs),
        trace=trace,
        low_precision_inputs=("codes",),
    )


def default_specs():
    """Representative spec instances checked in CI: the docstring's VMEM
    budget point (tc=8, M=128, d=960) in f32, the bf16-gather variant, and
    the int8 in-register-decode variant at the same point (codes block is
    a quarter of the f32 footprint)."""
    return [
        kernel_spec(n=64, m=128, d=960, tile_c=8, gram_dtype="f32"),
        kernel_spec(n=64, m=128, d=960, tile_c=8, gram_dtype="bf16"),
        kernel_spec_int8(n=64, m=128, d=960, tile_c=8),
    ]


__all__ = ["rng_prune", "rng_prune_ref", "rng_prune_int8", "kernel_spec",
           "kernel_spec_int8", "default_specs"]
