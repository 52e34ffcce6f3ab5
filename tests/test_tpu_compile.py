"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e chip.

The interpret-mode tests (test_kernels.py, test_beam_score.py, test_quant.py)
pin results on the CPU but never run Mosaic, which refuses constructs the
interpreter accepts (value-level dynamic slicing, 8-bit compares, dots with no
free dim). Here each kernel variant that ships for the chip is lowered and
compiled against a *described* v5e topology — the TPU compiler runs on the
host, no chip is attached — at the widths the index runs: M=128 candidates
per row, d=128 (SIFT) and d=960 (GIST) for the prune, a VMEM-resident corpus
for the beam step. Each compile must emit the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker imports
this file.
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import graph as G
from repro.core import search as S
from repro.kernels.beam_score import ops as beam_ops
from repro.kernels.rng_prune import ops as prune_ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("d", [128, 960])
@pytest.mark.parametrize("variant", ["f32", "bf16", "int8"])
def test_rng_prune_compiles(one_chip, variant, d):
    n_pts, rows, m = 4096, 64, 128
    graph = [((rows, m), jnp.int32), ((rows, m), jnp.float32),
             ((rows, m), jnp.uint8)]
    if variant == "int8":
        fn = functools.partial(prune_ops.rng_prune_int8, interpret=False)
        hlo = _compile(fn, one_chip, ((n_pts, d), jnp.int8),
                       ((d,), jnp.float32), ((d,), jnp.float32), *graph)
    else:
        fn = functools.partial(prune_ops.rng_prune, interpret=False,
                               gram_dtype=variant)
        hlo = _compile(fn, one_chip, ((n_pts, d), jnp.float32), *graph)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("metric", ["l2", "ip", "cos"])
@pytest.mark.parametrize("variant", ["f32", "bf16", "int8"])
def test_beam_score_compiles(one_chip, variant, metric):
    n, cap, d, b, k = 2048, 128, 128, 256, 32
    common = [((n, cap), jnp.int32), ((b,), jnp.int32), ((b, d), jnp.float32)]
    if variant == "int8":
        fn = functools.partial(beam_ops.beam_score_int8, k=k, metric=metric,
                               interpret=False)
        hlo = _compile(fn, one_chip, ((n, d), jnp.int8), ((d,), jnp.float32),
                       ((d,), jnp.float32), *common)
    else:
        fn = functools.partial(beam_ops.beam_score, k=k, metric=metric,
                               interpret=False, gram_dtype=variant)
        hlo = _compile(fn, one_chip, ((n, d), jnp.float32), *common)
    assert "tpu_custom_call" in hlo


def test_beam_score_pq_refuses_compiled_backend():
    """The PQ beam kernel's LUT read is a 3-D gather Mosaic cannot lower: a
    compiled (non-interpret) call must say so, never fall back."""
    n, cap, mq, b = 512, 16, 8, 8
    with pytest.raises(ValueError, match="does not compile for TPU"):
        beam_ops.beam_score_pq(
            jnp.zeros((n, mq), jnp.uint8), jnp.zeros((n, cap), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b, mq, 256)),
            jnp.zeros((mq, 256)), jnp.zeros((b,)), k=8, interpret=False)


_HLO_OP = re.compile(r"^\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z0-9-]*)\(")
_SHAPE = re.compile(r"[a-z][a-z0-9]*\[([0-9,]*)\]")


def _ops(hlo: str):
    """(opcode, [element count of each result shape], line) per HLO op."""
    for line in hlo.splitlines():
        m = _HLO_OP.match(line)
        if m:
            sizes = [math.prod(int(v) for v in dims.split(",") if v)
                     for dims in _SHAPE.findall(m.group(1))]
            yield m.group(2), sizes, line


def test_search_visited_table_stays_in_place(one_chip):
    """The beam's hashed visited table at the GIST cell's shape (256-lane
    tile, L=64, k=64, max_iters=256: 65,536 slots a lane, 16,777,216 int32
    a tile) is probed by one 128-slot row gather per candidate and written
    back by row, in place: no whole-table copy, slice or relayout, and no
    gather of single table elements."""
    n, d, cap, b = 4096, 128, 128, 256
    cfg = S.SearchConfig(l=64, k=64, max_iters=256, topk=10,
                         visited="hashed")
    slots = S.resolve_slots(cfg)
    table = b * slots
    assert table == 16_777_216

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    g = G.Graph(sds((n, cap), jnp.int32), sds((n, cap), jnp.float32),
                sds((n, cap), jnp.uint8))
    hlo = S._search_tiled_jit.lower(
        sds((n, d), jnp.float32), g, sds((b, d), jnp.float32),
        sds((), jnp.int32), cfg, b, None, sds((n,), jnp.bool_), None,
        "queries", True, None).compile().as_text()
    ops = list(_ops(hlo))
    assert ops
    moved = [line for op, sizes, line in ops
             if op in ("copy", "copy-start", "slice-start", "reshape")
             and table in sizes]
    assert not moved, moved[:3]
    scalar_gathers = [line for op, sizes, line in ops
                      if op == "gather" and b * cfg.k * cfg.probes in sizes
                      and re.search(r"slice_sizes=\{1(,1)*\}", line)]
    assert not scalar_gathers, scalar_gathers[:3]
    probe = [line for op, sizes, line in ops
             if op == "gather" and b * cfg.k * 128 in sizes
             and "slice_sizes={1,128}" in line
             and line.lstrip().split(" = ")[1].startswith("s32[")]
    assert probe


def test_filtered_search_compiles_with_table_in_place(one_chip):
    """The filtered beam (label words, one mask a query) at the
    sift1m-attr12 cell's search (256-lane tile, L=64, k=64, max_iters=2048,
    131,072 slots a lane: a 33,554,432-entry table a tile) compiles for the
    chip, with the visited table written by row in place, as in the
    unfiltered beam."""
    n, d, cap, b = 4096, 128, 128, 256
    cfg = S.SearchConfig(l=64, k=64, max_iters=2048, topk=10,
                         visited="hashed", slots=131_072)
    table = b * S.resolve_slots(cfg)
    assert table == 33_554_432

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    g = G.Graph(sds((n, cap), jnp.int32), sds((n, cap), jnp.float32),
                sds((n, cap), jnp.uint8))
    hlo = S._search_tiled_jit.lower(
        sds((n, d), jnp.float32), g, sds((b, d), jnp.float32),
        sds((), jnp.int32), cfg, b, None, sds((n,), jnp.bool_), None,
        "queries", True, None, sds((n,), jnp.uint32),
        sds((b,), jnp.uint32)).compile().as_text()
    ops = list(_ops(hlo))
    moved = [line for op, sizes, line in ops
             if op in ("copy", "copy-start", "slice-start", "reshape")
             and table in sizes]
    assert not moved, moved[:3]
    assert any(op == "scatter" and table in sizes for op, sizes, _ in ops)
