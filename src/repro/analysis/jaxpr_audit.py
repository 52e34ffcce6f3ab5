"""Jaxpr invariant auditor: abstract-trace every public entry point and walk
the closed jaxpr (recursing into scan/while/cond/jit/shard_map/pallas_call
sub-jaxprs) for dtype and semantics invariants the test suite can't see —
a leak only costs recall/memory at production scale, not correctness at
test scale.

Rules (each one finding per (entry, primitive) site):

``wide-dtype``
    No f64/c128 (or 64-bit integer) value anywhere in any traced program.
    The repo is 32-bit end-to-end; a stray ``np.float64`` scalar under
    x64-enabled deployments silently doubles HBM traffic and breaks the
    bitcast key transform (``graph.dist_key`` assumes f32 bit patterns).

``mixed-dot``
    ``dot_general`` operands must share a dtype. Mixed bf16 x f32 operands
    make XLA insert an implicit upcast of the *large* operand — exactly the
    hidden full-precision gather the ``gram_dtype="bf16"`` path exists to
    avoid; the repo's convention is an explicit ``.astype`` upcast of the
    VMEM-resident tile instead.

``low-precision-accum``
    ``dot_general`` with bf16/f16 operands must produce f32 (the
    ``preferred_element_type=jnp.float32`` accumulator rule): a bf16
    accumulator has 8 mantissa bits, and Gram-matrix distance errors at
    that precision reorder neighbor candidates.

``key-taint``
    uint32 distance keys (values born from ``bitcast_convert_type`` to
    uint32 — the ``graph.dist_key`` transform) are *ordinal*, not numeric:
    only comparisons, bitwise ops, min/max-style selection, sorting and
    data movement are meaningful. Arithmetic (add/mul/dot/float converts)
    on a key silently destroys the monotone order contract. Taint is
    propagated *through* call-style sub-jaxprs (``jit``/``remat`` — the
    wrappers jnp helpers like ``jnp.where`` insert) by positional argument
    mapping, but dropped at loop/branch boundaries (``scan``/``while``/
    ``cond`` carry structure): a key carried through a ``scan`` re-taints
    at the inner bitcast, which every real consumer in this repo performs.

``host-callback``
    No host callbacks (``pure_callback``/``io_callback``/``debug_callback``)
    inside library entry points: they serialize the device stream and dead-
    lock under multi-host shard_map.

``scatter-clip``
    Scatter ops must not use CLIP (clamp) out-of-bounds semantics: the
    streaming/bucket paths route dropped updates via ``mode="drop"``
    sentinels (-1 ids clamp to row 0 and silently corrupt a live vertex —
    the exact bug class of PR4's tombstone handling). FILL_OR_DROP and
    PROMISE_IN_BOUNDS are the two sanctioned modes.
"""
from __future__ import annotations

from typing import Iterable

import jax

from repro.analysis.baseline import Finding

from jax.extend import core as jcore

_WIDE = {"float64", "complex128", "int64", "uint64"}
_LOWP = {"bfloat16", "float16"}

# key-taint: primitives through which a uint32 key may legally flow.
# Comparison/argmin-style consumers are also legal but produce non-key
# outputs, so they appear in _TAINT_SINK (consume, don't propagate).
_TAINT_FLOW = {
    "and", "or", "xor", "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "min", "max", "reduce_min", "reduce_max",
    "cummin", "cummax", "scatter_min", "scatter_max", "select_n", "sort",
    "gather", "scatter", "slice", "dynamic_slice", "dynamic_update_slice",
    "squeeze", "reshape", "broadcast_in_dim", "transpose", "concatenate",
    "pad", "rev", "expand_dims", "copy", "stop_gradient", "device_put",
    "top_k",
    # pallas VMEM ref movement (kernel bodies): loads/stores of keys
    "get", "swap", "masked_load", "masked_swap",
    # cross-device data movement (corpus-sharded serving ships dist-key
    # tables between owners — a pure permutation, ordinal-safe; reductions
    # over keys must still go through min/max, never psum)
    "all_to_all", "ppermute", "all_gather",
}
_TAINT_SINK = {"eq", "ne", "lt", "le", "gt", "ge", "argmin", "argmax",
               "reduce_and", "reduce_or", "is_finite"}

# call-style primitives: one sub-jaxpr whose invars map positionally onto the
# equation's invars, so key taint threads straight through (jnp helpers like
# jnp.where / jnp.clip arrive wrapped in one of these).
_CALL_PRIMS = {"jit", "closed_call", "core_call", "remat", "checkpoint",
               "custom_jvp_call", "custom_vjp_call", "remat2"}


def iter_jaxprs(closed) -> Iterable:
    """Yield a jaxpr and, depth-first, every sub-jaxpr reachable through
    equation params (scan/while/cond bodies, jit/shard_map/pallas_call
    callees, custom_*_call rules) — whatever the param structure."""
    root = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    stack = [root]
    while stack:
        j = stack.pop()
        yield j
        for eqn in j.eqns:
            stack.extend(_sub_jaxprs(eqn.params))


def _sub_jaxprs(obj) -> list:
    if isinstance(obj, dict):
        obj = list(obj.values())
    if isinstance(obj, (list, tuple)):
        out = []
        for v in obj:
            out.extend(_sub_jaxprs(v))
        return out
    if isinstance(obj, jcore.ClosedJaxpr):
        return [obj.jaxpr]
    if isinstance(obj, jcore.Jaxpr):
        return [obj]
    return []


def _dtype_name(v) -> str:
    aval = getattr(v, "aval", None)
    dt = getattr(aval, "dtype", None)
    return str(dt) if dt is not None else ""


def _audit_rec(entry: str, jaxpr,
               taint_in: list[bool]) -> tuple[list[Finding], list[bool]]:
    """Audit ``jaxpr`` with ``taint_in`` marking which invars hold uint32
    dist keys; returns (findings, per-outvar taint) so call-style sub-jaxprs
    (jit/remat) thread taint through positionally."""
    findings: list[Finding] = []
    tainted: set = set()   # Vars holding uint32 dist keys (this jaxpr)
    for v, t in zip(jaxpr.invars, taint_in):
        if t:
            tainted.add(v)

    def flag(rule: str, prim: str, detail: str):
        findings.append(Finding("jaxpr", rule, f"{entry}:{prim}", detail))

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        in_dts = [_dtype_name(v) for v in eqn.invars]
        out_dts = [_dtype_name(v) for v in eqn.outvars]

        for dt in out_dts:
            if dt in _WIDE:
                flag("wide-dtype", prim,
                     f"produces {dt} (repo is 32-bit end-to-end; check for "
                     "np.float64 scalars / weak-type promotion)")
                break

        if prim == "dot_general":
            a, b = in_dts[0], in_dts[1]
            if a != b:
                flag("mixed-dot", prim,
                     f"operand dtypes {a} x {b}: XLA upcasts implicitly — "
                     "make the upcast explicit (.astype) on the small side")
            if (a in _LOWP or b in _LOWP) and out_dts[0] != "float32":
                flag("low-precision-accum", prim,
                     f"{a} x {b} -> {out_dts[0]}: low-precision operands "
                     "must accumulate in f32 "
                     "(preferred_element_type=jnp.float32)")

        if "callback" in prim:
            flag("host-callback", prim,
                 "host callback inside a library entry point (serializes "
                 "the device stream; deadlocks under multi-host shard_map)")

        if prim.startswith("scatter") and "CLIP" in str(
                eqn.params.get("mode", "")):
            flag("scatter-clip", prim,
                 "scatter with CLIP (clamp) OOB semantics: dropped updates "
                 "must use mode=\"drop\" — clamping writes them onto row 0")

        # ---- sub-jaxpr recursion ------------------------------------
        subs = _sub_jaxprs(eqn.params)
        if subs:
            if (prim in _CALL_PRIMS and len(subs) == 1
                    and len(subs[0].invars) == len(eqn.invars)):
                # positional arg mapping: taint flows through the call
                tin = [not isinstance(v, jcore.Literal) and v in tainted
                       for v in eqn.invars]
                got, tout = _audit_rec(entry, subs[0], tin)
                findings.extend(got)
                for v, t in zip(eqn.outvars, tout):
                    if t:
                        tainted.add(v)
            else:
                # loop/branch boundary: audit the bodies, drop taint
                # (documented limitation — real consumers re-taint at the
                # inner bitcast)
                for s in subs:
                    got, _ = _audit_rec(entry, s, [False] * len(s.invars))
                    findings.extend(got)
            continue

        # ---- key-taint dataflow -------------------------------------
        if prim == "bitcast_convert_type":
            # bitcast to uint32 births (or re-births) a key; bitcast back
            # to a float is the sanctioned decode (graph.key_dist) and
            # clears taint
            if out_dts[0] == "uint32":
                tainted.update(eqn.outvars)
            continue
        hit = [v for v in eqn.invars
               if not isinstance(v, jcore.Literal) and v in tainted]
        if not hit:
            continue
        if prim in _TAINT_SINK:
            continue  # legal consumer (compares etc. produce non-key output)
        if prim == "convert_element_type":
            if out_dts[0] not in ("uint32", "bool"):
                flag("key-taint", prim,
                     f"uint32 dist key converted to {out_dts[0]}: decode "
                     "with graph.key_dist, never a numeric cast")
            elif out_dts[0] == "uint32":
                tainted.update(eqn.outvars)
            continue
        if prim in _TAINT_FLOW:
            tainted.update(eqn.outvars)
            continue
        flag("key-taint", prim,
             f"uint32 dist key flows into `{prim}` (inputs "
             f"{in_dts}): keys are ordinal — only compare/bitwise/minmax/"
             "sort/data-movement ops are meaningful")
    taint_out = [not isinstance(v, jcore.Literal) and v in tainted
                 for v in jaxpr.outvars]
    return findings, taint_out


def audit_closed_jaxpr(entry: str, closed) -> list[Finding]:
    """Run every rule over ``closed`` and all reachable sub-jaxprs."""
    root = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    findings, _ = _audit_rec(entry, root, [False] * len(root.invars))
    return findings


def run(names: list[str] | None = None, log=print) -> list[Finding]:
    """Trace + audit the registry (all entries, or the named subset)."""
    from repro.analysis import registry

    findings: list[Finding] = []
    for name, thunk in registry.entries(names).items():
        closed = thunk()
        got = audit_closed_jaxpr(name, closed)
        log(f"jaxpr-audit: {name}: "
            f"{len(got) or 'no'} finding{'s' if len(got) != 1 else ''}")
        findings.extend(got)
    return findings
