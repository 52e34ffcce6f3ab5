"""Device time under the program's named phases, and device-idle time under
its host spans, from the current run's profiler trace (``.xplane.pb``).

The program names its phases in two ways:

* a ``jax.named_scope`` (``rnnd.prune``, ``beam.score``, ...; dotted, so
  that no JAX primitive or function shares the name) becomes a path segment
  of the ``tf_op`` stat on the event metadata of each XLA op in a device
  plane. ``jax.profiler.ProfileData`` exposes no metadata stats, so
  :func:`read_metadata` reads them from the protobuf wire format itself,
  skipping each plane's ``lines`` (the bulk of the file) by length. Each op
  run on the ``XLA Ops`` line is joined to its metadata by (program id,
  name); the program id is that of the enclosing ``XLA Modules`` event,
  ``jit_<module>(<program id>)``.
* a span of ``repro.obs.trace`` (``<layer>/<phase>``, such as
  ``streaming/search``) becomes a host event of its name on the profiler's
  clock.

A scope's time in a module is the union of the intervals of the ops whose
``tf_op`` holds the scope as a whole path segment. An op without a
``tf_op`` (a loop XLA made, a fusion that lost its metadata) takes the scope
of the ops nested in its interval when they share one; otherwise it stays
unattributed. Per module, the coverage (the union of all scoped ops over
the module's time) and the unattributed ops are printed to stderr, with the
longest device-idle gaps and the innermost program span over each.
"""
from __future__ import annotations

import bisect
import dataclasses
import os
import re
import sys

import jax
import numpy as np

from bench.trace_reduce import (_gaps, _host_label, _union_seconds,
                                find_xplane, module_name)

# JAX's persistent compile cache keys a program by its IR with the debug
# locations stripped, and a named scope is a location: a cache filled by a
# program without the scopes hands back executables whose op metadata lacks
# them, which is what this module reads. The harness imports the per-layer
# readers, and so this module, only for a traced run and before its set-up:
# keyed by the metadata too, such a run compiles (or finds) the programs as
# this checkout names them. Untraced runs keep the cache's default key.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")

SCOPE = re.compile(r"^[a-z_]+\.[a-z_.]+$")        # jax.named_scope segment
SPAN = re.compile(r"^[a-z_]+/[a-z_/]+$")          # repro.obs span name
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


# ------------------------------------------------------ protobuf wire format
def _varint(b, i: int) -> tuple[int, int]:
    v = s = 0
    while True:
        c = b[i]
        i += 1
        v |= (c & 0x7F) << s
        if c < 0x80:
            return v, i
        s += 7


def _fields(b):
    """(field number, value) of one message: an int for varints, a
    memoryview for length-delimited fields, None for fixed-width ones."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, v


def _map_entry(b):
    key = value = None
    for f, v in _fields(b):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def read_metadata(data: bytes) -> dict:
    """{device plane name: {(program id, op name): {tf_op, ...}}} from a
    serialized XSpace. Field numbers are those of the profiler's
    ``xplane.proto``: XSpace.planes 1; XPlane name 2, event_metadata 4,
    stat_metadata 5 (maps: key 1, value 2); XEventMetadata name 2, stats 5;
    XStatMetadata name 2; XStat metadata_id 1, uint64_value 3, int64_value
    4, str_value 5, ref_value 7 (the id of a stat metadata whose name is
    the string)."""
    out = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:
                events.append(v)
            elif pf == 5:
                k, md = _map_entry(v)
                stat_names[k] = next((bytes(s).decode() for sf, s in
                                      _fields(md) if sf == 2), "")
        if not _DEVICE_PLANE.match(name):
            continue
        ids = {v: k for k, v in stat_names.items()}
        tf_id, prog_id = ids.get("tf_op"), ids.get("program_id")
        ops = out.setdefault(name, {})
        for entry in events:
            _, md = _map_entry(entry)
            op, tf_op, prog = "", None, None
            for ef, v in _fields(md):
                if ef == 2:
                    op = bytes(v).decode()
                elif ef == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == prog_id:
                        prog = stat.get(3, stat.get(4))
                    elif stat.get(1) == tf_id:
                        tf_op = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if tf_op:
                ops.setdefault((prog, op), set()).add(tf_op)
    return out


def scopes_of(tf_op: str) -> frozenset:
    """The named scopes among a ``tf_op``'s path segments
    (``jit(f)/rnnd.prune/while/body/mul:mul`` -> {rnnd.prune})."""
    path = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    return frozenset(s for s in path.split("/") if SCOPE.match(s))


# ------------------------------------------------------------ interval sums
def _merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _covered_ns(merged, starts, s: float, e: float) -> float:
    """Length of [s, e) inside the merged, sorted intervals (``starts``:
    their starts)."""
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    total = 0.0
    for ms, me in merged[i:]:
        if ms >= e:
            break
        total += max(0.0, min(me, e) - max(ms, s))
    return total


# ------------------------------------------------------------------- reduce
@dataclasses.dataclass
class Scopes:
    runs: dict          # module -> runs (summed over device planes)
    module_s: dict      # module -> device seconds (summed over planes)
    scope_s: dict       # (module, scope) -> device seconds under the scope
    covered_s: dict     # module -> seconds under any scope
    unattributed: dict  # module -> (ops, seconds) under no scope
    span_calls: dict    # program span -> host events
    span_idle_s: dict   # program span -> device-idle seconds inside it
    gaps: list          # [(seconds, innermost program span or None,
    #                       share of the gap it covers)], longest first

    def scope_ms_per_run(self, module: str, scope: str) -> float | None:
        """Device ms under ``scope`` per run of the modules whose name
        contains ``module``; None when no op there carries the scope."""
        mods = [m for m in self.runs if module in m]
        runs = sum(self.runs[m] for m in mods)
        secs = sum(self.scope_s.get((m, scope), 0.0) for m in mods)
        return 1e3 * secs / runs if runs and secs > 0 else None

    def idle_ms_per_span(self, span: str) -> float | None:
        """Device-idle ms inside ``span`` per host event of it; None when
        the trace holds no such span."""
        n = self.span_calls.get(span, 0)
        return 1e3 * self.span_idle_s[span] / n if n else None


def _module_events(line):
    out = []
    for e in line.events:
        m = _PROGRAM_ID.search(e.name)
        out.append((e.start_ns, e.start_ns + e.duration_ns,
                    module_name(e.name), int(m.group(1)) if m else None))
    return sorted(out)


def _attribute(ops_s, ops_e, ids):
    """Scope-set ids of one module run's ops, sorted by start (an index
    into a list of frozensets; -1 none), and which unscoped ops hold scoped
    ones. An unscoped op takes the set of the scoped ops nested in its
    interval where all of them share one."""
    ids = np.asarray(ids, np.int64)
    named = np.concatenate([[0], np.cumsum(ids >= 0)])
    hi = np.searchsorted(ops_s, ops_e, side="left")
    holds = (ids < 0) & (named[hi] - named[np.arange(len(ids)) + 1] > 0)
    out = ids.copy()
    for i in np.nonzero(holds)[0]:
        inner = ids[i + 1:hi[i]]
        inner = inner[(ops_e[i + 1:hi[i]] <= ops_e[i]) & (inner >= 0)]
        if len(inner) and np.all(inner == inner[0]):
            out[i] = inner[0]
    return out, holds & (out < 0)


def _scope_run(run, scopes: dict, prog, sets: list, set_id: dict):
    """One module run's ops, sorted by start -> ({scope: seconds}, seconds
    under any scope, (unattributed ops, their seconds)). ``scopes``: the
    scopes of each (program id, op name)."""
    ids = []
    for _, _, name in run:
        sc = scopes.get((prog, name))
        if sc and sc not in set_id:
            set_id[sc] = len(sets)
            sets.append(sc)
        ids.append(set_id[sc] if sc else -1)
    starts = np.array([o[0] for o in run], np.float64)
    ends = np.array([o[1] for o in run], np.float64)
    ids, container = _attribute(starts, ends, ids)

    def union(mask):
        return _union_seconds(list(zip(starts[mask].tolist(),
                                       ends[mask].tolist())))

    named = ids >= 0
    per_scope = {}
    for scope in {x for i in set(ids[named]) for x in sets[i]}:
        member = np.array([scope in x for x in sets] + [False])
        per_scope[scope] = union(member[ids])    # id -1 reads the last False
    # unscoped ops, but for loops that hold several scopes' ops
    leak = ~named & ~container
    return per_scope, union(named), (int(leak.sum()), union(leak))


def reduce_trace(profile, metadata: dict, top: int = 10) -> Scopes:
    """Reduce a ``jax.profiler.ProfileData`` and its :func:`read_metadata`."""
    runs, module_s, scope_s, covered_s, unattr = {}, {}, {}, {}, {}
    sets, set_id = [], {}
    busy, host = [], []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for line in plane.lines for e in line.events
                     if e.duration_ns > 0 and SPAN.match(e.name)]
            continue
        if not _DEVICE_PLANE.match(plane.name):
            continue
        scopes = {k: frozenset.intersection(*map(scopes_of, v))
                  for k, v in metadata.get(plane.name, {}).items()}
        lines = {line.name: line for line in plane.lines}
        mods = _module_events(lines["XLA Modules"]) \
            if "XLA Modules" in lines else []
        ops = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for e in lines["XLA Ops"].events) \
            if "XLA Ops" in lines else []
        busy += [(s, e) for s, e, _ in ops] or [(s, e) for s, e, _, _ in mods]
        op_starts = [o[0] for o in ops]
        for ms, me, mod, prog in mods:
            runs[mod] = runs.get(mod, 0) + 1
            module_s[mod] = module_s.get(mod, 0.0) + (me - ms) * 1e-9
            run = [o for o in ops[bisect.bisect_left(op_starts, ms):
                                  bisect.bisect_left(op_starts, me)]
                   if o[1] <= me]
            if not run:
                continue
            per_scope, covered, (n, secs) = _scope_run(run, scopes, prog, sets,
                                                       set_id)
            for scope, v in per_scope.items():
                scope_s[(mod, scope)] = scope_s.get((mod, scope), 0.0) + v
            covered_s[mod] = covered_s.get(mod, 0.0) + covered
            n0, s0 = unattr.get(mod, (0, 0.0))
            unattr[mod] = (n0 + n, s0 + secs)
    merged = _merged(busy)
    starts = [m[0] for m in merged]
    span_calls, span_idle = {}, {}
    for name, s, e in host:
        span_calls[name] = span_calls.get(name, 0) + 1
        span_idle[name] = span_idle.get(name, 0.0) \
            + 1e-9 * ((e - s) - _covered_ns(merged, starts, s, e))
    spans = ([h[0] for h in host], np.array([h[1] for h in host], float),
             np.array([h[2] for h in host], float))
    gaps = []
    for s, e in _gaps(busy, top):
        cover = np.minimum(spans[2], e) - np.maximum(spans[1], s)
        share = max(float(cover.max()) if host else 0.0, 0.0) / (e - s)
        gaps.append(((e - s) * 1e-9, _host_label(spans, s, e) if share > 0
                     else None, share))
    return Scopes(runs=runs, module_s=module_s, scope_s=scope_s,
                  covered_s=covered_s, unattributed=unattr,
                  span_calls=span_calls, span_idle_s=span_idle, gaps=gaps)


def report(sc: Scopes, out=None) -> None:
    """Per module: ms per run under each scope, coverage, unattributed
    ops; then the longest idle gaps and the program span over each
    (standard error by default)."""
    out = out or sys.stderr
    for mod in sorted(sc.runs):
        n, t = sc.runs[mod], sc.module_s[mod]
        if t <= 0:
            continue
        per = {s: round(1e3 * v / n, 3) for (m, s), v in
               sorted(sc.scope_s.items()) if m == mod}
        if not per:
            continue
        k, u = sc.unattributed.get(mod, (0, 0.0))
        print(f"scopes of {mod}: {n} runs, {1e3 * t / n:.3f} ms per run; "
              f"ms per run {per}; coverage "
              f"{100 * sc.covered_s.get(mod, 0.0) / t:.2f}%; unattributed "
              f"{k} ops, {1e3 * u / n:.3f} ms per run", file=out)
    for span in sorted(sc.span_calls):
        print(f"span {span}: {sc.span_calls[span]} events, device idle "
              f"{1e3 * sc.span_idle_s[span] / sc.span_calls[span]:.3f} ms "
              "per event", file=out)
    for secs, span, share in sc.gaps:
        where = (f"{span} ({100 * share:.1f}% of it)" if span
                 else "outside the program")
        print(f"idle gap {1e3 * secs:.3f} ms: {where}", file=out)


_CACHE: dict = {}


def current() -> Scopes | None:
    """The current run's trace reduced (once per file); None without one."""
    try:
        path = find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _CACHE:
        with open(path, "rb") as f:
            data = f.read()
        _CACHE.clear()
        _CACHE[key] = reduce_trace(
            jax.profiler.ProfileData.from_serialized_xspace(data),
            read_metadata(data))
        report(_CACHE[key])
    return _CACHE[key]


def scope_ms(module: str, scope: str) -> float | None:
    """:meth:`Scopes.scope_ms_per_run` of the current run's trace."""
    sc = current()
    return None if sc is None else sc.scope_ms_per_run(module, scope)


def span_idle_ms(span: str) -> float | None:
    """:meth:`Scopes.idle_ms_per_span` of the current run's trace."""
    sc = current()
    return None if sc is None else sc.idle_ms_per_span(span)
