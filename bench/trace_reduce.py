"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time, the traced window, device time per XLA
module, the device operations that took most time, and the longest idle
gaps labelled by what the host was doing.

Device planes are those named ``/device:<PLATFORM>:<n>``. On each, the
``XLA Ops`` line holds one event per operation run and the ``XLA Modules``
line one event per program run, named ``<module>(<program id>)``. Busy time
is the union of the operation intervals (the module intervals where a plane
has no operation line), averaged over the device planes. The window is the
profiler session, from the ``Task Environment`` plane's
``profile_start_time`` to ``profile_stop_time``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

_MODULE_ID = re.compile(r"\(\d+\)$")
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")


def op_name(event_name: str) -> str:
    """``%fusion.8 = f32[48000000]{0:T(1024)} fusion(...)`` ->
    ``%fusion.8 = f32[48000000]``: the op and its result's shape."""
    return event_name.split("{")[0].split(" = (")[0].strip()


def module_name(event_name: str) -> str:
    """``jit_update_neighbors(1234)`` -> ``update_neighbors``."""
    name = _MODULE_ID.sub("", event_name)
    return name[4:] if name.startswith("jit_") else name


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                   # mean over device planes
    devices: int
    modules: dict                   # module -> {"count", "seconds"} (mean
    #                                 per device) and "runs_s", the seconds
    #                                 of each run on the first device
    device_ops: list                # [(op, seconds)], most time first; a
    #                                 loop's time includes its body's ops
    idle_gaps: list                 # [(host activity, seconds)], longest first

    def module_seconds(self, pattern: str) -> tuple[int, float]:
        """(runs, device seconds) summed over modules whose name contains
        ``pattern``."""
        runs = secs = 0
        for name, m in self.modules.items():
            if pattern in name:
                runs += m["count"]
                secs += m["seconds"]
        return runs, secs


def _union_seconds(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total * 1e-9


def _gaps(intervals, top: int):
    """Longest gaps between merged intervals: [(start_ns, end_ns)]."""
    out, end = [], None
    for s, e in sorted(intervals):
        if end is not None and s > end:
            out.append((end, s))
        end = e if end is None else max(end, e)
    out.sort(key=lambda g: g[0] - g[1])
    return out[:top]


def _host_label(host, start: float, end: float) -> str:
    """Name of the shortest host event among those that cover nearly as
    much of [start, end) as the one that covers most (so an event that
    spans the whole session does not name every gap)."""
    names, s, e = host
    cover = np.minimum(e, end) - np.maximum(s, start)
    if not names or cover.max() <= 0:
        return "no host event"
    near = np.nonzero(cover >= 0.9 * cover.max())[0]
    return names[near[np.argmin((e - s)[near])]]


def reduce_profile(profile, top: int = 10) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData``."""
    window_s = None
    dev_planes, host_events = [], []
    for plane in profile.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                window_s = (int(st["profile_stop_time"])
                            - int(st["profile_start_time"])) * 1e-9
        elif _DEVICE_PLANE.match(plane.name):
            dev_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_events.extend((e.name, e.start_ns, e.start_ns
                                    + e.duration_ns) for e in line.events
                                   if e.duration_ns > 0)
    if not dev_planes:
        raise ValueError("the trace holds no device plane (/device:<kind>:<n>)")
    busy, modules, ops, all_iv = 0.0, {}, {}, []
    for p, plane in enumerate(dev_planes):
        lines = {line.name: line for line in plane.lines}
        mod_iv = []
        if "XLA Modules" in lines:
            for e in lines["XLA Modules"].events:
                m = modules.setdefault(module_name(e.name),
                                       {"count": 0, "seconds": 0.0,
                                        "runs_s": []})
                m["count"] += 1
                m["seconds"] += e.duration_ns * 1e-9
                if p == 0:
                    m["runs_s"].append(e.duration_ns * 1e-9)
                mod_iv.append((e.start_ns, e.start_ns + e.duration_ns))
        op_iv = []
        if "XLA Ops" in lines:
            for e in lines["XLA Ops"].events:
                k = op_name(e.name)
                ops[k] = ops.get(k, 0.0) + e.duration_ns * 1e-9
                op_iv.append((e.start_ns, e.start_ns + e.duration_ns))
        iv = op_iv or mod_iv
        busy += _union_seconds(iv)
        all_iv.extend(iv)
    nd = len(dev_planes)
    for m in modules.values():
        m["count"] = m["count"] / nd
        m["seconds"] /= nd
    if window_s is None:
        window_s = ((max(e for _, e in all_iv) - min(s for s, _ in all_iv))
                    * 1e-9 if all_iv else 0.0)
    host = ([h[0] for h in host_events],
            np.array([h[1] for h in host_events], np.float64),
            np.array([h[2] for h in host_events], np.float64))
    gaps = [(_host_label(host, s, e), (e - s) * 1e-9)
            for s, e in _gaps(all_iv, top)]
    device_ops = sorted(((k, v / nd) for k, v in ops.items()),
                        key=lambda kv: -kv[1])[:top]
    return TraceSummary(window_s=window_s, busy_s=busy / nd, devices=nd,
                        modules=modules, device_ops=device_ops,
                        idle_gaps=gaps)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_file(path: str, top: int = 10) -> TraceSummary:
    import jax

    return reduce_profile(jax.profiler.ProfileData.from_file(path), top)
