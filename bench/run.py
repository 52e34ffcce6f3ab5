"""Run one cell of the benchmark once, on the accelerator this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name:

* ``BENCHMARK.json`` (checkout root): the cell's end-to-end metrics
  (``--trace 0``) and per-layer metrics (``--trace 1``);
* ``bench/workloads/<cell>.json``: its configuration, traffic driver and
  traffic parameters, and the limits of its correctness checks;
* ``bench/configs/<config>.json``: the deployment (sizes, build and search
  parameters);
* ``bench/traffic/<driver>.py``: ``setup(run) -> state``,
  ``window(run, state) -> Window``, optionally ``collect(run, state,
  window) -> outputs`` (after the profiler stops), and ``verify(run,
  outputs) -> ([Check], {metric: value})`` (the plain reference's checks,
  and end-to-end metrics that need the reference, such as a recall);
* ``bench/layer_metrics/<metric>.py``: ``read(ctx) -> float | None``.

A run: set-up (data from ``--seed``, index, every shape the window uses
warmed; timed as ``setup_s``), the measured window of ``--seconds`` seconds
(profiled with ``--trace 1``), the peak device memory, then the program's
state is dropped and the driver's plain reference checks what the window
produced. The last line of standard output is the JSON result; the checks,
each number beside its limit, are the last lines of standard error and the
last key of the result. Exits 2 with no result when JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")

from bench.common import Check, Run  # noqa: E402


def _load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _load_module(kind: str, name: str):
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, spec: dict | None = None):
    """(cell entry of BENCHMARK.json, cell file, config file) for a cell."""
    spec = spec if spec is not None else _load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = _load_json(BENCH, "workloads", name + ".json")
    if cell["config"] != entry["config"]:
        raise ValueError(f"{name}: BENCHMARK.json names config "
                         f"{entry['config']!r}, the cell file "
                         f"{cell['config']!r}")
    return entry, cell, _load_json(BENCH, "configs", cell["config"] + ".json")


def metrics_for(spec: dict, name: str, trace: bool) -> list[dict]:
    """The metrics a cell reports: its end-to-end metrics, or with a trace
    its per-layer metrics."""
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in moved
                             else [])]


class CompileCounter:
    """Counts programs compiled or loaded from the compile cache (JAX's
    backend-compile event wraps both)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def enable_compile_cache() -> None:
    """The persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` where
    it is set (JAX reads it), else ``.jax_cache/`` at the checkout root."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class GcPauses:
    """Pauses of Python's garbage collector, each a stall of the host
    between device calls: [(generation, seconds)]."""

    def __init__(self):
        self.pauses, self._t = [], None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def stop(self) -> str:
        gc.callbacks.remove(self._on)
        if not self.pauses:
            return "no garbage collection"
        g, longest = max(self.pauses, key=lambda p: p[1])
        return (f"{len(self.pauses)} garbage collections, longest "
                f"{1e3 * longest:.3f} ms (generation {g})")


def peak_memory_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def execute(name: str, seed: int, seconds: float, trace: bool,
            devices, spec: dict | None = None, cell: dict | None = None,
            config: dict | None = None) -> dict:
    """Run one cell once on ``devices`` and return the result object.
    ``spec``/``cell``/``config`` replace the files of that name (tests run
    cells at small sizes so)."""
    spec = spec if spec is not None else _load_json(ROOT, "BENCHMARK.json")
    _, cell_f, config_f = load_cell(name, spec)
    run = Run(name=name, cell=cell if cell is not None else cell_f,
              config=config if config is not None else config_f,
              seed=seed, seconds=seconds)
    driver = _load_module("traffic", run.cell["driver"])
    wanted = metrics_for(spec, name, trace)
    readers = {m["name"]: _load_module("layer_metrics", m["name"])
               for m in wanted} if trace else {}
    compiles = CompileCounter()

    state = driver.setup(run)
    # set-up's objects go where no collection in the window walks them
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    setup_compiles = compiles.n
    pauses = GcPauses()
    if trace:
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    win = driver.window(run, state)
    if trace:
        jax.profiler.stop_trace()
    window_compiles = compiles.n - setup_compiles
    print(f"in the window: {pauses.stop()}", file=sys.stderr)
    gc.unfreeze()
    if hasattr(driver, "collect"):
        win.outputs = driver.collect(run, state, win)
    mem = peak_memory_bytes(devices)
    del state
    gc.collect()
    checks, found = driver.verify(run, win.outputs)
    win.metrics.update(found)
    checks.append(Check("window_compiles", window_compiles, 0))

    metrics, summary = {}, None
    if trace:
        from bench import trace_reduce
        from bench.peaks import peaks_for

        summary = trace_reduce.reduce_file(trace_reduce.find_xplane(TRACE_DIR))
        for mod, m in sorted(summary.modules.items()):
            print(f"device ms per run of {mod}: "
                  f"{[round(1e3 * s, 3) for s in m['runs_s']]}",
                  file=sys.stderr)
        ctx = {"trace": summary, "counters": win.counters, "run": run,
               "peaks": peaks_for(devices[0].device_kind)}
        for m in wanted:
            v = readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in wanted:
            v = setup_s if m["name"] == "setup_s" else win.metrics[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": all(c.ok for c in checks),
           "attempted": win.attempted, "failed": win.failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    print(f"set-up {setup_s:.3f} s with {setup_compiles} programs compiled "
          f"or loaded; {window_compiles} in the window", file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = _load_json(ROOT, "BENCHMARK.json")
    entry, _, _ = load_cell(args.workload, spec)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < entry["chips"]:
        print(f"bench/run.py: {args.workload} needs {entry['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              "device(s). Nothing was run.", file=sys.stderr)
        return 2
    enable_compile_cache()
    out = execute(args.workload, args.seed, args.seconds, bool(args.trace),
                  devices[:entry["chips"]], spec=spec)
    for name, c in out["checks"].items():
        verdict = "ok" if Check(name, c["value"], c["limit"]).ok else "FAIL"
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
              f"{verdict}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
