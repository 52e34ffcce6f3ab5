"""Observability layer (src/repro/obs/): the tracer's nesting/export
contracts, the metrics registry's Prometheus semantics, and the two hard
repo-wide guarantees:

  * **zero-cost when disabled** — ``span()`` returns the shared falsy
    sentinel without allocating, no event is recorded, and instrumented
    hot paths never touch the process metrics registry while obs is off;
  * **bitwise parity** — enabling tracing changes no result bit: the
    traced build graph and search output are byte-identical to untraced
    runs (instrumentation is host-side only; same jitted programs).

Plus the jax.monitoring bridge (compile events land as counters +
back-dated spans) and the telemetry empty-session contract (``None``,
never a fabricated 0.0).
"""
import json
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import rnn_descent as rd
from repro.core import search as S
from repro.data.synthetic import VectorDatasetSpec, clustered_vectors
from repro.obs import jaxhooks, metrics
from repro.obs import trace as T

CFG = rd.RNNDescentConfig(s=8, r=16, t1=2, t2=2, capacity=24, chunk=128)
SCFG = S.SearchConfig(l=24, k=16, max_iters=64, topk=10)


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with obs disabled and a clean slate."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture(scope="module")
def tiny():
    x, q = clustered_vectors(
        jax.random.PRNGKey(3),
        VectorDatasetSpec("obs", n=512, d=24, n_queries=32, n_clusters=8))
    return np.asarray(x), np.asarray(q)


# ----------------------------------------------------------------- tracing
class TestTrace:
    def test_nesting_and_attrs(self):
        with T.enabled_scope():
            with T.span("outer", phase="a") as so:
                with T.span("inner") as si:
                    si.set(edges=7)
                assert so and si
            evs = T.events()
        by = {e["name"]: e for e in evs}
        assert by["outer"]["depth"] == 0
        assert by["inner"]["depth"] == 1
        assert by["inner"]["attrs"] == {"edges": 7}
        assert by["outer"]["attrs"] == {"phase": "a"}
        # inner is contained in outer on the same thread track
        assert by["inner"]["tid"] == by["outer"]["tid"]
        assert by["outer"]["start_s"] <= by["inner"]["start_s"]
        assert (by["inner"]["start_s"] + by["inner"]["dur_s"]
                <= by["outer"]["start_s"] + by["outer"]["dur_s"] + 1e-9)

    def test_disabled_span_is_shared_noop(self):
        s1, s2 = T.span("a", x=1), T.span("b")
        assert s1 is s2 is T.NOOP
        assert not s1
        with s1 as sp:
            sp.set(anything=1)       # no-op, records nothing
        assert T.events() == []

    def test_per_thread_tracks(self):
        def worker():
            with T.span("worker/span"):
                pass

        with T.enabled_scope():
            t = threading.Thread(target=worker)
            with T.span("main/span"):
                t.start()
                t.join()
            evs = T.events()
        tids = {e["name"]: e["tid"] for e in evs}
        assert tids["worker/span"] != tids["main/span"]
        # the worker's stack is its own: depth 0, not nested under main
        assert {e["depth"] for e in evs} == {0}

    def test_timed_always_measures_records_only_enabled(self):
        with T.timed("off/block") as tm:
            pass
        assert tm.seconds >= 0.0
        assert T.events() == []
        with T.enabled_scope():
            with T.timed("on/block", tag="z") as tm:
                pass
            assert tm.seconds >= 0.0
            evs = T.events()
        assert [e["name"] for e in evs] == ["on/block"]
        assert evs[0]["attrs"] == {"tag": "z"}

    def test_chrome_trace_round_trip(self, tmp_path):
        with T.enabled_scope():
            with T.span("a/b", n=3, label="x"):
                pass
            T.add_complete("retro", 0.5, 0.25, tid=1001, rid=4)
            path = str(tmp_path / "trace.json")
            T.write_chrome_trace(path, process_name="unit")
        doc = json.loads(open(path).read())
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M"]
        assert meta and meta[0]["args"]["name"] == "unit"
        xs = {e["name"]: e for e in evs if e["ph"] == "X"}
        assert set(xs) == {"a/b", "retro"}
        for e in xs.values():
            assert isinstance(e["ts"], (int, float)) and e["dur"] >= 0
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert xs["a/b"]["args"] == {"n": 3, "label": "x"}
        assert xs["retro"]["tid"] == 1001
        assert xs["retro"]["dur"] == pytest.approx(0.25e6)

    def test_summary_aggregates(self):
        with T.enabled_scope():
            for _ in range(3):
                with T.span("phase/x"):
                    pass
            with T.span("phase/y"):
                pass
            summ = T.summary(prefix="phase/")
        assert summ["phase/x"]["count"] == 3
        assert summ["phase/y"]["count"] == 1
        row = summ["phase/x"]
        assert row["min_s"] <= row["mean_s"] <= row["max_s"]
        assert row["total_s"] == pytest.approx(row["mean_s"] * 3)


# ----------------------------------------------------------------- metrics
class TestMetrics:
    def test_counter_gauge_semantics(self):
        reg = metrics.Registry()
        c = reg.counter("ops_total", help="ops")
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("depth")
        g.set(3)
        g.inc()
        g.dec(2)
        assert g.value == 2
        # same (name, labels) -> same child; different labels -> new child
        assert reg.counter("ops_total") is c
        assert reg.counter("ops_total", kind="x") is not c

    def test_type_and_bucket_conflicts_raise(self):
        reg = metrics.Registry()
        reg.counter("m")
        with pytest.raises(ValueError):
            reg.gauge("m")
        reg.histogram("h", buckets=(1.0, 2.0))
        with pytest.raises(ValueError):
            reg.histogram("h", buckets=(1.0, 3.0))
        with pytest.raises(ValueError):
            reg.counter("bad name")
        with pytest.raises(ValueError):
            reg.histogram("h2", buckets=(2.0, 1.0))

    def test_histogram_cumulative(self):
        reg = metrics.Registry()
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        assert h.cumulative() == [(0.1, 1), (1.0, 3), (10.0, 4),
                                  (float("inf"), 5)]
        assert h.count == 5
        assert h.sum == pytest.approx(56.05)

    def test_exposition_format(self):
        reg = metrics.Registry()
        reg.counter("reqs_total", help="admitted", shard="queries").inc(2)
        reg.gauge("qps").set(12.5)
        reg.histogram("occ", buckets=(0.5, 1.0), help="tile occ").observe(0.7)
        text = reg.exposition()
        assert "# HELP reqs_total admitted" in text
        assert "# TYPE reqs_total counter" in text
        assert 'reqs_total{shard="queries"} 2' in text
        assert "# TYPE qps gauge" in text
        assert "qps 12.5" in text
        assert "# TYPE occ histogram" in text
        assert 'occ_bucket{le="0.5"} 0' in text
        assert 'occ_bucket{le="1"} 1' in text
        assert 'occ_bucket{le="+Inf"} 1' in text
        assert "occ_sum 0.7" in text
        assert "occ_count 1" in text
        assert text.endswith("\n")

    def test_snapshot_round_trips_json(self):
        reg = metrics.Registry()
        reg.counter("a_total", event="x").inc()
        reg.histogram("b", buckets=(1.0,)).observe(2.0)
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["a_total"]["type"] == "counter"
        assert snap["a_total"]["samples"][0]["labels"] == {"event": "x"}
        assert snap["b"]["samples"][0]["buckets"] == {"1": 0, "+Inf": 1}


# ----------------------------------------- the two repo-wide hard contracts
class TestDisabledNoOp:
    def test_instrumented_paths_leave_registry_untouched(self, tiny):
        """With obs off, a full build + search touches neither the span
        list nor the process registry (the zero-cost contract)."""
        x, q = tiny
        assert not obs.enabled()
        g = rd.build(x, CFG, jax.random.PRNGKey(0))
        eps = S.default_entry_point(x, SCFG.metric)
        S.search_tiled(x, g, q, eps, SCFG, tile_b=32)
        assert T.events() == []
        assert len(metrics.REGISTRY) == 0

    def test_bitwise_parity_traced_vs_untraced(self, tiny):
        x, q = tiny
        key = jax.random.PRNGKey(0)

        def run_once():
            g = rd.build(x, CFG, key)
            eps = S.default_entry_point(x, SCFG.metric)
            ids, dists = S.search_tiled(x, g, q, eps, SCFG, tile_b=32)
            g = jax.block_until_ready(g)
            return (np.asarray(g.neighbors).tobytes(),
                    np.asarray(g.dists).tobytes(),
                    np.asarray(ids).tobytes(),
                    np.asarray(dists).tobytes())

        ref = run_once()
        with T.enabled_scope():
            got = run_once()
            names = {e["name"] for e in T.events()}
        assert got == ref
        # and the traced run actually recorded the hot-path spans
        assert "rnn_descent/sweep" in names
        assert "search/tiled" in names

    def test_search_stats_fold_into_registry(self, tiny):
        """With obs on, ``search_tiled(with_stats=True)`` adds its lane work
        and its lost visited-table inserts to the process registry."""
        x, q = tiny
        g = rd.build(x, CFG, jax.random.PRNGKey(0))
        eps = S.default_entry_point(x, SCFG.metric)
        cfg = S.SearchConfig(l=24, k=16, max_iters=64, topk=10, slots=16,
                             probes=2)
        with T.enabled_scope():
            *_, st = S.search_tiled(x, g, q, eps, cfg, tile_b=32,
                                    with_stats=True)
        snap = metrics.REGISTRY.snapshot()

        def total(name):
            return sum(s["value"] for s in snap[name]["samples"])

        assert int(st["visited_lost"]) > 0
        assert total("search_lane_work_total") == int(st["work"])
        assert total("search_visited_lost_total") == int(st["visited_lost"])


# ------------------------------------------------- on the profiler's clock
def _host_event_names(trace_dir) -> set:
    import glob
    import os

    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    prof = jax.profiler.ProfileData.from_file(path)
    return {e.name for plane in prof.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


class TestProfilerClock:
    """Spans mirror into ``jax.profiler.TraceAnnotation`` while a profiler
    collects; the program's phases carry ``jax.named_scope`` names into the
    compiled programs' op metadata."""

    def test_off_without_profiler_is_noop(self):
        assert not jax.profiler.TraceAnnotation.is_enabled()
        assert T.span("streaming/search") is T.NOOP

    def test_obs_off_under_profiler_only_annotates(self, tmp_path):
        with jax.profiler.trace(str(tmp_path)):
            sp = T.span("unit/annotated")
            with sp:
                assert not sp
                sp.set(ignored=1)
                jax.block_until_ready(jax.numpy.ones(4) + 1)
        assert sp is not T.NOOP
        assert T.events() == []
        assert len(metrics.REGISTRY) == 0
        assert "unit/annotated" in _host_event_names(tmp_path)

    def test_obs_on_under_profiler_records_and_annotates(self, tmp_path):
        with T.enabled_scope(), jax.profiler.trace(str(tmp_path)):
            with T.span("unit/both") as sp:
                assert sp
        assert [e["name"] for e in T.events()] == ["unit/both"]
        assert "unit/both" in _host_event_names(tmp_path)

    @pytest.mark.parametrize("program,scope", [
        ("update_neighbors", "rnnd.prune"),
        ("update_neighbors", "rnnd.merge"),
        ("search_tiled", "beam.select"),
        ("search_tiled", "beam.score"),
        ("search_tiled", "beam.visited"),
        ("search_tiled", "beam.topk"),
    ])
    def test_compiled_program_names_its_phase(self, tiny, program, scope):
        import re

        x, q = tiny
        g = rd.random_init(jax.random.PRNGKey(0), x, CFG)
        if program == "update_neighbors":
            lowered = rd.update_neighbors.lower(x, g, CFG)
        else:
            lowered = S._search_tiled_jit.lower(x, g, q, 0, SCFG, 16)
        # a persistent compile cache keys programs without their metadata:
        # key by it here, so an entry compiled without the scopes is no hit
        key = "jax_compilation_cache_include_metadata_in_key"
        before = getattr(jax.config, key)
        jax.config.update(key, True)
        try:
            text = lowered.compile().as_text()
        finally:
            jax.config.update(key, before)
        assert re.search(r'op_name="([^"]*/)?' + re.escape(scope) + '[/"]',
                         text)

    def test_streaming_search_bitwise_under_profiler(self, tiny, tmp_path):
        from repro.streaming import StreamingANN, StreamingConfig

        x, q = tiny
        ann = StreamingANN.from_corpus(x, StreamingConfig(build=CFG),
                                       key=jax.random.PRNGKey(1))
        ref = [np.asarray(a) for a in ann.search(q, SCFG, tile_b=16)]
        with jax.profiler.trace(str(tmp_path)):
            got = [np.asarray(a) for a in ann.search(q, SCFG, tile_b=16)]
        assert all(a.tobytes() == b.tobytes() for a, b in zip(got, ref))
        names = _host_event_names(tmp_path)
        assert {"streaming/search", "streaming/entry",
                "search/dispatch"} <= names
        assert T.events() == []

    def test_obs_smoke_session_passes(self, tmp_path):
        """``python -m repro.obs``: the scripted build + serve session and
        its contract checks."""
        from repro.obs.__main__ import main

        assert main(["--out", str(tmp_path)]) == 0


# ------------------------------------------------------------ jax bridge
class TestJaxHooks:
    def test_compile_events_captured(self):
        jaxhooks.install()
        jaxhooks.install()               # idempotent
        with T.enabled_scope():
            before = jaxhooks.backend_compiles()
            # a fresh lambda is never cache-hit: forces a real compile
            jax.jit(lambda v: v * 2 + 1)(np.arange(4.0))
            after = jaxhooks.backend_compiles()
            names = {e["name"] for e in T.events()}
        assert after > before
        assert any(n.startswith("jax/") for n in names)
        snap = metrics.REGISTRY.snapshot()
        assert "jax_compile_events_total" in snap
        assert "jax_compile_seconds" in snap

    def test_listener_quiet_while_disabled(self):
        jaxhooks.install()
        assert not obs.enabled()
        jax.jit(lambda v: v - 3)(np.arange(3.0))
        assert len(metrics.REGISTRY) == 0
        assert T.events() == []

    def test_record_memory(self):
        with T.enabled_scope():
            out = jaxhooks.record_memory(phase="unit")
        assert out
        assert all(v >= 0 for kinds in out.values() for v in kinds.values())
        assert "obs_device_bytes" in metrics.REGISTRY.snapshot()

    def test_traced_hlo_costs_attrs(self):
        attrs = jaxhooks.traced_hlo_costs(
            lambda a, b: a @ b,
            jax.ShapeDtypeStruct((32, 16), np.float32),
            jax.ShapeDtypeStruct((16, 8), np.float32))
        assert attrs["hlo_dot_flops_per_device"] > 0
        assert attrs["hlo_collective_instructions"] == 0


# ------------------------------------------------------- telemetry bridge
class TestTelemetryEmpty:
    def test_empty_session_reports_none(self):
        from repro.serving.telemetry import Telemetry

        summ = Telemetry().summary()
        assert summ["completed"] == 0
        assert summ["achieved_qps"] is None
        assert summ["deadline_hit_rate"] is None
        assert all(v is None for v in summ["latency_ms"].values())
        assert all(v is None for v in summ["dispatch_wait_ms"].values())
        assert summ["occupancy_mean"] is None
        assert summ["staleness_mean"] is None

    def test_explicit_registry_mirrors_even_disabled(self):
        from repro.serving.telemetry import Telemetry

        reg = metrics.Registry()
        tel = Telemetry(registry=reg)
        assert not obs.enabled()
        tel.record_enqueue(0, 0.0, 1.0)
        tel.record_dispatch([0], 0.01, occupancy=1, tile_lanes=4,
                            queue_depth=0, epoch=0)
        tel.record_complete([0], 0.02, tile_index=0, epoch=0)
        snap = reg.snapshot()
        assert snap["serving_requests_total"]["samples"][0]["value"] == 1
        assert "serving_request_latency_seconds" in snap
        # the *process* registry stayed untouched
        assert len(metrics.REGISTRY) == 0
