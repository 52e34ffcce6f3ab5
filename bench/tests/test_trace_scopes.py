"""Device time under the program's named scopes, and device-idle time under
its host spans, read from a profiler trace."""
import os

import jax
import pytest

from bench import trace_reduce as TR
from bench import trace_scopes as TS

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
ProfileData = jax.profiler.ProfileData

# One device plane, times in microseconds from the line's start. Two sweeps
# (program 11): a loop without metadata around two prune ops, two
# overlapping merge ops, a copy under no scope. A reverse pass (program 12)
# runs an op of the sweep's name under no scope. A search (program 13): a
# loop without metadata around ops of two scopes.
_MODULES = [(11, "jit_update_neighbors(11)", 0, 10000),
            (11, "jit_update_neighbors(11)", 12000, 5000),
            (12, "jit_add_reverse_edges(12)", 20000, 2000),
            (13, "jit__search_tiled_jit(13)", 30000, 4000)]
_OPS = [  # (metadata id, start_us, duration_us)
    (20, 0, 4000), (21, 1000, 1000), (22, 2500, 1000), (23, 4000, 3000),
    (24, 6000, 2000), (25, 8000, 1000),
    (20, 12000, 2000), (21, 12500, 500), (23, 14000, 2000),
    (30, 20000, 1000),
    (40, 30000, 4000), (41, 30500, 1000), (42, 32000, 1000)]
# metadata id -> (name, program id, tf_op stat: ("str", text) or ("ref",
# id of the stat metadata named by the text), or None)
_META = {
    20: ("%while.1", 11, None),
    21: ("%fusion.4", 11, ("str", "jit(update_neighbors)/rnnd.prune/while/"
                                  "body/closed_call/mul:")),
    22: ("%fusion.5", 11, ("ref", 100)),
    23: ("%fusion.2", 11, ("str", "jit(update_neighbors)/rnnd.merge/jit("
                                  "sort_rows)/sort:")),
    24: ("%fusion.6", 11, ("ref", 101)),
    25: ("%copy.3", 11, None),
    30: ("%fusion.2", 12, ("str", "jit(add_reverse_edges)/jit(argsort)/"
                                  "sort:")),
    40: ("%while.9", 13, None),
    41: ("%fusion.1", 13, ("str", "while/body/beam.score/gather:")),
    42: ("%fusion.2", 13, ("str", "jit(_search_tiled_jit)/while/body/"
                                  "beam.topk/top_k:")),
}
_REFS = {100: "jit(update_neighbors)/rnnd.prune/while/body/dot_general:",
         101: "jit(update_neighbors)/rnnd.merge/scatter:"}
# host: the program's spans and a frame of the client's, which is no span
_HOST = [("streaming/search", 24000, 6500), ("streaming/entry", 24000, 2000),
         ("closed_loop_search.py:58 window", 17000, 13000)]


def _xspace() -> str:
    mods = {name: 1 + i for i, name in
            enumerate(dict.fromkeys(m[1] for m in _MODULES))}
    mod_events = "".join(
        f"events {{ metadata_id: {mods[n]} offset_ps: {s * 10**6} "
        f"duration_ps: {d * 10**6} }}\n" for _, n, s, d in _MODULES)
    op_events = "".join(
        f"events {{ metadata_id: {m} offset_ps: {s * 10**6} "
        f"duration_ps: {d * 10**6} }}\n" for m, s, d in _OPS)
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in mods.items())
    for k, (name, prog, tf) in _META.items():
        stats = f"stats {{ metadata_id: 2 uint64_value: {prog} }}"
        if tf is not None:
            kind, v = tf
            stats += (f' stats {{ metadata_id: 1 str_value: "{v}" }}'
                      if kind == "str" else
                      f" stats {{ metadata_id: 1 ref_value: {v} }}")
        meta += (f'event_metadata {{ key: {k} value {{ id: {k} '
                 f'name: "{name}" {stats} }} }}\n')
    stat_meta = "".join(
        f'stat_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}\n'
        for k, v in {1: "tf_op", 2: "program_id", **_REFS}.items())
    host = "".join(
        f"events {{ metadata_id: {50 + i} offset_ps: {s * 10**6} "
        f"duration_ps: {d * 10**6} }}\n" for i, (_, s, d) in enumerate(_HOST))
    host_meta = "".join(
        f'event_metadata {{ key: {50 + i} value {{ id: {50 + i} '
        f'name: "{n}" }} }}\n' for i, (n, _, _) in enumerate(_HOST))
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000000
{mod_events} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000000
{op_events} }}
{meta}{stat_meta} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000000
{host} }}
{host_meta} }}
"""


@pytest.fixture(scope="module")
def data():
    return ProfileData.text_proto_to_serialized_xspace(_xspace())


@pytest.fixture(scope="module")
def scopes(data):
    return TS.reduce_trace(ProfileData.from_serialized_xspace(data),
                           TS.read_metadata(data))


def test_metadata_in_both_value_forms(data):
    md = TS.read_metadata(data)["/device:TPU:0"]
    assert md[(11, "%fusion.4")] == {_META[21][2][1]}          # str_value
    assert md[(11, "%fusion.5")] == {_REFS[100]}                # ref_value
    assert (11, "%while.1") not in md                           # none
    # one name, two programs, two tf_ops
    assert md[(11, "%fusion.2")] != md[(12, "%fusion.2")]


def test_scopes_of_reads_whole_segments():
    assert TS.scopes_of("jit(f)/rnnd.prune/while/body/mul:mul") == \
        {"rnnd.prune"}
    assert TS.scopes_of("while/body/beam.visited/reduce_or") == \
        {"beam.visited"}
    assert TS.scopes_of("jit(f)/jit(argsort)/sort:") == set()
    assert TS.scopes_of("jit(f)/rnnd.prunex/sort:") == {"rnnd.prunex"}
    assert "rnnd.prune" not in TS.scopes_of("jit(f)/rnnd.prunex/sort:")


def test_a_loop_without_metadata_takes_its_body_scope(scopes):
    # the loop's whole interval counts, not only its body's ops:
    # (4,000 + 2,000) us over two runs
    assert scopes.scope_ms_per_run("update_neighbors", "rnnd.prune") == \
        pytest.approx(3.0)


def test_scope_time_is_the_union_divided_per_run(scopes):
    # run 1: 4,000-7,000 and 6,000-8,000 us overlap -> 4 ms; run 2: 2 ms
    assert scopes.scope_ms_per_run("update_neighbors", "rnnd.merge") == \
        pytest.approx(3.0)
    assert scopes.runs["update_neighbors"] == 2


def test_a_name_of_two_tf_ops_is_joined_by_program(scopes):
    # %fusion.2 is in rnnd.merge in the sweep and in no scope in the
    # reverse pass, where the trace holds nothing for rnnd.merge
    assert scopes.scope_ms_per_run("add_reverse_edges", "rnnd.merge") is None
    assert scopes.unattributed["add_reverse_edges"] == (1, pytest.approx(1e-3))


def test_coverage_and_unattributed_ops(scopes):
    # 8 of run 1's 10 ms and 4 of run 2's 5 ms are scoped; the copy is not
    assert scopes.covered_s["update_neighbors"] == pytest.approx(12e-3)
    assert scopes.module_s["update_neighbors"] == pytest.approx(15e-3)
    assert scopes.unattributed["update_neighbors"] == (1, pytest.approx(1e-3))


def test_a_loop_over_several_scopes_stays_a_container(scopes):
    assert scopes.scope_ms_per_run("search_tiled", "beam.score") == \
        pytest.approx(1.0)
    assert scopes.scope_ms_per_run("search_tiled", "beam.topk") == \
        pytest.approx(1.0)
    assert scopes.unattributed["_search_tiled_jit"] == (0, 0.0)
    assert scopes.scope_ms_per_run("search_tiled", "beam.visited") is None


def test_device_idle_inside_program_spans(scopes):
    # the device idles 21-30 ms; streaming/search runs 24-30.5 ms
    assert scopes.idle_ms_per_span("streaming/search") == pytest.approx(6.0)
    assert scopes.idle_ms_per_span("streaming/entry") == pytest.approx(2.0)
    assert scopes.idle_ms_per_span("serving/dispatch") is None
    assert "closed_loop_search.py:58 window" not in scopes.span_calls


def test_idle_gaps_named_by_the_innermost_program_span(scopes):
    secs = [g[0] for g in scopes.gaps]
    assert secs[:3] == pytest.approx([9e-3, 4e-3, 3e-3])
    _, span, share = scopes.gaps[0]
    assert span == "streaming/search" and share == pytest.approx(6 / 9)
    assert scopes.gaps[1][1] is None and scopes.gaps[2][1] is None


def test_current_reads_the_run_trace_once(tmp_path, monkeypatch, data,
                                          capsys):
    monkeypatch.setattr(TS, "TRACE_DIR", str(tmp_path))
    assert TS.current() is None and TS.scope_ms("x", "y") is None
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(data)
    first = TS.current()
    assert TS.current() is first
    assert TS.scope_ms("update_neighbors", "rnnd.prune") == \
        pytest.approx(3.0)
    assert TS.span_idle_ms("streaming/search") == pytest.approx(6.0)
    err = capsys.readouterr().err
    assert "coverage 80.00%" in err and "outside the program" in err


def test_recorded_tpu_trace_with_scopes():
    """A trace recorded on one TPU v5e with the program's scopes and spans:
    one sweep at 2,048 x 128, then one 256-query ``StreamingANN.search``
    call on 4,096 x 960."""
    with open(os.path.join(FIXTURES, "scoped_v5e.xplane.pb"), "rb") as f:
        data = f.read()
    sc = TS.reduce_trace(ProfileData.from_serialized_xspace(data),
                         TS.read_metadata(data))
    summary = TR.reduce_profile(ProfileData.from_serialized_xspace(data))
    for module, scopes in (("update_neighbors", ("rnnd.prune", "rnnd.merge")),
                           ("search_tiled", ("beam.select", "beam.score",
                                             "beam.visited", "beam.topk"))):
        runs, secs = summary.module_seconds(module)
        per_run = 1e3 * secs / runs
        total = 0.0
        for s in scopes:
            ms = sc.scope_ms_per_run(module, s)
            assert ms is not None and 0 < ms <= per_run
            total += ms
        # the scopes do not overlap; a few ops carry no scope
        assert 0.8 * per_run < total <= per_run
    assert {s: sc.span_calls[s] for s in ("streaming/search",
                                          "streaming/entry",
                                          "search/dispatch")} == \
        {"streaming/search": 1, "streaming/entry": 1, "search/dispatch": 1}
    assert sc.idle_ms_per_span("streaming/search") > 0
