"""The reduction from a profiler trace to busy time, the window, device time
per XLA module, and idle gaps labelled by the host."""
import os

import jax
import pytest

from bench import trace_reduce as TR

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")

# One device plane: two sweeps (5 ms, 3 ms) and a reverse pass (2 ms); the
# ops of the first sweep overlap (1-3 ms and 2-4 ms), so their union is 3 ms.
# Times below are in microseconds from the line's start (1 ms).
_EVENTS = [  # (line, metadata id, start_us, duration_us)
    ("XLA Modules", 1, 0, 5000), ("XLA Modules", 1, 6000, 3000),
    ("XLA Modules", 2, 12000, 2000),
    ("XLA Ops", 3, 1000, 2000), ("XLA Ops", 3, 2000, 2000),
    ("XLA Ops", 4, 6000, 3000), ("XLA Ops", 5, 12000, 2000),
]
_NAMES = {1: "jit_update_neighbors(12)", 2: "jit_add_reverse_edges(13)",
          3: "fusion.1", 4: "sort.2", 5: "scatter.3", 6: "PjitFunction",
          7: "waiting for the host"}


def _xspace() -> str:
    def events(line):
        return "".join(
            f"events {{ metadata_id: {m} offset_ps: {s * 10**6} "
            f"duration_ps: {d * 10**6} }}\n"
            for ln, m, s, d in _EVENTS if ln == line)

    meta = "".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                   f'name: "{v}" }} }}\n' for k, v in _NAMES.items())
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000000
{events("XLA Modules")} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000000
{events("XLA Ops")} }}
{meta} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000000
    events {{ metadata_id: 6 offset_ps: 3500000000 duration_ps: 1000000000 }}
    events {{ metadata_id: 7 offset_ps: 9000000000 duration_ps: 3000000000 }}
  }}
{meta} }}
planes {{ id: 3 name: "Task Environment"
  stats {{ metadata_id: 1 uint64_value: 100000 }}
  stats {{ metadata_id: 2 uint64_value: 20100000 }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "profile_start_time" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "profile_stop_time" }} }}
}}
"""


@pytest.fixture(scope="module")
def summary():
    return TR.reduce_profile(jax.profiler.ProfileData.from_text_proto(
        _xspace()))


def test_window_is_the_profiler_session(summary):
    assert summary.window_s == pytest.approx(0.020)


def test_busy_is_the_union_of_operations(summary):
    # 1-4 ms (overlapping ops) + 6-9 ms + 12-14 ms
    assert summary.busy_s == pytest.approx(0.008)
    assert summary.devices == 1


def test_device_time_per_module(summary):
    m = summary.modules["update_neighbors"]
    assert m["count"] == 2 and m["seconds"] == pytest.approx(0.008)
    assert m["runs_s"] == pytest.approx([0.005, 0.003])
    assert summary.module_seconds("add_reverse") == (1, pytest.approx(0.002))
    assert summary.module_seconds("search") == (0, 0)


def test_device_ops_and_idle_gaps(summary):
    assert summary.device_ops[0] == ("fusion.1", pytest.approx(0.004))
    names = [g[0] for g in summary.idle_gaps]
    secs = [g[1] for g in summary.idle_gaps]
    assert secs == pytest.approx([0.003, 0.002])       # 9-12 ms, 4-6 ms
    assert names == ["waiting for the host", "PjitFunction"]


def test_op_name():
    assert TR.op_name("%fusion.8 = f32[480]{0:T(1024)} fusion(f32[1]{0} %a)") \
        == "%fusion.8 = f32[480]"
    assert TR.op_name("%while.2 = (s32[]{:T(128)}, f32[4]{0}) while(%t)") \
        == "%while.2"


def test_module_name():
    assert TR.module_name("jit_update_neighbors(1234)") == "update_neighbors"
    assert TR.module_name("jit__search_tiled_jit(7)") == "_search_tiled_jit"


def test_a_trace_without_device_raises():
    text = _xspace().split('planes { id: 2 name: "/host:CPU"')[0] \
        .replace("/device:TPU:0", "/host:CPU")
    with pytest.raises(ValueError):
        TR.reduce_profile(jax.profiler.ProfileData.from_text_proto(text))


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: two sweeps and one reverse pass of
    the builder at 2,048 rows, with a 20 ms host sleep between them."""
    s = TR.reduce_file(os.path.join(FIXTURES, "builder_2048.xplane.pb"))
    assert s.devices == 1
    assert s.modules["update_neighbors"]["count"] == 2
    assert s.modules["add_reverse_edges"]["count"] == 1
    runs, secs = s.module_seconds("update_neighbors")
    assert runs == 2 and 0.05 < secs / runs < 0.1        # 67.6 and 67.5 ms
    assert s.modules["update_neighbors"]["runs_s"] == pytest.approx(
        [0.0676, 0.0675], abs=2e-4)
    assert 0 < s.busy_s <= s.window_s
    assert s.busy_s <= sum(m["seconds"] for m in s.modules.values()) + 1e-9
    assert s.idle_gaps[0][1] >= 0.02                     # the host sleep
