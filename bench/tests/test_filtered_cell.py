"""The filtered-search cell, driven through the whole harness at a small
size on the CPU: a sound run is correct; the control (the program's bf16
path) and each planted fault are not."""
import json

import pytest

from bench.control import control_config
from conftest import run_small, small_cell

FILTERED = "sift1m-attr12-search"


def _failed(out):
    return [k for k, c in out["checks"].items() if not c["value"] <= c["limit"]]


def _filtered_small(fault=None, control=False, seconds=1.0):
    """The filtered cell at 2,048 rows and 512 queries in two calls of 256;
    its beam keeps the configuration's iteration room (the shared small size
    cuts it to 128, which a 1-in-12 predicate needs more than), and its
    table twice the rows, as the configuration's."""
    _, cell, config = small_cell(FILTERED)
    config.update(queries=512)
    config["search"].update(max_iters=1024, slots=4096)
    cell["params"].update(call_queries=256)
    if control or fault:
        config = control_config(cell, config, fault)
    return run_small(FILTERED, config=config, cell=cell, seconds=seconds)


def test_filtered_sound_run_is_correct():
    out = _filtered_small()
    json.dumps(out)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["filter_violations"]["value"] == 0


def test_filtered_control_is_not_correct():
    out = _filtered_small(control=True)
    assert not out["correct"] and "dist_gap" in _failed(out), out["checks"]


@pytest.mark.parametrize("fault", ["beam_cut", "post_filter"])
def test_filtered_faults_fail_on_recall(fault):
    """A beam cut to 4 expansions, and today's path (an unfiltered beam
    whose results are masked afterwards), both return passing rows at
    true distances; the shortfall against the exact filtered neighbours
    is what catches them."""
    out = _filtered_small(fault=fault)
    assert "recall_shortfall" in _failed(out), out["checks"]
    assert out["checks"]["filter_violations"]["value"] == 0


def test_predicate_violation_is_not_correct(monkeypatch):
    """A search that ignores the predicate returns rows of every label."""
    from repro.streaming import StreamingANN

    real = StreamingANN.search

    def ignore(self, queries, cfg=None, filters=None, **kw):
        return real(self, queries, cfg, **kw)

    monkeypatch.setattr(StreamingANN, "search", ignore)
    out = _filtered_small()
    assert "filter_violations" in _failed(out), out["checks"]


def test_filtered_window_searches_every_call_in_turn():
    """A window long enough to wrap round the query set checks every
    repeated call against the first, and counts each query it searched."""
    out = _filtered_small(seconds=4.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 512 and out["attempted"] % 256 == 0


def test_query_set_must_split_into_calls():
    _, cell, config = small_cell(FILTERED)
    config.update(queries=300)
    config["search"].update(max_iters=1024, slots=4096)
    cell["params"].update(call_queries=256)
    with pytest.raises(ValueError, match="calls of 256"):
        run_small(FILTERED, config=config, cell=cell)
