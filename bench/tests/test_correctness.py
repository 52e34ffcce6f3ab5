"""Each cell's checks, driven through the whole harness at a small size on
the CPU (the look for a chip skipped): a sound run is correct; the control
(the program's lower-precision path) and every fault the cell can have,
planted in the timed path underneath, are not."""
import json

import numpy as np
import pytest

from bench.control import control_config
from conftest import run_small, small_cell

CELLS = ["sift1m-build", "gist1m-search"]   # cell files


def _failed(out):
    return [k for k, c in out["checks"].items() if not c["value"] <= c["limit"]]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = run_small(name)
    json.dumps(out)                      # the result line is plain JSON
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    _, cell, config = small_cell(name)
    out = run_small(name, config=control_config(cell, config))
    assert not out["correct"] and _failed(out), out["checks"]


# ------------------------------------------------------------ build faults
def _unchanged(real):
    return lambda *a, **k: a[-2] if len(a) == 3 else a[0]


def _half_rows(real):
    def fault(*a, **k):
        g_in = a[1] if len(a) == 3 else a[0]
        out = real(*a, **k)
        h = g_in.neighbors.shape[0] // 2
        return type(out)(*(o.at[h:].set(i[h:]) for o, i in zip(out, g_in)))
    return fault


def _altered(real):
    def fault(*a, **k):
        out = real(*a, **k)
        n = out.neighbors.shape[0]
        nb = out.neighbors.at[5, 0].set((out.neighbors[5, 0] + 1) % n)
        return out._replace(neighbors=nb)
    return fault


@pytest.mark.parametrize("program", ["update_neighbors", "add_reverse_edges"])
@pytest.mark.parametrize("fault", [_unchanged, _half_rows, _altered])
def test_build_faults_are_not_correct(monkeypatch, program, fault):
    from repro.core import rnn_descent as rd

    monkeypatch.setattr(rd, program, fault(getattr(rd, program)))
    out = run_small("sift1m-build")
    assert not out["correct"], out["checks"]


# ----------------------------------------------------------- search faults
def _search_altered(real):
    def fault(self, queries, *a, **k):
        ids, dists, *rest = real(self, queries, *a, **k)
        ids = np.asarray(ids).copy()
        ids[0, 0] = (ids[0, 0] + 1) % self.store.x.shape[0]
        return (ids, dists, *rest)
    return fault


def _search_half(real):
    def fault(self, queries, *a, **k):
        ids, dists, *rest = real(self, queries, *a, **k)
        ids, dists = np.asarray(ids).copy(), np.asarray(dists).copy()
        h = ids.shape[0] // 2
        ids[h:], dists[h:] = -1, np.inf
        return (ids, dists, *rest)
    return fault


@pytest.mark.parametrize("fault", [_search_altered, _search_half])
def test_search_faults_are_not_correct(monkeypatch, fault):
    from repro.streaming import StreamingANN

    monkeypatch.setattr(StreamingANN, "search", fault(StreamingANN.search))
    out = run_small("gist1m-search")
    assert not out["correct"], out["checks"]


def test_beam_cut_short_is_not_correct():
    """The cell file's fault: a beam that stops after 4 expansions
    returns real rows at their true distances; only the shortfall against
    the exact neighbours catches it."""
    _, cell, config = small_cell("gist1m-search")
    out = run_small("gist1m-search",
                    config=control_config(cell, config, "beam_cut"))
    assert _failed(out) == ["recall_shortfall"], out["checks"]
