"""Closed-loop search traffic: one client searches the whole query set, waits
for the results, and searches again.

Set-up makes the corpus and queries from the seed, builds the index through
``StreamingANN.from_corpus`` with the configuration's build, and runs one
search, which warms every program the window uses. The window calls
``StreamingANN.search`` on the whole query set, in tiles of
``tile_lanes`` lanes, until ``--seconds`` have passed; a call counts as
complete when its results are on the host.

``search_qps`` = queries completed / window seconds. ``recall_at_10`` (%)
compares the window's results with the exact neighbours that the plain
reference (``bench/reference/knn_ref.py``) finds.

Checks: the shortfall of the window's results against the exact neighbours
(100 - ``recall_at_10``), so that a beam that stops early or a broken
visited set, which still return true distances of real rows, fails; every
returned distance against the reference's float64 distance of the same
pair; no result that is no corpus row or repeats in its list; every call of
the window returning what the first returned.
"""
from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

from bench.common import Check, Window, rel_gap
from bench.deploy import build_index, search_config
from bench.reference import knn_ref


@dataclasses.dataclass
class State:
    ann: object
    q: object
    cfg: object
    x_host: np.ndarray   # the benchmark's corpus, for the reference


def _search(run, st):
    return st.ann.search(st.q, st.cfg, tile_b=run.params["tile_lanes"],
                         with_stats=True)


def setup(run) -> State:
    import jax

    ann, x, q = build_index(run.config, run.seed)
    st = State(ann=ann, q=q, cfg=search_config(run.config),
               x_host=np.asarray(x))
    jax.block_until_ready(_search(run, st))
    return st


def window(run, st: State) -> Window:
    results, work, call_s = [], 0, []
    t0 = t = time.perf_counter()
    while t - t0 < run.seconds:
        ids, dists, stats = _search(run, st)
        results.append((np.asarray(ids), np.asarray(dists)))
        work += int(stats["work"])
        t_end = time.perf_counter()
        call_s.append(t_end - t)
        t = t_end
    nq = st.q.shape[0]
    calls = len(results)
    print(f"search calls: {calls}, beam expansions per call: "
          f"{work // calls}, host ms per call: "
          f"{[round(1e3 * c, 3) for c in call_s]}", file=sys.stderr)
    return Window(
        metrics={"search_qps": calls * nq / (t - t0)},
        attempted=calls * nq, failed=0,
        counters={"calls": calls, "queries": calls * nq, "work": work,
                  "window_s": t - t0},
        outputs={"results": results})


def collect(run, st: State, win: Window) -> dict:
    return {"x": st.x_host, "q": np.asarray(st.q), **win.outputs}


def verify(run, out: dict):
    x, q, results = out["x"], out["q"], out["results"]
    ids, dists = results[0]
    differ = sum(not (np.array_equal(i, ids) and
                      np.array_equal(d.view(np.uint32), dists.view(np.uint32)))
                 for i, d in results[1:])
    ref = knn_ref.pair_dists(x, q, ids)
    ok = ~np.isnan(ref)
    exact = knn_ref.exact_topk(x, q, ids.shape[1])
    lim = run.limits
    recall = 100.0 * knn_ref.recall(ids, exact)
    return [
        Check("recall_shortfall", 100.0 - recall, lim["recall_shortfall"]),
        Check("dist_gap", rel_gap(dists[ok], ref[ok]), lim["dist_gap"]),
        Check("bad_ids", knn_ref.bad_ids(ids, x.shape[0]), lim["bad_ids"]),
        Check("calls_differ", differ, lim["calls_differ"]),
    ], {"recall_at_10": recall}
