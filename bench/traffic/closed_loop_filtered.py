"""Closed-loop filtered search traffic: one client searches a call of
``call_queries`` queries, each under its own label predicate, waits for the
results, and searches the next call of the query set.

The configuration's ``labels`` group gives the attribute: each corpus row
holds one value drawn uniformly from ``values`` (1..V), independently of its
vector, stored as the label word ``1 << (value - 1)``; each query holds an
equality predicate on a value drawn the same way, the mask
``1 << (value - 1)``. Set-up makes the corpus, the whole query set, labels
and masks from the seed, builds the index through
``StreamingANN.from_corpus(labels=)`` with the configuration's build, and
runs one call, which warms every program the window uses (every call has one
shape). The window calls ``StreamingANN.search(filters=)`` on the query
set's calls in turn, wrapping round, in tiles of ``tile_lanes`` lanes, until
``--seconds`` have passed; a call counts as complete when its results are
on the host. Each call brings other queries, so a window's numbers average
over several calls' slowest lanes and several thousand queries.

``search_qps`` = queries completed / window seconds. ``recall_at_10`` (%)
compares the window's results with the exact filtered neighbours that the
plain reference (``bench/reference/filtered_knn_ref.py``) finds, over every
query the window searched.

The search group's ``filter`` says where the predicate applies: ``beam``
(the program's filtered traversal) or ``results`` (the fault
``post_filter``: an unfiltered beam of L whose results are masked
afterwards on the host, the way tombstones are masked).

Checks: the shortfall against the exact filtered neighbours (100 -
``recall_at_10``); ``filter_violations``, returned rows that fail their
query's predicate; every returned distance against the reference's float64
distance of the same pair; no result that is no corpus row or repeats in
its list; every call returning what the first call of the same queries
returned (after the window the first call is searched once more, so this
holds a window that never wraps round to it too).
"""
from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

from bench.common import Check, Window, rel_gap
from bench.deploy import build_config, make_data
from bench.reference import filtered_knn_ref, knn_ref


@dataclasses.dataclass
class State:
    ann: object
    calls: list               # (queries, masks) of each call, on the device
    cfg: object
    in_beam: bool
    x_host: np.ndarray        # the benchmark's corpus, for the reference
    labels_host: np.ndarray
    q_host: np.ndarray        # the whole query set and its masks
    masks_host: np.ndarray


def _attribute(key, n: int, values: int):
    """(n,) uint32 label words 1 << (value - 1), value uniform in 1..V."""
    import jax
    import jax.numpy as jnp

    v = jax.random.randint(key, (n,), 0, values)
    return jnp.left_shift(jnp.uint32(1), v.astype(jnp.uint32))


def search_cfg(config: dict):
    """(SearchConfig, predicate in the beam?) of the configuration."""
    from repro.core.search import SearchConfig

    s = dict(config["search"])
    where = s.pop("filter")
    if where not in ("beam", "results"):
        raise ValueError(f"search.filter must be beam or results: {where!r}")
    if where == "results":
        s["topk"] = s["l"]               # the whole beam, masked afterwards
    return SearchConfig(metric=config["metric"], **s), where == "beam"


def _split(run, q, masks) -> list:
    """The query set cut into calls of ``call_queries`` (queries, masks)."""
    per = run.params["call_queries"]
    if q.shape[0] % per:
        raise ValueError(f"{q.shape[0]} queries do not split into calls of "
                         f"{per}")
    return [(q[s:s + per], masks[s:s + per])
            for s in range(0, q.shape[0], per)]


def _search(run, st, call: int):
    q, masks = st.calls[call]
    kw = dict(tile_b=run.params["tile_lanes"], with_stats=True)
    if st.in_beam:
        return st.ann.search(q, st.cfg, filters=masks, **kw)
    return st.ann.search(q, st.cfg, **kw)


def _host(run, st, call: int, ids, dists):
    """(ids, dists) of a call on the host, masked afterwards when the
    predicate does not apply in the beam."""
    ids, dists = np.asarray(ids), np.asarray(dists)
    if st.in_beam:
        return ids, dists
    per = run.params["call_queries"]
    return _post_filter(ids, dists, st.labels_host,
                        st.masks_host[call * per:(call + 1) * per],
                        run.config["search"]["topk"])


def setup(run) -> State:
    import jax

    from repro.streaming import StreamingANN, StreamingConfig

    lab = run.config["labels"]
    if (lab["per_row"], lab["per_query"], lab["predicate"]) != \
            (1, 1, "equality"):
        raise ValueError(f"unsupported labels group {lab}")
    key, x, q = make_data(run.config, run.seed)
    labels = _attribute(jax.random.fold_in(key, 2), x.shape[0],
                        lab["values"])
    masks = _attribute(jax.random.fold_in(key, 3), q.shape[0],
                       lab["values"])
    ann = StreamingANN.from_corpus(
        x, StreamingConfig(build=build_config(run.config)),
        key=jax.random.fold_in(key, 1), labels=labels)
    cfg, in_beam = search_cfg(run.config)
    st = State(ann=ann, calls=jax.block_until_ready(_split(run, q, masks)),
               cfg=cfg, in_beam=in_beam, x_host=np.asarray(x),
               labels_host=np.asarray(labels), q_host=np.asarray(q),
               masks_host=np.asarray(masks))
    jax.block_until_ready(_search(run, st, 0))
    return st


def _post_filter(ids, dists, labels, masks, topk: int):
    """The first ``topk`` entries of each row that pass, (-1, +inf) after."""
    ok = filtered_knn_ref.passes(labels, masks, ids)
    order = np.argsort(~ok, axis=1, kind="stable")[:, :topk]
    keep = np.take_along_axis(ok, order, 1)
    return (np.where(keep, np.take_along_axis(ids, order, 1), -1),
            np.where(keep, np.take_along_axis(dists, order, 1), np.inf
                     ).astype(dists.dtype))


def window(run, st: State) -> Window:
    results, call_s = [], []
    counts = {"work": 0, "filter_scored": 0, "filter_passed": 0, "capped": 0,
              "visited_lost": 0}
    work_max = 0
    n_calls = len(st.calls)
    t0 = t = time.perf_counter()
    while t - t0 < run.seconds:
        call = len(results) % n_calls
        ids, dists, stats = _search(run, st, call)
        results.append((call, *_host(run, st, call, ids, dists)))
        for k in counts:
            counts[k] += int(stats.get(k, 0))
        work_max = max(work_max, int(stats.get("work_max", 0)))
        t_end = time.perf_counter()
        call_s.append(t_end - t)
        t = t_end
    per = run.params["call_queries"]
    calls = len(results)
    print(f"search calls: {calls} of {per} queries, per call: beam "
          f"expansions {counts['work'] // calls}, candidates scored "
          f"{counts['filter_scored'] // calls}, passed "
          f"{counts['filter_passed'] // calls}, visited inserts lost "
          f"{counts['visited_lost'] // calls}; most expansions of a lane "
          f"{work_max}, lanes capped by max_iters {counts['capped']}; host "
          f"ms per call: {[round(1e3 * c, 3) for c in call_s]}",
          file=sys.stderr)
    return Window(
        metrics={"search_qps": calls * per / (t - t0)},
        attempted=calls * per, failed=0,
        counters={"calls": calls, "queries": calls * per, **counts,
                  "work_max": work_max, "window_s": t - t0},
        outputs={"results": results})


def collect(run, st: State, win: Window) -> dict:
    ids, dists, _ = _search(run, st, 0)     # the first call once more
    return {"x": st.x_host, "q": st.q_host,
            "labels": st.labels_host, "masks": st.masks_host,
            "results": win.outputs["results"]
            + [(0, *_host(run, st, 0, ids, dists))]}


def verify(run, out: dict):
    x, q, results = out["x"], out["q"], out["results"]
    labels, masks = out["labels"], out["masks"]
    first, differ = {}, 0
    for call, i, d in results:
        if call not in first:
            first[call] = (i, d)
            continue
        i0, d0 = first[call]
        differ += not (np.array_equal(i, i0) and
                       np.array_equal(d.view(np.uint32), d0.view(np.uint32)))
    per = results[0][1].shape[0]
    rows = np.concatenate([np.arange(c * per, (c + 1) * per)
                           for c in sorted(first)])
    ids = np.concatenate([first[c][0] for c in sorted(first)])
    dists = np.concatenate([first[c][1] for c in sorted(first)])
    q, masks = q[rows], masks[rows]
    ref = knn_ref.pair_dists(x, q, ids)
    ok = ~np.isnan(ref)
    exact = filtered_knn_ref.exact_topk(x, q, labels, masks, ids.shape[1])
    lim = run.limits
    recall = 100.0 * knn_ref.recall(ids, exact)
    return [
        Check("recall_shortfall", 100.0 - recall, lim["recall_shortfall"]),
        Check("filter_violations",
              filtered_knn_ref.violations(labels, masks, ids),
              lim["filter_violations"]),
        Check("dist_gap", rel_gap(dists[ok], ref[ok]), lim["dist_gap"]),
        Check("bad_ids", knn_ref.bad_ids(ids, x.shape[0]), lim["bad_ids"]),
        Check("calls_differ", differ, lim["calls_differ"]),
    ], {"recall_at_10": recall}
