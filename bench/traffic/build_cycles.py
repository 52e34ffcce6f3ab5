"""Build traffic: the builder's two programs in the paper schedule's order.

Set-up makes the corpus from the seed, RandomGraph(S), and one sweep
(``rnn_descent.update_neighbors``) and one reverse pass
(``rnn_descent.add_reverse_edges``), which warm both programs. The window
then repeats the cycle named in the cell's ``cycle`` (sweeps, then a
reverse pass) on the evolving graph until ``--seconds`` have passed and at
least one whole cycle has run. Each call is timed from the end of the
previous one to its own ``block_until_ready``, so every second of the
window belongs to some call. As in the program's own build loop, the next
call is dispatched before the running one ends, so a pause of the host
shorter than a call leaves the device busy; the call still queued when the
window closes is waited for and not counted.

``build_s`` = T1*T2 x (sweep seconds / sweeps) + (T1 - 1) x (reverse
seconds / reverse passes): the paper schedule's build at the window's
speed, RandomGraph left out.

The check: one sweep of the window's first cycle, drawn from the seed, and
that cycle's reverse pass are redone by the plain reference
(``bench/reference/graph_ref.py``) from the graph the window handed them,
and their outputs compared row by row; and every edge the two outputs hold
carries the true distance of its two vertices.
"""
from __future__ import annotations

import collections
import dataclasses
import sys
import time

import numpy as np

from bench.common import Check, Window, rel_gap
from bench.deploy import build_config, make_data
from bench.reference import graph_ref


@dataclasses.dataclass
class State:
    x: object
    g: object
    cfg: object


def setup(run) -> State:
    import jax

    from repro.core import rnn_descent as rd

    cfg = build_config(run.config)
    key, x, _ = make_data(run.config, run.seed, queries=1)
    g = rd.random_init(jax.random.fold_in(key, 1), x, cfg)
    g = rd.add_reverse_edges(rd.update_neighbors(x, g, cfg), cfg)
    return State(x=x, g=jax.block_until_ready(g), cfg=cfg)


def window(run, st: State) -> Window:
    import jax

    from repro.core import rnn_descent as rd

    cycle = run.params["cycle"]
    n_sweeps = cycle.count("sweep")
    checked = int(np.random.default_rng(run.seed).integers(n_sweeps))
    times = {"sweep": [], "reverse": []}
    pairs = {}
    # calls dispatched and not yet timed: (kind, input graph, output graph)
    pending = collections.deque()
    g = st.g
    t0 = t_prev = time.perf_counter()
    i = done = 0
    while True:
        while len(pending) < 2:      # one call queued behind the running one
            kind = cycle[i % len(cycle)]
            out = (rd.update_neighbors(st.x, g, st.cfg) if kind == "sweep"
                   else rd.add_reverse_edges(g, st.cfg))
            pending.append((kind, g, out))
            g = out
            i += 1
        kind, g_in, out = pending.popleft()
        jax.block_until_ready(out)
        t = time.perf_counter()
        times[kind].append(t - t_prev)
        t_prev = t
        if done < len(cycle) and (kind == "reverse" or
                                  len(times["sweep"]) == checked + 1):
            pairs.setdefault(kind, (g_in, out))
        done += 1
        if t - t0 >= run.seconds and done >= len(cycle):
            break
    jax.block_until_ready([p[2] for p in pending])   # queued past the window
    for k, v in times.items():
        print(f"{k} calls: {len(v)}, host ms per call: "
              f"{[round(1e3 * c, 3) for c in v]}", file=sys.stderr)
    cfg = st.cfg
    mean = {k: sum(v) / len(v) for k, v in times.items()}
    build_s = cfg.t1 * cfg.t2 * mean["sweep"] + (cfg.t1 - 1) * mean["reverse"]
    return Window(
        metrics={"build_s": build_s}, attempted=done, failed=0,
        counters={"sweeps": len(times["sweep"]),
                  "reverses": len(times["reverse"]),
                  "window_s": t_prev - t0},
        outputs={"pairs": pairs})


def collect(run, st: State, win: Window) -> dict:
    """Brings the compared graphs and the corpus to the host."""
    host = lambda g: tuple(np.asarray(a) for a in g)  # noqa: E731
    return {"x": np.asarray(st.x),
            "pairs": {k: (host(a), host(b))
                      for k, (a, b) in win.outputs["pairs"].items()}}


def rows_off(got, ref, exact: bool) -> float:
    """Share of rows whose ids or flags differ (and, when ``exact``, whose
    distance bits differ)."""
    off = np.any((got[0] != ref[0]) | (got[2] != ref[2]), axis=1)
    if exact:
        off |= np.any(got[1].view(np.uint32) != ref[1].view(np.uint32),
                      axis=1)
    return float(off.mean())


def verify(run, out: dict):
    lim = run.limits
    x = out["x"]
    s_in, s_out = out["pairs"]["sweep"]
    r_in, r_out = out["pairs"]["reverse"]
    gap = 0.0
    for g in (s_out, r_out):
        true = graph_ref.edge_dists(x, g[0])
        ok = g[0] >= 0
        gap = max(gap, rel_gap(g[1][ok], true[ok]))
    return [
        Check("sweep_rows_off",
              rows_off(s_out, graph_ref.sweep(x, *s_in), exact=False),
              lim["sweep_rows_off"]),
        Check("reverse_rows_off",
              rows_off(r_out, graph_ref.reverse(*r_in,
                                                r=run.config["build"]["r"]),
                       exact=True),
              lim["reverse_rows_off"]),
        Check("edge_dist_gap", gap, lim["edge_dist_gap"]),
    ], {}
