"""Chip smoke: the index's main path, once, on one TPU v5e chip.

    python3 chip_smoke.py              # one chip: build, search, update, serve
    python3 chip_smoke.py --chips 4    # four chips: corpus-sharded build+search

Deployment: SIFT1M-shaped (ANN-benchmarks sift-128-euclidean) with its rows
cut to 125,000 (see ``N``): 125,000 x 128 f32 vectors, l2, 10,000 queries,
generated from ``--seed`` by
``repro.data.synthetic.clustered_vectors`` on the device, built with the
paper's section 5.1 configuration (``repro.configs.rnnd_ann.FULL``: S=20,
R=96, T1=4, T2=15, M=128).

One process drives the chip and starts no other. Every phase prints its
checks as ``[PASS]``/``[FAIL]`` lines and its timings (host clock around
``block_until_ready``, compiles included: smoke timings, not benchmark
metrics). The script exits non-zero if any check fails, and refuses to run
(exit 2, no result line) when JAX finds no TPU. The last line of a passing
run is the JSON result ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SIFT1M_ROWS = 1_000_000
# Corpus rows: SIFT1M's 1,000,000 halved three times. On one v5e a sweep of
# the paper config takes about 60 s at 1M rows (53 s of it the bucketed
# merge, 7 s the prune), so the 60 sweeps alone would take an hour; 125,000
# rows keep the smoke well inside its 20 minutes. Widths, metric, config
# and query count are SIFT1M's.
N = SIFT1M_ROWS // 8
QUERIES = 10_000             # query rows (SIFT1M's query set)
N_INSERT = 1024
N_DELETE = 1024
N_SERVE = 256
PRUNE_ROWS = 65536           # rows of the built graph the prune kernel redoes
BEAM_ROWS = 4096             # corpus rows the VMEM-resident beam kernel holds
RECALL_FLOOR = 0.95          # tests/test_recall_regression.py, rnn-descent
# Kernel-vs-XLA distance limit, relative (denominator floored at 1). The f32
# |a|^2+|b|^2-2ab form is within ~5e-7 of float64 on this data; a dot whose
# inputs were rounded to bf16 (the TPU's DEFAULT matmul precision) is off by
# up to ~2.5e-3 (median ~3e-4). 1e-5 sits between the two, so a kernel that
# lost HIGHEST precision fails; the beam check re-measures the bf16 figure on
# its own rows and requires that it exceeds this limit.
DIST_RTOL = 1e-5


class Checks:
    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, ok: bool, label: str) -> bool:
        print(f"  [{'PASS' if ok else 'FAIL'}] {label}", flush=True)
        if not ok:
            self.failed.append(label)
        return ok


def log(msg: str) -> None:
    print(msg, flush=True)


class Phase:
    """Prints a phase's wall seconds and the backend compiles it caused."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        from repro.obs import jaxhooks
        log(f"== {self.name} ==")
        self.c0 = jaxhooks.backend_compiles()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        from repro.obs import jaxhooks
        self.seconds = time.perf_counter() - self.t0
        self.compiles = int(jaxhooks.backend_compiles() - self.c0)
        log(f"  {self.name}: {self.seconds:.3f} s wall (smoke timing), "
            f"{self.compiles} backend compiles")
        return False


def make_data(seed: int, n: int, nq: int, extra: int):
    import jax

    from repro.data.synthetic import VectorDatasetSpec, clustered_vectors

    spec = VectorDatasetSpec("sift1m-shaped", n + extra, 128, nq)
    xall, q = clustered_vectors(jax.random.PRNGKey(seed), spec)
    return jax.block_until_ready((xall[:n], xall[n:], q))


def search_cfg(l: int):
    from repro.configs.rnnd_ann import SEARCH

    # the paper config's search side (k=64, max_iters=4L at L=64), at L
    return dataclasses.replace(SEARCH, l=l, max_iters=4 * l, topk=10)


def first_difference(a, b) -> str:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    bad = np.argwhere(a != b)
    if bad.size == 0:
        return "none"
    i = tuple(int(v) for v in bad[0])
    return f"{bad.shape[0]} differ; first at {i}: {a[i]!r} vs {b[i]!r}"


def max_rel(a, b) -> float:
    """Largest |a - b| / max(|b|, 1) (0.0 for empty inputs)."""
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0),
                        initial=0.0))


def near_tie_rows(x, g, rtol: float):
    """(rows,) bool: rows of ``g`` where some comparison of the RNG scan,
    d(v_j, v_i) <= d(u, v_i) for j < i on a pair it checks (not old-old),
    is within ``rtol`` relative (floored at 1) of a tie. The pair distances
    come from the XLA Gram the reference prune uses."""
    import jax
    import jax.numpy as jnp

    from repro.core import distances as D
    from repro.core import graph as G

    m = g.neighbors.shape[1]
    lower = jnp.tril(jnp.ones((m, m), bool), -1)     # [i, j]: j < i

    def chunk(args):
        ids, dists, flags = args
        valid = ids >= 0
        pair = D.batched_gram(x[jnp.maximum(ids, 0)])   # [c, i, j]
        old = flags == G.OLD
        checked = (lower & valid[:, :, None] & valid[:, None, :]
                   & ~(old[:, :, None] & old[:, None, :]))
        di = dists[:, :, None]
        near = jnp.abs(pair - di) <= rtol * jnp.maximum(jnp.abs(di), 1.0)
        return jnp.any(checked & near, axis=(1, 2))

    rows = g.neighbors.shape[0]
    c = min(512, rows)
    return jax.jit(lambda a: jax.lax.map(chunk, a).reshape(-1))(
        tuple(a.reshape(rows // c, c, m) for a in g))


# ----------------------------------------------------------------- one chip
def run_one_chip(seed: int, check: Checks) -> None:
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np

    from repro.configs.rnnd_ann import FULL
    from repro.core import eval as E
    from repro.core import graph as G
    from repro.core import rnn_descent as rd
    from repro.core import search as S
    from repro.kernels.beam_score import beam_score, beam_score_ref
    from repro.obs import jaxhooks, trace
    from repro.serving import AdmissionConfig, ServingConfig, ServingFrontend
    from repro.streaming import StreamingANN, StreamingConfig
    from repro.streaming import store as ST

    n, d, nq = N, 128, QUERIES
    log(f"deployment: SIFT1M-shaped n={n} d={d} metric=l2 queries={nq} "
        f"seed={seed}; build {FULL}")
    if n != SIFT1M_ROWS:
        log(f"  scale cut: n={n} of SIFT1M's {SIFT1M_ROWS} rows")

    with Phase("data"):
        x, x_new, q = make_data(seed, n, nq, N_INSERT)
        log(f"  x {x.shape} {x.dtype}, queries {q.shape}, "
            f"insert pool {x_new.shape}")

    # ---------------------------------------------------------------- build
    with Phase("build") as ph:
        ann = StreamingANN.from_corpus(
            x, StreamingConfig(build=FULL), key=jax.random.PRNGKey(seed))
        jax.block_until_ready(ann.store)
    g = ann.store.graph
    deg = np.asarray(G.out_degrees(g))[:n]
    log(f"  build_seconds={ph.seconds:.3f} compiles={ph.compiles} "
        f"capacity={ann.capacity} avg_out_degree={deg.mean():.2f} "
        f"max_out_degree={deg.max()}")
    for name, row in trace.summary("rnn_descent/").items():
        log(f"  {name}: {row['count']} x, total {row['total_s']:.3f} s, "
            f"min {row['min_s']:.3f} s, max {row['max_s']:.3f} s")
    check(bool(deg.min() > 0), "every vertex has an out-edge")
    # rows the kernel comparisons at the end read (the built graph itself is
    # replaced by the updates below)
    prune_g = G.Graph(*(a[:PRUNE_ROWS] for a in g))
    beam_nbrs = jnp.where(g.neighbors[:BEAM_ROWS] < BEAM_ROWS,
                          g.neighbors[:BEAM_ROWS], -1)
    del g

    # --------------------------------------------------------------- search
    with Phase("ground truth"):
        # 256 queries per tile: one (256, n) f32 distance block (1 GB at 1M)
        _, gt = E.ground_truth(x, q, k=10, tile=256)
        gt = jax.block_until_ready(gt)
    best = 0.0
    for l in (64, 128, 256):
        with Phase(f"search L={l}") as ph:
            ids, _ = ann.search(q, search_cfg(l))
            ids = jax.block_until_ready(ids)
        r = E.recall_topk(ids, gt)
        best = max(best, r)
        log(f"  L={l} recall@10={r:.4f} seconds={ph.seconds:.3f} "
            f"(compile included)")
    check(best >= RECALL_FLOOR,
          f"recall@10 >= {RECALL_FLOOR} at some L in (64, 128, 256): "
          f"best {best:.4f}")

    # ----------------------------------------------------- streaming updates
    cfg_upd = search_cfg(256)
    with Phase(f"insert {N_INSERT}"):
        new_ids = ann.insert(x_new)
    with Phase("search inserted points"):
        ids, dists = ann.search(x_new, cfg_upd)
        ids = np.asarray(ids)
    hits = int(np.sum(ids[:, 0] == new_ids))
    check(hits == N_INSERT,
          f"each inserted point is its own top-1: {hits}/{N_INSERT}")
    rng = np.random.default_rng(seed)
    del_ids = rng.choice(n, N_DELETE, replace=False).astype(np.int32)
    with Phase(f"delete {N_DELETE}"):
        newly = ann.delete(del_ids)
        jax.block_until_ready(ann.store)
    check(bool(np.all(newly)), "every delete landed on a live row")
    with Phase("search after delete"):
        ids_q, _ = ann.search(q, cfg_upd)
        ids_d, _ = ann.search(x[del_ids], cfg_upd)
        found = np.concatenate([np.asarray(ids_q), np.asarray(ids_d)])
    leaked = int(np.isin(found, del_ids).sum())
    check(leaked == 0, f"no deleted id in any top-10 ({nq} queries + the "
                       f"{N_DELETE} deleted vectors): {leaked} leaked")

    # -------------------------------------------------------------- serving
    scfg = search_cfg(64)
    lanes = 64
    fe = ServingFrontend(ann, ServingConfig(
        admission=AdmissionConfig(tile_lanes=lanes, deadline_s=10.0),
        search=scfg))
    rows = np.asarray(q[:N_SERVE])
    with Phase("serving warm-up"):
        for row in rows[:lanes]:
            fe.submit(row)
        fe.drain(flush_writes=False)
        warm = [fe.result(i) for i in range(lanes)]
    del warm
    with Phase(f"serving {N_SERVE} requests") as ph:
        rids = [fe.submit(row) for row in rows]
        fe.pump()                      # dispatches every full tile
        fe.drain(flush_writes=False)   # and harvests them
        served = [fe.result(r) for r in rids]
    check(ph.compiles == 0,
          f"zero backend compiles after warm-up: {ph.compiles}")
    # the direct reference: the same rows through ann.search, tile by tile,
    # against the snapshot and entry point the frontend served from
    _, st = ann.snapshot()
    eps = S.default_entry_point(st.x, scfg.metric, valid=ST.active_mask(st))
    direct = [ann.search(jnp.asarray(rows[i:i + lanes]), scfg,
                         entry_points=eps, tile_b=lanes,
                         lane_valid=jnp.ones((lanes,), bool), store=st)
              for i in range(0, N_SERVE, lanes)]
    d_ids = np.concatenate([np.asarray(a) for a, _ in direct])
    d_d = np.concatenate([np.asarray(b) for _, b in direct])
    s_ids = np.stack([s[0] for s in served])
    s_d = np.stack([s[1] for s in served])
    check(np.array_equal(s_ids, d_ids)
          and np.array_equal(s_d.view(np.uint32), d_d.view(np.uint32)),
          f"served results bitwise equal to ann.search on the same "
          f"{N_SERVE} rows (ids: {first_difference(s_ids, d_ids)})")

    # -------------------------------------------------- kernels vs XLA path
    outs = {}
    for use_pallas in (False, True):
        cfg = dataclasses.replace(FULL, use_pallas=use_pallas)
        with Phase(f"prune_rows {PRUNE_ROWS} rows use_pallas={use_pallas}"):
            outs[use_pallas] = jax.block_until_ready(jax.jit(
                rd.prune_rows, static_argnames="cfg")(
                    x, prune_g.neighbors, prune_g.dists,
                    prune_g.flags, cfg))
    (k0, w0, d0), (k1, w1, d1) = (
        [np.asarray(a) for a in outs[u]] for u in (False, True))
    log(f"  rng_prune keep: {first_difference(k1, k0)}")
    log(f"  rng_prune redirect ids: {first_difference(w1, w0)}")
    log("  rng_prune redirect dist bits: " + first_difference(
        d1.view(np.uint32), d0.view(np.uint32)))
    # A keep or redirect decision may flip only where the scan's comparison
    # d(v_j, v_i) <= d(u, v_i) is within the distance limit of a tie; a flip
    # cascades along its row, so count rows.
    ties = np.asarray(near_tie_rows(x, prune_g, DIST_RTOL))
    flipped = np.any((k1 != k0) | (w1 != w0), axis=1)
    stray = int(np.sum(flipped & ~ties))
    log(f"  rng_prune rows with a flipped decision: {int(flipped.sum())}; "
        f"rows holding a near-tie (rtol {DIST_RTOL:g}): {int(ties.sum())} "
        f"of {PRUNE_ROWS}")
    check(stray == 0, "Pallas rng_prune keep and redirect ids match the XLA "
                      f"path outside near-tie rows: {stray} stray rows")
    both = (w1 == w0) & (w0 >= 0)
    rel = max_rel(d1[both], d0[both])
    check(rel <= DIST_RTOL, "Pallas rng_prune redirect distances within "
                            f"{DIST_RTOL:g} relative of the XLA path "
                            f"(max {rel:.3e})")

    # beam_score holds its corpus in VMEM: score over the first rows only
    nb = min(512, nq)
    xs, u = x[:BEAM_ROWS], jnp.arange(nb, dtype=jnp.int32) * 7 % BEAM_ROWS
    with Phase(f"beam_score over {BEAM_ROWS} rows, kernel and XLA"):
        got = jax.block_until_ready(beam_score(xs, beam_nbrs, u, q[:nb],
                                               k=32))
        ref = jax.block_until_ready(jax.jit(
            beam_score_ref, static_argnames="k")(xs, beam_nbrs, u, q[:nb],
                                                 k=32))
    g_i, g_d = np.asarray(got[0]), np.asarray(got[1])
    r_i, r_d = np.asarray(ref[0]), np.asarray(ref[1])
    log(f"  beam_score ids: {first_difference(g_i, r_i)}")
    log("  beam_score dist bits: " + first_difference(g_d.view(np.uint32),
                                                     r_d.view(np.uint32)))
    check(np.array_equal(g_i, r_i),
          "Pallas beam_score gathers the same neighbor ids as the XLA path")
    fin = np.isfinite(r_d)
    rel = max_rel(g_d[fin], r_d[fin])
    check(bool(np.array_equal(np.isfinite(g_d), fin)) and rel <= DIST_RTOL,
          f"Pallas beam_score distances within {DIST_RTOL:g} relative of "
          f"the XLA path (max {rel:.3e})")
    # what the same distances come to when the dot's inputs are rounded to
    # bf16 (the TPU's DEFAULT precision), against float64: the limit above
    # must be tighter than that, or it could not catch a precision loss
    xv = np.asarray(xs, np.float64)[np.maximum(r_i, 0)]
    qv = np.asarray(q[:nb], np.float64)[:, None, :]
    exact = np.sum((xv - qv) ** 2, axis=-1)
    rb = lambda a: a.astype(ml_dtypes.bfloat16).astype(np.float64)
    bf16 = (np.sum(qv ** 2, -1) + np.sum(xv ** 2, -1)
            - 2 * np.sum(rb(qv) * rb(xv), -1))
    rel_bf16 = max_rel(bf16[fin], exact[fin])
    log(f"  beam_score vs float64: kernel {max_rel(g_d[fin], exact[fin]):.3e}"
        f", XLA {max_rel(r_d[fin], exact[fin]):.3e}, bf16-input dot "
        f"{rel_bf16:.3e}")
    check(rel_bf16 > DIST_RTOL, f"a bf16-input dot misses the {DIST_RTOL:g} "
                                f"limit on these rows ({rel_bf16:.3e})")
    jaxhooks.record_memory(phase="end")


# --------------------------------------------------------------- four chips
def run_four_chips(seed: int, check: Checks) -> None:
    import jax
    import numpy as np

    from repro.configs.rnnd_ann import FULL
    from repro.core import eval as E
    from repro.core.search_sharded import corpus_placement_bytes
    from repro.distributed.ann import ShardedANN
    from repro.launch.mesh import make_mesh

    n, d, nq = N, 128, QUERIES
    log(f"deployment: SIFT1M-shaped n={n} d={d} queries={nq} on 4 chips, "
        f"serve_shard=corpus; build {FULL}")
    mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
    with Phase("data"):
        x, _, q = make_data(seed, n, nq, 0)
    key = jax.random.PRNGKey(seed)
    cfg = search_cfg(128)
    with Phase("build one chip"):
        one = ShardedANN.build(x, cfg=FULL, key=key)
        jax.block_until_ready(one.graph)
    with Phase("search one chip"):
        ids1, _ = one.search(q, cfg)
        ids1 = np.asarray(ids1)
    with Phase("build 4 chips (rows sharded)"):
        four = ShardedANN.build(x, cfg=FULL, key=key, mesh=mesh,
                                serve_shard="corpus")
        jax.block_until_ready(four.graph)
    with Phase("search 4 chips (corpus sharded)"):
        ids4, _ = four.search(q, cfg)
        ids4 = np.asarray(ids4)
    same_graph = all(np.array_equal(np.asarray(a), np.asarray(b))
                     for a, b in zip(one.graph, four.graph))
    check(same_graph, "4-chip graph equal to the one-chip graph "
                      f"(neighbors: {first_difference(four.graph.neighbors, one.graph.neighbors)})")
    check(np.array_equal(ids1, ids4), "4-chip search ids equal to one-chip "
                                      f"({first_difference(ids4, ids1)})")
    _, gt = E.ground_truth(x, q, k=10, tile=256)
    log(f"  recall@10 L=128: one chip {E.recall_topk(ids1, gt):.4f}, "
        f"4 chips {E.recall_topk(ids4, gt):.4f}")
    rep = corpus_placement_bytes(n, d, FULL.capacity, 4)["replicated"]
    res = four.device_resident_bytes()
    log(f"  device_resident_bytes={res} replicated={rep} "
        f"ratio={res / rep:.4f}")
    check(abs(res / rep - 0.25) <= 0.01,
          "per-device resident bytes are 1/4 of the replicated footprint")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              "device(s)", file=sys.stderr)
        return 2
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"jax {jax.__version__}")

    from repro import obs
    from repro.launch import compile_cache

    log(f"compile cache: {compile_cache.enable()}")
    obs.enable()
    check = Checks()
    t0 = time.perf_counter()
    (run_four_chips if args.chips == 4 else run_one_chip)(args.seed, check)
    log(f"total {time.perf_counter() - t0:.1f} s")
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
