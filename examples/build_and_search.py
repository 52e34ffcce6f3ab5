"""Compare all three builders (paper Figures 2+3 in miniature): construction
time and the QPS/recall tradeoff on the same corpus, served through the
constant-memory tiled search driver.

    PYTHONPATH=src python examples/build_and_search.py

Search kernel
-------------
The beam inner loop (gather each frontier vertex's adjacency row, gather the
neighbor vectors, score them against the query) has two interchangeable
implementations behind ``SearchConfig.use_pallas``:

    scfg = S.SearchConfig(l=48, k=32)                      # jnp oracle (default)
    fused = dataclasses.replace(scfg, use_pallas=True)     # Pallas fused kernel

Both return *bitwise identical* results (they share one scoring function —
asserted in tests/test_beam_score.py); the fused path keeps the gathered
(B, K, d) candidate block in VMEM instead of round-tripping through HBM.
Tile sizing: ``kernel_tile_b`` lanes per grid step hold a
``kernel_tile_b * k * d * 4``-byte gathered block in VMEM — the default 64
with k=32, d=128 is 1 MiB; shrink it for wide vectors, grow it while VMEM
allows to amortize the corpus block. ``gram_dtype="bf16"`` halves the
neighbor-gather traffic (f32 accumulation, rng_prune convention). On CPU the
kernel runs interpreted (``kernels.default_interpret()``), so the fused path
is for correctness parity there; the speedup is a TPU property.

Scaling out
-----------
Both halves of the system run on a ``jax.sharding.Mesh``; results are
*exactly equal* to single-device (tests/test_sharded_parity.py):

    mesh = make_mesh((jax.device_count(),), ("data",))  # repro.launch.mesh

    # construction: graph rows shard across the mesh (core/shard.py);
    # x is replicated and each shard ships destination-bucketed
    # (n_pad/D, B) scatter blocks around a ppermute ring, folding the
    # running min as blocks arrive — every builder takes mesh=
    g = rd.build(x, cfg, key, mesh=mesh)

    # serving, two layouts. Query-tile sharding replicates corpus + graph
    # and splits the batch: per-device resident bytes stay the full
    # n*(d*4) + n*capacity*9 — fastest while the index fits
    ids, dists = S.search_tiled(x, g, q, entry, scfg, tile_b=256, mesh=mesh)

    # corpus sharding divides the index instead: each device keeps
    # ~n/D rows of x + adjacency (+ codes), so per-device bytes are
    #   (n/D) * (d*4 + capacity*9)        f32 corpus
    #   (n/D) * (d   + capacity*9)        int8 codes
    #   (n/D) * (m   + capacity*9)        pq codes
    # and the beam's frontier gathers ride owner-contribute collectives —
    # bitwise-equal results at ~1/D the footprint (the 100M-row unlock;
    # core/search_sharded.corpus_placement_bytes computes the table above)
    ids, dists = S.search_tiled(x, g, q, entry, scfg, tile_b=256, mesh=mesh,
                                shard="corpus")

On CPU, forge devices to try it (set BEFORE any jax import / in the shell):
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — that is exactly how
the CI mesh job runs the parity suite. On real hardware the same two lines
map onto TPU/GPU meshes (launch/mesh.make_production_mesh builds the pod
shapes; the logical "rows"/"queries" axes route via RULES in
distributed/sharding.py, so a (pod, data, model) mesh shards rows over
pod x data automatically). distributed/ann.py wraps build + serve +
checkpoint persistence into one mesh-bound object (ShardedANN) — restore a
saved index onto a *different* mesh shape and serve identical results.

The demo below runs the sharded paths on whatever devices exist (1 on a
plain CPU — still the full code path, degenerate exchange) and asserts
build parity.

Compressed corpora
------------------
The f32 corpus is the binding memory term at scale: ``n * d * 4`` bytes per
device (replicated for serving). ``repro.quant`` stores codes instead and
the fused kernels decode in-register next to the distance math:

    ============  ================  =========================  ============
    mode          per-row payload   O(1) auxiliary             n=1M, d=128
    ============  ================  =========================  ============
    f32           ``d * 4``         —                          512 MiB
    int8          ``d``             scale+zero: ``2 * d * 4``  128 MiB (4x)
    pq            ``m``             codebooks: ``256 * d * 4`` 32 MiB (16x
                                                               at m = d/4)
    ============  ================  =========================  ============

    quant = Quantization(mode="int8")            # or mode="pq", m=d//4
    bcfg  = dataclasses.replace(cfg, quant=quant)  # graph built in the
    g     = rd.build(x, bcfg, key)                 #   quantized geometry
    qx    = encode_corpus(x, quant)
    scfg  = S.SearchConfig(l=48, k=32, quant=quant)
    ids, d = S.search_tiled(x, g, q, entry, scfg, qx=qx)

Tuning: ``m`` must divide d — ``d // 4`` gives 16x payload compression and
is the benched sweet spot (smaller m compresses harder but each dropped
subspace costs recall). ``rerank_k`` (default 64) is the exact-f32 rerank
tail over the final candidates: it cancels most of the quantization noise
in the *ranking* (the graph walk still navigates coded distances), so keep
it 4-8x topk; ``rerank_k=0`` disables the tail and shows the raw coded
recall (BENCH_quant.json records both). int8 costs ~0.01-0.03 recall@10 and
needs no tuning; PQ+rerank lands within 0.05 at 16x. Build with the same
``quant=`` you serve with — the builders construct the graph over the
*decoded* corpus so edges are optimized for the distances coded search
actually sees. Fused kernels (``use_pallas=True``) gather code rows (4-16x
less HBM traffic than f32 rows) and stay bitwise-equal to the jnp decode
oracles (tests/test_quant.py).

Streaming updates
-----------------
Production corpora churn; ``repro.streaming`` maintains the index
incrementally instead of rebuilding (the property RNN-Descent's direct
construction uniquely enables — seeds for new rows come from beam-searching
the current graph, and repair is the same prune/merge primitives run over a
batch-sized frontier):

    from repro.streaming import StreamingANN, StreamingConfig

    ann = StreamingANN.from_corpus(x, StreamingConfig(build=cfg), mesh=mesh)
    row_ids = ann.insert(new_vectors)    # O(batch) localized sweeps
    ann.delete(row_ids[:k])              # tombstone + splice repair
    ids, d = ann.search(q, scfg)         # tombstones traverse, never surface
    ann.compact()                        # physically drop tombstones

Updates compose with the mesh (the frontier rides the same all_to_all
bucket exchange as the sharded build — bitwise-equal to single-device,
tests/test_streaming.py), serving snapshots are epoch-consistent during
updates, and the whole store persists through checkpoint/ onto any mesh
shape. The churn trajectory (insert/delete throughput, recall vs rebuild)
lives in repo-root BENCH_streaming.json.

Serving front end
-----------------
``repro.serving`` wraps the batch API in a serving loop (ROADMAP
"Serving" has the policy math). Arriving queries coalesce into
fixed-shape ``search_tiled`` tiles — dispatched when the tile fills or
the oldest request has spent half its latency budget — while concurrent
inserts/deletes batch to fixed sizes behind ``StreamingANN``'s epoch
swap; a dispatched tile keeps serving the snapshot it was built against.
Occupancy never changes a program shape (vacant lanes are zero-staged
and masked via ``lane_valid``), so a warmed server compiles nothing at
steady state:

    fe = ServingFrontend(ann, ServingConfig(
        admission=AdmissionConfig(tile_lanes=64, deadline_s=0.2),
        writer=WriterConfig(insert_batch=32, delete_batch=32),
        search=scfg))
    rid = fe.submit(query)               # any thread
    tk = fe.submit_insert(new_rows)      # batched behind the epoch swap
    fe.pump()                            # the serving loop's turn
    ids, dists = fe.result(rid)          # tk.ids -> assigned row ids

``fe.telemetry.summary()`` reports p50/p95/p99 latency, achieved QPS,
batch occupancy, queue depth, and per-tile epoch staleness; the
open-loop load generator (``run_session``/``LoadSpec``) drives the
QPS-under-churn trajectory in repo-root BENCH_serving.json. The demo
below replays a short churn session end to end.

Observability
-------------
Every hot path above is instrumented behind one switch (``repro.obs``,
off by default — a single flag check per site, and results stay bitwise
identical either way; ROADMAP "Observability" has the contract):

    from repro import obs
    from repro.obs import trace, metrics

    obs.enable()                  # spans + metrics + jax compile capture
    g = rd.build(x, cfg, key)     # rnn_descent/sweep + /reverse spans
    ids, d = S.search_tiled(...)  # search/tiled spans, lane-work counters
    fe.pump()                     # serving/dispatch|readout + request spans

    trace.write_chrome_trace("trace.json")   # open in ui.perfetto.dev
    print(trace.summary_table())             # flat phase breakdown
    print(metrics.REGISTRY.exposition())     # Prometheus text format

``python -m repro.obs`` runs a scripted build+serve session end to end,
asserts the bitwise-parity and zero-steady-compile contracts, and emits
``trace.json`` + ``metrics.prom`` (the CI obs smoke uploads them as a
workflow artifact). The traced-build walkthrough at the bottom of this
demo does the miniature version inline.
"""
import dataclasses
import time

import jax

from repro.core import eval as E
from repro.core import graph as G
from repro.core import nn_descent as nnd
from repro.core import nsg_style
from repro.core import rnn_descent as rd
from repro.core import search as S
from repro.data.synthetic import VectorDatasetSpec, clustered_vectors
from repro.launch.mesh import make_mesh

x, q = clustered_vectors(
    jax.random.PRNGKey(0),
    VectorDatasetSpec("demo", n=6000, d=96, n_queries=400, n_clusters=48))
_, gt = E.ground_truth(x, q, k=1)
entry = S.default_entry_point(x)
scfg = S.SearchConfig(l=48, k=32, max_iters=128)

# every builder defaults to merge="bucketed" (scatter-bucketed edge merging,
# the construction hot-loop optimization); pass merge="sort" to any config to
# time the exact lexsort oracle instead
builders = {
    "rnn-descent": lambda: rd.build(
        x, rd.RNNDescentConfig(s=12, r=48, t1=4, t2=6, capacity=64),
        jax.random.PRNGKey(1)),
    "rnn-descent[sort-oracle]": lambda: rd.build(
        x, rd.RNNDescentConfig(s=12, r=48, t1=4, t2=6, capacity=64,
                               merge="sort"),
        jax.random.PRNGKey(1)),
    "nn-descent": lambda: nnd.build(
        x, nnd.NNDescentConfig(k=32, s=12, iters=8), jax.random.PRNGKey(1)),
    "nsg-style": lambda: nsg_style.build(
        x, nsg_style.NSGStyleConfig(
            r=24, c=64, knn=nnd.NNDescentConfig(k=32, s=12, iters=8)),
        jax.random.PRNGKey(1)),
}

last_graph = None
for name, build in builders.items():
    jax.block_until_ready(build())        # warm the compile cache
    t0 = time.perf_counter()
    g = jax.block_until_ready(build())
    sec = time.perf_counter() - t0
    stats = E.evaluate_search(x, g, q, gt, scfg, entry_points=entry, tile_b=128)
    print(f"{name:24s} build {sec:6.2f}s  recall@1 {stats['recall_at_1']:.4f}  "
          f"qps {stats['qps']:8.1f}  "
          f"visited/tile {stats['visited_bytes_per_tile'] / 1024:.0f} KiB  "
          f"avg-out-degree {float(G.average_out_degree(g)):.1f}")
    if name == "rnn-descent":
        last_graph = g

# fused Pallas beam kernel vs the jnp oracle on the rnn-descent graph: same
# ids bit for bit (the parity the test harness guards); QPS differs only by
# where the gathered candidate block lives (VMEM vs HBM — on CPU the kernel
# is interpreted, so treat the fused number here as a correctness demo)
fused_cfg = dataclasses.replace(scfg, use_pallas=True, kernel_tile_b=64)
for label, cfg in (("jnp-ref", scfg), ("pallas-fused", fused_cfg)):
    stats = E.evaluate_search(x, last_graph, q, gt, cfg,
                              entry_points=entry, tile_b=128)
    print(f"search[{label:12s}]       recall@1 {stats['recall_at_1']:.4f}  "
          f"qps {stats['qps']:8.1f}  path {stats['search_path']}")

# scaling out (see "Scaling out" above): sharded build + sharded serving on
# a mesh over every visible device — bitwise-equal to the single-device runs
import numpy as np

mesh = make_mesh((jax.device_count(),), ("data",))
rnnd_cfg = rd.RNNDescentConfig(s=12, r=48, t1=4, t2=6, capacity=64)
g_shard = jax.block_until_ready(
    rd.build(x, rnnd_cfg, jax.random.PRNGKey(1), mesh=mesh))
assert np.array_equal(np.asarray(g_shard.neighbors),
                      np.asarray(last_graph.neighbors)), "sharded build diverged"
ids_1, _ = S.search_tiled(x, last_graph, q, entry, scfg, tile_b=128)
ids_m, _ = S.search_tiled(x, last_graph, q, entry, scfg, tile_b=128, mesh=mesh)
ids_c, _ = S.search_tiled(x, last_graph, q, entry, scfg, tile_b=128, mesh=mesh,
                          shard="corpus")
from repro.core.search_sharded import corpus_placement_bytes
place = corpus_placement_bytes(x.shape[0], x.shape[1], last_graph.capacity,
                               jax.device_count())
print(f"sharded[{jax.device_count()} dev]          build parity True  "
      f"search parity {bool(np.array_equal(np.asarray(ids_1), np.asarray(ids_m)))}  "
      f"corpus-sharded parity "
      f"{bool(np.array_equal(np.asarray(ids_1), np.asarray(ids_c)))}  "
      f"resident/dev {place['replicated'] // 1024} KiB -> "
      f"{place['sharded'] // 1024} KiB")

# streaming churn (see "Streaming updates" above): insert 20% new points and
# delete 10% of the originals without a rebuild, then serve tombstone-aware
from repro.streaming import StreamingANN, StreamingConfig
from repro.streaming.store import active_mask

n0 = 5000
ann = StreamingANN.from_corpus(x[:n0], StreamingConfig(build=rnnd_cfg),
                               key=jax.random.PRNGKey(1))
t0 = time.perf_counter()
ann.insert(x[n0:])                               # +1000 in one batch
ins_sec = time.perf_counter() - t0
ann.delete(np.arange(n0 // 10))                  # -500 tombstoned
live = active_mask(ann.store)
gt_sd, gt_si = E.ground_truth(ann.store.x, q, k=10, valid=live)
ids_s, _ = ann.search(q, dataclasses.replace(scfg, topk=10))
print(f"streaming churn           +{x.shape[0]-n0} pts in {ins_sec:5.2f}s  "
      f"-{n0 // 10} tombstoned  recall@10 "
      f"{E.recall_topk(ids_s, gt_si, valid=live):.4f}  epoch {ann.epoch}")

# serving front end (see "Serving front end" above): replay a short open-loop
# session against the churned index — queries coalesce into fixed-shape
# tiles while two write bursts commit mid-stream behind the epoch swap
from repro.serving import (AdmissionConfig, LoadSpec, ServingConfig,
                           ServingFrontend, WriterConfig, run_session)

srv_cfg = ServingConfig(
    admission=AdmissionConfig(tile_lanes=32, deadline_s=1.5),
    writer=WriterConfig(insert_batch=32, delete_batch=32),
    search=dataclasses.replace(scfg, topk=10))
# a real server warms its program shapes at startup — one full tile plus one
# insert/delete commit round; after this the session compiles nothing (the
# zero-steady-state-compile contract, guarded in CI)
fe = ServingFrontend(ann, srv_cfg)
for row in np.asarray(q[:32], np.float32):
    fe.submit(row)
wtk = fe.submit_insert(np.asarray(x[:32]))
fe.drain()
ann.delete(wtk.ids)                                    # retire the warm rows
fe = ServingFrontend(ann, srv_cfg)                     # fresh SLO telemetry
writes = [(64, "insert", np.asarray(x[:32])),          # re-add 32 old rows
          (128, "delete", np.arange(600, 632))]        # retire 32 live ones
summ = run_session(fe, np.asarray(q, np.float32),
                   LoadSpec(n_requests=256, qps=32.0, deadline_s=1.5),
                   writes=writes)
lat = summ["latency_ms"]
print(f"serving session           {summ['completed']} reqs  "
      f"p50 {lat['p50']:6.1f}ms  p99 {lat['p99']:6.1f}ms  "
      f"qps {summ['achieved_qps']:7.1f}  occupancy "
      f"{summ['occupancy_mean']:.2f}  staleness_max {summ['staleness_max']}  "
      f"epoch {ann.epoch}")

# compressed corpora (see "Compressed corpora" above): serve the rnn-descent
# graph from int8 and PQ codes — fused decode+score kernels, exact-f32
# rerank tail — and compare payload bytes and recall against the f32 rows
from repro.quant import Quantization, corpus_bytes, encode_corpus

r1_f32 = E.evaluate_search(x, last_graph, q, gt, scfg,
                           entry_points=entry, tile_b=128)["recall_at_1"]
for quant in (Quantization(mode="int8"), Quantization(mode="pq", m=24)):
    qx = encode_corpus(x, quant)
    mem = corpus_bytes(qx, x.shape[0], x.shape[1])
    qcfg = dataclasses.replace(scfg, quant=quant)
    ids_q, _ = S.search_tiled(x, last_graph, q, entry, qcfg, tile_b=128,
                              qx=qx)
    print(f"quantized[{quant.mode:4s}]          recall@1 "
          f"{E.recall_at_k(ids_q, gt):.4f} (f32 {r1_f32:.4f})  payload "
          f"{mem['payload_ratio']:.0f}x smaller  aux "
          f"{mem['aux_bytes'] / 1024:.0f} KiB")

# traced build (see "Observability" above): the same rnn-descent build with
# the obs switch on — per-sweep spans land on a shared timeline, candidate/
# prune counters land in the metrics registry, and the graph comes out
# byte-identical to the untraced build at the top of this script
from repro import obs
from repro.obs import trace

obs.enable()
obs.reset()
g_traced = rd.build(x, rnnd_cfg, jax.random.PRNGKey(1))
assert np.array_equal(np.asarray(g_traced.neighbors),
                      np.asarray(last_graph.neighbors)), \
    "tracing must not change a result bit"
S.search_tiled(x, g_traced, q[:128], entry, scfg, tile_b=128)
trace.write_chrome_trace("/tmp/ann_trace.json")
print("\ntraced build phase breakdown (full timeline: /tmp/ann_trace.json —"
      " load in https://ui.perfetto.dev):")
print(trace.summary_table())
obs.disable()
