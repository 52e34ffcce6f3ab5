"""Quickstart: build an RNN-Descent index and search it (the paper in ~30 lines).

    PYTHONPATH=src python examples/quickstart.py
"""
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.core import eval as E
from repro.core import rnn_descent as rd
from repro.core import search as S
from repro.data.synthetic import VectorDatasetSpec, clustered_vectors
from repro.launch.mesh import make_mesh

# 1. a corpus (SIFT-like dims at laptop scale) + queries + exact ground truth
x, queries = clustered_vectors(
    jax.random.PRNGKey(0),
    VectorDatasetSpec("demo", n=8000, d=128, n_queries=500, n_clusters=64))
_, gt = E.ground_truth(x, queries, k=1)

# 2. build the index — paper Algorithm 6 (S, R, T1, T2 scaled to corpus size).
# Edge merging defaults to the scatter-bucketed hot path (merge="bucketed");
# merge="sort" selects the exact lexsort oracle instead.
cfg = rd.RNNDescentConfig(s=12, r=48, t1=4, t2=6, capacity=64)
t0 = time.perf_counter()
graph = jax.block_until_ready(rd.build(x, cfg, jax.random.PRNGKey(1)))
print(f"built RNN-Descent index for n={x.shape[0]} in {time.perf_counter()-t0:.2f}s")

# 3. serve — paper Algorithm 1 with query-time out-degree limit K (Eq. 4),
# streamed through the constant-memory tiled driver: visited state is a
# per-query hashed table, so peak memory is O(tile_b * slots) however large
# the corpus or the query batch gets.
entry = jnp.broadcast_to(                       # multi-entry seeding (B, E)
    S.default_entry_points(x, n_entries=4)[None, :], (queries.shape[0], 4))
for L in (16, 32, 64):
    scfg = S.SearchConfig(l=L, k=32, max_iters=2 * L + 32)
    ids, dists = S.search_tiled(x, graph, queries, entry, scfg, tile_b=128)
    bytes_tile = S.visited_state_bytes(scfg, x.shape[0], 128, n_entry=4)
    print(f"  L={L:3d}  recall@1={E.recall_at_k(ids, gt):.4f}  "
          f"visited-state/tile={bytes_tile / 1024:.0f} KiB")

# 4. the beam inner loop can also run as a fused Pallas gather+score kernel
# (use_pallas=True): bitwise-identical results, gathered candidate block kept
# in VMEM instead of an HBM round-trip (interpreted on CPU).
fused = dataclasses.replace(S.SearchConfig(l=32, k=32, max_iters=96),
                            use_pallas=True)
ids_f, _ = S.search_tiled(x, graph, queries, entry, fused, tile_b=128)
print(f"  fused beam kernel: recall@1={E.recall_at_k(ids_f, gt):.4f} "
      "(identical to the jnp path)")

# 5. scale out: both build and serve take a mesh and return *exactly* the
# same results — rd.build(x, cfg, key, mesh=mesh) shards graph rows,
# search_tiled(..., mesh=mesh) shards query tiles. See the "Scaling out"
# section in examples/build_and_search.py; on CPU forge devices with
# XLA_FLAGS=--xla_force_host_platform_device_count=8.
mesh = make_mesh((jax.device_count(),), ("data",))
scfg = S.SearchConfig(l=32, k=32, max_iters=96)
ids_m, _ = S.search_tiled(x, graph, queries, entry, scfg, tile_b=128, mesh=mesh)
print(f"  sharded serving ({jax.device_count()} device(s)): "
      f"recall@1={E.recall_at_k(ids_m, gt):.4f} (identical to unsharded)")

# 6. streaming updates: the corpus churns without a rebuild. StreamingANN
# wraps the index in a capacity-padded store — insert() beam-seeds new rows
# off the current graph and runs localized RNN-Descent sweeps over the
# touched frontier; delete() tombstones rows (still traversable as bridges,
# never surfaced — search is tombstone-aware) and splices their neighbors
# back together; compact() physically drops the tombstones.
import numpy as np

from repro.streaming import StreamingANN, StreamingConfig

ann = StreamingANN.from_corpus(
    x[:7000], StreamingConfig(build=cfg), key=jax.random.PRNGKey(1))
new_ids = ann.insert(x[7000:])                  # +1000 points, no rebuild
ann.delete(np.arange(500))                      # -500 originals, tombstoned
ids_s, _ = ann.search(queries, S.SearchConfig(l=32, k=32, max_iters=96,
                                              topk=10))
from repro.streaming.store import active_mask
live = active_mask(ann.store)
gt_sd, gt_si = E.ground_truth(ann.store.x, queries, k=10, valid=live)
print(f"  streaming churn (+1000/-500): recall@10="
      f"{E.recall_topk(ids_s, gt_si, valid=live):.4f}  "
      f"epoch={ann.epoch}  live={ann.live}/{ann.capacity} rows")
assert not np.any(np.isin(np.asarray(ids_s), np.arange(500)))  # never surface

# 7. serve it: the admission queue coalesces arriving queries into
# fixed-shape search tiles (dispatch when full, or when the oldest request
# has spent half its latency budget), concurrent writes batch behind the
# epoch swap, and telemetry reports the SLO view. A warmed server compiles
# zero XLA programs at steady state — see ROADMAP "Serving".
from repro.serving import AdmissionConfig, ServingConfig, ServingFrontend

fe = ServingFrontend(ann, ServingConfig(
    admission=AdmissionConfig(tile_lanes=32, deadline_s=0.2),
    search=S.SearchConfig(l=32, k=32, max_iters=96, topk=10)))
rids = [fe.submit(row) for row in np.asarray(queries[:48], np.float32)]
tk = fe.submit_insert(np.asarray(x[:32]))       # rides the next full batch
fe.drain()                                      # demo: flush instead of pump
first_ids, _ = fe.result(rids[0])
summ = fe.telemetry.summary()
print(f"  serving: {summ['completed']} requests in {summ['tiles']} tiles  "
      f"p50={summ['latency_ms']['p50']:.1f}ms  "
      f"occupancy={summ['occupancy_mean']:.2f}  "
      f"insert ticket -> rows {tk.ids[:3]}...")
assert np.array_equal(first_ids, np.asarray(ids_s)[0])   # same store, same bits

# 8. compressed corpus: store int8 or PQ codes instead of f32 rows and let
# the fused kernels decode in-register next to the distance math. One
# Quantization object selects the representation everywhere (builder and
# search configs); coded searches finish with an exact-f32 rerank tail over
# the top rerank_k candidates, which is what keeps PQ recall close to f32.
from repro.quant import Quantization, corpus_bytes, encode_corpus

for quant in (Quantization(mode="int8"), Quantization(mode="pq", m=32)):
    qx = encode_corpus(x, quant)
    mem = corpus_bytes(qx, x.shape[0], x.shape[1])
    qcfg = S.SearchConfig(l=32, k=32, max_iters=96, quant=quant)
    ids_q, _ = S.search_tiled(x, graph, queries, entry, qcfg, tile_b=128,
                              qx=qx)
    print(f"  quantized[{quant.mode:4s}]: recall@1="
          f"{E.recall_at_k(ids_q, gt):.4f}  payload "
          f"{mem['payload_ratio']:.0f}x smaller "
          f"({mem['codes_bytes'] / 2**20:.1f} MiB vs "
          f"{mem['f32_bytes'] / 2**20:.1f} MiB f32)")
