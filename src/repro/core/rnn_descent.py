"""Relative NN-Descent (the paper's contribution), TPU-adapted.

Paper Algorithm 6:

    G <- RandomGraph(S); all flags "new"
    repeat T1 times:
        repeat T2 times:  UpdateNeighbors(G)       (Alg. 4)
        unless last:      AddReverseEdges(G, R)    (Alg. 5)

Adaptation (DESIGN.md §2): every vertex is updated in parallel per sweep
(Jacobi) instead of sequentially (Gauss–Seidel); replacement edges (w -> v)
produced by the fused RNG prune are buffered and merged instead of being
inserted under locks — by default through the scatter-bucketed merge
(``merge="bucketed"``: O(E) bucket scatter + per-row sorts), with the global
lexsort path (``merge="sort"``) kept as the exact oracle. Adjacency capacity is a static
``M``; the paper's unbounded out-degree is recovered at query time via the
top-K limit (paper Eq. 4).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import distances as D
from repro.core import graph as G
from repro.core.rng import rng_scan
from repro.quant import Quantization, QuantizedCorpus, prep_corpus


@dataclasses.dataclass(frozen=True)
class RNNDescentConfig:
    """Paper defaults: S=20, R=96, T1=4, T2=15 (§5.1)."""

    s: int = 20            # out-degree of the random initial graph
    r: int = 96            # reverse-edge degree cap
    t1: int = 4            # outer iterations (reverse-edge phases: t1 - 1)
    t2: int = 15           # UpdateNeighbors sweeps per outer iteration
    capacity: int = 128    # static adjacency capacity M (>= r)
    metric: str = "l2"
    chunk: int = 512       # vertices per fused-prune tile
    use_pallas: bool = False   # route the fused prune through the Pallas kernel
    gram_dtype: str = "f32"    # "bf16" halves the gather+Gram HBM traffic
                               # (accumulation stays f32; recall re-validated
                               # in tests/benchmarks)
    merge: str = "bucketed"    # edge-merge path: "bucketed" (scatter buckets,
                               # hot-loop default) | "sort" (lexsort oracle)
    n_buckets: int | None = None   # bucket width override (power of two;
                                   # default graph.default_buckets(cap))
    quant: Quantization = Quantization()  # corpus representation at build time

    def __post_init__(self):
        # config-time validation (ValueError, matching SearchConfig): a bad
        # capacity/merge used to die as a bare AssertionError deep in a trace
        if self.capacity < self.r:
            raise ValueError(
                f"capacity={self.capacity} must hold the R={self.r} reverse "
                "edges added by AddReverseEdges (capacity >= r)")
        if self.merge not in G.MERGE_MODES:
            raise ValueError(
                f"unknown merge mode {self.merge!r}: expected one of "
                f"{G.MERGE_MODES}")
        if not isinstance(self.quant, Quantization):
            raise ValueError(
                f"quant must be a repro.quant.Quantization, got "
                f"{type(self.quant).__name__}")
        if self.quant.is_coded and self.gram_dtype == "bf16":
            raise ValueError(
                f"quant.mode={self.quant.mode!r} conflicts with "
                "gram_dtype=\"bf16\": pick one compression (use "
                "quant.mode=\"bf16\" for half-width gathers)")

    @property
    def effective_gram_dtype(self) -> str:
        """``quant.mode="bf16"`` routes through the pre-existing bf16-gather
        path (SearchConfig convention)."""
        return "bf16" if self.quant.mode == "bf16" else self.gram_dtype


def random_init(key: jax.Array, x: jnp.ndarray, cfg: RNNDescentConfig) -> G.Graph:
    """RandomGraph(S) — shared helper in graph.py."""
    return G.random_init_graph(key, x, cfg.s, cfg.capacity, cfg.metric)


def _fused_prune_chunk(x, cid, cdist, cflag, metric, use_pallas,
                       gram_dtype="f32", qx=None):
    """One vertex tile of the fused NN-Descent-join + RNG-prune (Alg. 4).

    ``qx`` (int8 :class:`QuantizedCorpus`) switches both paths to gathering
    *code* rows (4x less gather traffic) with in-register dequantize. The
    jnp fallback decodes after the gather — the same op sequence as the
    kernel body — so use_pallas=True/False stay bitwise-equal; decoding a
    materialized ``x_hat`` up front would differ in the last ulp (XLA fuses
    the decode multiply-add differently per fusion context)."""
    if qx is not None:
        if use_pallas:
            from repro.kernels.rng_prune import ops as rng_ops
            return rng_ops.rng_prune_int8(
                qx.codes, qx.scale, qx.zero, cid, cdist, flags=cflag)
        from repro.quant import int8_decode
        vecs = int8_decode(qx.codes[jnp.maximum(cid, 0)], qx.scale, qx.zero)
    elif use_pallas:
        from repro.kernels.rng_prune import ops as rng_ops
        keep, red_w, red_d = rng_ops.rng_prune(
            x, cid, cdist, flags=cflag, gram_dtype=gram_dtype
        )
        return keep, red_w, red_d
    else:
        if gram_dtype == "bf16":
            x = x.astype(jnp.bfloat16)
        vecs = x[jnp.maximum(cid, 0)]
    pair = D.batched_gram(vecs, metric)
    old = cflag == G.OLD
    skip = old[:, :, None] & old[:, None, :]     # old-old pairs already verified
    res = rng_scan(cid, cdist, pair, skip_pair=skip)
    return res.keep, res.redirect_w, res.redirect_d


def prune_rows(
    x: jnp.ndarray, ids: jnp.ndarray, dists: jnp.ndarray, flags: jnp.ndarray,
    cfg: RNNDescentConfig, qx: QuantizedCorpus | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Chunked fused prune over a block of adjacency rows (the whole graph or
    one shard's rows — the computation is per-row, so any row partition gives
    bitwise-identical per-row results). Returns (keep, red_w, red_d).

    ``qx``: int8 codes for the code-gathering prune (see
    :func:`_fused_prune_chunk`); ``None`` keeps the f32/bf16 path."""
    with jax.named_scope("rnnd.prune"):    # the phase's device-trace name
        n_rows, m = ids.shape
        chunk = min(cfg.chunk, n_rows)
        pad = (-n_rows) % chunk
        ids = jnp.pad(ids, ((0, pad), (0, 0)), constant_values=-1)
        dists = jnp.pad(dists, ((0, pad), (0, 0)), constant_values=jnp.inf)
        flags = jnp.pad(flags, ((0, pad), (0, 0)), constant_values=G.OLD)

        def one_chunk(args):
            cid, cdist, cflag = args
            return _fused_prune_chunk(x, cid, cdist, cflag, cfg.metric,
                                      cfg.use_pallas, cfg.effective_gram_dtype,
                                      qx=qx)

        keep, red_w, red_d = jax.lax.map(
            one_chunk,
            (ids.reshape(-1, chunk, m), dists.reshape(-1, chunk, m),
             flags.reshape(-1, chunk, m)),
        )
        # The barrier stops XLA from fusing a caller's flatten of these outputs
        # (the sweep's candidate lists) into the map's stacked (rows/chunk,
        # chunk, m) layout: that fusion leaves a relayout of a packed bool mask
        # to an (n*m, 1) column whose TPU code generation time grows with n.
        return jax.lax.optimization_barrier((
            keep.reshape(-1, m)[:n_rows],
            red_w.reshape(-1, m)[:n_rows],
            red_d.reshape(-1, m)[:n_rows],
        ))


@functools.partial(jax.jit, static_argnames=("cfg",))
def update_neighbors(x: jnp.ndarray, g: G.Graph, cfg: RNNDescentConfig,
                     qx: QuantizedCorpus | None = None) -> G.Graph:
    """Paper Algorithm 4, one parallel sweep over all vertices.

    For each vertex u (rows sorted by distance):
      * keep candidate v iff it passes the RNG inequality against every
        already-kept w (old-old pairs exempt — NN-Descent flag optimization);
      * a dropped v yields the replacement edge (w -> v) with d(v, w) — the
        simultaneous "NN-Descent join" that keeps v reachable from u via w;
      * kept entries become "old"; replacement edges are inserted "new".

    The prune and the merge run under the ``jax.named_scope`` names
    ``rnnd.prune`` and ``rnnd.merge``: metadata that a device trace keeps
    on every compiled op, no op of their own.
    """
    keep, red_w, red_d = prune_rows(x, g.neighbors, g.dists, g.flags, cfg,
                                    qx=qx)

    with jax.named_scope("rnnd.merge"):    # the phase's device-trace name
        # Surviving adjacency: kept entries, flags forced to "old" (Alg. 4 L16).
        pruned = G.Graph(
            neighbors=jnp.where(keep, g.neighbors, -1),
            dists=jnp.where(keep, g.dists, jnp.inf),
            flags=jnp.zeros_like(g.flags),
        )
        pruned = G.sort_rows(pruned)

        # Replacement edges (w -> v): scatter-merge into w's rows, flagged "new".
        cand_src = red_w.reshape(-1)                                       # w
        cand_dst = jnp.where(red_w >= 0, g.neighbors, -1).reshape(-1)      # v
        cand_dist = red_d.reshape(-1)
        return G.merge_candidate_edges(
            pruned, cand_src, cand_dst, cand_dist,
            merge=cfg.merge, n_buckets=cfg.n_buckets,
        )


@functools.partial(jax.jit, static_argnames=("cfg",))
def add_reverse_edges(g: G.Graph, cfg: RNNDescentConfig) -> G.Graph:
    """Paper Algorithm 5 (vectorized in graph.py)."""
    return G.add_reverse_edges(g, cfg.r, merge=cfg.merge, n_buckets=cfg.n_buckets)


def build(x: jnp.ndarray, cfg: RNNDescentConfig, key: jax.Array,
          mesh=None) -> G.Graph:
    """Paper Algorithm 6 — eager Python loop (CPU experimentation path).

    ``mesh``: a ``jax.sharding.Mesh`` routes the build through the
    multi-device sharded path (core/shard.py): graph rows partitioned across
    the mesh's "rows" logical axis via shard_map, x replicated, bucket tables
    exchanged between shards. Bitwise-identical to ``mesh=None`` (asserted in
    tests/test_sharded_parity.py).

    ``cfg.quant`` int8/pq builds the graph over the *decoded* corpus (see
    :func:`prep_corpus`) — the geometry the coded search will traverse; the
    int8 prune additionally gathers code rows instead of f32 rows.

    Observability: with ``repro.obs`` enabled each sweep runs under an
    ``rnn_descent/sweep`` span (each reverse pass under
    ``rnn_descent/reverse``) that blocks once at span exit for an
    execution-accurate duration and records edge counters — the jitted
    programs issued are identical either way, so the built graph is
    bitwise-equal traced or untraced (tests/test_obs.py)."""
    from repro.obs import trace as _tr
    xb, qx = prep_corpus(x, cfg.quant)
    if mesh is not None:
        from repro.core import shard
        return shard.build_rnn_descent(xb, cfg, key, mesh, qx=qx)
    g = random_init(key, xb, cfg)
    prev_live, sweep = None, 0
    for t1 in range(cfg.t1):
        for _ in range(cfg.t2):
            with _tr.span("rnn_descent/sweep") as sp:
                g = update_neighbors(xb, g, cfg, qx=qx)
                if sp:
                    from repro.obs import graphstats as _gs
                    g = jax.block_until_ready(g)
                    prev_live = _gs.record_sweep(
                        sp, g, algo="rnn_descent", phase="sweep",
                        prev_live=prev_live, sweep=sweep, t1=t1)
            sweep += 1
        if t1 != cfg.t1 - 1:
            with _tr.span("rnn_descent/reverse") as sp:
                g = add_reverse_edges(g, cfg)
                if sp:
                    from repro.obs import graphstats as _gs
                    g = jax.block_until_ready(g)
                    prev_live = _gs.record_sweep(
                        sp, g, algo="rnn_descent", phase="reverse", t1=t1)
    return g


@functools.partial(jax.jit, static_argnames=("cfg",))
def build_jit(x: jnp.ndarray, cfg: RNNDescentConfig, key: jax.Array) -> G.Graph:
    """Paper Algorithm 6 as nested ``lax.scan`` — single XLA program.

    This is the lowering used for the dry-run / TPU path: the whole build is
    one compiled module regardless of (T1, T2).

    Coded-build parity note: use_pallas=True/False and mesh/no-mesh are
    bitwise-equal *within* each entry point, but :func:`build` and
    :func:`build_jit` under int8/pq can differ in the last ulp of ``dists``
    (same ids/flags): XLA contracts the decode multiply-add into FMA
    differently in the per-sweep jit vs this whole-program scan."""
    x, qx = prep_corpus(x, cfg.quant)
    g0 = random_init(key, x, cfg)

    def inner(g, _):
        return update_neighbors(x, g, cfg, qx=qx), None

    def outer(carry, t1):
        g = carry
        g, _ = jax.lax.scan(inner, g, None, length=cfg.t2)
        g = jax.lax.cond(
            t1 != cfg.t1 - 1, lambda gg: add_reverse_edges(gg, cfg), lambda gg: gg, g
        )
        return g, None

    g, _ = jax.lax.scan(outer, g0, jnp.arange(cfg.t1))
    return g
