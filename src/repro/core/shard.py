"""Multi-device sharded index construction (shard_map over graph rows).

Connects the mesh machinery (launch/mesh.py, distributed/sharding.py) to the
builders: graph adjacency rows are partitioned across the mesh axes the
logical ``"rows"`` axis resolves to (RULES in distributed/sharding.py —
``"data"``, joined by ``"pod"`` on multi-pod meshes), while the corpus ``x``
is replicated. All per-row work — the fused RNG prune, the NN-Descent local
join, the NSG candidate expansion, row sorts and degree caps — runs
shard-locally with no communication.

The only cross-shard traffic is candidate routing: a shard's rows emit
candidate edges whose *destination* rows live on other shards (RNN-Descent
replacement edges (w -> v) land in row w; reverse edges land in the reversed
source's row). PR 2's scatter-bucketed merge makes that exchange a pure
min-reduction, and :func:`exchange_scatter` runs it *destination-bucketed*:
on ring hop j every shard scatters its candidates into only the
(n_pad/D, B) table block owned by peer (me + j) % D, ships exactly that
block with a ``ppermute``, and folds arrivals pairwise with the staged
lexicographic min of :func:`repro.core.graph.combine_bucket_tables_pair`
— a reduce-scatter with min-by-(priority, dist_key, id) in place of sum
that never materializes a full-height (n_pad, B) table. Each shard ends
holding the combined block for exactly its own rows.

Exactness
---------
Because each (row, slot) bucket entry is the lexicographic minimum over the
candidates hashing there, and a minimum over any partition of the candidate
list combines associatively to the global minimum, the sharded build is
**bitwise identical** to the single-device build: same int32 neighbor ids,
same uint32 dist_keys, same flags, for every builder and metric — asserted
in tests/test_sharded_parity.py on an 8-virtual-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``). Two facts carry
the destination-bucketed form: a blockwise scatter with shifted rows and a
block-local height is exactly the block restriction of the full-height
scatter (out-of-block rows fail the range guard in
``bucket_scatter_tables``), and the staged fold is associative and
commutative, so accumulating one peer block per ring hop is bitwise equal
to the stacked all-partials fold.

Memory math (per device, n rows, D shards, bucket width B, capacity M):
  * adjacency rows:      3 fields * (n/D) * M           (sharded — the win)
  * corpus x:            n * d * 4 bytes                (replicated; serving
                                                         shards it — see
                                                         core/search_sharded)
  * partial bucket tabs: (9..13) * (n_pad/D) * B bytes  (transient: the live
                                                         accumulator + the
                                                         in-flight peer block,
                                                         ~2-3 blocks total)
No full-height transient remains: wire bytes are unchanged from the old
full-height ``all_to_all`` ((D-1)/D of the table crosses the wire either
way — the budget ``analysis/collectives.py`` enforces), but peak scatter
memory dropped from (9..13) * n_pad * B to O(n_pad/D) * B per merge.

``n`` not divisible by the shard count is handled by padding rows with empty
adjacency: padded rows emit no candidates (all ids are -1) and real
candidates never target them (every vertex id in the system is < n), so the
padding is inert and sliced off on exit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import graph as G
from repro.distributed import sharding as SH

ROWS = "rows"  # logical axis name for graph adjacency rows (RULES)


def row_axes(mesh: Mesh) -> tuple[str, ...]:
    """Physical mesh axes graph rows shard over (empty = replicated)."""
    return SH.mesh_axes(mesh, ROWS)


def n_shards(mesh: Mesh) -> int:
    return SH.axis_count(mesh, ROWS)


def _row_pspec(mesh: Mesh) -> P:
    return SH.pspec(mesh, ROWS, None)      # (rows, cols) arrays


def _row1_pspec(mesh: Mesh) -> P:
    return SH.pspec(mesh, ROWS)            # 1-D row-id arrays


def _graph_specs(mesh: Mesh) -> G.Graph:
    rp = _row_pspec(mesh)
    return G.Graph(rp, rp, rp)


def _check_mesh(mesh: Mesh, merge: str) -> None:
    from repro.launch.mesh import check_auto
    check_auto(mesh)
    if merge != "bucketed":
        raise ValueError(
            f"sharded builds require merge='bucketed' (got {merge!r}): the "
            "cross-shard exchange is a min-reduction over bucket tables; the "
            "'sort' oracle is a global lexsort with no shard-local form")
    if not row_axes(mesh):
        raise ValueError(
            f"mesh axes {mesh.axis_names} give the logical 'rows' axis "
            "nothing to shard over — see RULES in distributed/sharding.py")


def pad_rows(g: G.Graph, n_pad: int) -> G.Graph:
    """Append empty (inert) adjacency rows up to ``n_pad``."""
    n = g.n
    if n_pad == n:
        return g
    return G.Graph(
        neighbors=jnp.pad(g.neighbors, ((0, n_pad - n), (0, 0)),
                          constant_values=-1),
        dists=jnp.pad(g.dists, ((0, n_pad - n), (0, 0)),
                      constant_values=jnp.inf),
        flags=jnp.pad(g.flags, ((0, n_pad - n), (0, 0)),
                      constant_values=G.OLD),
    )


def _padded(n: int, d: int) -> int:
    return -(-n // d) * d


def exchange_bucket_tables(axes, n_dev, tabs):
    """Reduce-scatter-min of full-height partial bucket tables.

    ``tabs`` = (p, k, i, f) of shape (n_pad, B) each (p may be None): this
    shard's scatter over its own candidates, covering every row. Splits the
    row axis into ``n_dev`` blocks, ``all_to_all``-transposes so each shard
    holds every shard's partial for *its* block, and folds with the staged
    lexicographic min — psum_scatter with min in place of sum. Returns
    (n_pad / n_dev, B) tables equal to a single-device scatter of the union
    candidate list, restricted to this shard's rows."""

    def rs(t):
        if t is None:
            return None
        n_pad = t.shape[0]
        t = t.reshape(n_dev, n_pad // n_dev, t.shape[1])
        return jax.lax.all_to_all(t, axes, split_axis=0, concat_axis=0,
                                  tiled=False)

    p, k, i, f = tabs
    return G.combine_bucket_tables(rs(p), rs(k), rs(i), rs(f))


def exchange_scatter(axes, n_dev, n_pad, scatter_block):
    """Destination-bucketed reduce-scatter-min of bucket tables.

    ``scatter_block(lo, n_blk)`` must scatter this shard's candidates into
    the (n_blk, B) partial tables covering destination rows
    [lo, lo + n_blk) — the block restriction of the full-height scatter
    (out-of-block rows fail the range guard in
    :func:`repro.core.graph.bucket_scatter_tables`; ``lo`` may be traced).

    Ring exchange: on hop j every shard computes the block destined for
    peer (me + j) % n_dev, ships exactly that block with a ``ppermute``,
    and folds the arriving peer block into its accumulator with the
    pairwise staged lexicographic min. Hop 0 is the shard's own block (no
    communication). Total wire bytes equal the full-height ``all_to_all``
    ((n_dev - 1)/n_dev of the table crosses the wire either way), but the
    per-shard transient drops from (n_pad, B) to ~2-3 blocks of
    (n_pad/n_dev, B): the accumulator plus the in-flight block.

    Returns the combined (n_pad/n_dev, B) tables for this shard's own
    rows, bitwise equal to a full-height scatter of the union candidate
    list followed by a reduce-scatter (blockwise scatter = block
    restriction; pairwise fold = stacked fold)."""
    if not axes or n_dev == 1:
        return scatter_block(0, n_pad)
    if len(axes) > 1:
        # rows sharded over multiple physical axes: ring addressing wants a
        # single axis — keep the full-height all_to_all path on those meshes
        return exchange_bucket_tables(axes, n_dev, scatter_block(0, n_pad))
    ax = axes[0]
    n_blk = n_pad // n_dev
    me = jax.lax.axis_index(ax)
    acc = scatter_block(me * n_blk, n_blk)
    for j in range(1, n_dev):
        blk = scatter_block((me + j) % n_dev * n_blk, n_blk)
        perm = [(s, (s + j) % n_dev) for s in range(n_dev)]
        blk = jax.tree.map(lambda t: jax.lax.ppermute(t, ax, perm), blk)
        acc = G.combine_bucket_tables_pair(acc, blk)
    return acc


def _merge_candidates_shard(g_local, cand_src, cand_dst, cand_dist,
                            n_pad, cap, b, axes, n_dev) -> G.Graph:
    """Shard-local half of merge_candidate_edges(merge="bucketed"): scatter
    this shard's candidates one destination block at a time, ring-exchange
    the blocks, merge the combined block into the local rows."""
    flags = jnp.full(cand_dst.reshape(-1).shape, G.NEW)

    def scatter_block(lo, n_blk):
        return G.bucket_scatter_tables(
            cand_src - lo, cand_dst, cand_dist, flags, n_blk, b,
            row_ids=lo + jnp.arange(n_blk, dtype=jnp.int32))

    _, kt, it, ft = exchange_scatter(axes, n_dev, n_pad, scatter_block)
    b_ids, b_dist, b_flag = G.decode_bucket_tables(kt, it, ft)
    return G.merge_rows_with_buckets(
        g_local, b_ids, b_dist, b_flag, cap, g_local.neighbors.shape[1])


@functools.partial(jax.jit, static_argnames=("cap", "n_buckets", "mesh"))
def merge_candidate_edges(g: G.Graph, cand_src, cand_dst, cand_dist,
                          mesh: Mesh, cap: int | None = None,
                          n_buckets: int | None = None) -> G.Graph:
    """Sharded graph.merge_candidate_edges(merge="bucketed"): rows partition
    over the mesh, the flat candidate list is replicated (the bucket fold is
    an idempotent min, so identical partials combine exactly), and each shard
    merges the exchanged table block into its own rows. Bitwise-identical to
    the single-device bucketed merge."""
    n, m = g.neighbors.shape
    cap = m if cap is None else cap
    d = n_shards(mesh)
    n_pad = _padded(n, d)
    b = n_buckets or G.default_buckets(cap)
    axes = row_axes(mesh)

    def shard_fn(gl, cs, cd, cw):
        return _merge_candidates_shard(gl, cs, cd, cw, n_pad, cap, b, axes, d)

    gs = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(_graph_specs(mesh), P(), P(), P()),
                       out_specs=_graph_specs(mesh),
                       check_vma=False)(
        pad_rows(g, n_pad), cand_src.reshape(-1), cand_dst.reshape(-1),
        cand_dist.reshape(-1))
    return G.Graph(gs.neighbors[:n], gs.dists[:n], gs.flags[:n])


# ------------------------------------------------------------- RNN-Descent
@functools.partial(jax.jit, static_argnames=("cfg", "mesh"))
def rnn_update_neighbors(x, g: G.Graph, cfg, mesh: Mesh, qx=None) -> G.Graph:
    """Sharded paper Algorithm 4 sweep — rnn_descent.update_neighbors with
    rows partitioned over the mesh (bitwise-identical result).

    ``qx``: optional int8 :class:`repro.quant.QuantizedCorpus`, replicated
    like ``x`` — the per-shard prune gathers code rows exactly as the
    single-device path does, preserving bitwise mesh parity for quantized
    builds."""
    from repro.core import rnn_descent as rd

    n, m = g.neighbors.shape
    d = n_shards(mesh)
    n_pad = _padded(n, d)
    b = cfg.n_buckets or G.default_buckets(m)
    axes = row_axes(mesh)
    has_qx = qx is not None

    def shard_fn(xx, gl, *rest):
        qq = rest[0] if has_qx else None
        keep, red_w, red_d = rd.prune_rows(xx, gl.neighbors, gl.dists,
                                           gl.flags, cfg, qx=qq)
        pruned = G.sort_rows(G.Graph(
            neighbors=jnp.where(keep, gl.neighbors, -1),
            dists=jnp.where(keep, gl.dists, jnp.inf),
            flags=jnp.zeros_like(gl.flags),
        ))
        # replacement edges (w -> v): destination row w lives on any shard
        cand_src = red_w.reshape(-1)
        cand_dst = jnp.where(red_w >= 0, gl.neighbors, -1).reshape(-1)
        cand_dist = red_d.reshape(-1)
        return _merge_candidates_shard(
            pruned, cand_src, cand_dst, cand_dist, n_pad, m, b, axes, d)

    operands = [x, pad_rows(g, n_pad)]
    specs = [P(), _graph_specs(mesh)]
    if has_qx:
        operands.append(qx)
        specs.append(jax.tree.map(lambda _: P(), qx))
    gs = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=tuple(specs),
                       out_specs=_graph_specs(mesh),
                       check_vma=False)(*operands)
    return G.Graph(gs.neighbors[:n], gs.dists[:n], gs.flags[:n])


@functools.partial(jax.jit, static_argnames=("r", "n_buckets", "mesh"))
def add_reverse_edges(g: G.Graph, r: int, mesh: Mesh,
                      n_buckets: int | None = None) -> G.Graph:
    """Sharded paper Algorithm 5 — graph.add_reverse_edges(merge="bucketed")
    with rows partitioned over the mesh. Both degree caps run as bucket
    exchanges: the in-degree cap groups E ∪ reverse(E) by *destination* row,
    the out-degree cap regroups the survivors by *source* row; each regroup
    is one reduce-scatter-min of partial tables."""
    n, m = g.neighbors.shape
    d = n_shards(mesh)
    n_pad = _padded(n, d)
    b = n_buckets or G.default_buckets(r)
    wa = min(r, b)
    axes = row_axes(mesh)

    def shard_fn(gl, rid):
        n_loc = rid.shape[0]
        src = jnp.broadcast_to(rid[:, None], (n_loc, m)).reshape(-1)
        dst = gl.neighbors.reshape(-1)
        dist = gl.dists.reshape(-1)
        flag = gl.flags.reshape(-1)
        # E ∪ reverse(E), grouped by destination row for the in-degree cap:
        # forward (u -> v): row v holds u (prio 0, original flag); reversed
        # copy: row u holds v (prio 1, NEW) — the priority makes a
        # pre-existing copy of a mutual edge win, as in the oracle's dedup
        rows_cat = jnp.concatenate([dst, jnp.where(dst >= 0, src, -1)])
        ids_cat = jnp.concatenate([src, dst])
        dist_cat = jnp.concatenate([dist, dist])
        flag_cat = jnp.concatenate([flag, jnp.full_like(flag, G.NEW)])
        prio_cat = jnp.concatenate(
            [jnp.zeros_like(src), jnp.ones_like(src)])

        def scat_in(lo, n_blk):
            return G.bucket_scatter_tables(
                rows_cat - lo, ids_cat, dist_cat, flag_cat, n_blk, b,
                prio=prio_cat,
                row_ids=lo + jnp.arange(n_blk, dtype=jnp.int32))

        _, kt, it, ft = exchange_scatter(axes, d, n_pad, scat_in)
        in_ids, in_dist, in_flag = G.decode_bucket_tables(kt, it, ft)
        in_ids, in_dist, in_flag = G.row_topk(in_ids, in_dist, in_flag, r, wa)
        # surviving edges (u -> v), regrouped by source for the out-degree cap
        e_src = in_ids.reshape(-1)
        e_dst = jnp.where(
            e_src >= 0,
            jnp.broadcast_to(rid[:, None], (n_loc, wa)).reshape(-1), -1)
        e_dist = in_dist.reshape(-1)
        e_flag = in_flag.reshape(-1)

        def scat_out(lo, n_blk):
            return G.bucket_scatter_tables(
                e_src - lo, e_dst, e_dist, e_flag, n_blk, b,
                row_ids=lo + jnp.arange(n_blk, dtype=jnp.int32))

        _, kt2, it2, ft2 = exchange_scatter(axes, d, n_pad, scat_out)
        o_ids, o_dist, o_flag = G.decode_bucket_tables(kt2, it2, ft2)
        return G.Graph(*G.row_topk(o_ids, o_dist, o_flag, min(r, m), m))

    row_ids = jnp.arange(n_pad, dtype=jnp.int32)
    gs = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(_graph_specs(mesh), _row1_pspec(mesh)),
                       out_specs=_graph_specs(mesh),
                       check_vma=False)(pad_rows(g, n_pad), row_ids)
    return G.Graph(gs.neighbors[:n], gs.dists[:n], gs.flags[:n])


def _exchange_attrs(n: int, mesh: Mesh, buckets: int,
                    slot_bytes: int) -> dict:
    """Span attributes for one sweep's destination-bucketed ring exchange,
    from the closed form in analysis/collectives.py: D-1 ppermute hops,
    each shipping one (n_pad/D, B) block at ``slot_bytes`` per slot. The
    exchange itself runs inside the jitted sweep (spans stay host-side),
    so the hop structure is attached as attributes rather than timed."""
    d = n_shards(mesh)
    n_pad = _padded(n, d)
    wire = slot_bytes * buckets * n_pad * (d - 1) // d if d > 1 else 0
    return {
        "exchange_hops": d - 1,
        "exchange_block_rows": n_pad // d,
        "exchange_buckets": buckets,
        "exchange_bytes_per_device": wire,
        "devices": d,
    }


def build_rnn_descent(x, cfg, key, mesh: Mesh, qx=None) -> G.Graph:
    """Sharded paper Algorithm 6 (rnn_descent.build(mesh=...) entry point).
    RandomGraph(S) is computed replicated (same key -> same init), sweeps run
    row-sharded. ``x``/``qx`` arrive pre-prepped from rnn_descent.build
    (under ``cfg.quant`` x is already the decoded corpus).

    Observability: mirrors rnn_descent.build — per-sweep
    ``rnn_descent/sweep`` spans (attributes additionally carry the ring-
    exchange hop count and closed-form wire bytes) when ``repro.obs`` is
    enabled; identical jitted programs either way."""
    from repro.core import rnn_descent as rd
    from repro.obs import trace as _tr

    _check_mesh(mesh, cfg.merge)
    n = x.shape[0]
    g = rd.random_init(key, x, cfg)
    prev_live, sweep = None, 0
    for t1 in range(cfg.t1):
        for _ in range(cfg.t2):
            with _tr.span("rnn_descent/sweep") as sp:
                g = rnn_update_neighbors(x, g, cfg, mesh, qx=qx)
                if sp:
                    from repro.obs import graphstats as _gs
                    g = jax.block_until_ready(g)
                    prev_live = _gs.record_sweep(
                        sp, g, algo="rnn_descent", phase="sweep",
                        prev_live=prev_live, sweep=sweep, t1=t1,
                        **_exchange_attrs(
                            n, mesh,
                            cfg.n_buckets or G.default_buckets(cfg.capacity),
                            9))
            sweep += 1
        if t1 != cfg.t1 - 1:
            with _tr.span("rnn_descent/reverse") as sp:
                g = add_reverse_edges(g, cfg.r, mesh, cfg.n_buckets)
                if sp:
                    from repro.obs import graphstats as _gs
                    g = jax.block_until_ready(g)
                    prev_live = _gs.record_sweep(
                        sp, g, algo="rnn_descent", phase="reverse", t1=t1,
                        **_exchange_attrs(
                            n, mesh,
                            cfg.n_buckets or G.default_buckets(cfg.r), 22))
    return g


# -------------------------------------------------------------- NN-Descent
@functools.partial(jax.jit, static_argnames=("cfg", "mesh"))
def nn_join_and_update(x, g: G.Graph, cfg, mesh: Mesh) -> G.Graph:
    """Sharded NN-Descent iteration — nn_descent.join_and_update with rows
    partitioned over the mesh (bitwise-identical result)."""
    from repro.core import nn_descent as nnd

    n, m = g.neighbors.shape
    j = min(cfg.sample or m, m)
    d = n_shards(mesh)
    n_pad = _padded(n, d)
    nb = nnd.default_join_buckets(cfg, m)
    axes = row_axes(mesh)

    def shard_fn(xx, gl):
        src, dst, dist = nnd.join_candidates(
            xx, gl.neighbors[:, :j], gl.flags[:, :j], cfg)
        aged = G.Graph(gl.neighbors, gl.dists, jnp.zeros_like(gl.flags))
        return _merge_candidates_shard(
            aged, src, dst, dist, n_pad, cfg.k, nb, axes, d)

    gs = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P(), _graph_specs(mesh)),
                       out_specs=_graph_specs(mesh),
                       check_vma=False)(x, pad_rows(g, n_pad))
    return G.Graph(gs.neighbors[:n], gs.dists[:n], gs.flags[:n])


def build_nn_descent(x, cfg, key, mesh: Mesh) -> G.Graph:
    from repro.core import nn_descent as nnd
    from repro.obs import trace as _tr

    _check_mesh(mesh, cfg.merge)
    g = nnd.random_init(key, x, cfg)
    prev_live = None
    for it in range(cfg.iters):
        with _tr.span("nn_descent/iter") as sp:
            g = nn_join_and_update(x, g, cfg, mesh)
            if sp:
                from repro.obs import graphstats as _gs
                g = jax.block_until_ready(g)
                prev_live = _gs.record_sweep(
                    sp, g, algo="nn_descent", phase="sweep",
                    prev_live=prev_live, iter=it,
                    **_exchange_attrs(
                        x.shape[0], mesh,
                        nnd.default_join_buckets(cfg, g.neighbors.shape[1]),
                        9))
    return g


# ---------------------------------------------------------------- NSG-style
@functools.partial(jax.jit, static_argnames=("cfg", "mesh"))
def _nsg_expand_cap(x, knn: G.Graph, cfg, mesh: Mesh) -> G.Graph:
    """Sharded NSG candidate expansion + RNG prune + out-degree cap. The knn
    graph is replicated (2-hop pools read arbitrary rows); base rows shard."""
    from repro.core import nsg_style

    n = x.shape[0]
    d = n_shards(mesh)
    n_pad = _padded(n, d)
    rows = jnp.arange(n_pad, dtype=jnp.int32)
    rows = jnp.where(rows < n, rows, -1)  # padded base rows expand to empty

    def shard_fn(xx, gf, rloc):
        cand_ids, cand_d = nsg_style.expand_candidates(
            xx, gf, cfg.c, cfg.metric, cfg.chunk, rows=rloc)
        return nsg_style.rng_cap_rows(xx, cand_ids, cand_d, cfg)

    rep = G.Graph(P(), P(), P())
    gs = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P(), rep, _row1_pspec(mesh)),
                       out_specs=_graph_specs(mesh),
                       check_vma=False)(x, knn, rows)
    return G.Graph(gs.neighbors[:n], gs.dists[:n], gs.flags[:n])


def build_nsg_style(x, cfg, key, mesh: Mesh, entry=None) -> G.Graph:
    """Sharded NSG-style refinement (nsg_style.build(mesh=...) entry point).

    The knn stage and both per-row refinement stages run row-sharded; the
    final connectivity repair (ensure_reachable) runs *replicated* — it is a
    one-shot whole-graph BFS on the sort-oracle merge path with no
    shard-local form, and it is not on the construction critical path. The
    graph is pulled to host once so the repair is literally the single-device
    computation (bitwise parity preserved)."""
    from repro.core import nsg_style
    from repro.obs import trace as _tr

    _check_mesh(mesh, cfg.merge)
    if cfg.knn.merge != "bucketed":
        raise ValueError(
            f"sharded nsg-style requires knn.merge='bucketed', got "
            f"{cfg.knn.merge!r}")
    with _tr.span("nsg_style/knn") as sp:
        knn = build_nn_descent(x, cfg.knn, key, mesh)
        if sp:
            jax.block_until_ready(knn)
    with _tr.span("nsg_style/prune") as sp:
        capped = _nsg_expand_cap(x, knn, cfg, mesh)
        if sp:
            from repro.obs import graphstats as _gs
            jax.block_until_ready(capped)
            _gs.record_sweep(sp, capped, algo="nsg_style", phase="sweep")
    with _tr.span("nsg_style/reverse") as sp:
        g = add_reverse_edges(capped, cfg.r, mesh, cfg.n_buckets)
        if sp:
            from repro.obs import graphstats as _gs
            jax.block_until_ready(g)
            _gs.record_sweep(
                sp, g, algo="nsg_style", phase="reverse",
                **_exchange_attrs(
                    x.shape[0], mesh,
                    cfg.n_buckets or G.default_buckets(cfg.r), 22))
    # replicated connectivity repair: host round-trip pins the compute to the
    # default device so it is the exact single-device code path
    with _tr.span("nsg_style/repair") as sp:
        g = G.Graph(*(jnp.asarray(np.asarray(a)) for a in g))
        x_rep = jnp.asarray(np.asarray(x))
        if entry is None:
            from repro.core.search import default_entry_point
            entry = default_entry_point(x_rep, cfg.metric)
        g = nsg_style.ensure_reachable(x_rep, g, entry, cfg.metric)
        if sp:
            jax.block_until_ready(g)
    return g
