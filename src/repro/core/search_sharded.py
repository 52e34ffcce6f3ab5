"""Corpus-sharded serving: beam search over a row-partitioned index.

``search_tiled(..., shard="queries")`` replicates the corpus and graph on
every device and divides the query stream — throughput parallelism that
pays full corpus memory per device (``n * d * 4`` bytes plus the adjacency)
and therefore cannot serve a corpus larger than one device. This module is
the other axis: ``x``, the adjacency rows, and ``qx`` codes partition
across the mesh's "rows" axis (blocks of ``n_pad / D`` rows per device), so
per-device corpus memory drops to ~``n/D`` while the *queries* stream
through in super-tiles of ``D * tile_b`` lanes — device s owns lanes
``[s*tile_b, (s+1)*tile_b)`` of each super-tile and their whole beam state
(beam, visited table, retirement), which stays lane-local and identical to
the single-device loop.

Owner-contribute collectives
----------------------------
Only the three corpus-touching sites of the beam loop cross the wire, all
via :class:`repro.core.search.ScoreHooks`:

1. **Frontier adjacency**: each lane's frontier vertex ``u`` is
   ``all_gather``-ed (D * tile_b int32 per step); the device owning row
   ``u`` contributes ``neighbors[u][:k]``, everyone else INT32_MAX, and a
   ``pmin`` reconstructs the exact adjacency slice on every device.
2. **Scoring** (seeds, beam candidates, rerank tail): every device scores
   all lanes' candidates against its *own* row block — per lane-block j the
   gather+score shapes are (tile_b, K, d), identical to the single-device
   tile, so the arithmetic is the exact op sequence of the jnp oracle —
   and contributes ``dist_key(d)`` for rows it owns (the key sentinel
   elsewhere). An ``all_to_all`` reduce-scatter-min hands each device its
   own lanes' keys; ``key_dist`` is a bitwise-exact decode (the key map is
   a bijection on all float bits), so candidate distances equal the
   single-device values bit for bit.
3. **Termination**: the while condition must be uniform across devices, so
   the per-device "any lane active" bit is psum-combined in the loop body
   and carried in state. Retired lanes are exact fixed points of the beam
   body, so lanes that finish early are unaffected by the extra uniform
   iterations.

Per-lane trajectories therefore depend only on lane-local state plus
bitwise-reconstructed gathers — corpus-sharded results (ids and uint32 dist
bits) equal single-device across visited modes and quant modes, asserted in
tests/test_sharded_parity.py at 8 virtual devices.

Tile prefetch: the super-tile loop is a ``lax.scan`` whose carry holds the
current tile's pre-gathered queries and entry points; each step issues the
*next* tile's ``all_gather`` before running the beam loop, so the exchange
for tile t+1 overlaps the scoring of tile t.

``use_pallas`` falls back to the jnp scoring path here (the fused kernels
are bitwise-equal to it, so parity against a single-device pallas run still
holds); the win of this mode is memory capacity, not per-device FLOPs —
each device scores all D * tile_b lanes and masks to its own rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import graph as G
from repro.kernels.beam_score import score_block
from repro.quant import QuantizedCorpus, int8_score_block, pq_lut, \
    pq_score_codes

_I32_MAX = jnp.iinfo(jnp.int32).max


def corpus_placement_bytes(n: int, d: int, capacity: int, n_dev: int,
                           qmode: str | None = None, m_pq: int = 0) -> dict:
    """Per-device resident bytes for the two serving placements.

    Returns {"replicated": .., "sharded": ..} counting the corpus payload
    plus the adjacency (3 fields: int32 ids, f32 dists, uint8 flags) — the
    numbers BENCH_search.json records next to sharded QPS so "replicated
    and slow" can never masquerade as "sharded and slow" again."""
    if qmode == "int8":
        row = d                      # one int8 code per dim
    elif qmode == "pq":
        row = m_pq                   # m uint8 subspace codes
    else:
        row = d * 4                  # f32
    per_row = row + capacity * (4 + 4 + 1)
    n_blk = -(-n // n_dev)
    return {"replicated": n * per_row, "sharded": n_blk * per_row}


def search_tiled_corpus(x, g, queries, eps, cfg, tile_b, mesh,
                        valid=None, qx: QuantizedCorpus | None = None,
                        with_stats: bool = False,
                        lane_valid=None):
    """Row-sharded ``search_tiled`` body (call through ``search_tiled(...,
    shard="corpus")``; ``eps`` arrives validated to (B, E)). ``lane_valid``:
    optional (B,) bool — False lanes retire at iteration 0 (the serving
    fixed-tile seam, same contract as the queries-shard path)."""
    from repro.core import search as S
    from repro.core import shard as SHD

    axes = SHD.row_axes(mesh)
    n_dev = SHD.n_shards(mesh)
    if len(axes) != 1:
        raise ValueError(
            f"shard=\"corpus\" needs the logical \"rows\" axis on exactly one "
            f"physical mesh axis (got {axes!r} from mesh axes "
            f"{mesh.axis_names}): the owner-contribute collectives address a "
            "single ring")
    ax = axes[0]
    n = x.shape[0]
    b = queries.shape[0]
    mcap = g.neighbors.shape[1]
    qmode = cfg.quant.mode if cfg.quant.is_coded else None
    if qmode and qx is None:
        raise ValueError(
            f"cfg.quant selects mode {qmode!r} but no quantized corpus was "
            "passed (qx=) — encode with repro.quant.encode_corpus")
    if b == 0:
        out = (jnp.zeros((0, cfg.topk), jnp.int32), jnp.zeros((0, cfg.topk)))
        if with_stats:
            return out + ({"work": jnp.int32(0), "visited_lost": jnp.int32(0),
                           "launched": jnp.int32(0), "tiles": 0,
                           "tile_lanes": 0},)
        return out

    # lanes: super-tiles of n_dev * tile_b queries, device s owning block s.
    # The per-device lane count is floored at 2: XLA:CPU lowers batch-1
    # score einsums with different rounding than batch>=2, so 1-lane blocks
    # are reserved for the cases where the single-device reference also
    # scores batch 1 (b=1 or tile_b=1) and the shapes agree anyway
    tile_b = max(1, min(tile_b, b, max(2, -(-b // n_dev))))
    ba = tile_b * n_dev
    pad = (-b) % ba
    q_p = jnp.pad(queries, ((0, pad), (0, 0)))
    eps_p = jnp.concatenate(
        [eps, jnp.broadcast_to(eps[:1], (pad, eps.shape[1]))]) if pad else eps
    q_tiles = q_p.reshape(-1, ba, queries.shape[1])
    ep_tiles = eps_p.reshape(-1, ba, eps.shape[1])
    lv = jnp.arange(q_p.shape[0]) < b
    if lane_valid is not None:
        lv = lv & jnp.pad(jnp.asarray(lane_valid, bool), (0, pad))
    lv_tiles = lv.reshape(-1, ba)
    t_count = q_tiles.shape[0]

    # rows: pad to a multiple of the shard count; padded rows are zero
    # vectors with empty adjacency — unreachable (no in-edges, ids >= n
    # never emitted) and never seeded (entry wrap/clamp stays below n)
    n_pad = -(-n // n_dev) * n_dev
    n_blk = n_pad // n_dev
    x_pad = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    nb_pad = jnp.pad(g.neighbors, ((0, n_pad - n), (0, 0)),
                     constant_values=-1)
    k = min(cfg.k, g.capacity)

    row2 = P(ax, None)
    lane3 = P(None, ax, None)
    lane2 = P(None, ax)
    operands: list = [x_pad, nb_pad]
    specs: list = [row2, row2]
    has_valid = valid is not None
    if has_valid:
        operands.append(valid)
        specs.append(P())
    if qmode:
        codes_pad = jnp.pad(
            qx.codes, ((0, n_pad - n),) + ((0, 0),) * (qx.codes.ndim - 1))
        operands.append(codes_pad)
        specs.append(P(ax, *([None] * (qx.codes.ndim - 1))))
        if qmode == "int8":
            operands += [qx.scale, qx.zero]
            specs += [P(), P()]
        else:
            operands.append(qx.codebooks)
            specs.append(P())
    operands += [q_tiles, ep_tiles, lv_tiles]
    specs += [lane3, lane3, lane2]

    def shard_fn(x_loc, nb_loc, *rest):
        i = 0
        vv = rest[i] if has_valid else None
        i += has_valid
        codes_loc = scale = zero = codebooks = None
        if qmode == "int8":
            codes_loc, scale, zero = rest[i:i + 3]
            i += 3
        elif qmode == "pq":
            codes_loc, codebooks = rest[i:i + 2]
            i += 2
        qt, et, lt = rest[i], rest[i + 1], rest[i + 2]
        me = jax.lax.axis_index(ax)
        lo = me * n_blk
        # the bf16-gram path converts the corpus *before* the gather
        # (beam_score_ref op order); seeds always read f32
        x_gram = x_loc.astype(jnp.bfloat16) \
            if qmode is None and cfg.effective_gram_dtype == "bf16" else x_loc

        def owned(ids):
            """maximum(ids, 0) ownership + block-local gather rows — the
            single-device clamp semantics of x[maximum(ids, 0)]."""
            eff = jnp.maximum(ids, 0)
            own = (eff >= lo) & (eff < lo + n_blk)
            return jnp.clip(eff - lo, 0, n_blk - 1), own

        def reduce_keys(keys):
            """(D, tile_b, W) per-destination key blocks -> this device's
            lanes' combined keys, decoded. all_to_all transposes so block s
            of the result is what device s computed for *my* lanes; the min
            picks the one non-sentinel owner. key_dist(dist_key(d)) is the
            identity on every bit pattern, so this reconstructs the exact
            single-device distances."""
            got = jax.lax.all_to_all(jnp.stack(keys), ax,
                                     split_axis=0, concat_axis=0,
                                     tiled=False)
            return G.key_dist(jnp.min(got, axis=0))

        def beam_tile(q_all, ep_all, q_loc, ep_loc, lv_loc):
            qb = [jax.lax.dynamic_slice_in_dim(q_all, j * tile_b, tile_b, 0)
                  for j in range(n_dev)]
            if qmode == "pq":
                # one query-to-centroid LUT per lane block, shaped exactly
                # like the single-device per-tile LUT
                luts = [pq_lut(qb[j], codebooks, cfg.metric)
                        for j in range(n_dev)]

            def score_rows(loc, j, seed):
                if qmode == "int8":
                    return int8_score_block(codes_loc[loc], scale, zero,
                                            qb[j], cfg.metric)
                if qmode == "pq":
                    la, lb, qs = luts[j]
                    return pq_score_codes(codes_loc[loc], la, lb, qs,
                                          cfg.metric)
                return score_block((x_loc if seed else x_gram)[loc], qb[j],
                                   cfg.metric)

            def seed_hook(_eps_loc):
                # seeds use jnp wrap-then-clamp indexing semantics (x[eps])
                keys = []
                for j in range(n_dev):
                    epj = jax.lax.dynamic_slice_in_dim(
                        ep_all, j * tile_b, tile_b, 0)
                    eff = jnp.clip(jnp.where(epj < 0, epj + n, epj), 0, n - 1)
                    own = (eff >= lo) & (eff < lo + n_blk)
                    d = score_rows(jnp.clip(eff - lo, 0, n_blk - 1), j,
                                   seed=True)
                    keys.append(jnp.where(own, G.dist_key(d),
                                          G._KEY_SENTINEL))
                return reduce_keys(keys)

            def beam_hook(u):
                u_all = jax.lax.all_gather(u, ax, tiled=True)      # (BA,)
                uloc, uown = owned(u_all)
                contrib = jnp.where(uown[:, None], nb_loc[uloc][:, :k],
                                    _I32_MAX)
                nbrs_all = jax.lax.pmin(contrib, ax)               # (BA, k)
                keys = []
                for j in range(n_dev):
                    nbj = jax.lax.dynamic_slice_in_dim(
                        nbrs_all, j * tile_b, tile_b, 0)
                    loc, own = owned(nbj)
                    d = score_rows(loc, j, seed=False)
                    d = jnp.where(nbj >= 0, d, jnp.inf)
                    keys.append(jnp.where(own, G.dist_key(d),
                                          G._KEY_SENTINEL))
                cand_d = reduce_keys(keys)                         # (tile_b, k)
                nbrs = jax.lax.dynamic_slice_in_dim(
                    nbrs_all, me * tile_b, tile_b, 0)
                return nbrs, cand_d

            def rerank_hook(rids):
                r_all = jax.lax.all_gather(rids, ax, tiled=True)   # (BA, R)
                keys = []
                for j in range(n_dev):
                    rj = jax.lax.dynamic_slice_in_dim(
                        r_all, j * tile_b, tile_b, 0)
                    loc, own = owned(rj)
                    # exact-f32 rerank: always the uncompressed rows
                    d = score_block(x_loc[loc], qb[j], cfg.metric)
                    keys.append(jnp.where(own, G.dist_key(d),
                                          G._KEY_SENTINEL))
                return reduce_keys(keys)

            def any_hook(mask):
                return jax.lax.psum(jnp.any(mask).astype(jnp.int32), ax) > 0

            hooks = S.ScoreHooks(n=n, capacity=mcap, seed=seed_hook,
                                 beam=beam_hook, rerank=rerank_hook,
                                 any_active=any_hook)
            return S._search_impl(None, None, q_loc, ep_loc, cfg, valid=vv,
                                  lane_valid=lv_loc, hooks=hooks)

        def gather_tile(i):
            return (jax.lax.all_gather(qt[i], ax, tiled=True),
                    jax.lax.all_gather(et[i], ax, tiled=True))

        def step(carry, i):
            q_all, ep_all = carry
            # issue tile i+1's gather before tile i's beam loop runs: the
            # exchange overlaps the scoring (the last step re-gathers its
            # own tile — a no-op-sized redundancy)
            nxt = gather_tile(jnp.minimum(i + 1, t_count - 1))
            out = beam_tile(q_all, ep_all, qt[i], et[i], lt[i])
            return nxt, out

        _, outs = jax.lax.scan(step, gather_tile(0),
                               jnp.arange(t_count))
        return outs   # ids (T, tile_b, topk), dists, work, lost (T, tile_b), (T,)

    ids, dists, lane_work, lane_lost, tile_iters = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=tuple(specs),
        out_specs=(lane3, lane3, lane2, lane2, P()),
        check_vma=False,
    )(*operands)
    out = (ids.reshape(-1, cfg.topk)[:b], dists.reshape(-1, cfg.topk)[:b])
    if not with_stats:
        return out
    stats = {
        "work": jnp.sum(lane_work.reshape(-1)[:b]),
        "visited_lost": jnp.sum(lane_lost.reshape(-1)[:b]),
        "launched": jnp.sum(tile_iters) * ba,
        "tiles": t_count,
        "tile_lanes": ba,
    }
    return out + (stats,)
