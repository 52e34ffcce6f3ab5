"""Graph traversal search (paper Algorithm 1 + Eq. 4), batched over queries.

Best-first beam search with beam width L. RNN-Descent does not limit the
out-degree at build time; instead Eq. 4 truncates each visited vertex's
adjacency to its K nearest *at query time* (rows are distance-sorted, so this
is a prefix slice — zero-cost on TPU).

TPU adaptation: the paper's while-loop with dynamic candidate set becomes a
``lax.while_loop`` over fixed-shape state: a (B, L) beam (ids/dists/expanded)
plus per-query visited bookkeeping for dedup.

Visited-state memory
--------------------
Two interchangeable visited implementations, selected by
``SearchConfig.visited``:

``"dense"``  — the exact oracle: a (B, n+1) boolean bitmask (one scratch
    column for masked writes). Memory is ``B * (n + 1)`` bytes and grows with
    the corpus: at n = 1M and B = 1024 the bitmask alone is ~1 GB, which is
    what kept the old implementation out of the paper's million-scale regime.

``"hashed"`` — the production default: a per-query open-addressed hash table
    of ``slots`` int32 entries (``slots`` a power of two sized from L,
    max_iters and K — see :func:`resolve_slots`). Memory is
    ``B * slots * 4`` bytes, **independent of n**: the default config (L=64,
    K=32, max_iters=256) resolves to 32768 slots = 128 KiB per lane, so a
    256-lane tile carries 32 MiB of visited state no matter whether the
    corpus holds 10^4 or 10^9 vectors.

    Layout: a lane's slots are ``slots / W`` bucket rows of ``W = min(128,
    slots)`` entries, and a tile's table is one 2-D ``(B * slots / W, W)``
    array, so one bucket row lines up with the TPU's 128-lane minor
    dimension (bytes unchanged). A multiplicative hash of the id picks the
    bucket, ``h mod (slots / W)``, and a first slot inside it; the
    ``probes`` linear probes wrap inside that bucket's row. A lookup gathers
    one row per candidate and tests the probe window with element-wise masks
    over the whole row; an insert writes the candidate's whole row back with
    the id at its first empty window slot. Each step is one row gather and
    one row scatter on the table, which stays in place: it is never
    reshaped to (B, slots), and no single element is gathered.

The hash table stores only genuinely visited vertex ids, so membership tests
have **no false positives** — a candidate is never wrongly skipped. An insert
is lost in two ways only: every slot of the id's probe window is full, or two
fresh candidates of one lane land in one bucket in the same step, and the
later row write wins. Both rows are built from the same old row, so nothing
inserted earlier is ever erased. ``search_tiled(with_stats=True)`` counts the
lost inserts as ``visited_lost``. A lost insert can only yield a false
*negative*: the vertex may be re-scored. Because the beam's worst distance is
monotonically non-increasing, a re-scored vertex can never re-enter the beam
with a strictly better rank, and an explicit candidate-vs-beam dedup keeps
the beam duplicate-free — so hashed search converges to the *same* result as
the dense oracle, with the same beam expansions. Trust ``"hashed"`` for
serving; use ``"dense"`` as the exact reference in tests and when measuring
the approximation (equal results at equal L is asserted in
``tests/test_search.py``).

Termination is per lane: a lane retires once no unexpanded candidate could
beat its worst beam entry — with the merged beam/candidate representation
that is the moment its frontier is exhausted (worse candidates were already
evicted at merge, which is where the classic "best candidate > worst result"
cutoff is realized). A retired lane stops mutating state, and in
:func:`search_tiled` a tile whose lanes have all retired exits its loop
immediately instead of spinning to whole-batch quiescence.

For arbitrary query counts, :func:`search_tiled` streams B_tile-sized query
tiles through ``lax.map`` so peak memory is O(B_tile * slots) regardless of
the total batch size.

Beam inner loop
---------------
The hot step of every iteration — gather each lane's frontier adjacency row,
gather the neighbor vectors, score them against the query — is served by two
interchangeable implementations selected by ``SearchConfig.use_pallas``
(mirroring the builders' ``merge=`` and the visited-table duality):

``use_pallas=False`` — the pure-jnp oracle
    (:func:`repro.kernels.beam_score.beam_score_ref`): XLA row gathers plus a
    batched einsum. Exact reference; also the right path when the corpus
    exceeds the kernel's VMEM budget.

``use_pallas=True`` — the fused Pallas gather+score kernel
    (:mod:`repro.kernels.beam_score`): both gathers and the scoring happen in
    one kernel pass, so the (B, K, d) gathered candidate block never
    round-trips through HBM between gather and distance evaluation. Both
    paths share one scoring function, so fused results are *bitwise* equal to
    the oracle (asserted in tests/test_beam_score.py). Interpret mode follows
    ``kernels.default_interpret()`` (on CPU the kernel runs interpreted).

``SearchConfig.gram_dtype="bf16"`` gathers neighbor vectors in bfloat16
(the rng_prune convention — halves gather traffic, f32 accumulation);
``SearchConfig.kernel_tile_b`` sizes the kernel's lane tile.

Each iteration's phases run under ``jax.named_scope`` names —
``beam.select`` (frontier pick, retirement), ``beam.score`` (gather +
score), ``beam.visited`` (dedup + visited table), ``beam.topk`` (beam
merge), and in a filtered search ``beam.filter`` (label gather, predicate,
result-list merge) — which the compiled ops carry as metadata into a device
trace; a scope adds no op, so results are unchanged.

Filtered search
---------------
``labels=`` gives each corpus row one ``uint32`` label word (a bitset of up
to 32 labels) and ``filters=`` each query a ``uint32`` mask; a row passes
its query when ``(label & mask) == mask``, so a mask of several bits is a
conjunction. The predicate is checked inside the loop, hnswlib-style
(:func:`_filtered_search_impl`): a candidate list of the L nearest
unexpanded vertices drives the expansion, passing or not (non-passing
vertices still connect the graph), and an expanded vertex leaves it; a
result list keeps the L nearest passing vertices. A lane retires when its
result list is full and its best candidate lies beyond the worst result,
when its candidates run out, or at ``max_iters``. With ``filters=None`` the
traced program is the unfiltered one above (a static branch: no label
gather, no second list). Because expanded vertices leave the candidate
list, a vertex scored before may lie nearer than every listed one: the
visited table is what keeps it from being expanded again.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import distances as D
from repro.core import graph as G
from repro.kernels.beam_score import (
    beam_score,
    beam_score_int8,
    beam_score_int8_ref,
    beam_score_pq,
    beam_score_pq_ref,
    beam_score_ref,
    score_block,
)
from repro.quant import (
    Quantization,
    QuantizedCorpus,
    int8_score_block,
    pq_lut,
    pq_score_codes,
)

METRICS = ("l2", "ip", "cos")
GRAM_DTYPES = ("f32", "bf16")


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    l: int = 64              # beam width (paper's L)
    k: int = 32              # query-time out-degree limit (paper Eq. 4); <= capacity
    max_iters: int = 256     # hard bound on expansions (paper loops to quiescence)
    metric: str = "l2"
    topk: int = 1            # results returned per query
    visited: str = "hashed"  # "hashed" (O(slots), n-independent) | "dense" (exact oracle)
    slots: int | None = None  # hashed table size (power of two); None -> resolve_slots
    probes: int = 8          # linear-probe attempts per hashed lookup/insert
    use_pallas: bool = False  # fused Pallas gather+score kernel for the beam inner loop
    gram_dtype: str = "f32"  # neighbor-gather dtype: "f32" | "bf16" (rng_prune convention)
    kernel_tile_b: int = 64  # fused-kernel lane tile (VMEM ~ tile * k * d * 4 B)
    quant: Quantization = Quantization()  # corpus representation: f32/bf16/int8/pq

    def __post_init__(self):
        # config-time validation: a bad metric/gram_dtype used to surface only
        # as a cryptic trace-time error deep inside the distance kernels (and,
        # with use_pallas, inside the Pallas call) — reject it here instead.
        if self.metric not in METRICS:
            raise ValueError(
                f"unknown metric {self.metric!r}: expected one of {METRICS}")
        if self.gram_dtype not in GRAM_DTYPES:
            raise ValueError(
                f"unknown gram_dtype {self.gram_dtype!r}: expected one of "
                f"{GRAM_DTYPES} (bf16 = gather neighbor vectors in bfloat16, "
                "f32 accumulation)")
        if self.kernel_tile_b < 1:
            raise ValueError(
                f"kernel_tile_b must be >= 1, got {self.kernel_tile_b}")
        if min(self.l, self.k, self.max_iters, self.topk) < 1:
            raise ValueError(
                "l, k, max_iters and topk must all be >= 1: got "
                f"l={self.l}, k={self.k}, max_iters={self.max_iters}, "
                f"topk={self.topk}")
        if self.topk > self.l:
            raise ValueError(
                f"topk={self.topk} cannot exceed the beam width l={self.l}")
        if self.visited not in ("hashed", "dense"):
            raise ValueError(
                f"unknown visited mode {self.visited!r}: expected \"hashed\" "
                "(O(slots) table, n-independent) or \"dense\" (exact oracle "
                "bitmask)")
        if self.probes < 1:
            raise ValueError(f"probes must be >= 1, got {self.probes}")
        if self.slots is not None and (
                self.slots < 8 or (self.slots & (self.slots - 1)) != 0):
            raise ValueError(
                f"slots must be a power of two >= 8, got {self.slots}")
        if not isinstance(self.quant, Quantization):
            raise ValueError(
                f"quant must be a repro.quant.Quantization, got "
                f"{type(self.quant).__name__}")
        if self.quant.is_coded:
            if self.gram_dtype == "bf16":
                raise ValueError(
                    f"quant.mode={self.quant.mode!r} conflicts with "
                    "gram_dtype=\"bf16\": the coded paths gather codes, not "
                    "vectors — pick one compression (use quant.mode=\"bf16\" "
                    "for half-width gathers)")
            if 0 < self.quant.rerank_k < self.topk:
                raise ValueError(
                    f"quant.rerank_k={self.quant.rerank_k} is smaller than "
                    f"topk={self.topk}: the exact-f32 rerank tail must cover "
                    "at least the returned results (or be 0 to disable)")

    @property
    def effective_gram_dtype(self) -> str:
        """The gather dtype the beam step actually uses: ``quant.mode=
        "bf16"`` routes through the pre-existing bf16-gather path, so one
        ``quant=`` field selects every corpus representation."""
        return "bf16" if self.quant.mode == "bf16" else self.gram_dtype


def _next_pow2(v: int) -> int:
    return 1 << max(3, (v - 1).bit_length())


def resolve_slots(cfg: SearchConfig, n_entry: int = 1) -> int:
    """Hashed-table size: every visited vertex was either a seed or one of the
    <= K neighbors of one of the <= max_iters expansions, so 2x that bound
    keeps the load factor under 0.5 (open addressing stays near O(1))."""
    if cfg.slots is not None:
        return cfg.slots
    return _next_pow2(2 * (cfg.l + n_entry + cfg.max_iters * cfg.k))


def visited_state_bytes(cfg: SearchConfig, n: int, lanes: int, n_entry: int = 1) -> int:
    """Peak visited-state bytes for ``lanes`` concurrent queries over a corpus
    of ``n`` vectors. Dense scales with n; hashed does not."""
    if cfg.visited == "dense":
        return lanes * (n + 1)  # bool bitmask, one byte per element
    return lanes * resolve_slots(cfg, n_entry) * 4


# --------------------------------------------------------------- visited table
def _bucket_width(slots: int) -> int:
    """Slots per bucket row of the hashed table: one row spans the chip's
    128-lane minor dimension (the whole table when it is smaller)."""
    return min(128, slots)


def _probe_buckets(ids: jnp.ndarray, slots: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(..., C) ids -> ((..., C) bucket, (..., C) offset): a Knuth
    multiplicative hash + bit mix picks the bucket, ``h mod (slots / W)``,
    and the next bits the first probed slot inside it (``slots`` a power of
    two)."""
    w = _bucket_width(slots)
    nb = slots // w
    h = ids.astype(jnp.uint32) * jnp.uint32(2654435761)
    h = h ^ (h >> jnp.uint32(16))
    bucket = h & jnp.uint32(nb - 1)
    offset = (h >> jnp.uint32(nb.bit_length() - 1)) & jnp.uint32(w - 1)
    return bucket.astype(jnp.int32), offset.astype(jnp.int32)


def _visited_lookup_insert(
    table: jnp.ndarray, ids: jnp.ndarray, want: jnp.ndarray,
    rows: jnp.ndarray, probes: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Membership test + insert for a (B, C) id batch against the
    (B * slots / W, W) bucket-row table.

    Returns (seen, new_table, lost): ``lost`` (B,) counts the ``want``
    candidates not yet seen whose insert was dropped — a full probe window,
    or a later candidate of the lane writing the same bucket row in this
    step. No false positives ever; a lost insert only makes the vertex
    eligible for re-scoring."""
    b = rows.shape[0]
    nb, w = table.shape[0] // b, table.shape[1]
    bucket, offset = _probe_buckets(ids, nb * w)
    row = rows[:, None] * nb + bucket                             # (B, C)
    vals = table[row]                                             # (B, C, W)
    # probe window: the `probes` slots from `offset` on, wrapping in the row
    rank = (jnp.arange(w, dtype=jnp.int32) - offset[..., None]) & (w - 1)
    window = rank < probes
    seen = jnp.any(window & (vals == ids[..., None]), axis=-1)    # (B, C)
    first = jnp.min(jnp.where(window & (vals == -1), rank, w), axis=-1)
    wanted = want & ~seen
    do_ins = wanted & (first < w)
    # each insert writes its whole bucket row back (old row + its id), so
    # two inserts into one bucket in one step keep every earlier entry and
    # one of the two ids: the later candidate's row is counted as the one kept
    c = ids.shape[1]
    later = jnp.arange(c)[None, :] > jnp.arange(c)[:, None]       # (C, C)
    overwritten = jnp.any(
        later & do_ins[:, None, :] & (row[:, :, None] == row[:, None, :]),
        axis=-1)
    lost = jnp.sum(wanted & (~do_ins | overwritten), axis=-1, dtype=jnp.int32)
    new_rows = jnp.where(rank == first[..., None], ids[..., None], vals)
    tgt = jnp.where(do_ins, row, table.shape[0])                  # OOB -> dropped
    table = table.at[tgt].set(new_rows, mode="drop")
    return seen, table, lost


def _seed_visited(eps, dup, rows, n: int, slots: int, probes: int,
                  dense: bool):
    """(visited, lost): the visited state of a tile with its seeds marked."""
    b = rows.shape[0]
    if dense:
        visited = jnp.zeros((b, n + 1), bool)
        visited = visited.at[rows[:, None], jnp.where(dup, n, eps)].set(True)
        return visited, jnp.zeros((b,), jnp.int32)
    w = _bucket_width(slots)
    visited = jnp.full((b * slots // w, w), -1, jnp.int32)
    _, visited, lost = _visited_lookup_insert(visited, eps, ~dup, rows, probes)
    return visited, lost


def _visit(visited, nbrs, cand_ok, listed, rows, n: int, probes: int,
           dense: bool, lost):
    """One ``beam.visited`` step over a (B, C) block of scored neighbours:
    (fresh, visited, lost), ``fresh`` marking the candidates not seen
    before. Dense: the exact bitmask. Hashed: the table, backed by an exact
    dedup against the (B, L) id lists in ``listed`` — a lost insertion can
    cost a re-score, never a duplicate in a list."""
    if dense:
        seen = visited[rows[:, None], jnp.maximum(nbrs, 0)]
        fresh = cand_ok & ~seen
        ins_idx = jnp.where(fresh, nbrs, n)                   # n = scratch slot
        visited = visited.at[rows[:, None], ins_idx].set(True)
        return fresh, visited, lost
    in_list = functools.reduce(jnp.logical_or, [
        jnp.any(nbrs[:, :, None] == ids[:, None, :], axis=-1)
        for ids in listed])
    seen, visited, lost_now = _visited_lookup_insert(
        visited, nbrs, cand_ok & ~in_list, rows, probes)
    lost = lost + lost_now
    return cand_ok & ~seen & ~in_list, visited, lost


def _seed_dups(eps):
    """(B, E) bool: a seed repeating an earlier seed of its lane (inert)."""
    e = eps.shape[1]
    return jnp.any(
        (eps[:, :, None] == eps[:, None, :])
        & (jnp.arange(e)[None, :, None] > jnp.arange(e)[None, None, :]),
        axis=-1,
    )


# ------------------------------------------------------------ entry validation
def _validate_entry_points(entry_points, b: int, l: int) -> jnp.ndarray:
    """Normalize ``entry_points`` to (B, E) int32.

    Accepted: scalar (broadcast to every query), (B,) one seed per query,
    (B, E) multi-entry seeding with E <= L. Anything else raises — the old
    behaviour of silently truncating a wrong-length array to its first
    element is gone."""
    eps = jnp.asarray(entry_points)
    if eps.ndim == 0:
        return jnp.broadcast_to(eps.astype(jnp.int32).reshape(1, 1), (b, 1))
    if eps.ndim == 1:
        if eps.shape[0] != b:
            raise ValueError(
                f"entry_points has shape {eps.shape} but the query batch is {b}; "
                "pass a scalar to broadcast, (B,) for one seed per query, or "
                "(B, E) for multi-entry seeding")
        return eps.astype(jnp.int32)[:, None]
    if eps.ndim == 2:
        if eps.shape[0] != b:
            raise ValueError(
                f"entry_points batch dim {eps.shape[0]} != query batch {b}")
        if eps.shape[1] > l:
            raise ValueError(
                f"{eps.shape[1]} entry points exceed the beam width L={l}")
        return eps.astype(jnp.int32)
    raise ValueError(f"entry_points must be scalar, (B,) or (B, E); got ndim={eps.ndim}")


# -------------------------------------------------------------------- core
class ScoreHooks:
    """Pluggable scoring backend for :func:`_search_impl`.

    The corpus-sharded serving path (core/search_sharded.py) reuses the
    beam body — seeding, visited dedup, merge, retirement, rerank — and
    swaps only the places that touch corpus-sized state for
    owner-contribute collectives. Every hook must return values *bitwise
    equal* to the single-device computation it replaces; that is the whole
    parity argument for ``shard="corpus"``.

    ``n``/``capacity`` replace ``x.shape[0]``/``g.capacity`` (x and g are
    row-sharded, so their local shapes lie about the corpus); ``seed``,
    ``beam`` and ``rerank`` replace the three scoring sites; ``any_active``
    replaces ``jnp.any`` in the termination flag — under ``shard_map`` the
    while condition must be uniform across devices, so the corpus path
    psums it."""

    def __init__(self, n, capacity, seed, beam, rerank, any_active):
        self.n = n                  # global corpus size
        self.capacity = capacity    # global graph capacity (row width)
        self.seed = seed            # (B, E) eps -> (B, E) f32 seed distances
        self.beam = beam            # (B,) u -> ((B, K) nbrs, (B, K) cand_d)
        self.rerank = rerank        # (B, R) rids -> (B, R) exact f32
        self.any_active = any_active  # (B,) bool -> scalar bool (global)


def _search_impl(
    x: jnp.ndarray,
    g: G.Graph,
    queries: jnp.ndarray,
    eps: jnp.ndarray,            # (B, E) validated
    cfg: SearchConfig,
    valid: jnp.ndarray | None = None,   # (n,) bool — see tombstone note below
    qx: QuantizedCorpus | None = None,  # codes when cfg.quant is int8/pq
    lane_valid: jnp.ndarray | None = None,  # (B,) bool — padded lanes False
    hooks: ScoreHooks | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (ids, dists, work, lost, iters): results plus per-lane
    expansion counts, per-lane lost visited-table inserts and the executed
    iteration count (for the work-regression accounting in
    :func:`search_tiled` — ``work`` sums the lanes that were active each
    iteration, so it is invariant to how lanes are tiled; ``lost`` is 0 under
    ``visited="dense"``)."""
    n = x.shape[0] if hooks is None else hooks.n
    b = queries.shape[0]
    e = eps.shape[1]
    k = min(cfg.k, g.capacity if hooks is None else hooks.capacity)
    rows = jnp.arange(b)
    dense = cfg.visited == "dense"
    slots = resolve_slots(cfg, e)
    any_fn = jnp.any if hooks is None else hooks.any_active
    qmode = cfg.quant.mode if cfg.quant.is_coded else None
    if qmode and qx is None and hooks is None:
        raise ValueError(
            f"cfg.quant selects mode {qmode!r} but no quantized corpus was "
            "passed (qx=) — encode with repro.quant.encode_corpus")
    if qmode == "pq" and hooks is None:
        # the query-to-centroid LUT is loop-invariant across beam iterations:
        # computed once per query batch here, closed over by the loop body
        # (and by the seed scoring below), never recomputed
        lut_a, lut_b, qsq = pq_lut(queries, qx.codebooks, cfg.metric)

    # --- seed the beam with E entries (duplicate seeds within a lane inert).
    # Seeds score through score_block too — one op sequence for every distance
    # in the beam, so a seed rediscovered as a candidate (lost hashed insert)
    # re-enters under the identical f32 value. Seeds read the f32 corpus even
    # under gram_dtype="bf16": seed vertices are marked visited, so they are
    # never re-scored through the candidate path and the mixed precision is
    # inert. Under int8/pq the seeds score through the *quantized* corpus —
    # every beam distance lives on one scale, so candidate/seed comparisons
    # stay meaningful and the rerank tail restores exactness at the end.
    dup = _seed_dups(eps)
    if hooks is not None:
        ep_d = hooks.seed(eps)                                    # (B, E)
    elif qmode == "int8":
        ep_d = int8_score_block(qx.codes[eps], qx.scale, qx.zero,
                                queries, cfg.metric)              # (B, E)
    elif qmode == "pq":
        ep_d = pq_score_codes(qx.codes[eps], lut_a, lut_b, qsq, cfg.metric)
    else:
        ep_d = score_block(x[eps], queries, cfg.metric)           # (B, E)
    seed_ids = jnp.where(dup, -1, eps)
    seed_d = jnp.where(dup, jnp.inf, ep_d)

    beam_ids = jnp.full((b, cfg.l), -1, jnp.int32).at[:, :e].set(seed_ids)
    beam_d = jnp.full((b, cfg.l), jnp.inf).at[:, :e].set(seed_d)
    expanded = jnp.ones((b, cfg.l), bool).at[:, :e].set(dup)
    neg_d, order = jax.lax.top_k(-beam_d, cfg.l)                  # sort the seeds
    beam_d = -neg_d
    beam_ids = jnp.take_along_axis(beam_ids, order, axis=1)
    expanded = jnp.take_along_axis(expanded, order, axis=1)

    visited, lost = _seed_visited(eps, dup, rows, n, slots, cfg.probes, dense)

    # padded lanes (query-count padding in search_tiled) start retired: they
    # never expand, never score, and a tile made entirely of padding exits
    # its loop at iteration 0 instead of spinning to max_iters
    done = jnp.zeros((b,), bool) if lane_valid is None else ~lane_valid
    work = jnp.zeros((b,), jnp.int32)

    def cond(state):
        # the go flag is carried in state (computed in the body / before the
        # loop) rather than reduced here: under shard="corpus" the reduction
        # is a psum and collectives cannot live in a while condition
        _, _, _, _, _, it, _, _, go = state
        return jnp.logical_and(it < cfg.max_iters, go)

    def body(state):
        beam_ids, beam_d, expanded, visited, done, it, work, lost, _ = state
        with jax.named_scope("beam.select"):
            frontier = jnp.where(expanded, jnp.inf, beam_d)
            slot = jnp.argmin(frontier, axis=1)                       # (B,)
            best_unexp = frontier[rows, slot]
            # per-lane retirement: nothing unexpanded can displace a beam entry.
            # In-beam candidates always satisfy best_unexp <= beam_d[:, -1] (merge
            # already evicted anything worse), so the operative trigger is an
            # exhausted frontier; retired lanes stop mutating state and let their
            # tile's while_loop exit without waiting on other tiles.
            done = done | (best_unexp > beam_d[:, -1]) | ~jnp.isfinite(best_unexp)
            active = ~done
            work = work + active.astype(jnp.int32)
            u = jnp.where(active, beam_ids[rows, slot], 0)
            expanded = expanded.at[rows, slot].max(active)

        # fused gather+score (Eq. 4 prefix slice + distance evaluation): the
        # kernel and the jnp oracle share one scoring function, so the two
        # paths agree bitwise — use_pallas only changes where the gathered
        # candidate block lives (VMEM vs an HBM intermediate). Under int8/pq
        # the gather reads *codes* (4x / d/m-fold less traffic) and decode
        # happens in-register next to the distance math.
        with jax.named_scope("beam.score"):
            if hooks is not None:
                # owner-contribute collectives (corpus-sharded); bitwise equal
                # to the jnp oracle below — including the coded paths
                nbrs, cand_d = hooks.beam(u)
            elif qmode == "int8":
                if cfg.use_pallas:
                    nbrs, cand_d, _ = beam_score_int8(
                        qx.codes, qx.scale, qx.zero, g.neighbors, u, queries,
                        k=k, metric=cfg.metric, tile_b=cfg.kernel_tile_b)
                else:
                    nbrs, cand_d, _ = beam_score_int8_ref(
                        qx.codes, qx.scale, qx.zero, g.neighbors, u, queries,
                        k=k, metric=cfg.metric)
            elif qmode == "pq":
                if cfg.use_pallas:
                    nbrs, cand_d, _ = beam_score_pq(
                        qx.codes, g.neighbors, u, lut_a, lut_b, qsq,
                        k=k, metric=cfg.metric, tile_b=cfg.kernel_tile_b)
                else:
                    nbrs, cand_d, _ = beam_score_pq_ref(
                        qx.codes, g.neighbors, u, lut_a, lut_b, qsq,
                        k=k, metric=cfg.metric)
            elif cfg.use_pallas:
                nbrs, cand_d, _ = beam_score(
                    x, g.neighbors, u, queries, k=k, metric=cfg.metric,
                    tile_b=cfg.kernel_tile_b, gram_dtype=cfg.effective_gram_dtype)
            else:
                nbrs, cand_d, _ = beam_score_ref(
                    x, g.neighbors, u, queries, k=k, metric=cfg.metric,
                    gram_dtype=cfg.effective_gram_dtype)
        # cand_ok: per-candidate validity (real neighbor slot, live lane) —
        # distinct from the function-level `valid` tombstone mask
        with jax.named_scope("beam.visited"):
            cand_ok = (nbrs >= 0) & active[:, None]
            fresh, visited, lost = _visit(visited, nbrs, cand_ok, (beam_ids,),
                                          rows, n, cfg.probes, dense, lost)

        with jax.named_scope("beam.topk"):
            nd = jnp.where(fresh, cand_d, jnp.inf)

            all_d = jnp.concatenate([beam_d, nd], axis=1)
            all_ids = jnp.concatenate([beam_ids, jnp.where(fresh, nbrs, -1)], axis=1)
            all_exp = jnp.concatenate([expanded, ~fresh], axis=1)
            neg_d, order = jax.lax.top_k(-all_d, cfg.l)               # L smallest
            beam_d = -neg_d
            beam_ids = jnp.take_along_axis(all_ids, order, axis=1)
            expanded = jnp.take_along_axis(all_exp, order, axis=1)
        return (beam_ids, beam_d, expanded, visited, done, it + 1, work,
                lost, any_fn(~done))

    state = (beam_ids, beam_d, expanded, visited, done, jnp.int32(0), work,
             lost, any_fn(~done))
    beam_ids, beam_d, _, _, _, iters, work, lost, _ = jax.lax.while_loop(
        cond, body, state)
    # beam rows are top_k-sorted ascending and duplicate-free by construction,
    # so the topk prefix is sorted-valid for any topk <= L
    rerank = min(cfg.quant.rerank_k, cfg.l) if qmode else 0
    if rerank:
        # exact-f32 rerank tail: quantized distances ordered the traversal;
        # the final ranking re-scores the best `rerank` beam entries against
        # the uncompressed corpus (the only place the coded path touches x)
        # so the returned ids/dists carry exact f32 distances and quantizer
        # rank inversions inside the window are repaired.
        ok = beam_ids >= 0
        if valid is not None:
            ok &= valid[jnp.maximum(beam_ids, 0)]
        masked_d = jnp.where(ok, beam_d, jnp.inf)
        neg_q, order = jax.lax.top_k(-masked_d, rerank)
        rids = jnp.take_along_axis(beam_ids, order, axis=1)       # (B, rerank)
        if hooks is not None:
            exact = hooks.rerank(rids)
        else:
            exact = score_block(x[jnp.maximum(rids, 0)], queries, cfg.metric)
        exact = jnp.where(neg_q > -jnp.inf, exact, jnp.inf)
        neg_d, o2 = jax.lax.top_k(-exact, cfg.topk)
        out_ids = jnp.take_along_axis(rids, o2, axis=1)
        return (jnp.where(neg_d > -jnp.inf, out_ids, -1), -neg_d, work,
                lost, iters)
    if valid is not None:
        # tombstone-aware serving (streaming/): masked vertices traverse the
        # beam like any other (they are live bridges in the graph) but must
        # never surface as results — demote them to (+inf, -1) and re-rank.
        # The beam's L - topk slack absorbs masked entries; results stay
        # sorted, duplicate-free, and -1-padded when fewer than topk valid
        # vertices were reached.
        ok = (beam_ids >= 0) & valid[jnp.maximum(beam_ids, 0)]
        masked_d = jnp.where(ok, beam_d, jnp.inf)
        neg_d, order = jax.lax.top_k(-masked_d, cfg.topk)
        out_ids = jnp.take_along_axis(beam_ids, order, axis=1)
        return (jnp.where(neg_d > -jnp.inf, out_ids, -1), -neg_d, work,
                lost, iters)
    return beam_ids[:, : cfg.topk], beam_d[:, : cfg.topk], work, lost, iters


def _filtered_search_impl(
    x: jnp.ndarray,
    g: G.Graph,
    queries: jnp.ndarray,
    eps: jnp.ndarray,            # (B, E) validated
    cfg: SearchConfig,
    labels: jnp.ndarray,         # (n,) uint32 label word per row
    filters: jnp.ndarray,        # (B,) uint32 mask per query
    valid: jnp.ndarray | None = None,
    lane_valid: jnp.ndarray | None = None,
):
    """Beam search under a per-query label predicate (module docstring,
    "Filtered search"). Returns (ids, dists, work, lost, iters, scored,
    passed): as :func:`_search_impl`, plus per lane the fresh candidates
    scored and those of them that passed (live and matching the mask)."""
    n = x.shape[0]
    b = queries.shape[0]
    k = min(cfg.k, g.capacity)
    rows = jnp.arange(b)
    dense = cfg.visited == "dense"
    want = filters[:, None]

    def passes(ids):
        safe = jnp.maximum(ids, 0)
        ok = (ids >= 0) & ((labels[safe] & want) == want)
        return ok if valid is None else ok & valid[safe]

    def merge(ids, d, new_ids, new_d):
        # the L nearest of a (B, L) list and a (B, C) block, ascending
        neg_d, order = jax.lax.top_k(-jnp.concatenate([d, new_d], axis=1),
                                     cfg.l)
        all_ids = jnp.concatenate([ids, new_ids], axis=1)
        return jnp.take_along_axis(all_ids, order, axis=1), -neg_d

    dup = _seed_dups(eps)
    seed_ids = jnp.where(dup, -1, eps)
    seed_d = jnp.where(dup, jnp.inf, score_block(x[eps], queries, cfg.metric))
    no_ids = jnp.full((b, cfg.l), -1, jnp.int32)
    no_d = jnp.full((b, cfg.l), jnp.inf)
    cand_ids, cand_d = merge(no_ids, no_d, seed_ids, seed_d)
    ok = passes(seed_ids)
    res_ids, res_d = merge(no_ids, no_d, jnp.where(ok, seed_ids, -1),
                           jnp.where(ok, seed_d, jnp.inf))
    visited, lost = _seed_visited(eps, dup, rows, n, resolve_slots(
        cfg, eps.shape[1]), cfg.probes, dense)
    done = jnp.zeros((b,), bool) if lane_valid is None else ~lane_valid
    zeros = jnp.zeros((b,), jnp.int32)

    def cond(state):
        return jnp.logical_and(state[6] < cfg.max_iters, state[-1])

    def body(state):
        (cand_ids, cand_d, res_ids, res_d, visited, done, it, work, lost,
         scored, passed, _) = state
        with jax.named_scope("beam.select"):
            # both lists are top_k-sorted: column 0 is the best candidate,
            # column L-1 the worst result (+inf until L passing were found)
            best, worst = cand_d[:, 0], res_d[:, -1]
            done = (done | ~jnp.isfinite(best)
                    | (jnp.isfinite(worst) & (best > worst)))
            active = ~done
            work = work + active.astype(jnp.int32)
            u = jnp.where(active, cand_ids[:, 0], 0)
            # the expanded vertex leaves the candidate list
            cand_ids = cand_ids.at[:, 0].set(
                jnp.where(active, -1, cand_ids[:, 0]))
            cand_d = cand_d.at[:, 0].set(jnp.where(active, jnp.inf, best))
        with jax.named_scope("beam.score"):
            if cfg.use_pallas:
                nbrs, nd, _ = beam_score(
                    x, g.neighbors, u, queries, k=k, metric=cfg.metric,
                    tile_b=cfg.kernel_tile_b,
                    gram_dtype=cfg.effective_gram_dtype)
            else:
                nbrs, nd, _ = beam_score_ref(
                    x, g.neighbors, u, queries, k=k, metric=cfg.metric,
                    gram_dtype=cfg.effective_gram_dtype)
        with jax.named_scope("beam.visited"):
            fresh, visited, lost = _visit(
                visited, nbrs, (nbrs >= 0) & active[:, None],
                (cand_ids, res_ids), rows, n, cfg.probes, dense, lost)
        with jax.named_scope("beam.filter"):
            ok = fresh & passes(nbrs)
            scored = scored + jnp.sum(fresh, axis=1, dtype=jnp.int32)
            passed = passed + jnp.sum(ok, axis=1, dtype=jnp.int32)
            res_ids, res_d = merge(res_ids, res_d, jnp.where(ok, nbrs, -1),
                                   jnp.where(ok, nd, jnp.inf))
        with jax.named_scope("beam.topk"):
            cand_ids, cand_d = merge(cand_ids, cand_d,
                                     jnp.where(fresh, nbrs, -1),
                                     jnp.where(fresh, nd, jnp.inf))
        return (cand_ids, cand_d, res_ids, res_d, visited, done, it + 1,
                work, lost, scored, passed, jnp.any(~done))

    state = (cand_ids, cand_d, res_ids, res_d, visited, done, jnp.int32(0),
             zeros, lost, zeros, zeros, jnp.any(~done))
    _, _, res_ids, res_d, _, _, iters, work, lost, scored, passed, _ = \
        jax.lax.while_loop(cond, body, state)
    d = res_d[:, : cfg.topk]
    ids = jnp.where(jnp.isfinite(d), res_ids[:, : cfg.topk], -1)
    return ids, d, work, lost, iters, scored, passed


def _check_filters(x, queries, cfg: SearchConfig, labels, filters,
                   shard: str = "queries") -> None:
    """The combinations a filtered search supports, and the shapes it
    needs; raises ValueError otherwise."""
    if labels is None:
        raise ValueError(
            "filters= needs labels=: one uint32 label word per corpus row")
    if shard == "corpus":
        raise ValueError(
            "filters= is not supported with shard=\"corpus\": the "
            "corpus-sharded beam has no label store")
    if cfg.quant.is_coded:
        raise ValueError(
            f"filters= is not supported with quant mode "
            f"{cfg.quant.mode!r}: the filtered beam scores the f32 corpus")
    if labels.shape != (x.shape[0],):
        raise ValueError(
            f"labels has shape {labels.shape}, expected ({x.shape[0]},): "
            "one label word per corpus row")
    if filters.shape != (queries.shape[0],):
        raise ValueError(
            f"filters has shape {filters.shape}, expected "
            f"({queries.shape[0]},): one mask per query")


@functools.partial(jax.jit, static_argnames=("cfg",))
def search(
    x: jnp.ndarray,
    g: G.Graph,
    queries: jnp.ndarray,
    entry_points: jnp.ndarray,
    cfg: SearchConfig,
    valid: jnp.ndarray | None = None,
    qx: QuantizedCorpus | None = None,
    labels: jnp.ndarray | None = None,
    filters: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (ids, dists) of shape (B, topk), ascending distance.

    ``entry_points``: scalar | (B,) | (B, E) — see :func:`_validate_entry_points`.
    ``valid``: optional (n,) bool mask — vertices marked False (tombstones,
    capacity padding) are traversed normally but never returned; lanes
    reaching fewer than topk valid vertices pad with (-1, +inf). ``None``
    keeps the historical exact path (bitwise unchanged).
    ``qx``: the encoded corpus (:func:`repro.quant.encode_corpus`) — required
    when ``cfg.quant`` selects int8/pq; the beam then gathers codes and ``x``
    is touched only by the exact rerank tail.
    ``labels`` (n,) / ``filters`` (B,): a per-query label predicate, checked
    inside the beam (module docstring, "Filtered search"); rows that fail
    it are traversed but never returned. ``filters=None`` ignores
    ``labels``.
    """
    eps = _validate_entry_points(entry_points, queries.shape[0], cfg.l)
    if filters is not None:
        _check_filters(x, queries, cfg, labels, filters)
        ids, dists, *_ = _filtered_search_impl(
            x, g, queries, eps, cfg, labels.astype(jnp.uint32),
            filters.astype(jnp.uint32), valid=valid)
        return ids, dists
    ids, dists, *_ = _search_impl(x, g, queries, eps, cfg, valid=valid,
                                  qx=qx)
    return ids, dists


def search_tiled(
    x: jnp.ndarray,
    g: G.Graph,
    queries: jnp.ndarray,
    entry_points: jnp.ndarray,
    cfg: SearchConfig,
    tile_b: int = 256,
    mesh=None,
    valid: jnp.ndarray | None = None,
    qx: QuantizedCorpus | None = None,
    shard: str = "queries",
    with_stats: bool = False,
    lane_valid: jnp.ndarray | None = None,
    labels: jnp.ndarray | None = None,
    filters: jnp.ndarray | None = None,
):
    """Stream an arbitrary query count through B_tile-sized ``lax.map`` tiles.

    Only one tile's search state is alive at a time, so peak visited-state
    memory is O(tile_b * slots) — independent of both the total batch size
    and (in hashed mode) the corpus size. Results match :func:`search`
    exactly; lanes in a finished tile never block lanes in another tile.

    ``mesh`` + ``shard="queries"`` (default): query *tiles* shard across the
    mesh axes the logical ``"queries"`` axis resolves to (RULES in
    distributed/sharding.py), with corpus and graph replicated per device —
    each device streams its own tile subset. Per-device memory is the FULL
    corpus (``n * d * 4`` bytes) plus O(tile_b * slots) visited state: this
    mode divides queries, not data. Under a mesh the tile is shrunk toward
    ``ceil(b / n_dev)`` so a small batch never pads to ``n_dev`` full tiles,
    and query-count padding is lane-masked so padded lanes retire at
    iteration 0. Lanes are independent, so sharded results are exactly
    equal (ids and dist bits) to ``mesh=None`` — asserted in
    tests/test_sharded_parity.py — composing with both ``visited`` modes
    and ``use_pallas``.

    ``mesh`` + ``shard="corpus"``: ``x``, the adjacency rows, and ``qx``
    codes partition across the mesh's "rows" axis instead — per-device
    corpus memory drops to ~``n/D`` rows (the regime where the corpus does
    not fit one device) — and each beam step routes its frontier gathers
    through owner-contribute collectives (core/search_sharded.py). Results
    stay bitwise equal to single-device; ``use_pallas`` falls back to the
    jnp scoring path (the kernels are bitwise-equal to it, so parity
    holds either way).

    ``valid``: optional (n,) tombstone/padding mask (see :func:`search`) —
    replicated per device under a mesh, composing with every other option.
    ``qx``: encoded corpus for ``cfg.quant`` int8/pq — replicated under
    ``shard="queries"``, row-sharded under ``shard="corpus"``.
    ``with_stats``: also return a stats dict {"work": total lane-iterations
    actually expanded (tiling-invariant), "visited_lost": hashed-table
    inserts dropped (0 under ``visited="dense"``), "launched": iterations
    executed x lanes launched, "tiles", "tile_lanes"} — the accounting the
    work-regression tests pin down.

    ``lane_valid``: optional (B,) bool — lanes marked False retire at
    iteration 0 (they cost one seed scoring and nothing else) and their
    output rows are unspecified. This is the serving front end's fixed-shape
    dispatch seam: an admission tile is always padded to a constant lane
    count so the jit cache sees one shape, and the vacant lanes ride along
    masked instead of forcing a recompile per occupancy level. Results for
    True lanes are bitwise identical whatever the surrounding mask says
    (lanes never interact — the admission determinism contract in
    tests/test_serving.py).

    ``labels`` (n,) uint32 / ``filters`` (B,) uint32: a per-query label
    predicate checked inside the beam (see :func:`search`); composes with
    ``valid`` (a result must be live and pass) and with ``mesh`` under
    ``shard="queries"``. ``shard="corpus"`` and coded ``cfg.quant`` raise
    ValueError with ``filters``. ``with_stats`` then adds "filter_scored"
    (fresh candidates scored), "filter_passed" (those that passed),
    "work_max" (the most expansions of any lane) and "capped" (lanes whose
    expansions reached ``max_iters``).

    Returns (ids, dists), plus the stats dict when ``with_stats``.

    Observability: this host wrapper dispatches to one jitted program
    (``_search_tiled_jit`` — the only compiled entry point, unchanged by
    tracing). With concrete operands the dispatch runs under the spans
    ``search/tiled`` and ``search/dispatch``, which land on a running
    profiler's trace even with ``repro.obs`` off. With ``repro.obs`` enabled
    the ``search/tiled`` span also blocks for an execution-accurate
    duration, and folds the ``with_stats`` lane-work counters into the
    metrics registry; called with tracers (inside an outer jit or
    ``make_jaxpr``) it degrades to the plain dispatch, so traced callers
    like streaming updates and the analysis registry see the identical
    program with or without tracing.
    """
    from repro.obs import trace as _tr
    if filters is None:
        labels = None    # the unfiltered program takes no label operand
    args = (x, g, queries, entry_points, cfg, tile_b, mesh, valid, qx, shard,
            with_stats, lane_valid, labels, filters)
    if isinstance(queries, jax.core.Tracer):
        return _search_tiled_jit(*args)
    with _tr.span("search/tiled") as sp:
        with _tr.span("search/dispatch"):
            out = _search_tiled_jit(*args)
        if not sp:
            return out
        from repro.obs import metrics as _mx
        out = jax.block_until_ready(out)
        b = int(queries.shape[0])
        sp.set(b=b, tile_b=int(tile_b), shard=shard, l=cfg.l, k=cfg.k,
               quant=cfg.quant.mode, mesh=mesh is not None)
        if with_stats:
            stats = out[2]
            work = int(stats["work"])
            lost = int(stats["visited_lost"])
            launched = int(stats["launched"])
            tiles = int(stats["tiles"])
            sp.set(work=work, visited_lost=lost, launched=launched,
                   tiles=tiles, tile_lanes=int(stats["tile_lanes"]))
            reg = _mx.REGISTRY
            reg.counter("search_lane_work_total",
                        help="beam iterations actually expanded "
                             "(tiling-invariant lane work)").inc(work)
            reg.counter("search_visited_lost_total",
                        help="hashed visited-table inserts dropped (full "
                             "probe window or same-bucket write)").inc(lost)
            reg.counter("search_lanes_launched_total",
                        help="iterations executed x lanes launched "
                             "(includes padded/retired lanes)").inc(launched)
            reg.counter("search_tiles_total",
                        help="search tiles dispatched").inc(tiles)
            if "filter_scored" in stats:
                scored = int(stats["filter_scored"])
                passed = int(stats["filter_passed"])
                sp.set(filter_scored=scored, filter_passed=passed)
                reg.counter("search_filter_scored_total",
                            help="fresh candidates scored by filtered "
                                 "searches").inc(scored)
                reg.counter("search_filter_passed_total",
                            help="scored candidates that passed their "
                                 "query's predicate").inc(passed)
    return out


@functools.partial(jax.jit, static_argnames=("cfg", "tile_b", "mesh", "shard",
                                             "with_stats"))
def _search_tiled_jit(
    x: jnp.ndarray,
    g: G.Graph,
    queries: jnp.ndarray,
    entry_points: jnp.ndarray,
    cfg: SearchConfig,
    tile_b: int = 256,
    mesh=None,
    valid: jnp.ndarray | None = None,
    qx: QuantizedCorpus | None = None,
    shard: str = "queries",
    with_stats: bool = False,
    lane_valid: jnp.ndarray | None = None,
    labels: jnp.ndarray | None = None,
    filters: jnp.ndarray | None = None,
):
    if shard not in ("queries", "corpus"):
        raise ValueError(
            f"unknown shard mode {shard!r}: expected \"queries\" (tiles "
            "shard, corpus replicated) or \"corpus\" (rows shard, queries "
            "tile through collectives)")
    if mesh is not None:
        from repro.launch.mesh import check_auto
        check_auto(mesh)
    b = queries.shape[0]
    eps = _validate_entry_points(entry_points, b, cfg.l)
    filtered = filters is not None
    if filtered:
        _check_filters(x, queries, cfg, labels, filters, shard)
    if lane_valid is not None and lane_valid.shape != (b,):
        raise ValueError(
            f"lane_valid has shape {lane_valid.shape} but the query batch "
            f"is {b}: pass one bool per lane (or None for all-live)")
    if shard == "corpus":
        if mesh is None:
            raise ValueError(
                "shard=\"corpus\" requires mesh=: corpus sharding partitions "
                "x and the adjacency rows over the mesh's \"rows\" axis")
        from repro.core import search_sharded as SS
        return SS.search_tiled_corpus(x, g, queries, eps, cfg, tile_b, mesh,
                                      valid=valid, qx=qx,
                                      with_stats=with_stats,
                                      lane_valid=lane_valid)
    tile_b = min(tile_b, b) if b > 0 else 1   # b=0 -> zero tiles, empty result
    qaxes: tuple = ()
    n_dev = 1
    if mesh is not None and b > 0:
        from repro.distributed import sharding as SH
        qaxes = SH.mesh_axes(mesh, "queries")
        n_dev = SH.axis_count(mesh, "queries")
        if n_dev > 1:
            # shrink the tile toward an even lane split: b=100 on 8 devices
            # used to pad to 8 full 100-lane tiles (800 beam searches for
            # 100 queries); ceil(b / n_dev) caps the padding below one tile.
            # Floor at 2 lanes: XLA:CPU lowers batch-1 score einsums
            # differently than batch>=2 (last-bit divergence), so a 1-lane
            # tile only ever appears when the mesh=None reference itself
            # scores batch 1 (b=1 or tile_b=1) and shapes already match
            tile_b = min(tile_b, max(2, -(-b // n_dev)))
    # pad the lane count to tile_b * n_dev; padded lanes carry
    # lane_valid=False and retire at iteration 0 (sliced off on exit)
    pad = (-b) % (tile_b * n_dev)
    q_p = jnp.pad(queries, ((0, pad), (0, 0)))
    eps_p = jnp.concatenate([eps, jnp.broadcast_to(eps[:1], (pad, eps.shape[1]))]) \
        if pad else eps
    q_tiles = q_p.reshape(-1, tile_b, queries.shape[1])
    ep_tiles = eps_p.reshape(-1, tile_b, eps.shape[1])
    lv = jnp.arange(q_p.shape[0]) < b
    if lane_valid is not None:
        lv = lv & jnp.pad(lane_valid.astype(bool), (0, pad))
    lv_tiles = lv.reshape(-1, tile_b)
    # a filtered search adds the label store and one mask tile per query tile
    filt = (labels.astype(jnp.uint32),
            jnp.pad(filters.astype(jnp.uint32), (0, pad)).reshape(-1, tile_b)
            ) if filtered else ()

    def tiles_body(xx, gg, vv, qq, qt, et, lt, *lab_ft):
        if lab_ft:
            lab, ft = lab_ft
            return jax.lax.map(
                lambda t: _filtered_search_impl(
                    xx, gg, t[0], t[1], cfg, lab, t[3], valid=vv,
                    lane_valid=t[2]),
                (qt, et, lt, ft),
            )
        return jax.lax.map(
            lambda t: _search_impl(xx, gg, t[0], t[1], cfg, valid=vv, qx=qq,
                                   lane_valid=t[2]),
            (qt, et, lt),
        )

    if qaxes:
        # taken whenever the mesh routes a "queries" axis — including a
        # 1-wide mesh, so single-device runs still exercise the real
        # shard_map dispatch (the 1-device CI smoke relies on this)
        from jax.sharding import PartitionSpec as P
        qspec = SH.pspec(mesh, "queries", None, None)
        rep = G.Graph(P(), P(), P())
        # optional operands (valid mask, quantized store) join the operand
        # and spec lists only when present, so the shard_map signature — and
        # with it the absent-operand traces — stays identical to before
        operands: list = [x, g]
        specs: list = [P(), rep]
        has_valid, has_qx = valid is not None, qx is not None
        if has_valid:
            operands.append(valid)
            specs.append(P())
        if has_qx:
            operands.append(qx)
            specs.append(jax.tree.map(lambda _: P(), qx))
        lane_spec = SH.pspec(mesh, "queries", None)
        operands += [q_tiles, ep_tiles, lv_tiles, *filt]
        specs += [qspec, qspec, lane_spec] + ([P(), lane_spec] if filtered
                                              else [])

        def dispatch(xx, gg, *rest):
            i = 0
            vv = rest[i] if has_valid else None
            i += has_valid
            qq = rest[i] if has_qx else None
            i += has_qx
            return tiles_body(xx, gg, vv, qq, *rest[i:])

        outs = jax.shard_map(
            dispatch, mesh=mesh,
            in_specs=tuple(specs),
            out_specs=(qspec, qspec, lane_spec, lane_spec,
                       SH.pspec(mesh, "queries"))
            + ((lane_spec, lane_spec) if filtered else ()),
            check_vma=False,
        )(*operands)
    else:
        outs = tiles_body(x, g, valid, qx, q_tiles, ep_tiles, lv_tiles, *filt)
    ids, dists, lane_work, lane_lost, tile_iters = outs[:5]
    out = (ids.reshape(-1, cfg.topk)[:b], dists.reshape(-1, cfg.topk)[:b])
    if not with_stats:
        return out
    stats = {
        "work": jnp.sum(lane_work.reshape(-1)[:b]),
        "visited_lost": jnp.sum(lane_lost.reshape(-1)[:b]),
        "launched": jnp.sum(tile_iters) * tile_b,
        "tiles": q_tiles.shape[0],
        "tile_lanes": tile_b,
    }
    if filtered:
        scored, passed = (a.reshape(-1)[:b] for a in outs[5:])
        work = lane_work.reshape(-1)[:b]
        stats.update(filter_scored=jnp.sum(scored),
                     filter_passed=jnp.sum(passed),
                     work_max=jnp.max(work),
                     capped=jnp.sum(work >= cfg.max_iters))
    return out + (stats,)


def default_entry_point(
    x: jnp.ndarray, metric: str = "l2", valid: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """NSG-style navigating node: the vertex nearest the dataset centroid.

    ``valid``: optional (n,) bool mask — with a capacity-padded / tombstoned
    corpus (streaming/), the centroid is taken over live rows only and the
    returned seed is guaranteed live. Without it a tombstoned or padded row
    (an all-zeros vector is often centroid-nearest!) could be handed out as
    a seed and silently burn a beam slot."""
    if valid is None:
        c = jnp.mean(x, axis=0)
        return jnp.argmin(D.point_to_points(c, x, metric)).astype(jnp.int32)
    w = valid.astype(x.dtype)
    c = jnp.sum(x * w[:, None], axis=0) / jnp.maximum(jnp.sum(w), 1.0)
    d = jnp.where(valid, D.point_to_points(c, x, metric), jnp.inf)
    return jnp.argmin(d).astype(jnp.int32)


def default_entry_points(
    x: jnp.ndarray, n_entries: int = 1, metric: str = "l2",
    key: jax.Array | None = None, valid: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """(E,) seed set: the centroid-nearest vertex plus ``n_entries - 1``
    distinct random vertices (diversified seeding for multi-entry search).
    Broadcast to (B, E) to share across a query batch.

    ``valid``: optional (n,) bool mask — every returned seed is drawn from
    live rows only (tombstoned / capacity-padded rows are never handed out).
    ``None`` keeps the historical sampling bit-for-bit."""
    if n_entries > x.shape[0]:
        # without this the unmasked path dies inside jax.random.choice with
        # an opaque "cannot take a larger sample than population" internal
        # error — there are only n distinct vertices to seed from
        raise ValueError(
            f"n_entries={n_entries} exceeds the corpus size n={x.shape[0]}: "
            "entry points are distinct vertices, so at most n can be drawn")
    center = default_entry_point(x, metric, valid=valid)
    if n_entries <= 1:
        return center[None]
    key = jax.random.PRNGKey(0) if key is None else key
    if valid is None:
        # sample from [0, n-1) and shift indices >= center up by one: distinct
        # from each other (choice without replacement) and never equal to
        # center
        extra = jax.random.choice(key, x.shape[0] - 1, (n_entries - 1,),
                                  replace=False)
        extra = (extra + (extra >= center)).astype(jnp.int32)
        return jnp.concatenate([center[None], extra])
    # masked sampling without replacement: rank rows by a uniform draw, with
    # masked rows and the centroid seed pushed past every live row. If fewer
    # than n_entries rows are live, the tail repeats the centroid seed —
    # duplicate seeds within a lane are inert (see _search_impl).
    score = jax.random.uniform(key, (x.shape[0],))
    score = jnp.where(valid, score, jnp.inf).at[center].set(jnp.inf)
    order = jnp.argsort(score)[: n_entries - 1].astype(jnp.int32)
    live = jnp.isfinite(jnp.sort(score)[: n_entries - 1])
    extra = jnp.where(live, order, center)
    return jnp.concatenate([center[None], extra])
