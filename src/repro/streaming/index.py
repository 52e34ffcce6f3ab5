"""StreamingANN: a dynamic ANN index — insert, delete, search, compact,
save/restore — over the capacity-padded :class:`repro.streaming.store.Store`.

Epoch-snapshot serving
----------------------
Every store field is an immutable jax array and every update
(:func:`repro.streaming.updates.insert` / ``delete`` / ``compact``) is a pure
function returning a *new* store. ``StreamingANN`` therefore never mutates
index state in place: an update computes the next store off to the side and
then commits it with a single Python reference swap, bumping ``epoch``. A
reader that captured ``snapshot()`` (or simply entered ``search()``, which
reads the reference once) keeps serving the complete, internally-consistent
graph of its epoch no matter how many updates commit meanwhile — there is no
intermediate state to observe, the exact analogue of an RCU epoch scheme but
enforced by functional purity instead of barriers.

Serving is tombstone-aware end to end: ``search`` threads the store's
live-row mask through ``search_tiled(valid=)`` (deleted rows are traversed
as bridges but never surface; capacity padding is unreachable by
construction) and seeds entry points from live rows only. Each row also
carries a uint32 label word (``Store.labels``, set by ``from_corpus`` and
``insert``, moved by ``compact``); ``search(filters=)`` returns only rows
whose word holds every bit of their query's mask, checked in the beam.

Mesh composition: ``mesh=`` routes construction through the PR-4 row-sharded
build, updates through the frontier-sharded exchange in updates.py, and
serving through query-tile sharding — all bitwise-equal to single-device.
Persistence rides checkpoint/ (atomic-commit npz): the whole store pytree —
vectors, adjacency, masks, epoch — saves as host arrays and restores onto
any mesh shape (tests/test_index_persistence.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import checkpoint
from repro.core import graph as G
from repro.quant import QuantizedCorpus, encode_corpus
from repro.core import rnn_descent as rd
from repro.core import search as S
from repro.obs import trace as _tr
from repro.streaming import store as ST
from repro.streaming import updates as U


def _place(st: ST.Store, mesh: Mesh | None) -> ST.Store:
    """Commit a store to the mesh, replicated (serving reads everything per
    device; update programs re-shard internally via shard_map)."""
    if mesh is None:
        return st
    sh = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda a: jax.device_put(jnp.asarray(np.asarray(a)), sh), st)


@dataclasses.dataclass
class StreamingANN:
    """A dynamic index bound to a (possibly absent) mesh.

    >>> ann = StreamingANN.from_corpus(x, cfg=StreamingConfig(...))
    >>> new_ids = ann.insert(new_vectors)       # row ids of the new points
    >>> ann.delete(new_ids[:8])                 # tombstone + splice repair
    >>> ids, dists = ann.search(queries, S.SearchConfig(l=32, topk=10))
    >>> remap = ann.compact()                   # physically drop tombstones
    >>> ann.save("/ckpts/stream"); StreamingANN.restore("/ckpts/stream")
    """

    store: ST.Store
    cfg: U.StreamingConfig
    mesh: Mesh | None = None

    def __post_init__(self):
        # A freshly wrapped store (grow(), restore(), manual construction)
        # holds host-default-placed arrays, while every mesh update program
        # emits NamedSharding-placed ones — so without committing it to the
        # mesh here, the first insert/delete after construction recompiles
        # every update program at *identical shapes* (a sharding transition,
        # invisible to the shape-discipline argument and poison for the
        # serving path's zero-steady-state-compile contract).
        self.store = _place(self.store, self.mesh)

    # ------------------------------------------------------------ lifecycle
    @classmethod
    def from_corpus(cls, x, cfg: U.StreamingConfig | None = None,
                    key: jax.Array | None = None, mesh: Mesh | None = None,
                    capacity: int | None = None,
                    labels=None) -> "StreamingANN":
        """Batch-build the initial graph (``rnn_descent.build``, row-sharded
        over ``mesh`` when given) and wrap it into a padded store.
        ``labels``: optional (n,) uint32 label word per row, what
        ``search(filters=)`` tests (none held when None: every row reads
        as 0)."""
        cfg = cfg if cfg is not None else U.StreamingConfig()
        key = key if key is not None else jax.random.PRNGKey(0)
        x = jnp.asarray(x, jnp.float32)
        g = rd.build(x, cfg.build, key, mesh=mesh)
        # re-encode with the builder's exact quant config (deterministic:
        # same train rows, same pq seed) so serve-side codes match the
        # geometry the graph was optimized for.
        qx = (encode_corpus(x, cfg.build.quant)
              if cfg.build.quant.is_coded else None)
        st = ST.from_built(x, g, capacity=capacity, qx=qx, labels=labels)
        return cls(store=st, cfg=cfg, mesh=mesh)

    # -------------------------------------------------------------- queries
    def snapshot(self) -> tuple[int, ST.Store]:
        """(epoch, store) — the store pytree is immutable, so holding it
        serves a consistent graph across any number of later updates."""
        st = self.store
        return int(st.epoch), st

    def search(self, queries, cfg: S.SearchConfig | None = None,
               entry_points=None, tile_b: int = 256,
               shard: str = "queries", with_stats: bool = False,
               lane_valid=None, store: ST.Store | None = None,
               filters=None):
        """Tombstone-aware serving over the current epoch's snapshot:
        deleted rows route traffic but never appear in the top-k; lanes
        reaching fewer than topk live vertices pad with (-1, +inf).

        ``shard``/``with_stats``/``lane_valid`` pass straight through to
        :func:`repro.core.search.search_tiled` — the serving front end uses
        ``lane_valid`` to dispatch constant-shape admission tiles with the
        vacant lanes masked (zero steady-state recompiles) and ``shard=
        "corpus"`` to serve a row-partitioned store. ``store=`` searches an
        explicit snapshot (from :meth:`snapshot`) instead of re-reading the
        live reference — the seam that pins a dispatched tile to one epoch
        even while the writer commits. ``filters``: optional (B,) uint32
        mask per query; a row is returned only when its label word holds
        every bit of its query's mask (checked inside the beam, see
        :func:`repro.core.search.search_tiled`)."""
        st = self.store if store is None else store  # one read = one epoch
        cfg = cfg if cfg is not None else S.SearchConfig()
        qx = None
        if cfg.quant.is_coded:
            if st.qx is None:
                raise ValueError(
                    f"search config requests quant mode {cfg.quant.mode!r} "
                    "but the store holds no codes — call "
                    ".quantize(Quantization(...)) first")
            if st.qx.mode != cfg.quant.mode:
                raise ValueError(
                    f"search config requests quant mode {cfg.quant.mode!r} "
                    f"but the store's codes are {st.qx.mode!r}")
            qx = st.qx
        with _tr.span("streaming/search"):
            with _tr.span("streaming/entry"):
                valid = ST.active_mask(st)
                if entry_points is None:
                    entry_points = S.default_entry_point(st.x, cfg.metric,
                                                         valid=valid)
            labels = None
            if filters is not None:
                labels = ST.row_labels(st)
                filters = jnp.asarray(filters)
            return S.search_tiled(st.x, st.graph, jnp.asarray(queries),
                                  entry_points, cfg, tile_b=tile_b,
                                  mesh=self.mesh, valid=valid, qx=qx,
                                  shard=shard, with_stats=with_stats,
                                  lane_valid=lane_valid, labels=labels,
                                  filters=filters)

    # -------------------------------------------------------------- updates
    def insert(self, new_x, labels=None) -> np.ndarray:
        """Insert a batch; returns the assigned row ids. Grows the store
        (power-of-two capacity, a recompile event) when free rows run out,
        then commits the updated store atomically. ``labels``: optional
        (B,) uint32 label words of the new rows (0 when None)."""
        new_x = jnp.asarray(new_x, jnp.float32)
        b = int(new_x.shape[0])
        st = self.store
        if ST.free_count(st) < b:
            st = ST.grow(st, ST.occupied_count(st) + b)
            if self.mesh is not None:
                st = _place(st, self.mesh)
        st, slots = U.insert(st, new_x, self.cfg, mesh=self.mesh,
                             labels=labels)
        self.store = st                      # atomic epoch swap
        return slots

    def delete(self, ids) -> np.ndarray:
        """Tombstone + splice-repair a batch of row ids.

        Returns a bool mask aligned with ``ids``: True where the id was a
        live row at call entry (this call tombstoned it), False where it
        was already tombstoned (the repeat is a no-op — delete stays
        idempotent, but the caller now *sees* which deletes landed instead
        of a silent swallow). Ids that were never handed out — negative,
        beyond capacity, or pointing at an unoccupied row — raise
        ``IndexError``: they indicate a corrupted external id book, and the
        old silent skip turned that bug into quietly-undeleted data.
        Duplicate ids in one batch all report the pre-call liveness (each
        True)."""
        st = self.store
        ids_np = np.asarray(ids).reshape(-1).astype(np.int64)
        cap = st.capacity
        oob = (ids_np < 0) | (ids_np >= cap)
        if np.any(oob):
            bad = ids_np[oob][:8]
            raise IndexError(
                f"delete ids out of range [0, {cap}): {bad.tolist()}"
                f"{'...' if int(np.sum(oob)) > 8 else ''} — row ids come "
                "from insert()/from_corpus and never leave the capacity")
        occ = np.asarray(st.occupied)
        unocc = ~occ[ids_np]
        if np.any(unocc):
            bad = ids_np[unocc][:8]
            raise IndexError(
                f"delete ids name unoccupied rows: {bad.tolist()}"
                f"{'...' if int(np.sum(unocc)) > 8 else ''} — these were "
                "never assigned by insert() (stale ids from before a "
                "compact()? translate through last_remap)")
        newly = ~np.asarray(st.tombstone)[ids_np]
        self.store = U.delete(st, ids, self.cfg, mesh=self.mesh)
        return newly

    def compact(self, repair_sweeps: int = 1) -> np.ndarray:
        """Physically drop tombstoned rows (dense renumbering; returns the
        old-row -> new-row remap, -1 for removed). The remap also persists
        on the store (``last_remap``) and through ``save()``/``restore()``,
        so an external id book can still be translated after a checkpoint
        cycle — the pre-PR-9 behaviour dropped it. ``repair_sweeps`` full
        ``update_neighbors`` passes run afterwards to re-knit regions that
        leaned on tombstone bridges (0 to skip) — row-sharded over the mesh
        when one is bound (bitwise-identical to single-device, like every
        other sweep)."""
        st, remap = ST.compact(self.store)
        for _ in range(repair_sweeps):
            if self.mesh is not None:
                from repro.core import shard
                g = shard.rnn_update_neighbors(st.x, st.graph,
                                               self.cfg.build, self.mesh)
            else:
                g = rd.update_neighbors(st.x, st.graph, self.cfg.build)
            st = st._replace(graph=g)
        self.store = _place(st, self.mesh) if self.mesh is not None else st
        return remap

    def quantize(self, quant) -> None:
        """Attach (or retrain, or with a non-coded mode drop) quantized codes
        for the current store — see :func:`repro.streaming.store.quantize_store`.
        After this, searches whose config carries the same coded mode use the
        fused decode+score path with an exact-f32 rerank tail."""
        self.store = _place(ST.quantize_store(self.store, quant), self.mesh)

    # ---------------------------------------------------------- persistence
    def save(self, ckpt_dir: str, step: int | None = None) -> None:
        """Atomic-commit save of the whole store (host arrays —
        mesh-agnostic). Default step = current epoch."""
        st = self.store
        checkpoint.save(ckpt_dir, int(st.epoch) if step is None else step,
                        st)

    @classmethod
    def restore(cls, ckpt_dir: str, cfg: U.StreamingConfig | None = None,
                mesh: Mesh | None = None, step: int | None = None,
                ) -> "StreamingANN":
        """Elastic restore onto any mesh shape (or none): tombstones,
        capacity padding and the epoch counter all round-trip."""
        if step is None:
            step = checkpoint.latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(f"no committed checkpoint in {ckpt_dir}")
        # the store's qx subtree is optional and its None fields are leafless
        # under pytree flatten, so probe the manifest's leaf names to build a
        # like-tree with the exact structure that was saved.
        names = set(checkpoint.manifest_names(ckpt_dir, step))
        if ".qx.codebooks" in names:
            qx_like = QuantizedCorpus(codes=0, codebooks=0)
        elif ".qx.scale" in names:
            qx_like = QuantizedCorpus(codes=0, scale=0, zero=0)
        else:
            qx_like = None
        like = ST.Store(x=0, graph=G.Graph(0, 0, 0), occupied=0, tombstone=0,
                        epoch=0, qx=qx_like,
                        remap=0 if ".remap" in names else None,
                        labels=0 if ".labels" in names else None)
        st = checkpoint.restore(ckpt_dir, step, like)
        st = jax.tree.map(jnp.asarray, st)
        if cfg is None:
            m = st.graph.neighbors.shape[1]
            cfg = U.StreamingConfig(
                build=rd.RNNDescentConfig(capacity=m, r=min(96, m)),
                seed_k=min(24, m))
        return cls(store=_place(st, mesh), cfg=cfg, mesh=mesh)

    # ------------------------------------------------------------ inspection
    @property
    def epoch(self) -> int:
        return int(self.store.epoch)

    @property
    def live(self) -> int:
        return ST.live_count(self.store)

    @property
    def capacity(self) -> int:
        return self.store.capacity

    @property
    def last_remap(self) -> np.ndarray | None:
        """The most recent :meth:`compact`'s old-row -> new-row map (-1 =
        removed), or None if the store was never compacted. Survives
        ``save()``/``restore()``."""
        rm = self.store.remap
        return None if rm is None else np.asarray(rm)

    def stats(self) -> dict[str, Any]:
        st = self.store
        return {
            "epoch": int(st.epoch),
            "capacity": st.capacity,
            "occupied": ST.occupied_count(st),
            "live": ST.live_count(st),
            "tombstones": int(jnp.sum(st.tombstone)),
        }
