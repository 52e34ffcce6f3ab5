"""Capacity-padded corpus store for the streaming (dynamic) index.

The batch builders produce an immutable (x, graph) pair sized exactly to the
corpus. A churning corpus instead lives in a :class:`Store`: every array is
padded to a power-of-two ``capacity``, and two row masks track liveness —

``occupied``   the row holds a vector (inserted at some point). Occupied rows
               participate in graph traversal whether or not they are
               tombstoned; unoccupied rows are inert (zero vector, empty
               adjacency, no in-edges) and exist only so jitted update/search
               programs see stable shapes across update batches.

``tombstone``  the row was deleted (subset of ``occupied``). Tombstoned rows
               stay *traversable* — their out-edges survive and other rows
               may keep pointing at them, so they act as bridges for beam
               search — but they must never surface in results
               (``search_tiled(valid=...)``) and :func:`compact` eventually
               rebuilds the store without them.

Why power-of-two capacity: jit caches are keyed on shapes, so growing the
store by exactly one batch would recompile every update program on every
batch. Doubling instead amortizes recompilation to O(log n) growth events,
at the classic ≤ 2x memory overhead — the same tradeoff as the hashed visited
table and bucket widths elsewhere in the codebase. Per-row memory is
``d * 4`` (x) + ``M * 9`` (adjacency fields) + 2 bytes (masks), plus 4
bytes (label word) in a labelled store, so a store at capacity C carries at
most twice the footprint of an exact-fit corpus.

Everything here is a pure function from Store to Store: updates build a new
pytree and leave the input untouched, which is what makes the epoch-snapshot
serving contract in streaming/index.py trivially safe.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core import graph as G
from repro.quant import Quantization, QuantizedCorpus, encode_corpus


class Store(NamedTuple):
    """x: (C, d) f32 (zeros in unoccupied rows) | graph: (C, M) adjacency |
    occupied / tombstone: (C,) bool | epoch: () int32 update counter |
    qx: optional quantized codes | remap: optional last-compaction remap
    (both trailing, default None, so checkpoints and pytree traversals of
    stores that never held them are unchanged — None fields are leafless
    under pytree flatten) | labels: optional (C,) uint32 label word per row,
    the bitset a filtered search tests (``search(filters=)``); None until
    some rows are given labels, 0 in rows given none (such a row passes
    only the empty mask). Trailing and default None, like qx and remap.

    A quantized store keeps *both* representations resident: ``qx.codes``
    serve the coded search (and grow / compact / checkpoint exactly like
    ``x``), while ``x`` stays for the exact rerank tail and for the f32
    update/repair sweeps.

    ``remap`` is the survivor map of the most recent :func:`compact`:
    ``remap[old_row] -> new_row`` (-1 for removed rows), sized to the
    *pre*-compaction capacity. Callers that handed out row ids before the
    compaction translate through it; persisting it in the store means a
    ``save()``/``restore()`` cycle between compact and translation no
    longer strands external id books (the PR-9 bugfix)."""

    x: jnp.ndarray
    graph: G.Graph
    occupied: jnp.ndarray
    tombstone: jnp.ndarray
    epoch: jnp.ndarray
    qx: QuantizedCorpus | None = None
    remap: jnp.ndarray | None = None
    labels: jnp.ndarray | None = None

    @property
    def capacity(self) -> int:
        return self.x.shape[0]

    @property
    def dim(self) -> int:
        return self.x.shape[1]

    @property
    def m(self) -> int:
        return self.graph.neighbors.shape[1]


def next_capacity(n: int) -> int:
    """Smallest power of two >= max(n, 8)."""
    return 1 << max(3, (n - 1).bit_length())


def active_mask(store: Store) -> jnp.ndarray:
    """(C,) bool — rows that may surface in search results."""
    return store.occupied & ~store.tombstone


def live_count(store: Store) -> int:
    return int(jnp.sum(active_mask(store)))


def occupied_count(store: Store) -> int:
    return int(jnp.sum(store.occupied))


def free_count(store: Store) -> int:
    """Rows available for insertion. Tombstoned rows are NOT free until
    :func:`compact` — their vector must stay resident while in-edges may
    still route traffic through them."""
    return store.capacity - occupied_count(store)


def row_labels(store: Store) -> jnp.ndarray:
    """(C,) uint32 label words (zeros where the store holds none)."""
    if store.labels is None:
        return jnp.zeros((store.capacity,), jnp.uint32)
    return store.labels


def _pad_labels(labels: jnp.ndarray | None, pad: int) -> jnp.ndarray | None:
    return None if labels is None else jnp.pad(labels, (0, pad))


def _pad_graph(g: G.Graph, cap: int) -> G.Graph:
    n = g.n
    return G.Graph(
        neighbors=jnp.pad(g.neighbors, ((0, cap - n), (0, 0)),
                          constant_values=-1),
        dists=jnp.pad(g.dists, ((0, cap - n), (0, 0)),
                      constant_values=jnp.inf),
        flags=jnp.pad(g.flags, ((0, cap - n), (0, 0)), constant_values=G.OLD),
    )


def _pad_codes(qx: QuantizedCorpus | None, pad: int) -> QuantizedCorpus | None:
    """Capacity-pad the code rows (zeros — unoccupied rows are unreachable,
    so their decode value is inert); aux params are O(1) and untouched."""
    if qx is None or pad == 0:
        return qx
    return qx._replace(codes=jnp.pad(qx.codes, ((0, pad), (0, 0))))


def from_built(x: jnp.ndarray, g: G.Graph,
               capacity: int | None = None,
               qx: QuantizedCorpus | None = None,
               labels=None) -> Store:
    """Wrap a batch-built (x, graph) pair into a padded store (rows [0, n)
    occupied, nothing tombstoned, epoch 0). ``qx``: optional (n, ·) codes
    from the same encode the builder used — padded alongside x.
    ``labels``: optional (n,) uint32 label words (the store holds none
    when None)."""
    n = x.shape[0]
    if g.n != n:
        raise ValueError(
            f"graph has {g.n} rows but the corpus has {n}: from_built "
            "expects the (x, graph) pair of one batch build")
    if qx is not None and qx.codes.shape[0] != n:
        raise ValueError(
            f"qx holds {qx.codes.shape[0]} code rows but the corpus has {n}")
    if labels is not None:
        labels = jnp.asarray(labels).astype(jnp.uint32)
    if labels is not None and labels.shape != (n,):
        raise ValueError(
            f"labels has shape {labels.shape} but the corpus has {n} rows: "
            "pass one label word per row")
    cap = next_capacity(n if capacity is None else max(capacity, n))
    return Store(
        x=jnp.pad(x.astype(jnp.float32), ((0, cap - n), (0, 0))),
        graph=_pad_graph(g, cap),
        occupied=jnp.arange(cap) < n,
        tombstone=jnp.zeros((cap,), bool),
        epoch=jnp.int32(0),
        qx=_pad_codes(qx, cap - n),
        labels=_pad_labels(labels, cap - n),
    )


def grow(store: Store, min_capacity: int) -> Store:
    """Re-pad every array to ``next_capacity(min_capacity)`` (a host-level
    shape change — jitted update programs recompile at the new capacity,
    which the power-of-two schedule makes a O(log n)-times event)."""
    cap = store.capacity
    new_cap = next_capacity(min_capacity)
    if new_cap <= cap:
        return store
    pad = new_cap - cap
    return Store(
        x=jnp.pad(store.x, ((0, pad), (0, 0))),
        graph=_pad_graph(store.graph, new_cap),
        occupied=jnp.pad(store.occupied, (0, pad)),
        tombstone=jnp.pad(store.tombstone, (0, pad)),
        epoch=store.epoch,
        qx=_pad_codes(store.qx, pad),
        remap=store.remap,
        labels=_pad_labels(store.labels, pad),
    )


def compact(store: Store) -> tuple[Store, np.ndarray]:
    """Rebuild the store without tombstoned (and unoccupied) rows.

    Survivors are renumbered densely from 0 in ascending old-row order;
    edges into removed rows are dropped (the delete-time splice repair
    already bridged around them) and each row is re-sorted to the row
    invariant. Returns ``(new_store, remap)`` where ``remap[old_row]`` is the
    new row id, or -1 for removed rows — callers that hand out row ids must
    translate through it. The same remap is stored on ``new_store.remap``
    so it survives a ``save()``/``restore()`` cycle (a pre-PR-9 compact
    lost it the moment the returned array went out of scope). Host-level
    (shape change), like :func:`grow`."""
    occ = np.asarray(store.occupied)
    tomb = np.asarray(store.tombstone)
    alive = occ & ~tomb
    old_ids = np.flatnonzero(alive)
    n_new = int(old_ids.shape[0])
    cap2 = next_capacity(n_new)
    remap = np.full(store.capacity, -1, np.int32)
    remap[old_ids] = np.arange(n_new, dtype=np.int32)

    nb = np.asarray(store.graph.neighbors)[old_ids]
    nb2 = np.where(nb >= 0, remap[np.maximum(nb, 0)], -1)
    d2 = np.where(nb2 >= 0, np.asarray(store.graph.dists)[old_ids], np.inf)
    f2 = np.where(nb2 >= 0, np.asarray(store.graph.flags)[old_ids], G.OLD)
    g2 = G.sort_rows(G.Graph(
        neighbors=jnp.asarray(nb2, jnp.int32),
        dists=jnp.asarray(d2, jnp.float32),
        flags=jnp.asarray(f2, jnp.uint8),
    ))
    qx2 = None
    if store.qx is not None:
        qx2 = _pad_codes(
            store.qx._replace(
                codes=jnp.asarray(np.asarray(store.qx.codes)[old_ids])),
            cap2 - n_new)
    labels2 = None
    if store.labels is not None:
        labels2 = _pad_labels(
            jnp.asarray(np.asarray(store.labels)[old_ids]), cap2 - n_new)
    new = Store(
        x=jnp.pad(jnp.asarray(np.asarray(store.x)[old_ids]),
                  ((0, cap2 - n_new), (0, 0))),
        graph=_pad_graph(g2, cap2),
        occupied=jnp.arange(cap2) < n_new,
        tombstone=jnp.zeros((cap2,), bool),
        epoch=store.epoch + 1,
        qx=qx2,
        remap=jnp.asarray(remap),
        labels=labels2,
    )
    return new, remap


def quantize_store(store: Store, quant: Quantization) -> Store:
    """Attach (or retrain) quantized codes for an existing store.

    Scale / zero-point / codebooks are trained on the *live* rows only —
    capacity padding (zero vectors) and any row distribution it would drag
    in must not distort the code space — while codes are emitted for every
    row (tombstones stay traversable, padding is inert). Host-level like
    :func:`grow` (a one-shot train), bumps no epoch: the serving geometry
    changes only when a search config starts selecting the coded path."""
    if not quant.is_coded:
        return store._replace(qx=None)
    live = np.flatnonzero(np.asarray(active_mask(store)))
    qx = encode_corpus(store.x, quant,
                       train_rows=store.x[jnp.asarray(live)])
    return store._replace(qx=qx)
