"""Entry-point registry for the jaxpr auditor.

Every public jit surface of the library is registered here as a thunk that
*abstract-traces* it (``jax.make_jaxpr`` on ``ShapeDtypeStruct`` args — no
compilation, no execution) at a deliberately tiny problem size: the audited
invariants (dtype discipline, key taint, OOB modes, callbacks) are shape-
independent, so a 32x8 corpus exercises the same primitive stream as a
production build.

Registering a new entry point (the checklist for any PR that adds a public
jitted function):

1. Add a ``def _trace_<name>():`` thunk below returning
   ``jax.make_jaxpr(...)(...)`` over small abstract args.
2. Add it to ``_REGISTRY`` under ``"<module>/<name>"`` (plus a
   ``"<module>/<name>@mesh"`` variant if it takes a mesh — the sharded
   trace routes through shard_map and is a different program).
3. Run ``python -m repro.analysis --passes jaxpr`` — a clean entry adds no
   findings; a dirty one fails CI until fixed (or consciously baselined
   with ``--write-baseline``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

N, D, M, B = 32, 8, 16, 4     # corpus rows/dims, adjacency cap, query batch


def _mesh1():
    from repro.launch.mesh import make_mesh
    return make_mesh((1,), ("data",))


def _x():
    return jax.ShapeDtypeStruct((N, D), jnp.float32)


def _graph():
    from repro.core import graph as G
    return G.Graph(
        neighbors=jax.ShapeDtypeStruct((N, M), jnp.int32),
        dists=jax.ShapeDtypeStruct((N, M), jnp.float32),
        flags=jax.ShapeDtypeStruct((N, M), jnp.uint8),
    )


def _rnn_cfg(**kw):
    from repro.core import rnn_descent as rd
    base = dict(s=4, r=8, t1=2, t2=2, capacity=M, chunk=16)
    base.update(kw)
    return rd.RNNDescentConfig(**base)


def _nn_cfg(**kw):
    from repro.core import nn_descent as nnd
    base = dict(k=8, s=4, iters=2, chunk=16)
    base.update(kw)
    return nnd.NNDescentConfig(**base)


def _nsg_cfg():
    from repro.core import nsg_style as nsg
    return nsg.NSGStyleConfig(r=4, c=8, knn=_nn_cfg(iters=1), chunk=16)


def _search_cfg(**kw):
    from repro.core import search as S
    base = dict(l=8, k=4, max_iters=8, topk=2)
    base.update(kw)
    return S.SearchConfig(**base)


def _stream_cfg():
    from repro.streaming import StreamingConfig
    return StreamingConfig(build=_rnn_cfg(), seed_l=16, seed_k=8,
                           seed_iters=16, search_k=8, batch_k=2, sweeps=1,
                           splice_k=4, delete_fanout=8)


def _key():
    return jax.random.PRNGKey(0)


# ----------------------------------------------------------------- builders
def _trace_rnn_build_jit():
    from repro.core import rnn_descent as rd
    cfg = _rnn_cfg()
    return jax.make_jaxpr(lambda x, k: rd.build_jit(x, cfg, k))(_x(), _key())


def _trace_rnn_build_sharded():
    from repro.core import rnn_descent as rd
    cfg = _rnn_cfg()
    mesh = _mesh1()
    return jax.make_jaxpr(
        lambda x, k: rd.build(x, cfg, k, mesh=mesh))(_x(), _key())


def _trace_rnn_build_pallas():
    from repro.core import rnn_descent as rd
    cfg = _rnn_cfg(use_pallas=True, gram_dtype="bf16")
    return jax.make_jaxpr(lambda x, k: rd.build_jit(x, cfg, k))(_x(), _key())


def _trace_nn_build_jit():
    from repro.core import nn_descent as nnd
    cfg = _nn_cfg()
    return jax.make_jaxpr(lambda x, k: nnd.build_jit(x, cfg, k))(_x(), _key())


def _trace_nn_build_sharded():
    from repro.core import nn_descent as nnd
    cfg = _nn_cfg()
    mesh = _mesh1()
    return jax.make_jaxpr(
        lambda x, k: nnd.build(x, cfg, k, mesh=mesh))(_x(), _key())


def _trace_nsg_build():
    from repro.core import nsg_style as nsg
    cfg = _nsg_cfg()
    return jax.make_jaxpr(lambda x, k: nsg.build(x, cfg, k))(_x(), _key())


def _trace_nsg_build_sharded():
    """Device-side portion of shard.build_nsg_style: sharded knn + expand/
    cap + reverse edges. The final connectivity repair is a deliberate host
    round-trip (bitwise parity with single-device) and is audited through
    the unsharded ``core/nsg_style.build`` entry, which traces it."""
    from repro.core import shard
    cfg = _nsg_cfg()
    mesh = _mesh1()

    def device_side(x, k):
        knn = shard.build_nn_descent(x, cfg.knn, k, mesh)
        capped = shard._nsg_expand_cap(x, knn, cfg, mesh)
        return shard.add_reverse_edges(capped, cfg.r, mesh, cfg.n_buckets)

    return jax.make_jaxpr(device_side)(_x(), _key())


# ------------------------------------------------------------------- search
def _queries():
    return jax.ShapeDtypeStruct((B, D), jnp.float32)


def _trace_search():
    from repro.core import search as S
    cfg = _search_cfg()
    return jax.make_jaxpr(
        lambda x, g, q: S.search(x, g, q, jnp.int32(0), cfg)
    )(_x(), _graph(), _queries())


def _trace_search_pallas():
    from repro.core import search as S
    cfg = _search_cfg(use_pallas=True, gram_dtype="bf16", kernel_tile_b=4)
    return jax.make_jaxpr(
        lambda x, g, q: S.search(x, g, q, jnp.int32(0), cfg)
    )(_x(), _graph(), _queries())


def _trace_search_tiled():
    from repro.core import search as S
    cfg = _search_cfg()
    return jax.make_jaxpr(
        lambda x, g, q: S.search_tiled(x, g, q, jnp.int32(0), cfg, tile_b=2)
    )(_x(), _graph(), _queries())


def _trace_search_tiled_sharded():
    from repro.core import search as S
    cfg = _search_cfg()
    mesh = _mesh1()
    valid = jax.ShapeDtypeStruct((N,), jnp.bool_)
    return jax.make_jaxpr(
        lambda x, g, q, v: S.search_tiled(x, g, q, jnp.int32(0), cfg,
                                          tile_b=2, mesh=mesh, valid=v)
    )(_x(), _graph(), _queries(), valid)


def _trace_search_tiled_corpus():
    from repro.core import search as S
    cfg = _search_cfg()
    mesh = _mesh1()
    valid = jax.ShapeDtypeStruct((N,), jnp.bool_)
    return jax.make_jaxpr(
        lambda x, g, q, v: S.search_tiled(x, g, q, jnp.int32(0), cfg,
                                          tile_b=2, mesh=mesh, valid=v,
                                          shard="corpus")
    )(_x(), _graph(), _queries(), valid)


def _trace_search_tiled_serving():
    """The serving dispatch program: fixed-shape tile with per-lane
    validity (vacant admission lanes masked, see repro.serving.frontend)."""
    from repro.core import search as S
    cfg = _search_cfg()
    lv = jax.ShapeDtypeStruct((B,), jnp.bool_)
    return jax.make_jaxpr(
        lambda x, g, q, m: S.search_tiled(x, g, q, jnp.int32(0), cfg,
                                          tile_b=2, lane_valid=m)
    )(_x(), _graph(), _queries(), lv)


def _trace_search_tiled_serving_corpus():
    from repro.core import search as S
    cfg = _search_cfg()
    mesh = _mesh1()
    valid = jax.ShapeDtypeStruct((N,), jnp.bool_)
    lv = jax.ShapeDtypeStruct((B,), jnp.bool_)
    return jax.make_jaxpr(
        lambda x, g, q, v, m: S.search_tiled(x, g, q, jnp.int32(0), cfg,
                                             tile_b=2, mesh=mesh, valid=v,
                                             shard="corpus", lane_valid=m)
    )(_x(), _graph(), _queries(), valid, lv)


def _qx_int8():
    from repro.quant import QuantizedCorpus
    return QuantizedCorpus(
        codes=jax.ShapeDtypeStruct((N, D), jnp.int8),
        scale=jax.ShapeDtypeStruct((D,), jnp.float32),
        zero=jax.ShapeDtypeStruct((D,), jnp.float32),
    )


def _qx_pq(m=2):
    from repro.quant import QuantizedCorpus
    return QuantizedCorpus(
        codes=jax.ShapeDtypeStruct((N, m), jnp.uint8),
        codebooks=jax.ShapeDtypeStruct((m, 256, D // m), jnp.float32),
    )


def _quant(mode, **kw):
    from repro.quant import Quantization
    return Quantization(mode=mode, **kw)


def _trace_search_int8():
    from repro.core import search as S
    cfg = _search_cfg(quant=_quant("int8", rerank_k=4))
    return jax.make_jaxpr(
        lambda x, g, q, qx: S.search(x, g, q, jnp.int32(0), cfg, qx=qx)
    )(_x(), _graph(), _queries(), _qx_int8())


def _trace_search_int8_pallas():
    from repro.core import search as S
    cfg = _search_cfg(quant=_quant("int8", rerank_k=4), use_pallas=True,
                      kernel_tile_b=4)
    return jax.make_jaxpr(
        lambda x, g, q, qx: S.search(x, g, q, jnp.int32(0), cfg, qx=qx)
    )(_x(), _graph(), _queries(), _qx_int8())


def _trace_search_pq():
    from repro.core import search as S
    cfg = _search_cfg(quant=_quant("pq", m=2, rerank_k=4))
    return jax.make_jaxpr(
        lambda x, g, q, qx: S.search(x, g, q, jnp.int32(0), cfg, qx=qx)
    )(_x(), _graph(), _queries(), _qx_pq())


def _trace_search_tiled_pq_pallas():
    from repro.core import search as S
    cfg = _search_cfg(quant=_quant("pq", m=2, rerank_k=4), use_pallas=True,
                      kernel_tile_b=4)
    return jax.make_jaxpr(
        lambda x, g, q, qx: S.search_tiled(x, g, q, jnp.int32(0), cfg,
                                           tile_b=2, qx=qx)
    )(_x(), _graph(), _queries(), _qx_pq())


def _trace_rnn_build_int8_pallas():
    from repro.core import rnn_descent as rd
    cfg = _rnn_cfg(use_pallas=True, quant=_quant("int8"))
    return jax.make_jaxpr(lambda x, k: rd.build_jit(x, cfg, k))(_x(), _key())


# ---------------------------------------------------------------- streaming
def _trace_streaming_insert():
    """The jitted insert body (`updates._graft`): the seeding search it rides
    on is audited by the search entries; compact/grow are host-level numpy
    shape changes with no traced program of their own (their cost shows up
    in the recompile guard instead)."""
    from repro.streaming import updates as U
    cfg = _stream_cfg()
    cap, b, k = N, B, cfg.seed_k
    args = (
        _x(), _graph(),
        jax.ShapeDtypeStruct((cap,), jnp.bool_),       # occupied
        jax.ShapeDtypeStruct((b, D), jnp.float32),     # new_x
        jax.ShapeDtypeStruct((b,), jnp.int32),         # slots
        jax.ShapeDtypeStruct((b, k), jnp.int32),       # cand_ids
        jax.ShapeDtypeStruct((b, k), jnp.float32),     # cand_d
    )
    f_pad = b * (1 + k)
    return jax.make_jaxpr(
        lambda x, g, occ, nx, sl, ci, cd: U._graft(
            x, g, occ, nx, sl, ci, cd, cfg, None, f_pad))(*args)


def _trace_streaming_delete():
    from repro.streaming import updates as U
    cfg = _stream_cfg()
    args = (
        _x(), _graph(),
        jax.ShapeDtypeStruct((N,), jnp.bool_),         # tombstones
        jax.ShapeDtypeStruct((8,), jnp.int32),         # affected rows (-1 pad)
    )
    return jax.make_jaxpr(
        lambda x, g, t, a: U._repair(x, g, t, a, cfg, None))(*args)


# ------------------------------------------------------------ fused kernels
def _kernel_entries():
    from repro.kernels import beam_score, fm_interact, pairwise_l2, rng_prune
    out = {}
    for mod, label in ((beam_score, "kernels/beam_score"),
                       (rng_prune, "kernels/rng_prune"),
                       (pairwise_l2, "kernels/pairwise_l2"),
                       (fm_interact, "kernels/fm_interact")):
        for spec in mod.default_specs():
            out[f"{label}[{spec.name.split('[', 1)[1]}"] = spec.trace
    return out


_REGISTRY = {
    "core/rnn_descent.build_jit": _trace_rnn_build_jit,
    "core/rnn_descent.build_jit@pallas": _trace_rnn_build_pallas,
    "core/rnn_descent.build@mesh": _trace_rnn_build_sharded,
    "core/nn_descent.build_jit": _trace_nn_build_jit,
    "core/nn_descent.build@mesh": _trace_nn_build_sharded,
    "core/nsg_style.build": _trace_nsg_build,
    "core/nsg_style.build@mesh": _trace_nsg_build_sharded,
    "core/rnn_descent.build_jit@int8-pallas": _trace_rnn_build_int8_pallas,
    "core/search.search": _trace_search,
    "core/search.search@pallas": _trace_search_pallas,
    "core/search.search@int8": _trace_search_int8,
    "core/search.search@int8-pallas": _trace_search_int8_pallas,
    "core/search.search@pq": _trace_search_pq,
    "core/search.search_tiled": _trace_search_tiled,
    "core/search.search_tiled@mesh": _trace_search_tiled_sharded,
    "core/search.search_tiled@corpus-mesh": _trace_search_tiled_corpus,
    "core/search.search_tiled@pq-pallas": _trace_search_tiled_pq_pallas,
    "core/search.search_tiled@serving-lanes": _trace_search_tiled_serving,
    "core/search.search_tiled@serving-lanes-corpus-mesh":
        _trace_search_tiled_serving_corpus,
    "streaming/updates.insert": _trace_streaming_insert,
    "streaming/updates.delete": _trace_streaming_delete,
}


def entries(names: list[str] | None = None):
    """name -> thunk returning a ClosedJaxpr. ``names`` filters by exact
    match or substring (so ``--only search`` selects all search variants)."""
    reg = dict(_REGISTRY)
    reg.update(_kernel_entries())
    if names:
        reg = {k: v for k, v in reg.items()
               if any(s == k or s in k for s in names)}
    return reg
