"""Quantization config + codecs + shared decode-and-score math.

Two compressed corpus formats, one config object:

int8 (scalar, per-dim asymmetric)
    ``codes (n, d) int8`` + ``scale (d,) f32`` + ``zero (d,) f32``;
    ``x_hat = codes * scale + zero``. 4x smaller than f32. The scoring path
    never materializes ``x_hat`` in HBM: gathered code blocks decode
    in-register (for ``ip`` the per-dim scale folds straight into the
    query side of the distance einsum).

pq (product quantization)
    ``d`` split into ``m`` subspaces, each vector stored as ``m`` uint8
    centroid indices into per-subspace codebooks ``(m, 256, d/m) f32``
    trained by seeded Lloyd iterations. ``n*m`` payload bytes — 4*d/m x
    smaller than f32 (d=128, m=32 -> 16x). Scoring gathers from a per-query
    LUT of query-to-centroid partial distances (:func:`pq_lut`, computed
    once per query tile) instead of decoding vectors at all.

Every function here is pure jnp so kernel bodies (Pallas, VMEM refs) and
jnp oracles call the *same* code on the same values — decode is
elementwise, so decode-after-gather in the kernel is bitwise-equal to
gather-after-decode in the oracle, and the parity tests can assert
equality, not tolerance.

Quantized distances are approximations; searches over codes finish with an
exact-f32 rerank tail (``Quantization.rerank_k``) in ``core/search.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.beam_score.ref import HIGHEST, score_block

MODES = ("f32", "bf16", "int8", "pq")

# int8 code range is symmetric [-127, 127] (254 steps): keeping -128 out
# makes the range symmetric around the zero-point so |decode error| <=
# scale/2 uniformly, and the reserved value survives future sentinel use.
_INT8_STEPS = 254.0
_INT8_HALF = 127.0


@dataclasses.dataclass(frozen=True)
class Quantization:
    """How the corpus is stored and scored. Hashable — lives inside the
    frozen builder/search configs as a static jit argument.

    ``mode``
        ``"f32"`` (uncompressed), ``"bf16"`` (half-width gathers — the
        pre-existing ``gram_dtype`` path, selectable here so one field
        covers the whole menu), ``"int8"``, or ``"pq"``.
    ``m``
        PQ subspace count (``d % m == 0``; payload is ``n*m`` bytes).
    ``pq_iters`` / ``pq_seed``
        Lloyd iteration count and the PRNG seed for centroid init —
        encoding is a pure function of ``(x, quant)``, so builders and
        serving call :func:`encode_corpus` independently and get bitwise
        identical codes.
    ``rerank_k``
        Width of the exact-f32 rerank tail applied to coded searches
        (0 disables; otherwise must be >= the search ``topk``).
    """

    mode: str = "f32"
    m: int = 16
    pq_iters: int = 8
    pq_seed: int = 0
    rerank_k: int = 64

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(
                f"quant.mode {self.mode!r} not in {MODES}")
        if self.m < 1:
            raise ValueError(f"quant.m must be >= 1, got {self.m}")
        if self.pq_iters < 1:
            raise ValueError(
                f"quant.pq_iters must be >= 1, got {self.pq_iters}")
        if self.rerank_k < 0:
            raise ValueError(
                f"quant.rerank_k must be >= 0, got {self.rerank_k}")

    @property
    def is_coded(self) -> bool:
        """True when the corpus is stored as codes (int8 / pq)."""
        return self.mode in ("int8", "pq")


class QuantizedCorpus(NamedTuple):
    """Runtime companion of :class:`Quantization`: the coded corpus.

    int8: ``codes (n, d) int8``, ``scale (d,) f32``, ``zero (d,) f32``.
    pq:   ``codes (n, m) uint8``, ``codebooks (m, 256, d/m) f32``.
    Unused fields are ``None`` (leafless under jit, absent from
    checkpoints — restore discriminates formats by manifest leaf names).
    """

    codes: Any
    scale: Any = None
    zero: Any = None
    codebooks: Any = None

    @property
    def mode(self) -> str:
        return "pq" if self.codebooks is not None else "int8"


# ----------------------------------------------------------------- int8 codec
def encode_int8_rows(x: jnp.ndarray, scale: jnp.ndarray,
                     zero: jnp.ndarray) -> jnp.ndarray:
    """Encode rows against frozen ``scale``/``zero`` (streaming inserts use
    this so new rows join an existing code space)."""
    q = jnp.round((x.astype(jnp.float32) - zero) / scale)
    return jnp.clip(q, -_INT8_HALF, _INT8_HALF).astype(jnp.int8)


def quantize_int8(x: jnp.ndarray,
                  valid: jnp.ndarray | None = None) -> QuantizedCorpus:
    """Per-dim asymmetric int8: range from the (optionally masked) rows,
    codes for every row. ``valid`` keeps capacity padding / tombstones out
    of the range statistics without excluding them from the code array."""
    xf = x.astype(jnp.float32)
    if valid is None:
        lo = jnp.min(xf, axis=0)
        hi = jnp.max(xf, axis=0)
    else:
        v = valid[:, None]
        lo = jnp.min(jnp.where(v, xf, jnp.inf), axis=0)
        hi = jnp.max(jnp.where(v, xf, -jnp.inf), axis=0)
    lo = jnp.where(jnp.isfinite(lo), lo, 0.0)
    hi = jnp.where(jnp.isfinite(hi), hi, 0.0)
    scale = jnp.maximum(hi - lo, 1e-8) / _INT8_STEPS
    zero = lo + _INT8_HALF * scale
    return QuantizedCorpus(codes=encode_int8_rows(xf, scale, zero),
                           scale=scale, zero=zero)


def int8_decode(codes: jnp.ndarray, scale: jnp.ndarray,
                zero: jnp.ndarray) -> jnp.ndarray:
    """``(..., d) int8 -> (..., d) f32``. Elementwise, so it commutes with
    row gathers — the bitwise-parity keystone for the int8 kernels."""
    return codes.astype(jnp.float32) * scale + zero


# ------------------------------------------------------------------- pq codec
def train_pq(x: jnp.ndarray, m: int, iters: int = 8,
             seed: int = 0) -> jnp.ndarray:
    """Seeded Lloyd k-means per subspace -> codebooks (m, 256, d/m) f32.
    Empty clusters keep their previous centroid (the standard fix that
    keeps the iteration well-defined when n < 256 or clusters collapse)."""
    n, d = x.shape
    if d % m != 0:
        raise ValueError(f"pq requires d % m == 0, got d={d}, m={m}")
    dsub = d // m
    xs = jnp.transpose(x.astype(jnp.float32).reshape(n, m, dsub),
                       (1, 0, 2))                       # (m, n, dsub)
    key = jax.random.PRNGKey(seed)
    perm = jax.random.permutation(key, n)
    init_idx = perm[jnp.arange(256) % n]                # distinct when n>=256
    cents = xs[:, init_idx, :]                          # (m, 256, dsub)

    def assign(data, cent):
        # (n, dsub) x (256, dsub) -> (n,) argmin over squared distance;
        # ||data||^2 is constant per point and dropped from the argmin.
        dot = jnp.einsum("nd,cd->nc", data, cent,
                         preferred_element_type=jnp.float32,
                         precision=HIGHEST)
        csq = jnp.einsum("cd,cd->c", cent, cent,
                         preferred_element_type=jnp.float32,
                         precision=HIGHEST)
        return jnp.argmin(csq[None, :] - 2.0 * dot, axis=1)

    def lloyd_step(_, cent):
        def one(data, c):
            a = assign(data, c)
            onehot = (a[:, None] == jnp.arange(256)[None, :]).astype(
                jnp.float32)                            # (n, 256)
            counts = jnp.sum(onehot, axis=0)            # (256,)
            sums = jnp.einsum("nc,nd->cd", onehot, data,
                              preferred_element_type=jnp.float32,
                              precision=HIGHEST)
            return jnp.where(counts[:, None] > 0,
                             sums / jnp.maximum(counts[:, None], 1.0), c)
        return jax.vmap(one)(xs, cent)

    return jax.lax.fori_loop(0, iters, lloyd_step, cents)


def encode_pq_rows(x: jnp.ndarray, codebooks: jnp.ndarray) -> jnp.ndarray:
    """(n, d) f32 x (m, 256, d/m) -> (n, m) uint8 nearest-centroid codes."""
    n, d = x.shape
    m, _, dsub = codebooks.shape
    xs = x.astype(jnp.float32).reshape(n, m, dsub)
    cb = codebooks.astype(jnp.float32)
    dot = jnp.einsum("nmd,mcd->nmc", xs, cb,
                     preferred_element_type=jnp.float32,
                     precision=HIGHEST)
    csq = jnp.einsum("mcd,mcd->mc", cb, cb,
                     preferred_element_type=jnp.float32,
                     precision=HIGHEST)
    return jnp.argmin(csq[None] - 2.0 * dot, axis=2).astype(jnp.uint8)


def decode_pq(codes: jnp.ndarray, codebooks: jnp.ndarray) -> jnp.ndarray:
    """(..., m) uint8 -> (..., d) f32 centroid reconstruction."""
    m, _, dsub = codebooks.shape
    # per-subspace centroid rows: codebooks[s, codes[..., s], :]
    sub = jax.vmap(lambda cb, c: cb[c], in_axes=(0, -1),
                   out_axes=-2)(codebooks, codes.astype(jnp.int32))
    return sub.reshape(codes.shape[:-1] + (m * dsub,))


# ------------------------------------------------------- corpus-level helpers
def encode_corpus(x: jnp.ndarray, quant: Quantization,
                  train_rows: jnp.ndarray | None = None
                  ) -> QuantizedCorpus | None:
    """Encode the whole corpus under ``quant``. Deterministic in
    ``(x, quant)`` — builders and serving each call this and get identical
    codes. ``train_rows`` optionally restricts range / codebook training to
    a row subset (streaming stores pass their live rows so capacity padding
    doesn't distort the statistics); codes still cover every row of ``x``.
    Returns ``None`` for the uncoded modes (f32 / bf16)."""
    if quant.mode == "int8":
        if train_rows is None:
            return quantize_int8(x)
        ref = quantize_int8(train_rows)
        return QuantizedCorpus(
            codes=encode_int8_rows(x, ref.scale, ref.zero),
            scale=ref.scale, zero=ref.zero)
    if quant.mode == "pq":
        cb = train_pq(x if train_rows is None else train_rows,
                      quant.m, quant.pq_iters, quant.pq_seed)
        return QuantizedCorpus(codes=encode_pq_rows(x, cb), codebooks=cb)
    return None


def encode_rows(x_new: jnp.ndarray, qx: QuantizedCorpus) -> jnp.ndarray:
    """Encode new rows into an existing code space (frozen scale / zero /
    codebooks) — the streaming-insert path."""
    if qx.mode == "int8":
        return encode_int8_rows(x_new, qx.scale, qx.zero)
    return encode_pq_rows(x_new, qx.codebooks)


def dequantize(qx: QuantizedCorpus) -> jnp.ndarray:
    """Full decoded corpus ``x_hat`` (n, d) f32 — what builders construct
    the graph over, so build-time and serve-time geometry agree."""
    if qx.mode == "int8":
        return int8_decode(qx.codes, qx.scale, qx.zero)
    return decode_pq(qx.codes, qx.codebooks)


def prep_corpus(
    x: jnp.ndarray, quant: Quantization,
) -> tuple[jnp.ndarray, QuantizedCorpus | None]:
    """Build-time corpus prep shared by the three builders.

    Coded modes train/encode once and return ``(x_hat, qx)`` where ``x_hat``
    is the decoded reconstruction the builder's non-prune distance math runs
    over — the graph is built in the *quantized* geometry, so the index the
    coded search traverses was optimized for the distances it will actually
    see. ``qx`` is returned only for int8, where rnn_descent's fused prune
    gathers code rows and decodes in-register (PQ pruning decodes at entry:
    symmetric code-to-code PQ distances double the quantization noise inside
    the RNG inequality, so ``x_hat`` is the better geometry there). f32/bf16
    pass through untouched."""
    if not quant.is_coded:
        return x, None
    qx = encode_corpus(x, quant)
    x_hat = dequantize(qx)
    return x_hat, (qx if quant.mode == "int8" else None)


def corpus_bytes(qx: QuantizedCorpus | None, n: int, d: int) -> dict:
    """Memory accounting for the BENCH tables: per-row payload (codes)
    versus O(1) auxiliary parameters (scale/zero/codebooks), compared to
    the ``n*d*4`` f32 baseline."""
    f32 = n * d * 4
    if qx is None:
        return {"f32_bytes": f32, "codes_bytes": f32, "aux_bytes": 0,
                "payload_ratio": 1.0}
    codes = int(qx.codes.size) * qx.codes.dtype.itemsize
    aux = sum(int(a.size) * a.dtype.itemsize
              for a in (qx.scale, qx.zero, qx.codebooks) if a is not None)
    return {"f32_bytes": f32, "codes_bytes": codes, "aux_bytes": aux,
            "payload_ratio": f32 / codes}


# ------------------------------------------------- shared decode+score math
def int8_score_block(codes: jnp.ndarray, scale: jnp.ndarray,
                     zero: jnp.ndarray, q: jnp.ndarray,
                     metric: str) -> jnp.ndarray:
    """(..., K, d) int8 code block x (..., d) queries -> (..., K) f32
    distances. The single source for the int8 kernels and their oracles.

    The dequantize is a scale-multiply + zero-add on the upcast block,
    fused directly into the distance einsum's operand — the decoded block
    stays in-register (VMEM under Pallas); no ``x_hat`` intermediate ever
    reaches HBM. Algebraically-reassociated forms (e.g. folding ``scale``
    into the query side for ``ip``) are deliberately avoided: they change
    which FMA contractions XLA may pick per fusion context, breaking the
    bitwise fused-vs-oracle parity this function exists to guarantee."""
    return score_block(codes.astype(jnp.float32) * scale + zero,
                       q.astype(jnp.float32), metric)


def pq_lut(queries: jnp.ndarray, codebooks: jnp.ndarray, metric: str
           ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-query-tile lookup tables of query-to-centroid partial scores —
    computed once, then candidate scoring is pure gather-accumulate.

    Returns ``(lut_a (B, m, 256), lut_b (m, 256), qsq (B,))``:

    - l2:  ``lut_a[b,s,c] = ||q_bs - C_sc||^2`` (clamped >= 0); sum over s
      is the exact squared distance to the decoded vector.
    - ip:  ``lut_a[b,s,c] = -(q_bs . C_sc)``.
    - cos: ``lut_a`` holds raw dots, ``lut_b[s,c] = ||C_sc||^2`` (query
      independent), ``qsq[b] = ||q_b||^2``; :func:`pq_score_codes`
      normalizes with the same 1e-12 guards as :func:`score_block`.
    """
    bsz = queries.shape[0]
    m, _, dsub = codebooks.shape
    qf = queries.astype(jnp.float32)
    qs = qf.reshape(bsz, m, dsub)
    cb = codebooks.astype(jnp.float32)
    dot = jnp.einsum("bmd,mcd->bmc", qs, cb,
                     preferred_element_type=jnp.float32,
                     precision=HIGHEST)
    csq = jnp.einsum("mcd,mcd->mc", cb, cb,
                     preferred_element_type=jnp.float32,
                     precision=HIGHEST)
    if metric == "l2":
        qsq_s = jnp.einsum("bmd,bmd->bm", qs, qs,
                           preferred_element_type=jnp.float32,
                           precision=HIGHEST)
        lut_a = jnp.maximum(qsq_s[..., None] + csq[None] - 2.0 * dot, 0.0)
        return lut_a, jnp.zeros_like(csq), jnp.zeros((bsz,), jnp.float32)
    if metric == "ip":
        return -dot, jnp.zeros_like(csq), jnp.zeros((bsz,), jnp.float32)
    if metric == "cos":
        qsq = jnp.einsum("bd,bd->b", qf, qf,
                         preferred_element_type=jnp.float32,
                         precision=HIGHEST)
        return dot, csq, qsq
    raise ValueError(f"unknown metric {metric!r}")


def pq_score_codes(codes: jnp.ndarray, lut_a: jnp.ndarray,
                   lut_b: jnp.ndarray, qsq: jnp.ndarray,
                   metric: str) -> jnp.ndarray:
    """(..., K, m) codes + :func:`pq_lut` tables -> (..., K) f32 distances.
    Pure gather-accumulate: no arithmetic ever touches the codes (they are
    table indices), which is why the pq kernel needs no dequantize step and
    the kernel spec declares no low-precision inputs."""
    c = codes.astype(jnp.int32)
    # lut_a (..., m, 256) broadcast-gathered at (..., K, m) indices
    terms = jnp.take_along_axis(lut_a[..., None, :, :], c[..., None],
                                axis=-1)[..., 0]        # (..., K, m)
    acc = jnp.sum(terms, axis=-1)                       # (..., K)
    if metric in ("l2", "ip"):
        return acc
    lb = lut_b.reshape((1,) * (c.ndim - 1) + lut_b.shape)
    vsq = jnp.sum(jnp.take_along_axis(lb, c[..., None], axis=-1)[..., 0],
                  axis=-1)                              # ||x_hat||^2
    qn = jnp.maximum(jnp.sqrt(qsq), 1e-12)[..., None]
    vn = jnp.maximum(jnp.sqrt(vsq), 1e-12)
    return 1.0 - acc / (qn * vn)
