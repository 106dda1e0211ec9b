"""Attention ops: reference softmax attention, a Pallas TPU
flash-attention kernel, and the online-softmax block primitives that
ring attention (singa_tpu/parallel/ring.py) stitches across chips.

The reference system predates transformers — no attention op exists
anywhere in it (layer registry, src/worker/neuralnet.cc:13-33) — so this
is a singa-tpu extension making long-context models first-class. The
kernels follow the standard flash recipe: process K/V blockwise with
running (max, sum, output) statistics per query block so the S x S
score matrix never materializes in HBM.

Each of the three kernels (fwd, dq, dkv) ships in two variants chosen
per call by K/V footprint (_variant): *staged* keeps the whole K/V in
VMEM per program (fastest while it fits), *streamed* keeps K/V in HBM
and double-buffers (D, block) slices through async DMA — VMEM holds
O(block), so sequence length is bounded by HBM, not VMEM.

All shapes are (batch, heads, seq, head_dim).
"""

from __future__ import annotations

import functools
import math
import os
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
) -> jnp.ndarray:
    """Reference dense attention: softmax(QK^T / sqrt(d)) V."""
    d = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        scores = jnp.where(mask, scores, NEG_INF)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)


# ---------------------------------------------------------------------
# online-softmax block math (shared by the Pallas kernel and ring
# attention): process one K/V block, fold into running (out, m, l)
# ---------------------------------------------------------------------


def block_attn_update(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    out: jnp.ndarray,
    m: jnp.ndarray,
    l: jnp.ndarray,
    *,
    q_offset=0,
    k_offset=0,
    causal: bool = False,
):
    """Fold one K/V block into running flash statistics.

    q (..., Sq, D); k/v (..., Sk, D); out (..., Sq, D) unnormalized;
    m/l (..., Sq) running rowmax / normalizer. Offsets give the global
    positions of the local blocks so causal masking works when the
    sequence is sharded (ring attention) or blocked (the kernel).
    Returns the updated (out, m, l).
    """
    d = q.shape[-1]
    scores = jnp.einsum("...qd,...kd->...qk", q, k) / math.sqrt(d)
    if causal:
        qpos = q_offset + jnp.arange(q.shape[-2])
        kpos = k_offset + jnp.arange(k.shape[-2])
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask, scores, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    if causal:
        p = jnp.where(mask, p, 0.0)
    out = out * alpha[..., None] + jnp.einsum("...qk,...kd->...qd", p, v)
    l = l * alpha + jnp.sum(p, axis=-1)
    return out, m_new, l


def block_attn_init(q_like: jnp.ndarray):
    """Zero-state (out, m, l) for block_attn_update accumulation.

    Derived arithmetically from ``q_like`` (not via zeros()) so that
    under shard_map the state inherits q's varying-axis type and can
    serve as a fori_loop carry (JAX's vma tracking)."""
    out = q_like * 0.0
    m = q_like[..., 0] * 0.0 + NEG_INF
    l = q_like[..., 0] * 0.0
    return out, m, l


def block_attn_finish(out, m, l):
    """Normalize accumulated output (fully-masked rows emit zeros)."""
    safe = jnp.where(l == 0.0, 1.0, l)
    return out / safe[..., None]


# ---------------------------------------------------------------------
# Pallas flash-attention kernel
# ---------------------------------------------------------------------


def _causal_nlive(q_offset, bq, block_k):
    """Number of K blocks at or below a q block's diagonal — the causal
    loop bound every kernel shares."""
    return jax.lax.div(q_offset + bq - 1, block_k) + 1


def _causal_first(k_offset, block_q):
    """First q block that can see a k block (dkv kernels' loop start)."""
    return jax.lax.div(k_offset, block_q)


def _causal_mask(q_offset, bq, k_offset, bk, transposed=False):
    """(Bq, Bk) keep-mask qpos >= kpos; (Bk, Bq) when ``transposed``."""
    qpos = q_offset + jnp.arange(bq)
    kpos = k_offset + jnp.arange(bk)
    if transposed:
        return qpos[None, :] >= kpos[:, None]
    return qpos[:, None] >= kpos[None, :]


def _stream(hbm, buf, sem, bh_idx):
    """Double-buffered HBM->VMEM block streamer along the LAST axis of
    ``hbm[bh_idx]``.

    Streamed arrays put the sequence on the minor (lane) dimension so
    every block slice is 128-aligned: K/V/Q/dO stream in transposed
    (BH, D, S) layout (D=64 rides the 8-tiled sublanes — slicing the
    64-wide minor dim of an (S, D) layout trips Mosaic's 128-lane tile
    alignment), lse/delta rows in their native (BH, 1, S). ``buf`` is
    (2, rows, block) VMEM scratch — the slot dim must stay a leading
    batch dim (slicing a tiled sublane dim at width 1 is rejected), so
    row vectors buffer as (2, 1, block). ``sem`` is a (2,) DMA
    semaphore array. Returns (start, wait) taking (block_idx, slot).
    """
    block = buf.shape[-1]

    def src(blk):
        return hbm.at[bh_idx, :, pl.ds(blk * block, block)]

    def start(blk, slot):
        pltpu.make_async_copy(src(blk), buf.at[slot], sem.at[slot]).start()

    def wait(blk, slot):
        pltpu.make_async_copy(src(blk), buf.at[slot], sem.at[slot]).wait()

    return start, wait


def _db_loop(lo, hi, streams, compute):
    """Run ``compute(blk, slot, carry)`` over blocks [lo, hi) with all
    ``streams`` ((start, wait) pairs) double-buffered: block i+1's DMA
    is in flight while block i computes."""

    def starts(blk, slot):
        for s, _ in streams:
            s(blk, slot)

    def body(blk, carry):
        slot = jax.lax.rem(blk, 2)

        @pl.when(blk + 1 < hi)
        def _prefetch():
            starts(blk + 1, jax.lax.rem(blk + 1, 2))

        for _, w in streams:
            w(blk, slot)
        return compute(blk, slot, carry)

    starts(lo, jax.lax.rem(lo, 2))
    return lambda carry: jax.lax.fori_loop(lo, hi, body, carry)


def _flash_kernel(
    q_ref, k_hbm, v_hbm, o_ref, lse_ref, kbuf, vbuf, ksem, vsem,
    *, causal, block_k,
):
    """One (batch*head, q-block) program; K/V stream from HBM.

    K^T/V^T live in HBM ((BH, D, S) layout — see _stream) and are
    pulled one (D, block_k) block at a time through double-buffered
    async DMA — VMEM holds O(block), never O(S), so S is bounded by HBM
    capacity, not VMEM (the r3 kernel staged the full K/V per program,
    capping S near 64k). The causal loop bound skips fully-masked K
    blocks entirely — their DMA never starts (a 3-D-grid formulation
    measured ~2x slower here: dead blocks still pay DMA + grid latency).
    The transposed layout also makes every matmul the natural MXU
    orientation: q @ kt for scores, minor-minor contraction for p @ v.
    lse is laid out (BH, 1, S) so every block index is static and
    lane-aligned (Mosaic rejects dynamic sublane loads).
    """
    i = pl.program_id(0)
    qi = pl.program_id(1)
    bq = q_ref.shape[1]
    seq_k = k_hbm.shape[2]
    nk = seq_k // block_k
    q_offset = qi * bq
    if causal:
        nlive = _causal_nlive(q_offset, bq, block_k)
    else:
        nlive = nk

    q = q_ref[0].astype(jnp.float32)
    scale = 1.0 / math.sqrt(q.shape[-1])
    kst = _stream(k_hbm, kbuf, ksem, i)
    vst = _stream(v_hbm, vbuf, vsem, i)

    def compute(blk, slot, carry):
        out, m, l = carry
        kt = kbuf[slot].astype(jnp.float32)  # (D, Bk)
        vt = vbuf[slot].astype(jnp.float32)
        s = (q @ kt) * scale  # (Bq, Bk)
        if causal:
            mask = _causal_mask(q_offset, bq, blk * block_k, block_k)
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        # p @ v: contract Bk (minor of p) with Bk (minor of vt)
        pv = jax.lax.dot_general(p, vt, (((1,), (1,)), ((), ())))
        out = out * alpha[:, None] + pv
        l = l * alpha + jnp.sum(p, axis=-1)
        return out, m_new, l

    out, m, l = _db_loop(0, nlive, [kst, vst], compute)(block_attn_init(q))
    o_ref[0] = block_attn_finish(out, m, l).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(jnp.where(l == 0.0, 1.0, l))


def _flash_bwd_dq_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_hbm, v_hbm, dq_ref,
    kbuf, vbuf, ksem, vsem, *, causal, block_k, scale,
):
    """dQ for one (batch*head, q-block) program; K^T/V^T stream from HBM.

    FlashAttention backward recurrences: P = exp(S - lse),
    dS = P * (dO V^T - D) with D = rowsum(dO * O), dQ = dS K * scale.
    D arrives precomputed per row (like lse) so neither backward kernel
    redoes the rowsum. Same double-buffered streaming + exact causal
    loop bound as the forward.
    """
    i = pl.program_id(0)
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]  # D, (Bq,)
    bq, d = q.shape
    seq_k = k_hbm.shape[2]
    q_offset = qi * bq
    if causal:
        nlive = _causal_nlive(q_offset, bq, block_k)
    else:
        nlive = seq_k // block_k

    kst = _stream(k_hbm, kbuf, ksem, i)
    vst = _stream(v_hbm, vbuf, vsem, i)

    def compute(blk, slot, dq):
        kt = kbuf[slot].astype(jnp.float32)  # (D, Bk)
        vt = vbuf[slot].astype(jnp.float32)
        s = (q @ kt) * scale
        if causal:
            mask = _causal_mask(q_offset, bq, blk * block_k, block_k)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        ds = p * (do @ vt - delta[:, None])
        # ds @ k: contract Bk (minor of ds) with Bk (minor of kt)
        dsk = jax.lax.dot_general(ds, kt, (((1,), (1,)), ((), ())))
        return dq + dsk * scale

    dq = _db_loop(0, nlive, [kst, vst], compute)(
        jnp.zeros((bq, d), dtype=jnp.float32)
    )
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    k_ref, v_ref, q_hbm, do_hbm, lse_hbm, delta_hbm, dk_ref, dv_ref,
    qbuf, dobuf, lsebuf, dbuf, qsem, dosem, lsesem, dsem,
    *, causal, block_q, scale,
):
    """dK/dV for one (batch*head, k-block) program; Q^T/dO^T/lse/D
    stream from HBM.

    dV = P^T dO; dK = (P * (dO V^T - D))^T Q * scale. With Q/dO
    streaming in transposed (D, Bq) blocks, the kernel works on the
    TRANSPOSED score matrix s_t[kk, qq] directly — k @ qt is the
    natural orientation, and both accumulations contract the shared Bq
    minor dim. The causal loop starts at the first q block that can see
    this k block — earlier blocks' DMA never starts.
    """
    i = pl.program_id(0)
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    bk, d = k.shape
    seq_q = q_hbm.shape[2]
    nq = seq_q // block_q
    k_offset = ki * bk
    first = _causal_first(k_offset, block_q) if causal else 0

    streams = [
        _stream(q_hbm, qbuf, qsem, i),
        _stream(do_hbm, dobuf, dosem, i),
        _stream(lse_hbm, lsebuf, lsesem, i),
        _stream(delta_hbm, dbuf, dsem, i),
    ]

    def compute(blk, slot, carry):
        dk, dv = carry
        qt = qbuf[slot].astype(jnp.float32)  # (D, Bq)
        dot = dobuf[slot].astype(jnp.float32)
        lse = lsebuf[slot][0]  # (Bq,)
        delta = dbuf[slot][0]
        s_t = (k @ qt) * scale  # (Bk, Bq): transposed scores
        if causal:
            mask = _causal_mask(
                blk * block_q, block_q, k_offset, bk, transposed=True
            )
            s_t = jnp.where(mask, s_t, NEG_INF)
        p_t = jnp.exp(s_t - lse[None, :])  # (Bk, Bq)
        # dO V^T transposed = V dO^T: (Bk, D) @ (D, Bq)
        ds_t = p_t * (v @ dot - delta[None, :])
        # contract Bq (minor of both): dk += ds^T q, dv += p^T do
        dk = dk + jax.lax.dot_general(
            ds_t, qt, (((1,), (1,)), ((), ()))
        ) * scale
        dv = dv + jax.lax.dot_general(p_t, dot, (((1,), (1,)), ((), ())))
        return dk, dv

    zeros = jnp.zeros((bk, d), dtype=jnp.float32)
    dk, dv = _db_loop(first, nq, streams, compute)((zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ------------------- staged-K/V kernel variants -----------------------
# For sequences whose K/V fit a VMEM budget, staging the whole K/V per
# program (grid-pipelined BlockSpec, pl.ds loads) beats HBM streaming:
# measured f+b at S=8192 (v5e, 8 heads, d=64): staged 4.9 ms vs
# streamed 10.4 ms — short live ranges don't amortize per-block DMA.
# Past the budget the streamed kernels take over (S is then bounded by
# HBM, not VMEM): streamed 46-50 TF/s at S=32k-131k where staged
# cannot run at all. Selection in _variant().


def _flash_kernel_staged(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, block_k, seq_k
):
    """One (batch*head, q-block) program; K/V staged whole in VMEM."""
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    bq, d = q.shape
    nblocks = seq_k // block_k
    q_offset = qi * bq

    def body(i, carry):
        out, m, l = carry
        k = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        return block_attn_update(
            q, k, v, out, m, l,
            q_offset=q_offset, k_offset=i * block_k, causal=causal,
        )

    if causal:
        nlive = _causal_nlive(q_offset, bq, block_k)
    else:
        nlive = nblocks
    out, m, l = jax.lax.fori_loop(
        0, nlive, body,
        (jnp.zeros((bq, d), jnp.float32),
         jnp.full((bq,), NEG_INF, jnp.float32),
         jnp.zeros((bq,), jnp.float32)),
    )
    o_ref[0] = block_attn_finish(out, m, l).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(jnp.where(l == 0.0, 1.0, l))


def _flash_bwd_dq_staged(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    *, causal, block_k, seq_k, scale,
):
    """dQ for one (batch*head, q-block) program; K/V staged in VMEM."""
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    bq, d = q.shape
    q_offset = qi * bq

    def body(i, dq):
        k = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = (q @ k.T) * scale
        if causal:
            mask = _causal_mask(q_offset, bq, i * block_k, block_k)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        ds = p * (do @ v.T - delta[:, None])
        return dq + (ds @ k) * scale

    if causal:
        nlive = _causal_nlive(q_offset, bq, block_k)
    else:
        nlive = seq_k // block_k
    dq = jax.lax.fori_loop(0, nlive, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_staged(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, causal, block_q, seq_q, scale,
):
    """dK/dV for one (batch*head, k-block) program; Q/dO staged in VMEM."""
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    bk, d = k.shape
    k_offset = ki * bk

    def body(j, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(j * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(j * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(j * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(j * block_q, block_q)]
        s = (q @ k.T) * scale
        if causal:
            mask = _causal_mask(j * block_q, block_q, k_offset, bk)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        ds = p * (do @ v.T - delta[:, None])
        return dk + (ds.T @ q) * scale, dv + p.T @ do

    nblocks = seq_q // block_q
    first = _causal_first(k_offset, block_q) if causal else 0
    zeros = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(first, nblocks, body, (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


#: VMEM staging budget, read ONCE at import: _variant runs at trace
#: time from both the fwd and bwd custom-vjp halves, and jit caches are
#: not keyed on env vars — a mid-process change would leave stale
#: compilations (or mismatched fwd/bwd variants). Fixing it per process
#: keeps variant selection stable.
_FLASH_STAGE_BYTES = (
    float(os.environ.get("SINGA_TPU_FLASH_STAGE_MB", "8")) * 1e6
)


def _variant(s: int, d: int, dtype) -> str:
    """'staged' while K+V for one head row fit the VMEM budget
    (SINGA_TPU_FLASH_STAGE_MB, import-time), else 'streamed'."""
    kv_bytes = 2 * s * d * jnp.dtype(dtype).itemsize
    return "staged" if kv_bytes <= _FLASH_STAGE_BYTES else "streamed"


def _auto_block(s: int) -> int:
    """Largest supported block size dividing S. Measured on TPU v5e
    (S=8192, fwd+bwd): 512-blocks run 4.4x faster than 128-blocks —
    fewer grid programs, longer MXU-resident loops; VMEM per program
    stays small (a 512 x 64 fp32 tile is 128 KB)."""
    for b in (512, 256, 128):
        if s % b == 0:
            return b
    return 128  # _use_kernel rejects non-128-divisible S anyway


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(
    q, k, v, causal=False, block_q=None, block_k=None, interpret=None
):
    """Flash attention: Pallas forward AND backward.

    Falls back to the dense reference when the sequence does not tile
    evenly or Sq != Sk (announced by a warning on a TPU, see
    ``_use_kernel``). ``interpret=True`` runs the kernels in the Pallas
    interpreter (CPU testing); the default compiles them on a TPU and
    runs dense elsewhere. Block sizes default to _auto_block(S); pass
    explicit values to override.

    Training memory is O(S) per head row (out + lse residuals) instead
    of the dense O(S^2): the backward recomputes P blockwise from
    (q, k, v, lse) inside its own kernels.
    """
    out, _ = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret)
    return out


def _use_kernel(q, k, block_q, block_k, interpret):
    """Whether the Pallas kernels serve this call. ``interpret=None``
    decides from the platform: compiled on a TPU, the dense reference
    elsewhere. A TPU call the kernel cannot serve says so at trace time
    — a dense S x S score tensor where the caller asked for the kernel
    must not arrive unannounced."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None and not on_tpu:
        return False
    s = q.shape[2]
    reason = None
    if s != k.shape[2]:
        reason = f"Sq {s} != Sk {k.shape[2]} (the kernel assumes Sq == Sk)"
    elif s % block_q or s % block_k:
        reason = f"S {s} does not tile by blocks ({block_q}, {block_k})"
    elif not interpret and (block_q % 128 or block_k % 128):
        # Mosaic requires lane blocks in multiples of 128: the lse lane
        # dimension is blocked by block_q, and the streamed variant
        # slices the lane (S) dim of the transposed K/V in block_k
        # chunks (the interpreter is laxer — tests exercise smaller
        # geometries there)
        reason = (
            f"blocks ({block_q}, {block_k}) are not multiples of the "
            "128-lane tile"
        )
    if reason is None:
        return True
    if on_tpu and not interpret:
        warnings.warn(
            f"flash_attention runs the DENSE reference on this TPU: {reason}",
            RuntimeWarning,
            stacklevel=2,
        )
    return False


def _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret):
    """-> (out, lse | None); lse None means the dense fallback ran."""
    block_q = block_q or _auto_block(q.shape[2])
    block_k = block_k or _auto_block(k.shape[2])
    if not _use_kernel(q, k, block_q, block_k, interpret):
        return attention(q, k, v, causal=causal), None
    b, h, s, d = q.shape
    bh = b * h
    qf = q.reshape(bh, s, d)
    kf = k.reshape(bh, s, d)
    vf = v.reshape(bh, s, d)
    qblk = pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0))
    lse_blk = pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j))
    out_shape = [
        jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
    ]
    if _variant(s, d, k.dtype) == "staged":
        out, lse = pl.pallas_call(
            functools.partial(
                _flash_kernel_staged,
                causal=causal, block_k=block_k, seq_k=s,
            ),
            grid=(bh, s // block_q),
            in_specs=[
                qblk,
                pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0)),
            ],
            out_specs=[qblk, lse_blk],
            out_shape=out_shape,
            interpret=bool(interpret),
            name="flash_fwd",
        )(qf, kf, vf)
        return out.reshape(b, h, s, d), lse
    # streamed: K/V stay in HBM in transposed (BH, D, S) layout (see
    # _stream); the transposes are one XLA pass over K/V, outside the
    # kernel
    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, block_k=block_k),
        grid=(bh, s // block_q),
        in_specs=[
            qblk,
            pl.BlockSpec(memory_space=pltpu.HBM),  # K^T stays in HBM
            pl.BlockSpec(memory_space=pltpu.HBM),  # V^T stays in HBM
        ],
        out_specs=[qblk, lse_blk],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((2, d, block_k), k.dtype),
            pltpu.VMEM((2, d, block_k), v.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=bool(interpret),
        name="flash_fwd",
    )(qf, jnp.swapaxes(kf, 1, 2), jnp.swapaxes(vf, 1, 2))
    return out.reshape(b, h, s, d), lse


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    # resolve auto blocks exactly as the forward did (same S)
    block_q = block_q or _auto_block(q.shape[2])
    block_k = block_k or _auto_block(k.shape[2])
    if lse is None:
        # dense fallback path: recompute through the reference math
        _, vjp = jax.vjp(
            lambda q, k, v: attention(q, k, v, causal=causal), q, k, v
        )
        return vjp(g)
    b, h, s, d = q.shape
    bh = b * h
    scale = 1.0 / math.sqrt(d)
    flat = lambda x: x.reshape(bh, s, d)  # noqa: E731
    # D = rowsum(dO * O), computed ONCE per row and fed to both kernels
    # laid out (BH, 1, S) like lse
    delta = jnp.sum(
        flat(g).astype(jnp.float32) * flat(out).astype(jnp.float32),
        axis=-1,
    )[:, None, :]
    qspec = pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    lse_blk = pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j))
    if _variant(s, d, k.dtype) == "staged":
        args = (flat(q), flat(k), flat(v), flat(g), lse, delta)
        full = pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0))
        lse_full = pl.BlockSpec((1, 1, s), lambda i, j: (i, 0, 0))
        dq = pl.pallas_call(
            functools.partial(
                _flash_bwd_dq_staged,
                causal=causal, block_k=block_k, seq_k=s, scale=scale,
            ),
            grid=(bh, s // block_q),
            in_specs=[qspec, full, full, qspec, lse_blk, lse_blk],
            out_specs=qspec,
            out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            interpret=bool(interpret),
            name="flash_bwd_dq",
        )(*args)
        dk, dv = pl.pallas_call(
            functools.partial(
                _flash_bwd_dkv_staged,
                causal=causal, block_q=block_q, seq_q=s, scale=scale,
            ),
            grid=(bh, s // block_k),
            in_specs=[full, kspec, kspec, full, lse_full, lse_full],
            out_specs=[kspec, kspec],
            out_shape=[
                jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                jax.ShapeDtypeStruct((bh, s, d), v.dtype),
            ],
            interpret=bool(interpret),
            name="flash_bwd_dkv",
        )(*args)
        unflat = lambda x: x.reshape(b, h, s, d)  # noqa: E731
        return unflat(dq), unflat(dk), unflat(dv)
    kt = jnp.swapaxes(flat(k), 1, 2)  # streamed layouts (see _stream)
    vt = jnp.swapaxes(flat(v), 1, 2)
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel,
            causal=causal, block_k=block_k, scale=scale,
        ),
        grid=(bh, s // block_q),
        in_specs=[qspec, qspec, lse_blk, lse_blk, hbm, hbm],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, d, block_k), k.dtype),
            pltpu.VMEM((2, d, block_k), v.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=bool(interpret),
        name="flash_bwd_dq",
    )(flat(q), flat(g), lse, delta, kt, vt)
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel,
            causal=causal, block_q=block_q, scale=scale,
        ),
        grid=(bh, s // block_k),
        in_specs=[kspec, kspec, hbm, hbm, hbm, hbm],
        out_specs=[kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, d, block_q), q.dtype),
            pltpu.VMEM((2, d, block_q), g.dtype),
            pltpu.VMEM((2, 1, block_q), jnp.float32),
            pltpu.VMEM((2, 1, block_q), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=bool(interpret),
        name="flash_bwd_dkv",
    )(
        flat(k), flat(v),
        jnp.swapaxes(flat(q), 1, 2), jnp.swapaxes(flat(g), 1, 2),
        lse, delta,
    )
    unflat = lambda x: x.reshape(b, h, s, d)  # noqa: E731
    return unflat(dq), unflat(dk), unflat(dv)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def auto_attention(q, k, v, *, causal=False, n_devices=1):
    """Pick dense vs the Pallas kernel by score-tensor footprint.

    XLA's fused dense attention serves every size where the S x S
    score tensor comfortably fits HBM; the kernel's job is the
    long-context regime where dense would blow memory. The footprint
    estimate is per device (fwd+bwd fp32 scores / ``n_devices`` — pass
    the mesh size when batch/seq dims are sharded); the threshold is
    SINGA_TPU_DENSE_ATTN_MB (default 512).
    """
    import os

    b, h, s, _ = q.shape
    scores_mb = b * h * s * s * 4 * 2 / 1e6 / max(1, n_devices)
    if scores_mb <= float(os.environ.get("SINGA_TPU_DENSE_ATTN_MB", "512")):
        return attention(q, k, v, causal=causal)
    return flash_attention(q, k, v, causal)
