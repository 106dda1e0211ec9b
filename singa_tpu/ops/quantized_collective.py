"""True int8-on-the-wire gradient collectives: a quantized ring reduce.

PR 8's ``grad_comm`` block models quantized gradient reduction — each
bucket is cast to a scaled int8/bf16 wire value *around* the data-axis
reduction — but the documented carry-over stands: the cast sits on the
logical (already-summed) gradient, so XLA's implicit GSPMD ``psum`` /
reduce-scatter still moves full-precision bytes. The wire is not
actually 4x narrower. EQuARX (PAPERS.md, arxiv 2506.17615) shows the
win comes from keeping the *reduction itself* in the quantized domain.

This module is that reduction: a ring reduce-scatter + allgather over
the data axis whose wire value is genuinely int8. It runs per shard
under ``shard_map`` (``parallel/ring.py``'s ppermute ring is the
structural precedent), so each shard holds its own LOCAL partial
gradient — the thing GSPMD never exposes — and every hop
``lax.ppermute``s a *quantized* chunk, with the bucket's f32 scale
riding alongside as a tiny scalar operand:

  reduce-scatter   each param's gradient is chunked over the data axis
                   (``chunk_dims``); at hop t every shard quantizes its
                   accumulated chunk (one symmetric max-abs scale per
                   BUCKET — the grad_comm scale granularity), ppermutes
                   the int8 bytes + the scale one hop, dequantizes what
                   arrives, and accumulates its own local partial of
                   that chunk in f32 — the EQuARX two-level
                   construction: narrow on the wire, full precision in
                   the accumulator.
  allgather        after N-1 hops each shard owns its chunk's full sum;
                   the owner quantizes it ONCE (banking the
                   quantization error as the error-feedback residual)
                   and the (q, scale) pair rides N-1 more hops around
                   the ring — every shard dequantizes the identical
                   bytes, so the gathered gradient is bitwise identical
                   on every shard. Under ``zero_update`` this phase is
                   skipped: the ring's natural scatter output IS the
                   update layout (each shard keeps exactly its
                   shard-local chunk).

Error feedback (the one-shot-EF caveat): PR 8's reference path banks
the ENTIRE compression error — quantization there is one shot on the
summed gradient. The ring re-quantizes per hop, and a hop's rounding
error is only known to the shard that rounded, for a chunk it does not
own — so the residual banks the final (owner-side) quantization error
exactly, in full f32, while per-hop wire errors go un-fed-back. They
are bounded by the same 1/127 relative scale and convergence stays
within the CI parity bar (tools/convergence.py ``--grad_comm q8wire``);
the trade is documented in README "Kernels".

NaN-poisoned-scale semantics are preserved: a NaN/Inf partial drives
its bucket's max-abs scale to NaN, dequantization multiplies by the
scale, and the poison propagates through every downstream accumulation
— the divergence guard's verdict over the reduced grads fires on the
same step as fp32.

The pure-ppermute form here is plain XLA ops — the interpret/CPU-CI
path that every test run exercises. ``fused_hop`` swaps the per-hop
dequantize+accumulate onto a small Pallas kernel for real hardware
(``quant_acc``), gated by the same ``fusable``-style geometry predicate
pattern as the paged-attention kernel (``ring_fusable``).

Hierarchical two-level form (``kernels { grad_allreduce: q8_hier }``):
EQuARX's deployment topology is not one flat ring — it is fast
intra-slice ICI feeding ONE scarce inter-slice DCN hop, and the int8
saving matters exactly on the scarce hop. ``hier_ring_geometry``
factors the n-wide data reduction as K (intra) x M (inter): rank
r = g*K + p runs

  intra reduce-scatter   K-1 hops over the fast axis in FULL f32 (ICI
                         bandwidth is cheap; no quantization error is
                         introduced where it buys nothing), piece-major
                         — after K-1 hops rank (g, p) holds the
                         group-local sum of every chunk at position p,
                         an (M, chunk) plane.
  inter quantized ring   M-1 hops over the scarce axis with the SAME
                         int8 + per-bucket-scale + dequant/accumulate/
                         requant discipline as the flat ring — rank
                         (g, p) finishes owning the global sum of chunk
                         g*K + p, the identical post-scatter state as
                         the flat ring, so error feedback and the
                         owner-side final quantize are literally shared
                         code.
  two-level allgather    the (int8 bytes, scale) pairs ride M-1 inter
                         hops then K-1 intra hops (whole plane at a
                         time), every rank dequantizes identical bytes
                         — the gathered gradient stays bitwise
                         ring-invariant. zero_update still skips it.

Chunk granularity stays n = K*M, so residual layouts, zero_update
shards, and sharded checkpoints are indistinguishable from a flat ring
of the same total width. Per-level wire accounting lives in
``modeled_wire_bytes_levels`` (analytic) and
``ppermute_wire_bytes_levels`` (jaxpr-counted), parity-held in tests;
the inter-slice bytes shrink by ~the intra degree vs the flat ring
(exactly: K*(M-1) <= n-1 chunks cross the scarce axis instead of n-1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.extend import core as jcore

#: int8 symmetric range: q in [-127, 127], scale = max|e| / 127 (shared
#: with parallel/collectives.py's reference quantized path — ONE
#: quantize/dequantize pair, so the ring and the oracle cannot drift)
INT8_MAX = 127.0

#: scale floor: an all-zero bucket must not divide by zero
_SCALE_FLOOR = 1e-30

#: hardware tile floor for the compiled (fused_hop) inner kernel: the
#: per-hop chunk is processed as (rows, 128) f32 tiles — sublanes of 8,
#: lanes of 128, like ops/paged_attention's floor
_SUBLANE, _LANE = 8, 128


# ---------------------------------------------------------------------------
# shared quantize/dequantize helpers (the one pair both the reference
# grad_comm path and the ring consult)
# ---------------------------------------------------------------------------


def symmetric_scale(arrays) -> jnp.ndarray:
    """One symmetric int8 scale for a bucket: max-abs over every array
    in it, floored away from zero so an all-zero bucket cannot divide
    by zero. Max is exactly associative, so the scale is
    bitwise-independent of layout — and a NaN/Inf element poisons it
    (``jnp.max`` propagates NaN), which is the guard contract: the
    poison survives dequantization."""
    amax = functools.reduce(
        jnp.maximum,
        (jnp.max(jnp.abs(a.astype(jnp.float32))) for a in arrays),
    )
    return jnp.maximum(amax, jnp.float32(_SCALE_FLOOR)) / INT8_MAX


def quantize_int8(e: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Symmetric int8 cast: round(e / scale), clipped to [-127, 127].
    A NaN scale produces implementation-defined int8 bytes — harmless,
    because ``dequantize_int8`` multiplies by the same NaN scale."""
    return jnp.clip(
        jnp.round(e.astype(jnp.float32) / scale), -INT8_MAX, INT8_MAX
    ).astype(jnp.int8)


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """int8 wire value back to f32: q * scale (NaN scale -> NaN out)."""
    return q.astype(jnp.float32) * scale


def wire_cast(e: jnp.ndarray, scale, dtype: str):
    """Cast ``e`` to the wire dtype: (wire array, scale or None)."""
    if dtype == "int8":
        return quantize_int8(e, scale), scale
    return e.astype(jnp.bfloat16), None


def wire_uncast(w: jnp.ndarray, scale, dtype: str) -> jnp.ndarray:
    if dtype == "int8":
        return dequantize_int8(w, scale)
    return w.astype(jnp.float32)


# ---------------------------------------------------------------------------
# geometry predicates (consulted by the trainer's runtime rejection AND
# netlint's KRN002 — a static mirror must never drift from its runtime)
# ---------------------------------------------------------------------------


def ring_reducible(
    shapes: dict, ndata: int, chunk_dims: dict | None = None
) -> str | None:
    """None if the ring can chunk every gradient over an ``ndata``-wide
    data axis, else the reason it cannot. ``shapes`` maps param name ->
    stored shape; ``chunk_dims`` maps name -> the dim the ring chunks
    (default 0 — the update-layout dim under ``zero_update``). The ring
    sends fixed equal chunks, so the chunk dim must divide evenly: a
    padded phantom chunk would ppermute garbage into real sums."""
    if ndata <= 1:
        return None
    for name in sorted(shapes):
        shape = tuple(shapes[name])
        if not shape:
            return (
                f"param {name!r} is a scalar: the ring cannot chunk a "
                "0-d gradient over the data axis"
            )
        d = (chunk_dims or {}).get(name, 0)
        if shape[d] % ndata:
            return (
                f"param {name!r} dim {d} ({shape[d]}) not divisible by "
                f"the data-axis width {ndata}: the ring's bucket "
                "chunking cannot split it into equal wire chunks"
            )
    return None


def ring_fusable(
    shapes: dict, ndata: int, chunk_dims: dict | None = None,
    interpret: bool = True,
) -> str | None:
    """None if the fused (Pallas) per-hop quantize+accumulate kernel can
    serve this geometry, else the reason. The interpret form tiles
    anything (plain XLA ops); the compiled form processes each chunk as
    (rows, 128) f32 register tiles, so the per-shard chunk element
    count must align to the (8, 128) tile."""
    reason = ring_reducible(shapes, ndata, chunk_dims)
    if reason is not None:
        return reason
    if interpret or ndata <= 0:
        return None
    tile = _SUBLANE * _LANE
    for name in sorted(shapes):
        shape = tuple(shapes[name])
        d = (chunk_dims or {}).get(name, 0)
        elems = shape[d] // max(1, ndata)
        for i, s in enumerate(shape):
            if i != d:
                elems *= s
        if elems % tile:
            return (
                f"param {name!r} ring chunk has {elems} elements, not a "
                f"multiple of the ({_SUBLANE}, {_LANE}) f32 tile: the "
                "compiled quantize+accumulate kernel cannot tile it"
            )
    return None


def hier_ring_geometry(widths, ring, *, data_axis: str = "data"):
    """Resolve the two-level ring geometry for ``q8_hier``: returns
    ``(intra_axis, inter_axis, K, M)`` when the mesh admits the
    factorization, else the reason string. The trainer raises the
    reason at construction and netlint's KRN002 reports it statically
    — one predicate, so the static mirror cannot drift. This is the
    generalization seam for ``ring_reducible``/``ring_fusable``: the
    flat ring's loud composed-mesh rejection becomes the FALLBACK
    (``quantized_ring`` keeps it), while ``q8_hier`` accepts any mesh
    this factorization covers, then runs the chunkability predicates
    with the TOTAL width n = K*M.

    ``widths`` maps mesh axis -> width; ``ring`` is the model conf's
    ``ring {}`` block (or None). Factored form: ``intra_degree: K``
    splits the ``data`` axis into M = n/K groups of K adjacent ranks
    (K must divide the data width; every other axis must be 1-wide —
    nothing else covers them). Named form: ``intra_axis`` /
    ``inter_axis`` name two distinct mesh axes whose product IS the
    data reduction (the batch shards over both); the ``data`` axis
    must be one of them when >1-wide, and no third axis may be >1-wide.
    A 1-wide reduction degenerates to K = M = 1 (the ring is a no-op,
    same as ``ring_reducible``'s ``ndata <= 1`` convention)."""
    widths = {k: int(v) for k, v in (widths or {}).items()}
    intra = getattr(ring, "intra_axis", "") if ring is not None else ""
    inter = getattr(ring, "inter_axis", "") if ring is not None else ""
    degree = int(getattr(ring, "intra_degree", 0) or 0)
    if not degree and not intra and not inter:
        return (
            "kernels { grad_allreduce: q8_hier } needs a ring {} block "
            "declaring the two-level geometry: intra_degree to factor "
            "the data axis, or intra_axis/inter_axis naming mesh axes"
        )
    if degree and (intra or inter):
        return (
            "ring { intra_degree } and ring { intra_axis/inter_axis } "
            "are mutually exclusive: the factored form splits the data "
            "axis itself, the named form rides two real mesh axes"
        )
    if degree:
        n = widths.get(data_axis, 1)
        others = sorted(
            a for a, wd in widths.items() if a != data_axis and wd > 1
        )
        if others:
            return (
                f"ring {{ intra_degree: {degree} }} factors the "
                f"{data_axis!r} axis only, but the mesh also shards "
                + ", ".join(f"{a!r} (width {widths[a]})" for a in others)
                + " — name the extra axis with ring { intra_axis/"
                "inter_axis } if the reduction should ride it"
            )
        if n <= 1:
            return (data_axis, data_axis, 1, 1)
        if degree > n or n % degree:
            return (
                f"ring {{ intra_degree: {degree} }} does not divide the "
                f"{data_axis!r} axis width {n}: the two-level "
                "factorization needs n = intra_degree * inter groups"
            )
        return (data_axis, data_axis, degree, n // degree)
    if not intra or not inter:
        return (
            "ring { intra_axis/inter_axis } must name BOTH axes (got "
            f"intra_axis={intra!r}, inter_axis={inter!r}) — or use "
            "intra_degree to factor the data axis"
        )
    if intra == inter:
        return (
            f"ring {{ intra_axis: {intra!r} }} and inter_axis name the "
            "same mesh axis — use intra_degree to factor one axis"
        )
    for role, ax in (("intra_axis", intra), ("inter_axis", inter)):
        if ax not in widths:
            return (
                f"ring {{ {role}: {ax!r} }} names no mesh axis "
                f"(mesh axes: {', '.join(sorted(widths)) or 'none'})"
            )
    if widths.get(data_axis, 1) > 1 and data_axis not in (intra, inter):
        return (
            f"the {data_axis!r} axis (width {widths[data_axis]}) is "
            "not covered by ring { intra_axis/inter_axis } — the "
            "gradient reduction must include every data shard"
        )
    leftovers = sorted(
        a for a, wd in widths.items()
        if wd > 1 and a not in (intra, inter)
    )
    if leftovers:
        return (
            "mesh axes "
            + ", ".join(f"{a!r} (width {widths[a]})" for a in leftovers)
            + " are >1-wide but outside the ring { intra_axis/"
            "inter_axis } factorization — the two-level ring covers "
            "exactly two axes"
        )
    return (intra, inter, widths[intra], widths[inter])


# ---------------------------------------------------------------------------
# optional Pallas inner kernel: dequantize + accumulate fused per hop
# ---------------------------------------------------------------------------


#: rows of the (rows, 128) chunk view one grid step of ``quant_acc``
#: holds in VMEM: a multiple of the int8 (32, 128) tile, and 1024 rows
#: x (1 + 4 + 4) bytes double-buffered stays near 2.4 MB — far inside
#: the 16 MB scoped-VMEM limit a single whole-chunk block overran at
#: ~1.2M elements
_ACC_BLOCK_ROWS = 1024


def _quant_acc_kernel(s_ref, q_ref, x_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[0, 0] + x_ref[...]


def quant_acc(
    q: jnp.ndarray, scale: jnp.ndarray, local: jnp.ndarray,
    *, interpret: bool = True,
) -> jnp.ndarray:
    """``dequantize_int8(q, scale) + local`` as ONE fused Pallas kernel
    — the per-hop accumulation's memory traffic is one read of the int8
    chunk, one read of the local f32 partial, one write, with no f32
    dequantized intermediate ever hitting HBM. The chunk is viewed as
    (rows, 128) and walked ``_ACC_BLOCK_ROWS`` rows per grid step, so
    VMEM holds one block whatever the chunk's size. ``interpret=True``
    runs it through the Pallas interpreter (plain XLA ops — the unit
    test pins it to the jnp form within 1 ulp; the interpreter may
    contract the multiply-add into an fma); ``interpret=False``
    compiles through Mosaic and needs ``ring_fusable`` geometry (a
    chunk that is no multiple of 128 rides as one (1, n) block, which
    only the interpreter takes)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = local.size
    cols = _LANE if n % _LANE == 0 else n
    rows = n // cols
    block_rows = min(rows, _ACC_BLOCK_ROWS)
    block = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    out = pl.pallas_call(
        _quant_acc_kernel,
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), block, block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        interpret=bool(interpret),
    )(
        scale.reshape(1, 1).astype(jnp.float32),
        q.reshape(rows, cols),
        local.astype(jnp.float32).reshape(rows, cols),
    )
    return out.reshape(local.shape)


# ---------------------------------------------------------------------------
# the ring itself (runs per shard, inside shard_map)
# ---------------------------------------------------------------------------


def _chunked(x: jnp.ndarray, d: int, n: int) -> jnp.ndarray:
    """(..., S[d], ...) -> (n, S[d]//n, ...rest) with the chunk dim
    moved to the front."""
    y = jnp.moveaxis(x, d, 0)
    return y.reshape((n, y.shape[0] // n) + y.shape[1:])


def _unchunk(y: jnp.ndarray, d: int, shape) -> jnp.ndarray:
    """Inverse of ``_chunked``: (n, c, ...rest) -> the original shape."""
    z = y.reshape((y.shape[0] * y.shape[1],) + y.shape[2:])
    return jnp.moveaxis(z, 0, d).reshape(shape)


def _shard_shape(shape, d: int, n: int):
    return tuple(
        s // n if i == d else s for i, s in enumerate(shape)
    )


def _hier_reduce_scatter(
    chunks: dict, p, g, K: int, M: int, pperm_intra, pperm_inter,
    dtype: str, fused_hop: bool, fused_interpret: bool,
) -> dict:
    """Two-level reduce-scatter over already-chunked grads: -> each
    rank's fully-summed own chunk (index g*K + p), shape (c, ...) —
    the same post-scatter state as the flat ring's scan.

    Level 1 (intra, f32 wire): view the n = K*M chunks piece-major as
    (K, M, c, ...) — piece j holds every chunk at intra position j —
    and ring-reduce-scatter the K pieces over the fast axis in full
    f32: after K-1 hops rank (g, p) holds the group-g-local sum of
    piece p, an (M, c, ...) plane. Quantizing here would buy nothing
    (ICI is the cheap hop) and would cost rounding error per hop.

    Level 2 (inter, quantized wire): ring-reduce-scatter the M plane
    entries over the scarce axis with the flat ring's exact per-hop
    discipline — one symmetric scale per bucket, int8 bytes + scale
    ppermute'd, dequant + f32 accumulate (+ requant next hop)."""
    # piece-major view: pieces[nm][j, gg] = chunk gg*K + j, f32 so the
    # intra accumulation (and its wire) is full precision by contract
    pieces = {
        nm: jnp.swapaxes(
            c.reshape((M, K) + c.shape[1:]), 0, 1
        ).astype(jnp.float32)
        for nm, c in chunks.items()
    }

    def pick_piece(idx):
        return {
            nm: jax.lax.dynamic_index_in_dim(
                pc, idx % K, axis=0, keepdims=False
            )
            for nm, pc in pieces.items()
        }

    acc = pick_piece(p - 1)  # (M, c, ...) per param

    def ihop(carry, t):
        moved = {nm: pperm_intra(a) for nm, a in carry.items()}
        local = pick_piece(p - t - 2)
        return {nm: moved[nm] + local[nm] for nm in carry}, None

    if K > 1:
        acc, _ = jax.lax.scan(ihop, acc, jnp.arange(K - 1))

    def pick_group(idx):
        return {
            nm: jax.lax.dynamic_index_in_dim(
                a, idx % M, axis=0, keepdims=False
            )
            for nm, a in acc.items()
        }

    out = pick_group(g - 1)  # (c, ...) per param

    def xhop(carry, t):
        scale = (
            symmetric_scale(carry.values()) if dtype == "int8" else None
        )
        wires = {
            nm: wire_cast(a, scale, dtype)[0] for nm, a in carry.items()
        }
        wires = {nm: pperm_inter(w) for nm, w in wires.items()}
        if scale is not None:
            scale = pperm_inter(scale)
        local = pick_group(g - t - 2)
        nxt = {}
        for nm, w in wires.items():
            if fused_hop and dtype == "int8":
                nxt[nm] = quant_acc(
                    w, scale, local[nm], interpret=fused_interpret
                )
            else:
                nxt[nm] = wire_uncast(w, scale, dtype) + local[nm]
        return nxt, None

    if M > 1:
        out, _ = jax.lax.scan(xhop, out, jnp.arange(M - 1))
    return out


def _hier_allgather(
    fq: dict, fscale, p, g, K: int, M: int, pperm_intra, pperm_inter,
    dtype: str,
) -> dict:
    """Two-level allgather of the owner-quantized (wire bytes, scale)
    pairs: the inter ring collects the M chunk planes at this rank's
    intra position, then the intra ring carries the collected
    (M, c, ...) plane + (M,) scales around the group whole. Every rank
    dequantizes IDENTICAL bytes with identical scales, so the gathered
    gradient stays bitwise ring-invariant — same contract as the flat
    allgather, int8 on the scarce hops only by construction (the intra
    hops move the already-int8 planes too: bytes, not f32).
    Returns {nm: (n, c, ...) f32} in chunk-index order."""
    wnames = list(fq)
    planes = {
        nm: jax.lax.dynamic_update_index_in_dim(
            jnp.zeros((M,) + fq[nm].shape, fq[nm].dtype),
            fq[nm], g, axis=0,
        )
        for nm in wnames
    }
    scales = (
        jax.lax.dynamic_update_index_in_dim(
            jnp.zeros((M,), jnp.float32), fscale, g, axis=0
        )
        if fscale is not None
        else None
    )

    def gxhop(carry, t):
        planes, scales, w, s = carry
        w = {nm: pperm_inter(v) for nm, v in w.items()}
        if s is not None:
            s = pperm_inter(s)
        idx = (g - t - 1) % M
        planes = {
            nm: jax.lax.dynamic_update_index_in_dim(
                planes[nm], w[nm], idx, axis=0
            )
            for nm in wnames
        }
        if s is not None:
            scales = jax.lax.dynamic_update_index_in_dim(
                scales, s, idx, axis=0
            )
        return (planes, scales, w, s), None

    if M > 1:
        (planes, scales, _, _), _ = jax.lax.scan(
            gxhop,
            (planes, scales, dict(fq), fscale),
            jnp.arange(M - 1),
        )
    big = {
        nm: jax.lax.dynamic_update_index_in_dim(
            jnp.zeros((K,) + planes[nm].shape, planes[nm].dtype),
            planes[nm], p, axis=0,
        )
        for nm in wnames
    }
    bigs = (
        jax.lax.dynamic_update_index_in_dim(
            jnp.zeros((K, M), jnp.float32), scales, p, axis=0
        )
        if scales is not None
        else None
    )

    def gihop(carry, t):
        big, bigs, w, s = carry
        w = {nm: pperm_intra(v) for nm, v in w.items()}
        if s is not None:
            s = pperm_intra(s)
        idx = (p - t - 1) % K
        big = {
            nm: jax.lax.dynamic_update_index_in_dim(
                big[nm], w[nm], idx, axis=0
            )
            for nm in wnames
        }
        if s is not None:
            bigs = jax.lax.dynamic_update_index_in_dim(
                bigs, s, idx, axis=0
            )
        return (big, bigs, w, s), None

    if K > 1:
        (big, bigs, _, _), _ = jax.lax.scan(
            gihop, (big, bigs, planes, scales), jnp.arange(K - 1)
        )
    out = {}
    for nm in wnames:
        arr = big[nm]  # (K, M, c, ...) wire dtype
        if bigs is not None:
            f = arr.astype(jnp.float32) * bigs.reshape(
                (K, M) + (1,) * (arr.ndim - 2)
            )
        else:
            f = arr.astype(jnp.float32)
        # [j, gg] holds chunk gg*K + j -> chunk-index-major (n, c, ...)
        f = jnp.swapaxes(f, 0, 1)
        out[nm] = f.reshape((M * K,) + arr.shape[2:])
    return out


def ring_reduce_gradients(
    grads: dict,
    residuals: dict,
    buckets: tuple,
    *,
    axis_name: str,
    nshards: int,
    chunk_dims: dict,
    gather: dict,
    dtype: str = "int8",
    error_feedback: bool = True,
    overlapped: bool = False,
    residual_key=None,
    fused_hop: bool = False,
    fused_interpret: bool = True,
    hier: tuple | None = None,
) -> tuple[dict, dict]:
    """The quantized ring all-reduce, per shard: -> (reduced grads,
    new error-feedback residual chunks).

    Runs INSIDE ``shard_map`` over the data axis. ``grads`` are this
    shard's local partials, pre-scaled so the cross-shard sum is the
    desired reduction (the trainer divides its local-batch mean grads
    by ``nshards``). ``residuals`` hold this shard's OWN chunk of each
    param's error-feedback residual (sliced by the shard_map in_specs).
    ``buckets`` are the reverse-topo groups from
    ``parallel.collectives.reverse_topo_buckets`` — one wire scale per
    bucket per hop, and with ``overlapped`` the buckets chain through
    ``optimization_barrier`` in gradient-readiness order exactly like
    the reference path. ``gather[name]`` False keeps the scatter layout
    (zero_update: the shard's chunk IS its update shard; the allgather
    phase never runs for that param).

    Output identity: gathered params are reconstructed from the SAME
    (int8 bytes, f32 scale) pairs on every shard, so the reduced
    gradient is bitwise identical ring-wide — tested, and what lets the
    step's out_specs declare them replicated.

    ``hier = (intra_axis, inter_axis, K, M)`` (from
    ``hier_ring_geometry``, with ``nshards == K*M``) swaps both phases
    onto the hierarchical two-level form: f32 intra reduce-scatter,
    quantized inter ring, two-level byte-carrying allgather. The
    factored single-axis form has ``intra_axis == inter_axis`` and
    builds structured perms on that one axis (rank r = g*K + p);
    chunk granularity, the error-feedback/owner-quantize step between
    the phases, and every output layout are SHARED with the flat ring.
    """
    n = nshards
    perm = [(j, (j + 1) % n) for j in range(n)]
    if hier is not None:
        intra_ax, inter_ax, K, M = hier
        if K * M != n:
            raise ValueError(
                f"hier geometry {K}x{M} does not match nshards {n}"
            )
        if intra_ax == inter_ax:  # factored data axis: rank = g*K + p
            me = jax.lax.axis_index(intra_ax)
            p, g = me % K, me // K
            iperm = [
                (gg * K + j, gg * K + (j + 1) % K)
                for gg in range(M)
                for j in range(K)
            ]
            xperm = [
                (gg * K + j, ((gg + 1) % M) * K + j)
                for gg in range(M)
                for j in range(K)
            ]
        else:  # named mesh axes: chunk index = g*K + p by in_specs order
            p = jax.lax.axis_index(intra_ax)
            g = jax.lax.axis_index(inter_ax)
            me = g * K + p
            iperm = [(j, (j + 1) % K) for j in range(K)]
            xperm = [(j, (j + 1) % M) for j in range(M)]

        def pperm_intra(x):
            return jax.lax.ppermute(x, intra_ax, iperm)

        def pperm_inter(x):
            return jax.lax.ppermute(x, inter_ax, xperm)

    else:
        me = jax.lax.axis_index(axis_name)
    out: dict = {}
    new_res: dict = {}
    token = None

    for bucket in buckets:
        gs = {nm: grads[nm] for nm in bucket}
        if token is not None:
            # pin this bucket's ring after the previous bucket's first
            # reduced array: the same reverse-topo issue-order chain as
            # the reference path (optimization_barrier is a value
            # identity that adds a scheduling edge)
            names = list(gs)
            fused = jax.lax.optimization_barrier(
                tuple(gs[nm] for nm in names) + (token,)
            )
            gs = dict(zip(names, fused[:-1]))
        chunks = {
            nm: _chunked(g, chunk_dims[nm], n) for nm, g in gs.items()
        }

        def pick(idx):
            return {
                nm: jax.lax.dynamic_index_in_dim(
                    c, idx % n, axis=0, keepdims=False
                )
                for nm, c in chunks.items()
            }

        # --- reduce-scatter: after n-1 hops shard ``me`` holds the
        # full sum of its own chunk ``me`` (start chunk me-1; the chunk
        # arriving at hop t is me-t-2, accumulated in f32). The
        # hierarchical form reaches the identical state through the
        # two-level schedule (f32 intra, quantized inter) ---
        if hier is not None:
            acc = _hier_reduce_scatter(
                chunks, p, g, K, M, pperm_intra, pperm_inter,
                dtype, fused_hop, fused_interpret,
            )
        else:
            acc = pick(me - 1)

        def hop(carry, t):
            acc = carry
            scale = (
                symmetric_scale(acc.values()) if dtype == "int8" else None
            )
            wires = {
                nm: wire_cast(a, scale, dtype)[0] for nm, a in acc.items()
            }
            wires = {
                nm: jax.lax.ppermute(w, axis_name, perm)
                for nm, w in wires.items()
            }
            if scale is not None:
                scale = jax.lax.ppermute(scale, axis_name, perm)
            local = pick(me - t - 2)
            nxt = {}
            for nm, w in wires.items():
                if fused_hop and dtype == "int8":
                    nxt[nm] = quant_acc(
                        w, scale, local[nm], interpret=fused_interpret
                    )
                else:
                    nxt[nm] = wire_uncast(w, scale, dtype) + local[nm]
            return nxt, None

        if n > 1 and hier is None:
            acc, _ = jax.lax.scan(hop, acc, jnp.arange(n - 1))

        # --- error-feedback injection + the one owner-side quantize:
        # the owner adds its residual chunk in full f32, quantizes the
        # finished sum once for the broadcast, and banks the exact
        # quantization error as the next step's residual (per-hop wire
        # errors above are the documented un-fed-back caveat) ---
        if error_feedback and residual_key is not None:
            # the residual arrives as the shard's slice in ORIGINAL dim
            # order (the shard_map in_specs slice dim chunk_dims[nm]);
            # acc is in chunk-front layout, so move the chunk dim up
            # before adding (identity when the chunk dim is 0)
            acc = {
                nm: a + jnp.moveaxis(
                    residuals[residual_key(nm)].astype(jnp.float32),
                    chunk_dims[nm], 0,
                )
                for nm, a in acc.items()
            }
        fscale = symmetric_scale(acc.values()) if dtype == "int8" else None
        fq = {nm: wire_cast(a, fscale, dtype)[0] for nm, a in acc.items()}
        deq = {nm: wire_uncast(w, fscale, dtype) for nm, w in fq.items()}
        if error_feedback and residual_key is not None:
            for nm in bucket:
                # bank the owner-side quantization error back in the
                # residual's original dim order (the out_specs layout)
                new_res[residual_key(nm)] = jnp.moveaxis(
                    acc[nm] - deq[nm], 0, chunk_dims[nm]
                )

        # --- allgather: the (int8 bytes, scale) pair rides n-1 more
        # hops; chunk c lands dequantized from identical bytes on every
        # shard, so the gathered value is bitwise ring-invariant.
        # zero_update params skip this: their scatter chunk IS the
        # update-layout shard ---
        gathered = [nm for nm in bucket if gather[nm]]
        if gathered and n > 1 and hier is not None:
            full = _hier_allgather(
                {nm: fq[nm] for nm in gathered}, fscale,
                p, g, K, M, pperm_intra, pperm_inter, dtype,
            )
            for nm in gathered:
                out[nm] = _unchunk(
                    full[nm], chunk_dims[nm], gs[nm].shape
                ).astype(gs[nm].dtype)
        elif gathered and n > 1:
            buf = {
                nm: jax.lax.dynamic_update_index_in_dim(
                    jnp.zeros_like(chunks[nm], dtype=jnp.float32),
                    deq[nm], me, axis=0,
                )
                for nm in gathered
            }

            def ghop(carry, t):
                buf, fq, fscale = carry
                fq = {
                    nm: jax.lax.ppermute(w, axis_name, perm)
                    for nm, w in fq.items()
                }
                if fscale is not None:
                    fscale = jax.lax.ppermute(fscale, axis_name, perm)
                idx = (me - t - 1) % n
                buf = {
                    nm: jax.lax.dynamic_update_index_in_dim(
                        b, wire_uncast(fq[nm], fscale, dtype), idx, axis=0
                    )
                    for nm, b in buf.items()
                }
                return (buf, fq, fscale), None

            (buf, _, _), _ = jax.lax.scan(
                ghop,
                (buf, {nm: fq[nm] for nm in gathered}, fscale),
                jnp.arange(n - 1),
            )
            for nm in gathered:
                out[nm] = _unchunk(
                    buf[nm], chunk_dims[nm], gs[nm].shape
                ).astype(gs[nm].dtype)
        else:
            for nm in gathered:  # n == 1: the chunk is the whole array
                out[nm] = _unchunk(
                    deq[nm][None], chunk_dims[nm], gs[nm].shape
                ).astype(gs[nm].dtype)
        for nm in bucket:
            if not gather[nm]:
                d = chunk_dims[nm]
                out[nm] = jnp.moveaxis(
                    deq[nm], 0, d
                ).reshape(
                    _shard_shape(gs[nm].shape, d, n)
                ).astype(gs[nm].dtype)
        for nm in bucket:
            # materialize the reduced gradient before anything consumes
            # it. A 2-wide ring's one-trip allgather scan is inlined, and
            # XLA then fuses the owner's local dequantize and the
            # received chunks' dequantize — two code paths — into the
            # optimizer update, where the backend may contract each
            # path's multiply-add differently (seen on XLA:CPU): the
            # owner's copy of its own chunk lands an ulp off the copies
            # it broadcast, "replicated" params drift apart across
            # shards, and a resumed run (whose restore re-equalizes
            # them) stops matching the uninterrupted one
            out[nm] = jax.lax.optimization_barrier(out[nm])
        if overlapped:
            token = out[bucket[0]]
    return out, new_res


# ---------------------------------------------------------------------------
# wire-bytes accounting (the deterministic arm of the stall gate)
# ---------------------------------------------------------------------------


def _wire_itemsize(dtype: str) -> int:
    return 1 if dtype == "int8" else 2


def modeled_wire_bytes(
    sizes: dict, buckets: tuple, ndata: int, *,
    dtype: str = "int8", gather: dict | None = None,
) -> int:
    """Per-device bytes the quantized ring moves across the data axis
    in one step — what each hop's ppermute operands add up to: the
    reduce phase sends n-1 (chunk + scale) payloads per bucket, the
    allgather n-1 more for gathered params (skipped under zero_update's
    scatter layout). ``sizes`` maps param name -> element count;
    ``tests`` pin this model against the step jaxpr's actual ppermute
    operand bytes (``ppermute_wire_bytes``), so the gated number cannot
    drift from what the program sends."""
    if ndata <= 1:
        return 0
    w = _wire_itemsize(dtype)
    scale_bytes = 4 if dtype == "int8" else 0
    total = 0
    for bucket in buckets:
        chunk = sum(sizes[nm] // ndata for nm in bucket)
        total += (ndata - 1) * (chunk * w + scale_bytes)  # reduce phase
        gchunk = sum(
            sizes[nm] // ndata
            for nm in bucket
            if gather is None or gather[nm]
        )
        if gchunk:
            total += (ndata - 1) * (gchunk * w + scale_bytes)  # allgather
    return total


def modeled_wire_bytes_levels(
    sizes: dict, buckets: tuple, ndata: int, *,
    intra_degree: int, dtype: str = "int8", gather: dict | None = None,
) -> dict:
    """Per-device, per-LEVEL bytes the hierarchical ring moves in one
    step: ``{"intra": ..., "inter": ..., "total": ...}``. Per bucket
    with chunk = sum(sizes)/n, K = intra_degree, M = n/K:

      intra reduce   (K-1) hops x an (M, chunk) f32 plane (no scale —
                     the fast hop is unquantized by design)
      inter reduce   (M-1) hops x (chunk wire bytes + one f32 scale)
      inter gather   (M-1) hops x (chunk wire bytes + scale), gathered
                     params only (zero_update skips them)
      intra gather   (K-1) hops x (M x chunk wire bytes + M scales) —
                     the collected byte plane rides whole

    ``total`` equals what ``ppermute_wire_bytes`` counts from the
    traced step; the split is what ``ppermute_wire_bytes_levels``
    attributes per level — both parities are CI-held. The scarce-hop
    win vs the flat ring is exact integer math: K*(M-1) <= K*M - 1 =
    n - 1 chunks cross the inter axis, so
    inter_bytes * intra_degree <= flat modeled_wire_bytes always."""
    if ndata <= 1:
        return {"intra": 0, "inter": 0, "total": 0}
    K = max(1, int(intra_degree))
    if ndata % K:
        raise ValueError(
            f"intra_degree {K} does not divide ndata {ndata}"
        )
    M = ndata // K
    w = _wire_itemsize(dtype)
    scale_bytes = 4 if dtype == "int8" else 0
    intra = inter = 0
    for bucket in buckets:
        chunk = sum(sizes[nm] // ndata for nm in bucket)
        intra += (K - 1) * M * chunk * 4
        inter += (M - 1) * (chunk * w + scale_bytes)
        gchunk = sum(
            sizes[nm] // ndata
            for nm in bucket
            if gather is None or gather[nm]
        )
        if gchunk:
            inter += (M - 1) * (gchunk * w + scale_bytes)
            intra += (K - 1) * (M * gchunk * w + M * scale_bytes)
    return {
        "intra": int(intra),
        "inter": int(inter),
        "total": int(intra + inter),
    }


def ppermute_wire_bytes_levels(
    jaxpr, *, intra_axis: str = "data", inter_axis: str = "data",
    intra_degree: int = 1,
) -> dict:
    """Per-level ppermute byte attribution for the hierarchical ring,
    counted from the traced program: ``{"intra": ..., "inter": ...}``.
    Distinct mesh axes classify each ppermute by its ``axis_name``;
    the factored single-axis form classifies by perm STRUCTURE — a
    within-group hop keeps ``src//K == dst//K``, the cross-group hop
    keeps ``src%K == dst%K`` (disjoint for K, M > 1; a perm matching
    neither — e.g. a flat ring's — raises, misuse is loud)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    K = max(1, int(intra_degree))
    out = {"intra": 0, "inter": 0}

    def level(eqn) -> str:
        ax = eqn.params.get("axis_name")
        if isinstance(ax, (tuple, list)) and len(ax) == 1:
            ax = ax[0]
        if intra_axis != inter_axis:
            if ax == intra_axis:
                return "intra"
            if ax == inter_axis:
                return "inter"
            raise ValueError(
                f"ppermute over unexpected axis {ax!r} (expected "
                f"{intra_axis!r} or {inter_axis!r})"
            )
        pairs = [(int(s), int(d)) for s, d in eqn.params["perm"]]
        if all(s // K == d // K for s, d in pairs):
            return "intra"
        if all(s % K == d % K for s, d in pairs):
            return "inter"
        raise ValueError(
            f"ppermute perm {pairs!r} matches neither ring level "
            f"(intra_degree={K})"
        )

    def walk(jx, mult: int) -> None:
        for eqn in jx.eqns:
            if eqn.primitive.name == "ppermute":
                lv = level(eqn)
                for v in eqn.invars:
                    aval = v.aval
                    out[lv] += (
                        mult * int(aval.size) * jnp.dtype(aval.dtype).itemsize
                    )
            submult = mult
            if eqn.primitive.name == "scan":
                submult = mult * int(eqn.params.get("length", 1))
            for val in eqn.params.values():
                vals = val if isinstance(val, (list, tuple)) else (val,)
                for v in vals:
                    if isinstance(v, jcore.ClosedJaxpr):
                        walk(v.jaxpr, submult)
                    elif isinstance(v, jcore.Jaxpr):
                        walk(v, submult)

    walk(inner, 1)
    return out


def reference_wire_bytes(
    sizes: dict, ndata: int, *, scatter_only: bool = False
) -> int:
    """Per-device bytes the REFERENCE path's fp32 data-axis collective
    moves per step: a bandwidth-optimal ring all-reduce of E elements
    costs each device 2(n-1)/n * 4E bytes (reduce-scatter + allgather);
    under zero_update the allgather half moves to the param constraint
    and the grad collective is the reduce-scatter alone. This is the
    wire PR 8's quantize-around-the-psum could not shrink — the
    comparison baseline for ``wire_bytes_ratio``."""
    if ndata <= 1:
        return 0
    total_elems = sum(sizes.values())
    phases = 1 if scatter_only else 2
    return int(phases * (ndata - 1) * total_elems * 4 / ndata)


def ppermute_wire_bytes(jaxpr) -> int:
    """Sum the per-device bytes every ``ppermute`` in ``jaxpr`` moves,
    recursing into scans (multiplied by trip count), conds, and other
    sub-jaxprs — the measured half of the wire-bytes gate: counted from
    the program the step actually traces, not from the model. Accepts a
    ClosedJaxpr (``jax.make_jaxpr(...)(...)``) or a raw Jaxpr."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)

    def walk(jx, mult: int) -> int:
        total = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "ppermute":
                for v in eqn.invars:
                    aval = v.aval
                    total += (
                        mult * int(aval.size) * jnp.dtype(aval.dtype).itemsize
                    )
            submult = mult
            if eqn.primitive.name == "scan":
                submult = mult * int(eqn.params.get("length", 1))
            for val in eqn.params.values():
                vals = val if isinstance(val, (list, tuple)) else (val,)
                for v in vals:
                    if isinstance(v, jcore.ClosedJaxpr):
                        total += walk(v.jaxpr, submult)
                    elif isinstance(v, jcore.Jaxpr):
                        total += walk(v, submult)
        return total

    return walk(inner, 1)
