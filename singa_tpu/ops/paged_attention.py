"""Fused paged attention: a Pallas kernel that reads K/V blocks IN
PLACE through the block table.

The serving engine's reference attention path is gather -> attend ->
scatter: every decode tick, prefill chunk, and verify pass first
materializes a dense ``(slots, heads, cache_len, head_dim)`` view of
the paged pool PER LAYER (``Engine._gather``) before ``cache_attend``
runs — for a pool that is mostly shared prefix blocks and trash
padding, that materialization is the serving tier's main memory
traffic. This kernel removes it: the per-sequence block table rides in
as a scalar-prefetch operand, the grid's block dimension maps each
step straight at the sequence's next pool block (``BlockSpec`` index
map = a table lookup), and masked online-softmax statistics accumulate
across grid steps in VMEM scratch — flash-attention tiling over
block-granular K/V, the PagedAttention idea from vLLM-style serving.
No dense ``(S, H, C, D)`` intermediate ever exists.

WHAT THE KERNEL IS HANDED: the pools as the engine stores them,
``(n_blocks, block_len, Hkv * D)`` (serve/kv_pool.py: one row a token,
the K/V heads side by side in it), with no view or relayout in between,
and queries of H heads, H a multiple of Hkv: query head ``j`` reads K/V
head ``j // (H // Hkv)``, as in ``cache_attend``.

TWO FORMS behind the two entry points, chosen in ``_call`` from the
call's shape:

the grid form (``_kernel``)
    any number of queries a sequence, with or without an overlaid
    chunk. One grid step fetches one whole block for ALL heads — a
    ``(1, block_len, H * D)`` tile, its minor dimension the model's
    width — and the heads are walked inside the kernel with static
    column slices, so the grid is ``(S, max_blocks)`` and the
    statistics are kept per head. A step past a sequence's live range
    skips its fetch and its compute, but not the step.

the one-query form (``_one_query_kernel``)
    the decode tick: ONE query a sequence, nothing overlaid. At a
    serving cell's shape the grid form is all steps and small
    products — 32 slots x 64 table entries x 24 layers are 49,152
    steps a tick, under a third of them live, each walking 16 heads of
    ``(1, 64) x (64, 16)`` — so this form has no grid: one program
    walks a flat list of the LIVE chunks of every sequence, copies a
    chunk's blocks from HBM by hand (the next chunk's while this one
    is folded) and folds all heads at once. On a v5e it reads the
    live blocks of GPT-2 medium's pools at 0.95 of what a plain sum
    over a pool reads them at (PERF.md §6, PR 29). Query heads over
    fewer K/V heads (PR 38) are two products a chunk over a
    block-diagonal query; a chunk is sized by bytes, some 512 KB of K
    rows. It is the only form that knows fewer K/V heads: the grid
    form refuses them.

Two entry points cover the engine's call shapes:

``paged_attention``
    write-then-read — the decode tick's ``(slots, 1)`` pattern (and a
    chunk of queries, ``(1, chunk)``, which the engine's prefill no
    longer asks for): the fresh K/V were already
    scattered into the pool (padding/dead lanes to the trash block),
    so every attended entry lives behind the table and the mask is
    ``cache_attend``'s exactly: pool position <= query position.

``paged_attention_overlay``
    the speculative verify ``(slots, k+1)`` pattern: the pool must NOT
    be written before acceptance is known (KV rewind is "rejected
    positions were never written"), so the chunk's fresh K/V ride as a
    separate operand attended after the pool blocks — pool entries
    strictly BEFORE the chunk, chunk columns causally within it, the
    same split the reference path's gathered-view ``.at[].set``
    overlay encodes.

Masking discipline is inherited from ``cache_attend``: out-of-range
entries score ``NEG_INF`` (-1e30, finite — ``exp(m - m)`` stays 1 on
fully-masked rows) and their probabilities are zeroed explicitly, so
trash-block garbage and stale pool bytes never move an output bit. A
fully-masked query row emits zeros (the ``l == 0`` guard), where the
reference emits a uniform average of masked garbage — both are
garbage no caller reads (dead slots / padding queries), documented
rather than matched.

Parity with the reference is TOLERANCE-LEVEL, not bitwise: online
softmax reorders the reduction (blockwise running max/sum vs one
global softmax), the same cross-shape caveat PR 9 documents for XLA's
own re-tiled GEMM accumulation. Greedy token STREAMS are pinned
identical in tests — argmax decisions survive reduction-order ulps on
every workload the suite drives.

Bytes skipped, not just bytes reorganized: in the grid form the causal
bound clamps the fetch index map so blocks past a sequence's live
range re-fetch the previous block id — Pallas skips the DMA when
consecutive grid steps map to the same block — and ``pl.when`` skips
their compute; the one-query form never lists them.

``interpret`` is decided from the platform (``_call``): on a TPU the
kernel compiles through Mosaic, elsewhere it runs through the Pallas
interpreter — plain XLA ops, so the masking/online-softmax logic is
tested on every CPU run and the kernel composes with GSPMD sharding
(``serving_kv_shardings`` lays the pool's last dimension, whole heads
a shard, over the model axis). Tests pass an
explicit ``True``. Every operand's block has its last two dims EQUAL
to the array's, so Mosaic takes any ``kv_block_len`` / ``head_dim`` /
query count (``fusable``; tests/test_chip_compile.py asks the v5e
compiler) — tiles off the (8, 128) register tile are padded, not
refused. The one-query form copies blocks by hand onto whole register
tiles, so it takes a ``kv_block_len`` that is a multiple of the pool
dtype's tile rows and leaves every other to the grid form.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF


def fusable(block_len: int):
    """None if the kernel can serve this geometry, else the reason it
    cannot — the ONE geometry predicate the engine's runtime rejection
    and netlint's KRN001 both consult (a static mirror must never
    drift from the thing it mirrors). The interpreter and the v5e
    compiler both take every block length and head dim (each block's
    last two dims equal its array's), so a pool block holding at least
    one position is the whole condition."""
    if block_len < 1:
        return f"kv_block_len {block_len} < 1"
    return None


def _kernel(
    tab_ref, nlive_ref,
    q_ref, k_ref, v_ref, pos_ref, *rest,
    block_len, mb, per_query_pool_mask, has_chunk,
):
    """One (sequence, pool-block) program over ALL heads.

    A pool block is ``(block_len, H * D)``: one row a token, the heads
    side by side in it (serve/kv_pool.py), so the block is fetched
    whole and head ``h`` is columns ``[h * D, (h + 1) * D)`` of it,
    walked here with static slices.

    Grid iterates the block dimension innermost and sequentially, so
    the flash (acc, m, l) statistics live in VMEM scratch across steps
    of the same sequence: initialized at b == 0, folded per live
    block, normalized at b == mb - 1 (where the overlay chunk, if any,
    is folded last — online softmax is order-free).

    ``per_query_pool_mask``: True = write-then-read (pool position <=
    query position, cache_attend's mask); False = overlay (pool
    position strictly < the chunk's first position — every query sees
    every pool entry, the chunk columns carry [pos0, pos0+Q)).
    """
    if has_chunk:
        ck_ref, cv_ref, valid_ref, o_ref, acc, m, l = rest
    else:
        o_ref, acc, m, l = rest
    b = pl.program_id(1)
    s = pl.program_id(0)
    n_heads, nq, d = q_ref.shape[1:]
    pos = pos_ref[0, 0]                            # (Q,) int32
    scale = 1.0 / math.sqrt(d)

    def fold(h, scores, mask, values):
        """One online-softmax update of head h's running (acc, m, l)."""
        scores = jnp.where(mask, scores, NEG_INF)
        m_prev = m[h]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(scores - m_new[:, None]), 0.0)
        acc[h] = acc[h] * alpha[:, None] + p @ values
        l[h] = l[h] * alpha + jnp.sum(p, axis=-1)
        m[h] = m_new

    def query(h):
        return q_ref[0, h].astype(jnp.float32)     # (Q, D)

    @pl.when(b == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)

    @pl.when(b < nlive_ref[s])
    def _pool_block():
        kpos = b * block_len + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_len), 1
        )[0]
        if per_query_pool_mask:
            mask = kpos[None, :] <= pos[:, None]   # (Q, BL)
        else:
            mask = jnp.broadcast_to(
                kpos[None, :] < pos[0], (nq, block_len)
            )
        for h in range(n_heads):
            cols = pl.ds(h * d, d)
            k = k_ref[0, :, cols].astype(jnp.float32)   # (BL, D)
            v = v_ref[0, :, cols].astype(jnp.float32)
            fold(h, (query(h) @ k.T) * scale, mask, v)

    @pl.when(b == mb - 1)
    def _finish():
        if has_chunk:
            vld = valid_ref[0, 0] != 0
            # column jj holds the entry AT position pos[jj]: causal
            # within the chunk, padding/rejected columns masked out
            mask = (pos[None, :] <= pos[:, None]) & vld[None, :]
        for h in range(n_heads):
            if has_chunk:
                ck = ck_ref[0, h].astype(jnp.float32)   # (Q, D)
                cv = cv_ref[0, h].astype(jnp.float32)
                fold(h, (query(h) @ ck.T) * scale, mask, cv)
            safe = jnp.where(l[h] == 0.0, 1.0, l[h])
            o_ref[0, h] = (acc[h] / safe[:, None]).astype(o_ref.dtype)


#: bytes of K (and as many of V) one step of the one-query kernel copies
#: and folds: 128 positions of GPT-2 medium's 4 KB float32 rows, 1,024 of
#: a 512 B bfloat16 row of 2 K/V heads (``_item_positions``)
_CHUNK_BYTES = 512 * 1024


def _item_positions(row_width: int, dtype) -> int:
    """Pool positions one item of the one-query kernel copies, from the
    bytes of a pool row (whole blocks of them are copied: this over the
    block length, at least 1)."""
    return _CHUNK_BYTES // (row_width * jnp.dtype(dtype).itemsize)


def _spread(x, heads_to_columns):
    """(R, H) float32 -> (R, H * D), head h's value in each of its D
    columns, to the last bit: a product with a 0/1 matrix in float32
    (a few rows of running statistics, not a hot product)."""
    return jnp.dot(
        x, heads_to_columns, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _one_query_kernel(
    tab_ref, nlive_ref, seq_ref, chunk_ref, n_ref, pos_ref, q_ref, *refs,
    block_len, mb, group, per_kv,
):
    """The decode tick's shape — ONE query a sequence — as one program
    over a flat list of LIVE chunks: item ``i`` is chunk
    ``chunk_ref[i]`` (``group`` consecutive table entries) of sequence
    ``seq_ref[i]``, and ``n_ref[0]`` items are walked, so a block past
    a sequence's live range costs no step at all.

    The pools stay in HBM: a chunk's live blocks are copied by hand
    into one of two ``(group * block_len, Hkv * D)`` buffers, the next
    item's while this one is folded, across sequence boundaries too.

    The heads are folded together instead of walked: a pool row holds
    all H heads side by side, so ``K * q`` (the query as ONE H * D-wide
    row) is every head's products at once, and the sum within each
    head's D columns is one product with the 0/1 matrix ``fold_ref``
    (H * D, H): scores (positions, H). The running (m, l) are (1, H);
    the probabilities go back to H * D columns through ``spread_ref``
    (H, H * D) to meet V. Both are products at the platform's default
    precision, as the reference path's are (on a TPU one bfloat16 pass
    of ``K * q`` and of the probabilities, accumulated in float32; on
    a CPU float32 throughout); ``K * q``, ``p * V`` and every statistic
    are float32 everywhere.

    ``per_kv`` query heads over ONE K/V head (``per_kv`` > 1: query head
    ``j`` reads K/V head ``j // per_kv``): the pool row holds the Hkv
    K/V heads alone, and ``q_ref`` holds a sequence's query laid out
    block-diagonally, (H, Hkv * D) with query head j in the D columns of
    its K/V head and zeros elsewhere, so a chunk's scores (H, positions)
    are ONE product with the copied K rows, as are its values
    (H, positions) x (positions, Hkv * D) into the running (H, Hkv * D);
    ``_finish`` keeps each query head's own D columns. Both products
    take the operands as they lie (the pool's dtype) and accumulate in
    float32, as ``cache_attend``'s do; the statistics are a column a
    head, float32."""
    if per_kv == 1:
        fold_ref, spread_ref, *refs = refs
    k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, acc, m, l = refs
    gbl = group * block_len
    n = n_ref[0]
    if per_kv == 1:
        scale = 1.0 / math.sqrt(q_ref.shape[-1] // m.shape[-1])
    else:
        scale = 1.0 / math.sqrt(o_ref.shape[-1])

    def copies(i, buf):
        """Item i's (is it live?, its K copy, its V copy), a block each."""
        seq = seq_ref[i]
        for g in range(group):
            b = chunk_ref[i] * group + g
            bid = tab_ref[seq * mb + jnp.minimum(b, mb - 1)]
            rows = pl.ds(g * block_len, block_len)
            yield b < nlive_ref[seq], (
                pltpu.make_async_copy(
                    k_hbm.at[bid], kbuf.at[buf, rows], sem.at[0, buf]
                ),
                pltpu.make_async_copy(
                    v_hbm.at[bid], vbuf.at[buf, rows], sem.at[1, buf]
                ),
            )

    def each_copy(i, buf, do):
        for live, pair in copies(i, buf):
            @pl.when(live)
            def _():
                for copy in pair:
                    do(copy)

    # rows a part-filled chunk leaves stale are masked, but 0 * NaN is
    # NaN: the buffers start as zeros, and hold pool bytes ever after
    kbuf[...] = jnp.zeros_like(kbuf)
    vbuf[...] = jnp.zeros_like(vbuf)
    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n > 0)
    def _first():
        each_copy(0, 0, lambda copy: copy.start())

    def fold_grouped(seq, c, buf):
        """One item of ``per_kv`` query heads a K/V head: scores and
        values as two products on the MXU, the statistics a column a
        head (the latent kernel's form over the block-diagonal query)."""
        rows = kbuf[buf]                                         # (GBL, HD)
        scores = jax.lax.dot_general(
            q_ref[seq], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                                # (H, GBL)
        kpos = c * gbl + jax.lax.broadcasted_iota(jnp.int32, (1, gbl), 1)
        mask = kpos <= pos_ref[seq]
        scores = jnp.where(mask, scores, NEG_INF)
        m_prev = m[...]                                          # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)        # (H, GBL)
        l[...] = l[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m[...] = m_new
        values = vbuf[buf]
        acc[...] = acc[...] * alpha + jnp.dot(
            p.astype(values.dtype), values, preferred_element_type=jnp.float32
        )                                                        # (H, HD)

        @pl.when((c + 1) * group >= nlive_ref[seq])
        def _finish():
            total = l[...]
            out = acc[...] / jnp.where(total == 0.0, 1.0, total)
            d = o_ref.shape[-1]
            for j in range(out.shape[-1] // d):
                heads = slice(j * per_kv, (j + 1) * per_kv)
                o_ref[seq, heads, :] = out[heads, j * d:(j + 1) * d].astype(
                    o_ref.dtype
                )

    def fold_item(i, carry):
        buf = i % 2

        @pl.when(i + 1 < n)
        def _next():
            each_copy(i + 1, 1 - buf, lambda copy: copy.start())

        each_copy(i, buf, lambda copy: copy.wait())
        seq, c = seq_ref[i], chunk_ref[i]
        if per_kv == 1:
            spread = spread_ref[...]

        @pl.when(c == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)
            m[...] = jnp.full_like(m, NEG_INF)
            l[...] = jnp.zeros_like(l)

        if per_kv > 1:
            fold_grouped(seq, c, buf)
            return carry
        q = q_ref[seq].astype(jnp.float32) * scale               # (1, HD)
        products = kbuf[buf].astype(jnp.float32) * q             # (GBL, HD)
        scores = jnp.dot(
            products, fold_ref[...], preferred_element_type=jnp.float32
        )                                                        # (GBL, H)
        kpos = c * gbl + jax.lax.broadcasted_iota(jnp.int32, (gbl, 1), 0)
        mask = kpos <= pos_ref[seq]
        scores = jnp.where(mask, scores, NEG_INF)
        m_prev = m[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)                          # (1, H)
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)        # (GBL, H)
        l[...] = l[...] * alpha + jnp.sum(p, axis=0, keepdims=True)
        m[...] = m_new
        p_wide = jnp.dot(
            p, spread, preferred_element_type=jnp.float32
        )                                                        # (GBL, HD)
        acc[...] = acc[...] * _spread(alpha, spread) + jnp.sum(
            p_wide * vbuf[buf].astype(jnp.float32), axis=0, keepdims=True
        )

        @pl.when((c + 1) * group >= nlive_ref[seq])
        def _finish():
            lw = _spread(l[...], spread)
            o_ref[seq] = (
                acc[...] / jnp.where(lw == 0.0, 1.0, lw)
            ).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, n, fold_item, 0)


def _call_one_query(q, k_pool, v_pool, tables, positions, interpret):
    """``_one_query_kernel`` on (S, H, 1, D) queries over pools of
    ``Hkv * D``-wide rows: the list of live chunks and the queries as
    the kernel reads them are made here, in plain XLA (a tick's layers
    ask for the same list, which the compiler computes once). One K/V
    head a query head: the queries as H * D-wide rows and the two 0/1
    matrices; fewer: each query block-diagonal, (H, Hkv * D)."""
    s, h, _, d = q.shape
    _, bl, hd = k_pool.shape
    per_kv = h * d // hd
    mb = tables.shape[1]
    group = max(1, min(_item_positions(hd, k_pool.dtype) // bl, mb))
    pos = positions[:, 0].astype(jnp.int32)
    nlive = live_blocks(pos, bl, mb).astype(jnp.int32)
    seq, chunk, n_items = _live_items(nlive, group, mb)

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    if per_kv == 1:
        fold = (
            jnp.arange(hd)[:, None] // d == jnp.arange(h)[None, :]
        ).astype(jnp.float32)                            # (HD, H)
        tflat = tables.reshape(-1).astype(jnp.int32)
        queries = [jnp.moveaxis(q, 1, 2).reshape(s, 1, hd), fold, fold.T]
        query_specs = [whole(s, 1, hd), whole(hd, h), whole(h, hd)]
        out_shape, stats = (s, 1, hd), [(1, hd), (1, h), (1, h)]
    else:
        tflat = tables.reshape(-1).astype(jnp.int32)
        own = jnp.arange(hd)[None, :] // d == jnp.arange(h)[:, None] // per_kv
        queries = [jnp.where(own, jnp.tile(q[:, :, 0], (1, 1, hd // d)), 0)]
        query_specs = [whole(s, h, hd)]
        out_shape, stats = (s, h, d), [(h, hd), (h, 1), (h, 1)]

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(
            _one_query_kernel, block_len=bl, mb=mb, group=group, per_kv=per_kv,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(1,),
            in_specs=[*query_specs, in_hbm, in_hbm],
            out_specs=whole(*out_shape),
            scratch_shapes=[
                pltpu.VMEM((2, group * bl, hd), k_pool.dtype),
                pltpu.VMEM((2, group * bl, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),       # (K or V, buffer)
                *(pltpu.VMEM(shape, jnp.float32) for shape in stats),
            ],                                         # acc, m (max), l (sum)
        ),
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(tflat, nlive, seq, chunk, n_items, pos, *queries, k_pool, v_pool)
    if per_kv == 1:
        return jnp.moveaxis(out.reshape(s, 1, h, d), 2, 1)
    return out[:, :, None]


#: pool positions one step of the latent kernel fetches and folds: four
#: blocks of 128 at a published serving shape, 0.65 MB of bfloat16 rows
_LATENT_CHUNK_POSITIONS = 512
#: what the latent kernel may hold in VMEM: the queries and the results
#: of every slot (3.9 and 3.1 MB at 48 slots of 64 heads) beside its two
#: buffers
_LATENT_VMEM_BYTES = 48 * 1024 * 1024


def _latent_kernel(
    tab_ref, nlive_ref, seq_ref, chunk_ref, n_ref, pos_ref,
    q_ref, pool_hbm, o_ref,
    buf, sem, acc, m, l,
    *, block_len, mb, group, scale,
):
    """The decode tick of a LATENT cache — one absorbed query a
    sequence, (H, W) wide: every head's query taken into the latent
    space — as one program over a flat list of live chunks, as
    ``_one_query_kernel`` walks one: item ``i`` is chunk
    ``chunk_ref[i]`` (``group`` consecutive table entries) of sequence
    ``seq_ref[i]``, ``n_ref[0]`` items are walked, and a chunk's live
    blocks are copied by hand into one of two ``(group * block_len, W)``
    buffers while the chunk before is folded.

    A latent row serves every head as key AND as value, so a chunk is
    two plain products on the MXU: scores ``(H, W) x (W, rows)``, then
    probabilities ``(H, rows) x (rows, W)`` into the running sum; the
    statistics are a column a head. The row's tail beyond the latent
    (the rotary key, a pool's zeros) meets the query's own tail in the
    scores and is dropped from the result by the caller's width
    (``o_ref`` holds the first columns)."""
    gbl = group * block_len
    n = n_ref[0]
    out_w = o_ref.shape[-1]

    def copies(i, b):
        """Item i's (is it live?, its copy), a block each."""
        seq = seq_ref[i]
        for g in range(group):
            blk = chunk_ref[i] * group + g
            bid = tab_ref[seq * mb + jnp.minimum(blk, mb - 1)]
            rows = pl.ds(g * block_len, block_len)
            yield blk < nlive_ref[seq], pltpu.make_async_copy(
                pool_hbm.at[bid], buf.at[b, rows], sem.at[b]
            )

    def each_copy(i, b, do):
        for live, copy in copies(i, b):
            @pl.when(live)
            def _():
                do(copy)

    # rows a part-filled chunk leaves stale are masked, but 0 * NaN is
    # NaN: the buffers start as zeros, and hold pool bytes ever after
    buf[...] = jnp.zeros_like(buf)
    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n > 0)
    def _first():
        each_copy(0, 0, lambda copy: copy.start())

    def fold_item(i, carry):
        b = i % 2

        @pl.when(i + 1 < n)
        def _next():
            each_copy(i + 1, 1 - b, lambda copy: copy.start())

        each_copy(i, b, lambda copy: copy.wait())
        seq, c = seq_ref[i], chunk_ref[i]

        @pl.when(c == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)
            m[...] = jnp.full_like(m, NEG_INF)
            l[...] = jnp.zeros_like(l)

        rows = buf[b]                                            # (GBL, W)
        scores = jax.lax.dot_general(
            q_ref[seq], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                                # (H, GBL)
        kpos = c * gbl + jax.lax.broadcasted_iota(jnp.int32, (1, gbl), 1)
        mask = kpos <= pos_ref[seq]
        scores = jnp.where(mask, scores, NEG_INF)
        m_prev = m[...]                                          # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)        # (H, GBL)
        l[...] = l[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m[...] = m_new
        acc[...] = acc[...] * alpha + jnp.dot(
            p.astype(rows.dtype), rows, preferred_element_type=jnp.float32
        )

        @pl.when((c + 1) * group >= nlive_ref[seq])
        def _finish():
            total = l[...]
            o_ref[seq] = (
                acc[...][:, :out_w] / jnp.where(total == 0.0, 1.0, total)
            ).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, n, fold_item, 0)


def one_query_fusable(block_len: int, dtype) -> str | None:
    """None if the one-query kernels with no grid form beside them (the
    latent kernel, and ``_one_query_kernel`` over K/V heads that query
    heads share) can serve this pool, else the reason they cannot: they
    copy blocks by hand onto whole register tiles, so a block is a
    multiple of the pool dtype's tile rows."""
    if block_len % _sublanes(dtype):
        return (
            f"kv_block_len {block_len} is no multiple of "
            f"{_sublanes(dtype)} rows of {jnp.dtype(dtype).name}"
        )
    return None


def paged_latent_attention(
    q, pool, tables, positions, *, scale: float, out_width: int,
    interpret=None,
):
    """Masked paged attention of ONE absorbed query a sequence over a
    latent pool, read in place through the block table.

    ``q`` (S, H, W): every head's query taken into the latent space and
    laid out as a pool row is (``models.transformer.latent_absorb``);
    ``pool`` (n_blocks, block_len, W), each row a token's latent (the
    fresh token's already scattered in); ``tables`` (S, max_blocks);
    ``positions`` (S,) the last pool position each sequence may see,
    -1 for a sequence with nothing to attend to (a dead lane: its
    result is zeros). -> (S, H, out_width): the first ``out_width``
    columns of ``softmax(scale * q rows^T) rows``, allclose to
    ``latent_attend``'s absorbed form over the gathered view."""
    s, h, w = q.shape
    _, bl, pw = pool.shape
    if pw != w:
        raise ValueError(
            f"pool rows are {pw} wide, the absorbed queries {w}"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    mb = tables.shape[1]
    group = max(1, min(_LATENT_CHUNK_POSITIONS // bl, mb))
    pos = positions.astype(jnp.int32)
    nlive = live_blocks(pos, bl, mb).astype(jnp.int32)
    seq, chunk, n = _live_items(nlive, group, mb)

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    return pl.pallas_call(
        functools.partial(
            _latent_kernel, block_len=bl, mb=mb, group=group, scale=scale,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(1,),
            in_specs=[whole(s, h, w), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole(s, h, out_width),
            scratch_shapes=[
                pltpu.VMEM((2, group * bl, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((h, w), jnp.float32),       # acc
                pltpu.VMEM((h, 1), jnp.float32),       # m (running max)
                pltpu.VMEM((h, 1), jnp.float32),       # l (running sum)
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s, h, out_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_LATENT_VMEM_BYTES
        ),
        interpret=bool(interpret),
        name="paged_latent_attention",
    )(tables.reshape(-1).astype(jnp.int32), nlive, seq, chunk, n, pos, q, pool)


def _live_items(nlive, group: int, mb: int):
    """The flat list both one-query kernels walk: for ``nlive`` (S,)
    live blocks a sequence and chunks of ``group`` table entries ->
    (sequence of item i, its chunk within the sequence, how many items
    there are (1,)), the first two ``S * ceil(mb / group)`` long."""
    s = nlive.shape[0]
    chunks = (nlive + group - 1) // group                # (S,) a sequence
    ends = jnp.cumsum(chunks)
    item = jnp.arange(s * -(-mb // group), dtype=jnp.int32)
    seq = jnp.minimum(
        jnp.searchsorted(ends, item, side="right", method="compare_all"),
        s - 1,
    ).astype(jnp.int32)
    chunk = item - (ends - chunks)[seq]
    return seq, chunk.astype(jnp.int32), ends[-1:].astype(jnp.int32)


def live_blocks(last_position, block_len, max_blocks):
    """Blocks the kernel's clamped grid actually fetches for one
    sequence whose last attended POOL position is ``last_position``
    (= ceil((last_position + 1) / block_len), clipped to the table
    width; -1 = no pool blocks): the ``nlive`` of both kernel forms.
    Works on scalars and arrays."""
    return jnp.clip((last_position + block_len) // block_len, 0, max_blocks)


def _sublanes(dtype) -> int:
    """Rows of one register tile of ``dtype``: 8 of float32, 16 of
    bfloat16."""
    return 32 // jnp.dtype(dtype).itemsize


def _call(q, k_pool, v_pool, tables, positions, chunk, interpret):
    s, h, nq, d = q.shape
    _, bl, hd = k_pool.shape
    if hd % d or h % (hd // d):
        raise ValueError(
            f"pool rows are {hd} wide, the queries' {h} heads of {d} "
            f"need a whole number of K/V heads that divides {h}: the pool "
            f"is (n_blocks, block_len, Hkv * D)"
        )
    mb = tables.shape[1]
    if interpret is None:
        # THE place the fused serving kernel picks its form: compiled
        # through Mosaic on a TPU, the Pallas interpreter anywhere else
        interpret = jax.default_backend() != "tpu"
    if chunk is None and nq == 1 and bl % _sublanes(k_pool.dtype) == 0:
        # the decode tick: blocks copied by hand land on whole tiles
        return _call_one_query(
            q, k_pool, v_pool, tables, positions, bool(interpret)
        )
    if hd != h * d:
        raise ValueError(
            f"the grid form walks one K/V head a query head: {h} query "
            f"heads over {hd // d} K/V heads are read by the one-query "
            f"form alone (one query a sequence, no overlay, blocks of "
            f"whole {jnp.dtype(k_pool.dtype).name} register tiles)"
        )
    if chunk is None:
        # write-then-read: blocks must cover every query position
        live_to = jnp.max(positions, axis=1)
    else:
        # overlay: blocks cover strictly-before-the-chunk positions
        live_to = positions[:, 0] - 1
    nlive = live_blocks(live_to, bl, mb).astype(jnp.int32)
    tflat = tables.reshape(-1).astype(jnp.int32)

    def kmap(i, b, tref, nref):
        # clamp dead iterations at the last live block: the repeated
        # index lets the grid pipeline skip the re-fetch, pl.when
        # skips the compute — bytes saved, not just masked
        bb = jnp.minimum(b, jnp.maximum(nref[i] - 1, 0))
        return (tref[i * mb + bb], 0, 0)

    # every operand's block has its last two dims EQUAL to the array's,
    # which Mosaic takes at any size: a sequence's queries over all its
    # heads, a whole pool block, and per-sequence rows as (S, 1, Q)
    # with a (1, 1, Q) block — a (1, Q) block of an (S, Q) array is
    # refused unless S == 1 (sublane dim neither 8-divisible nor the
    # array's)
    qspec = pl.BlockSpec((1, h, nq, d), lambda i, b, t, n: (i, 0, 0, 0))
    rowspec = pl.BlockSpec((1, 1, nq), lambda i, b, t, n: (i, 0, 0))
    kvspec = pl.BlockSpec((1, bl, hd), kmap)
    in_specs = [qspec, kvspec, kvspec, rowspec]
    args = [q, k_pool, v_pool, positions.astype(jnp.int32)[:, None, :]]
    if chunk is not None:
        ck, cv, valid = chunk
        in_specs += [qspec, qspec, rowspec]
        args += [ck, cv, valid.astype(jnp.int32)[:, None, :]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, mb),
        in_specs=in_specs,
        out_specs=qspec,
        scratch_shapes=[
            pltpu.VMEM((h, nq, d), jnp.float32),   # acc
            pltpu.VMEM((h, nq), jnp.float32),      # m (running rowmax)
            pltpu.VMEM((h, nq), jnp.float32),      # l (running rowsum)
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _kernel,
            block_len=bl, mb=mb,
            per_query_pool_mask=chunk is None,
            has_chunk=chunk is not None,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=bool(interpret),
        name="paged_attention",
    )(tflat, nlive, *args)


def paged_attention(
    q, k_pool, v_pool, tables, positions, *, interpret=None
):
    """Masked paged attention, write-then-read form.

    ``q`` (S, H, Q, D) queries at absolute ``positions`` (S, Q);
    ``k_pool``/``v_pool`` (n_blocks, block_len, H * D) pools already
    holding every attended entry (the fresh chunk was scattered in,
    padding to the trash block); ``tables`` (S, max_blocks) block ids.
    -> (S, H, Q, D), allclose to
    ``cache_attend(q, gather(k_pool), gather(v_pool), positions)``
    without the gather's dense intermediate.
    """
    return _call(q, k_pool, v_pool, tables, positions, None, interpret)


def paged_attention_overlay(
    q, k_pool, v_pool, tables, positions, chunk_k, chunk_v, chunk_valid,
    *, interpret=None,
):
    """Masked paged attention with the fresh chunk OVERLAID — the
    verify tick's no-pool-write form (KV rewind by construction).

    ``chunk_k``/``chunk_v`` (S, H, Q, D) hold the K/V of the chunk's
    own positions (column jj lives at ``positions[s, jj]``);
    ``chunk_valid`` (S, Q) marks real columns (draft-width/liveness
    padding rides masked). Pool entries are attended strictly BEFORE
    ``positions[:, 0]``; the pool is never written here.
    """
    return _call(
        q, k_pool, v_pool, tables, positions,
        (chunk_k, chunk_v, chunk_valid), interpret,
    )
