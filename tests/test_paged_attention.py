"""Fused paged attention (singa_tpu/ops/paged_attention.py) and its
``kernels { paged_attention }`` seam through the serving engine.

Two correctness bars:

  - the KERNEL is allclose to the gather -> ``cache_attend`` oracle
    (online softmax reorders the reduction, so parity is
    tolerance-level — the PR 9 cross-shape caveat at kernel
    granularity), across block/head/fill geometries, with trash-block
    garbage provably inert;
  - the ENGINE under ``fused`` emits greedy token streams IDENTICAL
    to the reference path — interleaved ragged workloads, speculative
    verify ticks, a warm prefix cache, and the TP mesh — while the
    default config's compiled programs stay jaxpr-identical to an
    explicit ``reference`` selection (the oracle path is untouched).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.models.transformer import (
    TransformerConfig,
    cache_attend,
    init_lm,
)
from singa_tpu.ops.paged_attention import (
    fusable,
    paged_attention,
    paged_attention_overlay,
)
from singa_tpu.serve import Engine, EngineConfig, Request, Scheduler
from singa_tpu.serve.engine import choose_attend


def tiny_cfg(**kw):
    base = dict(
        vocab=32, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=32
    )
    base.update(kw)
    return TransformerConfig(**base)


def mixed_workload(vocab, n=6, seed=0):
    rs = np.random.RandomState(seed)
    prompts = [
        rs.randint(0, vocab, size=(int(rs.randint(3, 9)),)).astype(np.int32)
        for _ in range(n)
    ]
    budgets = [int(rs.randint(4, 10)) for _ in range(n)]
    return prompts, budgets


def run_streams(params, cfg, impl, *, spec_k=0, prefix_cache=False,
                mesh=None, n=6, seed=0, slots=3):
    """The scheduler workload under one attend implementation ->
    {rid: tokens}."""
    prompts, budgets = mixed_workload(cfg.vocab, n=n, seed=seed)
    eng = Engine(
        params, cfg,
        EngineConfig(
            slots=slots, kv_block_len=8, max_prefill_chunk=4,
            attend_impl=impl, spec_k=spec_k, prefix_cache=prefix_cache,
        ),
        mesh=mesh,
    )
    sched = Scheduler(eng)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    sched.serve()
    return {r.rid: r.tokens for r in sched.finished}


def oracle_gather(pool_arr, tables, cache_len):
    g = jnp.moveaxis(pool_arr[tables], 2, 1)
    return g.reshape(g.shape[0], g.shape[1], cache_len, g.shape[-1])


def stored(pool_arr):
    """A (NB, H, BL, D) pool, the axes the oracle above reads, in the
    shape the engine stores and the kernel takes: (NB, BL, H * D)."""
    p = jnp.moveaxis(jnp.asarray(pool_arr), 1, 2)
    return p.reshape(*p.shape[:2], -1)


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "block_len,head_dim,fill,per_kv",
    [
        (4, 8, 3, 1),     # partial first block
        (8, 16, 17, 1),   # mid-pool fill, blocks crossed
        (8, 16, 31, 1),   # cache full to the last position
        (16, 32, 40, 1),  # wide blocks, deeper pool
        (2, 4, 9, 1),     # tiny blocks: many grid steps
        (8, 16, 17, 2),   # query heads over fewer K/V heads: 2 a K/V head
        (16, 32, 40, 8),  # 8 a K/V head
        (8, 8, 63, 16),   # 16 a K/V head, the cache full
    ],
)
def test_kernel_matches_gather_oracle(block_len, head_dim, fill, per_kv):
    """Write-then-read form == cache_attend over the dense gather,
    across block_len / head_dim / cache-fill geometry and query heads a
    K/V head (allclose: the online softmax reorders the reduction)."""
    rs = np.random.RandomState(fill)
    s, h, q = 3, 2, 1
    max_len = 64
    mb = max_len // block_len
    nb = s * mb + 1
    kp = jnp.asarray(rs.randn(nb, h, block_len, head_dim), jnp.float32)
    vp = jnp.asarray(rs.randn(nb, h, block_len, head_dim), jnp.float32)
    qh = jnp.asarray(rs.randn(s, h * per_kv, q, head_dim), jnp.float32)
    # each sequence owns a disjoint table slice (1-based: 0 is trash)
    tables = jnp.asarray(
        1 + np.arange(s * mb).reshape(s, mb), jnp.int32
    )
    pos = jnp.asarray(
        rs.randint(0, fill + 1, size=(s, q)), jnp.int32
    )
    got = paged_attention(
        qh, stored(kp), stored(vp), tables, pos, interpret=True
    )
    want = cache_attend(
        qh,
        oracle_gather(kp, tables, mb * block_len),
        oracle_gather(vp, tables, mb * block_len),
        pos,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize("per_kv", [1, 2, 8, 16])
@pytest.mark.parametrize("chunk_positions", [16, 128, 4096])
def test_one_query_kernel_walks_the_live_chunks(
    monkeypatch, chunk_positions, per_kv
):
    """The decode tick's form (one query a sequence, blocks copied by
    hand a chunk at a time over a flat list of live chunks) at
    sequences of very different lengths: one position, a chunk's last
    and first, a block's edge, the whole table, and a dead lane (-1:
    nothing to attend to, zeros out); a chunk of two blocks, of sixteen,
    and wider than the table; one query head a K/V head and 2, 8 or 16.
    Table entries past a sequence's live blocks are the trash block,
    poisoned. Each against the oracle; one query head a K/V head also
    against the grid form, which the same call takes for a block length
    off the register tile and which refuses fewer K/V heads."""
    from singa_tpu.ops import paged_attention as pa

    rs = np.random.RandomState(chunk_positions + per_kv)
    hkv, d, bl, mb = 3, 8, 8, 48
    h = hkv * per_kv
    monkeypatch.setattr(pa, "_CHUNK_BYTES", chunk_positions * hkv * d * 4)
    positions = [0, 7, 8, 127, 128, 200, mb * bl - 1, -1]
    s = len(positions)
    nb = s * mb + 1
    kp = rs.randn(nb, hkv, bl, d).astype(np.float32)
    vp = rs.randn(nb, hkv, bl, d).astype(np.float32)
    kp[0], vp[0] = 1e9, -1e9                 # the trash block
    qh = jnp.asarray(rs.randn(s, h, 1, d), jnp.float32)
    tables = 1 + rs.permutation(s * mb).reshape(s, mb)
    for row, p in enumerate(positions):
        tables[row, p // bl + 1:] = 0
    tables = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)[:, None]
    want = cache_attend(
        qh, oracle_gather(kp, tables, mb * bl),
        oracle_gather(vp, tables, mb * bl), pos,
    )
    kp, vp = stored(kp), stored(vp)
    got = np.asarray(paged_attention(qh, kp, vp, tables, pos, interpret=True))
    np.testing.assert_allclose(
        got[:-1], np.asarray(want)[:-1], atol=1e-5, rtol=1e-5
    )
    assert not got[-1].any()                 # the dead lane
    monkeypatch.setattr(pa, "_sublanes", lambda dtype: bl + 1)
    if per_kv > 1:
        with pytest.raises(
            ValueError, match=f"{h} query heads over {hkv} K/V heads"
        ):
            paged_attention(qh, kp, vp, tables, pos, interpret=True)
        return
    grid = paged_attention(qh, kp, vp, tables, pos, interpret=True)
    np.testing.assert_allclose(
        got[:-1], np.asarray(grid)[:-1], atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize("row,dtype,positions", [
    (16 * 64, jnp.float32, 128),     # gpt2_medium_serve_closed's pools
    (2 * 128, jnp.bfloat16, 1024),   # nemotron_3_super_serve_chat's
])
def test_an_item_copies_half_a_megabyte(row, dtype, positions):
    """Positions an item of the one-query kernel copies follow the
    row's bytes: 128 at GPT-2 medium's 4 KB float32 rows, as before
    they were sized by bytes, and 8 blocks of 128 of a 512 B row."""
    from singa_tpu.ops.paged_attention import _item_positions

    assert _item_positions(row, dtype) == positions


@pytest.mark.parametrize("form", ["many_queries", "overlay"])
def test_the_grid_form_refuses_fewer_kv_heads(form):
    """The grid form (a sequence's several queries, the verify pass's
    overlay; a block off the register tile is the walk's case above)
    walks one K/V head a query head, and says so by both head counts
    where it is handed fewer."""
    rs = np.random.RandomState(2)
    s, hkv, per_kv, d, bl, mb = 2, 2, 4, 8, 8, 4
    nq = 3 if form == "many_queries" else 1
    pool = jnp.asarray(rs.randn(s * mb + 1, bl, hkv * d), jnp.float32)
    qh = jnp.asarray(rs.randn(s, hkv * per_kv, nq, d), jnp.float32)
    tables = jnp.asarray(1 + np.arange(s * mb).reshape(s, mb), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(nq, dtype=jnp.int32), (s, nq))
    with pytest.raises(ValueError, match="8 query heads over 2 K/V heads"):
        if form == "overlay":
            paged_attention_overlay(
                qh, pool, pool, tables, pos, qh, qh,
                jnp.ones((s, nq), bool), interpret=True,
            )
        else:
            paged_attention(qh, pool, pool, tables, pos, interpret=True)


def test_trash_block_garbage_never_moves_the_output():
    """The cache_attend -1e30 invariant holds in the kernel: poisoning
    the trash block (and every position past the queries) with huge
    garbage changes no output bit."""
    rs = np.random.RandomState(0)
    s, h, bl, d, mb = 2, 2, 4, 8, 4
    nb = s * mb + 1
    kp = np.asarray(rs.randn(nb, h, bl, d), np.float32)
    vp = np.asarray(rs.randn(nb, h, bl, d), np.float32)
    q = jnp.asarray(rs.randn(s, h, 1, d), jnp.float32)
    tables = jnp.asarray(1 + np.arange(s * mb).reshape(s, mb), jnp.int32)
    pos = jnp.asarray([[5], [9]], jnp.int32)
    base = paged_attention(
        q, stored(kp), stored(vp), tables, pos, interpret=True
    )
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[0], vp2[0] = 1e9, -1e9              # the trash block
    for row, p in enumerate(np.asarray(pos)[:, 0]):
        blk, off = divmod(int(p) + 1, bl)    # every position PAST p
        for b in range(blk, mb):
            lo = off if b == blk else 0
            kp2[1 + row * mb + b, :, lo:] = 7e8
            vp2[1 + row * mb + b, :, lo:] = -7e8
    poisoned = paged_attention(
        q, stored(kp2), stored(vp2), tables, pos, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(base), np.asarray(poisoned))


def test_overlay_matches_dense_overlay_oracle():
    """The verify-shape overlay form == the reference's gathered-view
    ``.at[].set`` overlay + cache_attend, on valid queries (invalid
    draft-padding queries attend garbage differently by design — no
    caller reads them)."""
    rs = np.random.RandomState(1)
    s, h, q, bl, d, mb = 3, 2, 4, 8, 16, 4
    nb = s * mb + 1
    kp = jnp.asarray(rs.randn(nb, h, bl, d), jnp.float32)
    vp = jnp.asarray(rs.randn(nb, h, bl, d), jnp.float32)
    qh = jnp.asarray(rs.randn(s, h, q, d), jnp.float32)
    ck = jnp.asarray(rs.randn(s, h, q, d), jnp.float32)
    cv = jnp.asarray(rs.randn(s, h, q, d), jnp.float32)
    tables = jnp.asarray(1 + np.arange(s * mb).reshape(s, mb), jnp.int32)
    pos0 = jnp.asarray([0, 7, 21])           # incl. zero pool blocks
    pos = pos0[:, None] + jnp.arange(q)[None, :]
    valid = jnp.asarray(
        [[1, 1, 1, 0], [1, 1, 1, 1], [1, 0, 0, 0]], bool
    )
    got = paged_attention_overlay(
        qh, stored(kp), stored(vp), tables, pos, ck, cv, valid,
        interpret=True,
    )
    sidx = jnp.arange(s)[:, None]
    gk = oracle_gather(kp, tables, mb * bl).at[sidx, :, pos].set(
        jnp.moveaxis(ck, 1, 2)
    )
    gv = oracle_gather(vp, tables, mb * bl).at[sidx, :, pos].set(
        jnp.moveaxis(cv, 1, 2)
    )
    want = np.asarray(cache_attend(qh, gk, gv, pos))
    gota = np.asarray(got)
    for i in range(s):
        for j in range(q):
            if valid[i, j]:
                np.testing.assert_allclose(
                    gota[i, :, j], want[i, :, j], atol=1e-5, rtol=1e-5
                )


def test_fusable_predicate():
    """Interpreter and compiler both tile any block (the (8, 128)
    demand was the repo's, not Mosaic's — tests/test_chip_compile.py
    asks the v5e compiler about 12 and 96); an empty block is
    refused."""
    assert fusable(3) is None
    assert fusable(16) is None
    assert fusable(12) is None
    assert "kv_block_len" in fusable(0)


# ---------------------------------------------------------------------------
# the engine seam: fused streams == reference streams
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lm():
    cfg = tiny_cfg()
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


def test_fused_streams_identical_interleaved(lm):
    """Greedy token streams under `fused` == the reference path across
    an interleaved ragged workload (admits/retires at different
    ticks)."""
    cfg, params = lm
    assert run_streams(params, cfg, "fused") == run_streams(
        params, cfg, "reference"
    )


def test_fused_streams_identical_under_speculation(lm):
    """The verify tick's overlay kernel preserves stream identity at
    spec_k > 0 — and the fused path's unconditional post-acceptance
    scatter leaves the paged pool BITWISE what the reference (and
    sequential one-token decode) leaves."""
    cfg, params = lm
    prompts, budgets = mixed_workload(cfg.vocab, n=4, seed=3)

    def run(impl, spec_k):
        eng = Engine(params, cfg, EngineConfig(
            slots=2, kv_block_len=8, max_prefill_chunk=4,
            attend_impl=impl, spec_k=spec_k,
        ))
        sched = Scheduler(eng)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
        sched.serve()
        return {r.rid: r.tokens for r in sched.finished}, eng

    ref, ref_eng = run("reference", 3)
    fus, fus_eng = run("fused", 3)
    seq, _ = run("reference", 0)
    assert ref == fus == seq
    # REAL-block pool parity across impls is tolerance-level, not
    # bitwise: layer 1's attend output (reordered reduction) feeds
    # layer 2's written K/V, so low bits may drift — the same reason
    # verify-vs-decode parity is token-level (the PR 9 cross-shape
    # caveat). The TRASH block is excluded: rejected/padding writes
    # collide there and XLA's duplicate-scatter winner is
    # implementation-defined between two different compiled programs —
    # its contents are masked out of every attend by construction (the
    # poisoning test pins that). The rewind contract itself (rejected
    # positions never written) is structural in the fused path: no
    # pool write happens before the acceptance scatter.
    for layer in range(cfg.n_layers):
        np.testing.assert_allclose(
            np.asarray(ref_eng.state["k"][layer])[1:],
            np.asarray(fus_eng.state["k"][layer])[1:],
            atol=1e-5, rtol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(ref_eng.state["v"][layer])[1:],
            np.asarray(fus_eng.state["v"][layer])[1:],
            atol=1e-5, rtol=1e-5,
        )


def test_fused_verify_zero_draft_width_matches_reference(lm):
    """The machinery-probe shape: verify at kd == 0 (an (S, 0) draft)
    under `fused` rides the overlay kernel + the unconditional
    post-acceptance scatter — emitted tokens identical to the
    reference's write-then-gather special case, real-block pool
    allclose."""
    cfg, params = lm
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab, size=(5,)).astype(np.int32)
               for _ in range(2)]

    def build(impl):
        eng = Engine(params, cfg, EngineConfig(
            slots=2, kv_block_len=8, max_prefill_chunk=4,
            attend_impl=impl,
        ))
        for s in range(2):
            eng.admit(s, 20)
            eng.prefill_chunk(s, prompts[s][:4], 0)
            last = eng.prefill_chunk(s, prompts[s][4:], 4)
            eng.activate(s, last, 5, seed=s)
        return eng

    ref, fus = build("reference"), build("fused")
    empty = np.zeros((2, 0), np.int32)
    nd = np.zeros((2,), np.int32)
    for _ in range(4):
        er, _ = ref.verify(empty, nd)
        ef, _ = fus.verify(empty, nd)
        np.testing.assert_array_equal(np.asarray(er), np.asarray(ef))
    for layer in range(cfg.n_layers):
        np.testing.assert_allclose(
            np.asarray(ref.state["k"][layer])[1:],
            np.asarray(fus.state["k"][layer])[1:],
            atol=1e-5, rtol=1e-5,
        )


def test_fused_streams_identical_prefix_warm(lm):
    """A warm prefix cache (shared blocks + COW + LRU revival) under
    `fused` still matches the reference streams — block sharing is
    table indirection the kernel reads through like any other
    table."""
    cfg, params = lm
    rs = np.random.RandomState(7)
    prefix = rs.randint(0, cfg.vocab, size=(16,)).astype(np.int32)

    def run(impl):
        eng = Engine(params, cfg, EngineConfig(
            slots=2, kv_block_len=8, max_prefill_chunk=4,
            attend_impl=impl, prefix_cache=True,
        ))
        sched = Scheduler(eng)
        for i in range(4):
            tail = rs.randint(0, cfg.vocab, size=(2,)).astype(np.int32)
            sched.submit(Request(
                rid=i, prompt=np.concatenate([prefix, tail]),
                max_new_tokens=5,
            ))
        sched.serve()
        return (
            {r.rid: r.tokens for r in sched.finished},
            sched.prefix_hits,
        )

    rs = np.random.RandomState(7)
    _ = rs.randint(0, cfg.vocab, size=(16,))
    ref, _ = run("reference")
    rs = np.random.RandomState(7)
    _ = rs.randint(0, cfg.vocab, size=(16,))
    fus, hits = run("fused")
    assert hits > 0          # the cache actually shared blocks
    assert ref == fus


def test_fused_jit_cache_pinned_one_program_per_shape(lm):
    """admit/retire/decode under `fused` never recompiles: the three
    serving programs stay pinned at one compiled instance each."""
    cfg, params = lm
    eng = Engine(params, cfg, EngineConfig(
        slots=3, kv_block_len=8, max_prefill_chunk=4,
        attend_impl="fused", spec_k=2,
    ))
    prompts, budgets = mixed_workload(cfg.vocab, n=5, seed=2)
    sched = Scheduler(eng)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    sched.serve()
    assert eng._verify_jit._cache_size() == 1
    assert eng._prefill_jit._cache_size() == 1


def test_fused_under_tensor_parallel_matches_single_device(lm):
    """serving_kv_shardings lays pool heads over the model axis; the
    kernel's (S*H, blocks) grid partitions with them (interpret mode
    lowers to plain XLA ops, so GSPMD shards it like any program) —
    every emitted token equals the unsharded fused engine's AND the
    reference path's."""
    from jax.sharding import Mesh

    from singa_tpu.models.transformer import lm_param_shardings
    from singa_tpu.parallel.shardings import serving_kv_shardings

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    cfg, params = lm
    plain = run_streams(params, cfg, "fused", slots=2, n=4, seed=5)
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    sh = lm_param_shardings(mesh, params)
    sharded = {k: jax.device_put(v, sh[k]) for k, v in params.items()}
    pool_sh, _ = serving_kv_shardings(mesh, cfg.n_heads)
    assert "model" in [str(a) for a in pool_sh.spec if a is not None]
    tp = run_streams(sharded, cfg, "fused", mesh=mesh, slots=2, n=4,
                     seed=5)
    assert tp == plain
    assert tp == run_streams(params, cfg, "reference", slots=2, n=4,
                             seed=5)


def test_default_config_jaxpr_identical_to_explicit_reference(lm):
    """The `kernels {}` seam is inert when unselected: an engine built
    with no kernels knob traces the SAME decode jaxpr as one built
    with an explicit `paged_attention: reference` — the oracle path is
    untouched by this seam's existence."""
    cfg, params = lm

    def decode_jaxpr(serving):
        eng = Engine(params, cfg, serving)
        return str(jax.make_jaxpr(eng._decode)(params, eng.state))

    default = decode_jaxpr(EngineConfig(slots=2, kv_block_len=8))
    explicit = decode_jaxpr(EngineConfig(
        slots=2, kv_block_len=8, attend_impl="reference"
    ))
    assert default == explicit


def test_engine_rejects_untileable_fused_geometry(lm):
    """The runtime rejection KRN001 statically mirrors: a pool block
    with no positions raises at construction under fused; a head_dim
    off the 128-lane tile (16 here) builds in every form — Mosaic pads
    it, the v5e ran it — and junk impl names raise loudly."""
    cfg, params = lm  # head_dim 16: not a multiple of 128
    with pytest.raises(ValueError, match="kv_block_len"):
        Engine(params, cfg, EngineConfig(
            slots=2, kv_block_len=0, attend_impl="fused",
        ))
    for interpret in (None, True, False):
        Engine(params, cfg, EngineConfig(
            slots=2, kv_block_len=8, attend_impl="fused",
            interpret=interpret,
        ))
    with pytest.raises(ValueError, match="reference"):
        Engine(params, cfg, EngineConfig(slots=2, attend_impl="fusedx"))
    # fewer K/V heads have the one-query form alone, which copies whole
    # register tiles: 8 rows of bfloat16 are half a tile
    gqa = tiny_cfg(n_heads=4, n_kv_heads=2)
    half = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), init_lm(jax.random.PRNGKey(0), gqa)
    )
    with pytest.raises(ValueError, match="no multiple of 16 rows"):
        Engine(half, gqa, EngineConfig(
            slots=2, kv_block_len=8, attend_impl="fused",
        ))


# ---------------------------------------------------------------------------
# the engine chooses
# ---------------------------------------------------------------------------


def sdar_shaped_cfg(**kw):
    """Fewer K/V heads than query heads, generated by diffusion over
    blocks: a block step's queries ride the kernel as query rows."""
    base = dict(
        vocab=40, d_model=32, n_heads=4, n_layers=2, max_len=32,
        norm="rmsnorm", pos="rope", n_kv_heads=2, head_dim=8, qk_norm=True,
        tied_head=False, moe_experts=4, moe_top_k=2, moe_d_ff=16,
        diffusion_block=4, mask_id=39,
    )
    base.update(kw)
    return TransformerConfig(**base)


def nemotron_shaped_cfg():
    """One-mixer layers: ONE attention layer of 4 query heads over 2 K/V
    heads with no positions, an expert layer, a Mamba-2 layer."""
    return TransformerConfig(
        vocab=40, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        n_layers=3, layers=("attn", "moe", "mamba"), d_ff=16, max_len=32,
        norm="rmsnorm", pos="none", mlp="relu2", tied_head=False,
        mamba_heads=4, mamba_head_dim=8, ssm_state=8, ssm_groups=2,
        moe_experts=4, moe_top_k=2, moe_d_ff=16, moe_act="relu2",
    )


@pytest.mark.parametrize("cfg,serving,mesh,platform,choice", [
    (tiny_cfg(), {}, None, "tpu", "fused"),
    (tiny_cfg(), {}, None, "cpu", "reference: platform = cpu"),
    (tiny_cfg(), {}, None, "gpu", "reference: platform = gpu"),
    (tiny_cfg(), {}, "a mesh", "tpu", "reference: a tensor-parallel mesh"),
    (tiny_cfg(n_heads=4, n_kv_heads=2), {}, None, "tpu", "fused"),
    (nemotron_shaped_cfg(), {}, None, "tpu", "fused"),
    (sdar_shaped_cfg(n_kv_heads=4), {}, None, "tpu", "fused"),
    # no field of the model refuses the kernel any more: the mesh is
    # the reason (and the engine refuses a mesh for this model)
    (sdar_shaped_cfg(), {}, "a mesh", "tpu",
     "reference: a tensor-parallel mesh"),
    # a pin wins over everything the choice looks at
    (tiny_cfg(), {"attend_impl": "reference"}, None, "tpu", "reference"),
    (tiny_cfg(), {"attend_impl": "fused"}, None, "cpu", "fused"),
    (tiny_cfg(), {"attend_impl": "fused"}, "a mesh", "cpu", "fused"),
    (sdar_shaped_cfg(), {}, None, "tpu", "fused"),
    (sdar_shaped_cfg(), {}, None, "cpu", "reference: platform = cpu"),
], ids=[
    "tpu_mha_no_mesh", "cpu", "gpu", "mesh", "fewer_kv_heads",
    "one_mixer_layers", "diffusion_block", "the_model_is_named_first",
    "pinned_reference", "pinned_fused", "pinned_fused_under_a_mesh",
    "block_steps_on_a_tpu", "block_steps_on_a_cpu",
])
def test_choose_attend(cfg, serving, mesh, platform, choice):
    """The kernel where it compiles and knows the model, the gather
    path everywhere else, each with the reason the ``kernel_select``
    event carries; asked by hand, so the CPU can ask about a TPU."""
    assert choose_attend(
        cfg, EngineConfig(kv_block_len=8, **serving), mesh, platform
    ) == choice


def test_unset_engine_on_the_cpu_takes_the_gather_path_and_says_why(lm):
    cfg, params = lm
    eng = Engine(params, cfg, EngineConfig(slots=2, kv_block_len=8))
    assert eng.attend_choice == "reference: platform = cpu"
    assert not eng._fused
    events = []

    class Recorder:
        def event(self, kind, **payload):
            events.append((kind, payload))

    Scheduler(eng, recorder=Recorder())
    assert ("kernel_select", {
        "step": 0, "site": "serve.paged_attention",
        "impl": "reference: platform = cpu",
    }) in events


def test_unset_engine_on_a_tpu_runs_the_kernel(lm, monkeypatch):
    """What the CPU cannot be asked by construction is asked with the
    platform steered from the test: the engine's decode holds the
    kernel, its pinned twin's does not."""
    cfg, params = lm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = Engine(params, cfg, EngineConfig(
        slots=2, kv_block_len=8, interpret=True,
    ))
    assert eng.attend_choice == "fused" and eng._fused
    jaxpr = str(jax.make_jaxpr(eng._decode)(params, eng.state))
    assert "name=paged_attention" in jaxpr
    pinned = Engine(params, cfg, EngineConfig(
        slots=2, kv_block_len=8, attend_impl="reference",
    ))
    assert "pallas_call" not in str(
        jax.make_jaxpr(pinned._decode)(params, pinned.state)
    )


def test_sdar_shaped_engine_lowers_the_same_block_step_unset_or_pinned(
    monkeypatch,
):
    """Left to itself on a TPU, a model generated by diffusion over
    blocks runs its block step on the kernel: ``_block_step`` lowers to
    the text it lowers to under an explicit ``fused`` and holds the
    kernel, and ``_prefill`` to the text of an explicit ``reference``
    (a chunk keeps its one-slot gather)."""
    cfg = sdar_shaped_cfg()
    params = init_lm(jax.random.PRNGKey(0), cfg)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def texts(**kw):
        eng = Engine(params, cfg, EngineConfig(
            slots=2, kv_block_len=8, max_prefill_chunk=4, block_steps=2,
            interpret=True, **kw,
        ))
        return str(jax.make_jaxpr(eng._block_step)(params, eng.state)), (
            eng._block_step_jit.lower(eng.params, eng.state).as_text(),
            eng._prefill_jit.lower(
                eng.params, eng.state, jnp.int32(0),
                jnp.zeros((4,), jnp.int32), jnp.int32(0), jnp.int32(4),
            ).as_text(),
        )

    unset, unset_texts = texts()
    _, fused = texts(attend_impl="fused")
    reference_jaxpr, reference = texts(attend_impl="reference")
    assert unset_texts == fused
    assert "name=paged_attention" in unset
    assert "pallas_call" not in reference_jaxpr
    assert fused[1] == reference[1]


# ---------------------------------------------------------------------------
# conf / lint
# ---------------------------------------------------------------------------


KERNELS_LINT_CONF = """
name: "kernels-lint"
train_steps: 1
updater {{ base_learning_rate: 0.05 }}
neuralnet {{
  layer {{ name: "data" type: "kSequenceData"
    data_param {{ path: "{shard}" batchsize: 8 }} }}
  layer {{ name: "embed" type: "kEmbedding" srclayers: "data"
    embedding_param {{ vocab_size: 64 embedding_dim: 256 max_len: 128 }}
    param {{ name: "tok" init_method: "kGaussian" std: 0.02 }}
    param {{ name: "pos" init_method: "kGaussian" std: 0.02 }} }}
  layer {{ name: "ln" type: "kLayerNorm" srclayers: "embed"
    param {{ name: "scale" init_method: "kConstant" value: 1 }}
    param {{ name: "bias" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "attn" type: "kAttention" srclayers: "ln"
    attention_param {{ num_heads: 2 }}
    param {{ name: "qkv" init_method: "kUniformSqrtFanIn" }}
    param {{ name: "out" init_method: "kUniformSqrtFanIn" }} }}
  layer {{ name: "head" type: "kDense" srclayers: "attn"
    dense_param {{ num_output: 64 bias_term: false }}
    param {{ name: "weight" init_method: "kGaussian" std: 0.02 }} }}
  layer {{ name: "loss" type: "kLMLoss" srclayers: "head"
    srclayers: "data" }}
}}
serving {{ slots: 4 kv_block_len: 16 kv_blocks: 0 }}
kernels {{ paged_attention: fused interpret: false }}
"""


@pytest.fixture()
def kernels_conf(tmp_path):
    from singa_tpu.data.loader import synthetic_token_arrays, write_records

    shard = str(tmp_path / "tokens")
    write_records(shard, *synthetic_token_arrays(16, seq_len=16, vocab=64))
    return KERNELS_LINT_CONF.format(shard=shard)


def _diags(text, code=None):
    from singa_tpu.lint import Collector, lint_model_text

    col = Collector()
    lint_model_text(text, "job.conf", col)
    return [d for d in col.sorted() if code is None or d.code == code]


def test_kernels_conf_lint_did_you_mean(kernels_conf):
    """netlint's schema walk covers the kernels block: both knobs and
    the block name typo'd get CFG001 with a did-you-mean; a junk impl
    value gets CFG002."""
    assert not _diags(kernels_conf, "CFG001"), _diags(kernels_conf)
    for typo, want in [
        ("paged_attention:", "paged_attention"),
        ("interpret:", "interpret"),
        ("kernels {{", "kernels"),
    ]:
        t = typo.replace("{{", "{")
        text = kernels_conf.replace(t, t[:-2] + "x" + t[-2:], 1)
        assert any(
            want in (d.fix_hint or "") for d in _diags(text, "CFG001")
        ), (typo, _diags(text))
    bad_enum = kernels_conf.replace(
        "paged_attention: fused", "paged_attention: fuzed"
    )
    assert any(
        "fused" in (d.fix_hint or "") for d in _diags(bad_enum, "CFG002")
    ), _diags(bad_enum)


def test_krn001_untileable_fused_geometry_lint(kernels_conf):
    """KRN001: `fused` with a geometry the engine would refuse at
    construction is a lint ERROR (the static mirror of
    paged_attention.fusable) whatever `interpret` says; the reference
    impl stays clean — and the geometries the old (8, 128) demand
    refused (block 12, head_dim 96) are clean, as the compiler is."""
    assert not _diags(kernels_conf, "KRN001")
    off_tile = kernels_conf.replace(
        "kv_block_len: 16", "kv_block_len: 12"
    ).replace("embedding_dim: 256", "embedding_dim: 192")
    assert not _diags(off_tile, "KRN001")
    bad_bl = kernels_conf.replace("kv_block_len: 16", "kv_block_len: 0")
    assert len(_diags(bad_bl, "KRN001")) == 1
    for pin in ("interpret: true", ""):
        assert len(_diags(
            bad_bl.replace("interpret: false", pin), "KRN001"
        )) == 1
    assert not _diags(
        bad_bl.replace("paged_attention: fused",
                       "paged_attention: reference"),
        "KRN001",
    )


def test_engine_config_from_conf_reads_kernels_block():
    from singa_tpu.config.schema import KernelsConfig, ServingConfig

    ec = EngineConfig.from_conf(None, None)
    # unset: the engine chooses the path (choose_attend) and the
    # platform the kernel's form (ops/paged_attention._call)
    assert ec.attend_impl is None and ec.interpret is None
    # a kernels block that names no paged_attention leaves it unset too
    assert EngineConfig.from_conf(
        None, KernelsConfig.from_fields({"interpret": [True]})
    ).attend_impl is None
    assert EngineConfig.from_conf(
        None, KernelsConfig.from_fields({"paged_attention": ["fused"]})
    ).interpret is None
    kern = KernelsConfig.from_fields(
        {"paged_attention": ["fused"], "interpret": [False]}
    )
    ec = EngineConfig.from_conf(ServingConfig(), kern)
    assert ec.attend_impl == "fused" and ec.interpret is False


# ---------------------------------------------------------------------------
# tools: serve_bench --kernels, trace attend_impl
# ---------------------------------------------------------------------------


def test_serve_bench_kernels_fused_smoke(capsys):
    """serve_bench --kernels fused at toy size: the measured engine
    runs the kernel while the baselines stay reference, so the
    standing token-identity bar doubles as a fused-vs-reference stream
    check."""
    from singa_tpu.tools.serve_bench import main as sb_main

    rc = sb_main([
        "--d_model", "32", "--n_heads", "2", "--n_layers", "1",
        "--d_ff", "64", "--vocab", "32", "--max_len", "32",
        "--prompt_len", "4", "--max_new", "6", "--block_len", "8",
        "--prefill_chunk", "4", "--requests", "4", "--concurrency", "2",
        "--kernels", "fused", "--no_gate",
    ])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0, out
    assert out["kernels"] == "fused"
    assert out["token_mismatches"] == 0


def test_kernel_select_event_and_trace_attend_impl(tmp_path, lm):
    """The run-start kernel_select event rides the flight recorder and
    trace --summarize's serving section reports which attend
    implementation the run took."""
    from singa_tpu.obs.recorder import FlightRecorder
    from singa_tpu.tools.trace import load_events, summarize

    cfg, params = lm
    rec = FlightRecorder(str(tmp_path / "events"), rank=0, run_id="t")
    eng = Engine(params, cfg, EngineConfig(
        slots=2, kv_block_len=8, max_prefill_chunk=4,
        attend_impl="fused",
    ))
    sched = Scheduler(eng, recorder=rec)
    prompts, budgets = mixed_workload(cfg.vocab, n=2, seed=1)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    sched.serve()
    rec.close()
    records, _ = load_events(str(tmp_path))
    sel = [r for r in records if r.get("kind") == "kernel_select"]
    assert sel and sel[0]["data"] == {
        "site": "serve.paged_attention", "impl": "fused"
    }
    summary = summarize(records)
    assert summary["serving"]["attend_impl"] == "fused"
