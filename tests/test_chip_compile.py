"""The main path's kernels, compiled for a DESCRIBED v5e — no chip.

The TPU's compiler is installed beside the CPU backend and compiles for
a topology that is described, not attached; what it refuses here it
refuses on the chip (interpret mode hid a refused block shape in the
paged kernel and a scoped-VMEM overrun in ``quant_acc`` until this file
asked). A compile that passes is not a chip run: ``chip_smoke.py``
checks the answers.

Rules this file keeps (the libtpu lock allows one process, and every
xdist worker imports every test file): the topology is described inside
a module-scoped, non-autouse fixture that skips where it cannot be;
nothing chip-related happens at import, in a ``skipif`` or in a
``parametrize`` argument; compiles run in the test's own process; the
persistent cache is off around them (a described-device entry cannot be
read back and would warn). One file, so one worker holds the library.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from singa_tpu.models.transformer import TransformerConfig, init_lm
from singa_tpu.ops.attention import flash_attention
from singa_tpu.ops.paged_attention import (
    fusable,
    paged_attention,
    paged_attention_overlay,
)
from singa_tpu.ops.quantized_collective import quant_acc
from singa_tpu.serve import Engine, EngineConfig


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_flash_forward_and_backward_compile(one_chip):
    """The streamed-K/V flash kernels at the long-context shape (one
    head of 128 over S=8192): forward, dQ and dK/dV all reach Mosaic."""
    qkv = jax.ShapeDtypeStruct(
        (1, 1, 8192, 128), jnp.bfloat16, sharding=one_chip
    )

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, None, None, False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert text.count("tpu_custom_call") >= 3


# the serve cells' geometry (slots 8, 2 heads of 128, kv_block_len 16,
# 32-block tables over a 257-block pool), then a geometry off the
# (8, 128) register tile, which the repo's predicate used to refuse
SERVE = dict(slots=8, heads=2, head_dim=128, block_len=16)
OFF_TILE = dict(slots=3, heads=2, head_dim=96, block_len=12)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("q_len", [1, 5])
@pytest.mark.parametrize("form", ["write_then_read", "overlay"])
@pytest.mark.parametrize(
    "geometry", [SERVE, OFF_TILE], ids=["serve", "off_tile"]
)
def test_paged_attention_compiles(one_chip, geometry, form, q_len, dtype):
    """Both kernel forms at the decode (Q=1) and verify (Q=spec_k+1=5)
    shapes — and ``fusable`` says yes exactly where the compiler does."""
    s, h = geometry["slots"], geometry["heads"]
    d, bl, mb = geometry["head_dim"], geometry["block_len"], 32

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q = sds((s, h, q_len, d), dtype)
    pool = sds((s * mb + 1, bl, h * d), dtype)
    tables = sds((s, mb), jnp.int32)
    rows = sds((s, q_len), jnp.int32)
    assert fusable(bl) is None
    if form == "overlay":
        text = _compiled_text(
            lambda *a: paged_attention_overlay(*a, interpret=False),
            q, pool, pool, tables, rows, q, q, rows,
        )
    else:
        text = _compiled_text(
            lambda *a: paged_attention(*a, interpret=False),
            q, pool, pool, tables, rows,
        )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("s,h,hkv,d,bl,mb,dtype", [
    (128, 32, 2, 128, 128, 38, jnp.bfloat16),  # nemotron_3_super_serve_chat
    (64, 32, 4, 128, 16, 96, jnp.bfloat16),    # 8 a K/V head, blocks of 16
    (8, 4, 2, 128, 16, 32, jnp.float32),       # 2 query heads a K/V head
    (64, 128, 4, 128, 16, 96, jnp.bfloat16),   # sdar_30b_a3b_serve_blocks
    (128, 32, 8, 64, 128, 32, jnp.bfloat16),   # lfm2_8b_a1b_serve_rag
], ids=["hybrid_cell", "per_kv_8", "per_kv_2", "block_step", "rag_cell"])
def test_one_query_kernel_over_fewer_kv_heads_compiles(
    one_chip, s, h, hkv, d, bl, mb, dtype
):
    """The decode tick's form with query heads over fewer K/V heads
    (scores and values as products over a block-diagonal query, each
    head's own columns kept at the end) reaches Mosaic: at the hybrid
    cell's shape, 16 query heads a K/V head and items of 8 blocks; at
    the block step's, 32 heads x a block of 4 as 128 query rows over 4
    K/V heads, an 8 MiB block-diagonal query within the default VMEM; at
    the RAG cell's, 4 query heads over each of 8 K/V heads of 64 (rows
    of 512, each head's output 64 of them)."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((s * mb + 1, bl, hkv * d), dtype)
    text = _compiled_text(
        lambda *a: paged_attention(*a, interpret=False),
        sds((s, h, 1, d), dtype), pool, pool, sds((s, mb), jnp.int32),
        sds((s, 1), jnp.int32),
    )
    assert "tpu_custom_call" in text


def _serve_cell(one_chip):
    """The engine of ``gpt2_medium_serve_closed`` (32 slots, blocks of
    16, 128-token chunks; GPT-2 medium's width) cut to 2 layers, and
    its arguments as shapes on the described chip: the pools are
    ``f32[2049, 16, 1024]``, 134 MB each, 4 of them."""
    cfg = TransformerConfig(
        vocab=50257, d_model=1024, n_heads=16, n_layers=2, d_ff=4096,
        max_len=1024,
    )
    params = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    eng = Engine(params, cfg, EngineConfig(
        slots=32, kv_block_len=16, max_prefill_chunk=128, spec_k=4,
    ))

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def i32(*shape):
        return arg(jnp.int32, *shape)

    def sds(tree):
        return jax.tree.map(lambda a: arg(a.dtype, *a.shape), tree)

    # shapes from here on: the engine's own zeros (0.5 GB) are let go
    eng.state = state = sds(eng.state)
    head = (sds(params), state)
    mb = eng.pool.max_blocks_per_seq
    # a slot's blocks in the fleet's wire format, (L, MB, H, BL, D)
    wire = arg(jnp.float32, 2, mb, 16, 16, 64)
    lanes = (i32(), i32(), arg(jnp.float32), arg(jnp.uint32, 2))
    # name -> (function, arguments, which argument holds the pools and
    # is donated, whether the pools come back out)
    return eng, {
        "decode": (eng._decode, head, 1, True),
        "prefill": (
            eng._prefill, head + (i32(), i32(128), i32(), i32()), 1, True,
        ),
        "verify": (eng._verify, head + (i32(32, 4), i32(32)), 1, True),
        "cow": (eng._cow_prog, (state, i32(), i32()), 0, True),
        "import": (
            eng._import_prog,
            (state, i32(), i32(mb), i32(mb), wire, wire) + lanes, 0, True,
        ),
        "install": (
            eng._install_prog, (state, i32(mb), wire, wire), 0, True,
        ),
        "export": (eng._export_prog, (state, i32()), 0, False),
        "export_blocks": (
            eng._export_blocks_prog, (state, i32(mb)), 0, False,
        ),
    }


@pytest.fixture(scope="module")
def serve_cell(one_chip):
    """The cell's engine as this process builds it: on the CPU the
    engine chooses the gather path."""
    return _serve_cell(one_chip)


@pytest.fixture(scope="module")
def serve_cell_on_tpu(one_chip):
    """The cell's engine as the chip builds it, ``attend_impl`` unset:
    ``jax.default_backend()`` still says cpu here (the chip is
    described, not attached), so the answer is steered while the
    engine is built, and again by the test while the kernel lowers."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return _serve_cell(one_chip)


@pytest.mark.parametrize("program", [
    "decode", "prefill", "verify", "cow", "import", "install", "export",
    "export_blocks",
])
def test_serving_programs_relayout_no_pool(serve_cell, program):
    """The pools are stored in the layout the programs' scatter and
    gather use (serve/kv_pool.py): no compiled program copies a whole
    pool on its way in or out (two such copies a pool cost 61 of a
    decode tick's 114 ms and 62 of a prefill chunk's 64 on the chip
    before), and every pool leaves in the layout it arrived in, so the
    donated buffer is written in place. The admission path's programs
    (copy-on-write, migration, prefix shipping) are held to the same:
    they transpose a slot's blocks at the wire, never a pool."""
    eng, programs = serve_cell
    assert eng.attend_choice == "reference: platform = cpu"
    _no_pool_is_relaid_out(eng, *_compiled_program(programs[program]))


def _compiled_program(program):
    fn, args, pools_at, pools_out = program
    donate = (pools_at,) if pools_out else ()
    text = (
        jax.jit(fn, donate_argnums=donate).lower(*args).compile().as_text()
    )
    return text, pools_out


def _no_pool_is_relaid_out(eng, text, pools_out):
    pool = eng.state["k"][0]
    n_pools = 2 * eng.cfg.n_layers
    copies = [
        m.group(0)
        for m in re.finditer(r"= \w+\[([\d,]+)\]\S* copy\(", text)
        if math.prod(map(int, m.group(1).split(","))) == pool.size
    ]
    assert copies == []
    header = text[:text.index("\n")]
    layouts = re.search(
        r"entry_computation_layout=\{\((.*)\)->(.*)\}", header
    )
    dims = ",".join(map(str, pool.shape))
    arrive, leave = (
        re.findall(rf"f32\[{dims}\](\{{[^}}]*\}})", side)
        for side in layouts.groups()
    )
    assert len(arrive) == n_pools and len(set(arrive)) == 1
    if pools_out:
        assert len(leave) == n_pools and set(leave) == set(arrive)
        assert header.count("-alias)") >= n_pools


@pytest.mark.parametrize("program,kernel", [
    ("decode", True), ("verify", True), ("prefill", False),
])
def test_engine_on_a_tpu_reads_the_pool_in_place(
    serve_cell_on_tpu, program, kernel, monkeypatch
):
    """Left to itself on a TPU, the engine's decode tick (and the
    verify pass that follows the same choice) holds the Mosaic kernel
    and no dense ``(slots, cache_len, heads, head_dim)`` view of a
    pool in any order of its dimensions — and still relays out no
    pool. The prefill chunk keeps its one-slot gather whatever the
    choice: the program decides that, not the platform."""
    eng, programs = serve_cell_on_tpu
    assert eng.attend_choice == "fused"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text, pools_out = _compiled_program(programs[program])
    assert ("tpu_custom_call" in text) == kernel
    s, cl = eng.serving.slots, eng.pool.cache_len
    h, d = eng.cfg.n_heads, eng.cfg.head_dim
    dense = {(s, cl, h, d), (s, h, cl, d), (s, cl, h * d)}
    shapes = {
        tuple(map(int, m.group(1).split(",")))
        for m in re.finditer(r"f32\[([\d,]+)\]", text)
    }
    assert not dense & shapes
    _no_pool_is_relaid_out(eng, text, pools_out)


@pytest.mark.parametrize(
    "n_elements",
    [
        256 * 1024 // 4,   # tinylm_d128's b0_up weight, one of 4 shards
        1224 * 1024,       # aligned, and past what ONE VMEM block held
        1100 * 128,        # rows not a multiple of the grid's block
    ],
)
def test_quant_acc_compiles(one_chip, n_elements):
    """The ring's per-hop kernel walks its chunk through a grid: an
    aligned chunk of any size fits VMEM."""
    text = _compiled_text(
        lambda q, s, x: quant_acc(q, s, x, interpret=False),
        jax.ShapeDtypeStruct((n_elements,), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n_elements,), jnp.float32, sharding=one_chip),
    )
    assert "tpu_custom_call" in text


# -- a latent cache at the published width of ``kimi_k2_serve_long`` ------


def test_latent_kernel_compiles_at_the_cells_shape(one_chip):
    """48 absorbed queries of 64 heads, 640 wide, over a bfloat16 pool
    of 4,801 blocks of 128 latent rows: through Mosaic, within the
    kernel's own VMEM limit."""
    from singa_tpu.ops.paged_attention import paged_latent_attention

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    text = _compiled_text(
        lambda q, p, t, pos: paged_latent_attention(
            q, p, t, pos, scale=0.13, out_width=512, interpret=False
        ),
        sds((48, 64, 640), jnp.bfloat16),
        sds((48 * 100 + 1, 128, 640), jnp.bfloat16),
        sds((48, 100), jnp.int32), sds((48,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def latent_cell_on_tpu(one_chip):
    """The engine of ``kimi_k2_serve_long`` (48 slots, blocks of 128,
    512-token chunks, the published widths, 12 of 384 experts held) cut
    to its dense layer and one expert layer, as the chip builds it, and
    its arguments as shapes on the described chip: the pools are
    ``bf16[4801, 128, 640]``, 787 MB each."""
    cfg = TransformerConfig(
        vocab=20480, d_model=7168, n_heads=64, n_layers=2, d_ff=18432,
        max_len=12800, norm="rmsnorm", norm_eps=1e-6, pos="rope",
        rope_theta=50000.0, rope_yarn=(32.0, 4096, 1, 1, 1, 1),
        head_dim=128, rope_dim=64, v_head_dim=128, kv_latent=512,
        q_latent=1536, tied_head=False, mlp="swiglu", dense_layers=1,
        moe_experts=384, moe_top_k=8, moe_d_ff=2048, moe_score="sigmoid",
        moe_bias=True, moe_scale=2.827, moe_shared_d_ff=2048,
        moe_held=(96, 12),
    )
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg)),
    )
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        # the least pool an engine takes: the cell's is set below, as
        # shapes (its zeros would be 1.6 GB here)
        eng = Engine(params, cfg, EngineConfig(
            slots=48, kv_block_len=128, kv_blocks=101, max_prefill_chunk=512,
        ))

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def sds(a):
        shape = (4801,) + a.shape[1:] if a.shape[1:] == (128, 640) else a.shape
        return arg(a.dtype, *shape)

    state = jax.tree.map(sds, eng.state)
    head = (jax.tree.map(sds, params), state)
    i32 = lambda *shape: arg(jnp.int32, *shape)  # noqa: E731
    return eng, {
        "decode": (eng._decode, head),
        "prefill": (eng._prefill, head + (i32(), i32(512), i32(), i32())),
    }


@pytest.fixture(scope="module")
def latent_texts(latent_cell_on_tpu):
    """The cell's two programs compiled for the described chip (a
    minute for the chunk: made once for the tests below)."""
    _, programs = latent_cell_on_tpu
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return {
            name: jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
            .as_text()
            for name, (fn, args) in programs.items()
        }


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_latent_programs_relayout_no_pool(
    latent_cell_on_tpu, latent_texts, program
):
    """A latent pool's row is rounded up to whole 128-lane tiles
    (``KVPool.latent_row``) so that it arrives row-major and no program
    copies it: at 576 wide the runtime stored it block-length-minor and
    each program copied every pool in and out (PERF.md, PR 34). The
    decode tick holds the latent kernel and no dense view of a pool;
    the prefill chunk keeps its one-slot gather."""
    eng, _ = latent_cell_on_tpu
    assert eng.attend_choice == "fused"
    assert eng.state["k"][0].shape[1:] == (128, 640) and not eng.state["v"]
    text = latent_texts[program]
    assert not re.findall(r"= bf16\[4801,128,640\]\S* copy\(", text)
    header = text[:text.index("\n")]
    arrive, leave = (
        re.findall(r"bf16\[4801,128,640\](\{[^}]*\})", side)
        for side in re.search(
            r"entry_computation_layout=\{\((.*)\)->(.*)\}", header
        ).groups()
    )
    assert len(arrive) == 2 and set(arrive) == set(leave) == {
        "{2,1,0:T(8,128)(2,1)}"
    }
    assert header.count("-alias)") >= 2
    assert ("paged_latent_attention" in text) == (program == "decode")
    dense = "bf16[48,12800,640]" in text
    assert dense is False


@pytest.mark.parametrize("program,n", [("prefill", 512), ("decode", 48)])
def test_latent_programs_hold_the_grouped_expert_product(
    latent_cell_on_tpu, latent_texts, program, n
):
    """PR 35: a chunk of 512 tokens over 12 of 384 experts computes the
    routed pairs alone — the three grouped products of its expert layer
    are megablox ``gmm`` Mosaic calls under ``experts`` and ``combine``
    and no per-expert activation ``(12, 512, 2048)`` is formed; the
    tick's 48 tokens take the same form (a flat router leaves a third
    of the held experts without a token)."""
    eng, _ = latent_cell_on_tpu
    assert eng.expert_forms[f"jit__{program}"].startswith("grouped"), (
        eng.expert_forms
    )
    text = latent_texts[program]
    calls = re.findall(
        r'%gmm[.\d]* = [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        r'op_name="([^"]*)"', text,
    )
    scope = f"jit(_{program})/blk1/moe/"
    assert len(calls) == 3 and all(
        c.startswith(scope) and c.endswith("jit(gmm)/pallas_call")
        for c in calls
    ), calls
    assert sum("/experts/" in c for c in calls) == 2
    assert sum("/combine/" in c for c in calls) == 1
    assert f"[12,{n},2048]" not in text


def test_a_block_step_over_every_expert_stays_dense(one_chip, monkeypatch):
    """``sdar_30b_a3b_serve_blocks``' pass (64 slots x a block of 4 =
    256 tokens over 128 of 128 experts, published widths, one layer)
    rides on the weight reads: the chooser leaves it the dense product
    and its compiled text holds no grouped kernel. Its attention is the
    paged kernel, one call a layer over the block's 128 query rows, and
    no gathered view of a pool is made."""
    cfg = TransformerConfig(
        vocab=4096, d_model=2048, n_heads=32, n_kv_heads=4, head_dim=128,
        n_layers=1, d_ff=768, max_len=1536, norm="rmsnorm", norm_eps=1e-6,
        pos="rope", rope_theta=1e6, qk_norm=True, tied_head=False,
        moe_experts=128, moe_top_k=8, moe_d_ff=768,
        diffusion_block=4, mask_id=4095,
    )
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg)),
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = Engine(params, cfg, EngineConfig(
        slots=64, kv_block_len=16, kv_blocks=129, max_prefill_chunk=256,
        block_steps=2,
    ))
    assert all(f.startswith("dense: 256 tokens") for f in
               eng.expert_forms.values()), eng.expert_forms

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    assert eng.attend_choice == "fused"
    text = jax.jit(eng._block_step, donate_argnums=(1,)).lower(
        jax.tree.map(sds, params), jax.tree.map(sds, eng.state)
    ).compile().as_text()
    kernels = re.findall(
        r'= [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        r'op_name="([^"]*)"', text,
    )
    assert "jit(gmm)" not in text and len(kernels) == 1, kernels
    assert kernels[0] == (
        "jit(_block_step)/blk0/attend/paged_attention/paged_attention/"
        "pallas_call"
    )
    assert "bf16[128,256,768]" in text or "f32[128,256,768]" in text
    assert not re.search(r"bf16\[64,[\d,]*1536", text)   # a slot's whole view


# -- one-mixer layers: recurrent state beside a paged pool ---------------

#: slots the engine below is built with (its zeros stay small); the
#: shapes handed to the compiler hold the cell's 128 in their place
_FEW = 5


@pytest.fixture(scope="module")
def hybrid_cell_on_tpu(one_chip):
    """The engine of ``nemotron_3_super_serve_chat`` (128 slots, blocks
    of 128, 512-token chunks, the published widths, 128 of 512 experts
    held) cut to its attention layer, one expert layer and one Mamba
    layer, as the chip builds it, and its arguments as shapes on the
    described chip: the state is ``f32[128, 128, 64, 128]``, 537 MB a
    layer, the tail ``bf16[3, 128, 10240]``, the pools
    ``bf16[4865, 128, 256]``."""
    cfg = TransformerConfig(
        vocab=32768, d_model=4096, n_heads=32, n_kv_heads=2, head_dim=128,
        n_layers=3, layers=("attn", "moe", "mamba"), d_ff=2688, max_len=4864,
        norm="rmsnorm", pos="none", mlp="relu2", tied_head=False,
        mamba_heads=128, mamba_head_dim=64, ssm_state=128, ssm_groups=8,
        moe_experts=512, moe_top_k=22, moe_d_ff=2688, moe_score="sigmoid",
        moe_bias=True, moe_scale=5.0, moe_shared_d_ff=5376,
        moe_held=(128, 128), moe_act="relu2", moe_latent=1024,
    )
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg)),
    )
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        eng = Engine(params, cfg, EngineConfig(
            slots=_FEW, kv_block_len=128, kv_blocks=39, max_prefill_chunk=512,
        ))

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def sized(a):
        if a.shape[:2] == (39, 128):            # a pool: every slot's blocks
            return arg(a.dtype, 128 * 38 + 1, *a.shape[1:])
        return arg(a.dtype, *(128 if d == _FEW else d for d in a.shape))

    head = (
        jax.tree.map(lambda a: arg(a.dtype, *a.shape), params),
        jax.tree.map(sized, eng.state),
    )
    i32 = lambda *shape: arg(jnp.int32, *shape)  # noqa: E731
    programs = {
        "decode": (eng._decode, head),
        "prefill": (eng._prefill, head + (i32(), i32(512), i32(), i32())),
    }
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        texts = {
            name: jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
            .as_text()
            for name, (fn, args) in programs.items()
        }
    return eng, texts


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_hybrid_programs_relayout_no_state(hybrid_cell_on_tpu, program):
    """The recurrent state, the convolution's tail and the attention
    layer's pools arrive row-major, leave as they arrived and alias
    their inputs; no program copies a state array at all, and the one
    copy a tick makes of the 7.9 MB tail keeps its layout."""
    eng, texts = hybrid_cell_on_tpu
    assert len(eng.state["k"]) == len(eng.state["ssm"]) == 1
    text = texts[program]
    header = text[:text.index("\n")]
    sides = re.search(
        r"entry_computation_layout=\{\((.*)\)->(.*)\}", header
    ).groups()
    for dims, layout, n in (
        (r"f32\[128,128,64,128\]", "{3,2,1,0:T(8,128)}", 1),
        (r"bf16\[3,128,10240\]", "{2,1,0:T(8,128)(2,1)}", 1),
        (r"bf16\[4865,128,256\]", "{2,1,0:T(8,128)(2,1)}", 2),
    ):
        arrive, leave = (
            re.findall(dims + r"(\{[^}]*\})", side) for side in sides
        )
        assert arrive == leave == [layout] * n, (dims, arrive, leave)
    assert header.count("-alias)") >= 4
    assert not re.findall(r"= f32\[128,128,64,128\]\S* copy\(", text)
    assert not re.findall(r"= f32\[128,8,16,64,128\]\S* copy\(", text)
    assert not re.findall(r"= bf16\[4865,128,256\]\S* copy\(", text)
    for layout in re.findall(r"= bf16\[3,128,10240\](\{[^}]*\})\S* copy\(", text):
        assert layout.startswith("{2,1,0:"), layout
    assert not re.findall(r"= bf16\[128,3,10240\]\S* copy\(", text)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_hybrid_tick_reads_the_pool_in_place(hybrid_cell_on_tpu, program):
    """PR 38: the engine picks the paged kernel for the attention layer's
    32 query heads over 2 K/V heads, so the tick holds it and neither a
    gathered view of a pool nor its relayout copy
    ``bf16[128,4864,2,128]`` (2 x 1.29 ms a tick on the chip, PERF.md
    §5, PR 37); the chunk keeps its one-slot gather."""
    eng, texts = hybrid_cell_on_tpu
    assert eng.attend_choice == "fused"
    text = texts[program]
    kernel = re.findall(
        r'= [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        r'op_name="([^"]*)"', text,
    )
    paged = [k for k in kernel if "paged_attention" in k]
    assert len(paged) == (program == "decode"), kernel
    assert "bf16[128,4864,2,128]" not in text
    assert "bf16[128,4864,256]" not in text


def test_hybrid_programs_take_their_forms(hybrid_cell_on_tpu):
    """A tick's 128 tokens over 128 held experts stay dense (no grouped
    kernel in the tick, and its Mamba layer is one ``step``); a chunk's
    512 go grouped: TWO megablox calls an expert layer (an expert has no
    gate matrix), under ``experts`` and ``combine``, in the latent's
    width, and no per-expert activation ``(128, 512, 2688)`` is
    formed."""
    from singa_tpu.parallel.moe import choose_expert_form

    eng, texts = hybrid_cell_on_tpu
    assert choose_expert_form(128, 128, 512, 22, "tpu").startswith("dense")
    assert eng.expert_forms["jit__prefill"].startswith("grouped: 512 tokens")
    assert eng.mamba_forms["jit__decode"].startswith("step")
    assert eng.mamba_forms["jit__prefill"].startswith(
        "chunked: 512 positions in 4 blocks of 128"
    )
    assert "jit(gmm)" not in texts["decode"]
    assert "[128,128,2688]" in texts["decode"]      # the dense activation
    calls = re.findall(
        r'%gmm[.\d]* = [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        r'op_name="([^"]*)"', texts["prefill"],
    )
    assert len(calls) == 2 and all(
        c.startswith("jit(_prefill)/blk1/moe/")
        and c.endswith("jit(gmm)/pallas_call") for c in calls
    ), calls
    assert sum("/experts/" in c for c in calls) == 1
    assert sum("/combine/" in c for c in calls) == 1
    assert "[128,512,2688]" not in texts["prefill"]
    assert "/mamba/step/" in texts["decode"]
    assert "/mamba/scan/" in texts["prefill"]


# -- short convolutions: a tail alone beside a paged pool ----------------


@pytest.fixture(scope="module")
def rag_cell_on_tpu(one_chip):
    """The engine of ``lfm2_8b_a1b_serve_rag`` (128 slots, blocks of 128,
    512-token chunks, the published widths, every expert held) cut to
    one short convolution, the dense MLP, one attention layer and one
    expert layer, as the chip builds it, and its arguments as shapes on
    the described chip: the tail is ``bf16[2, 128, 2048]``, the pools
    ``bf16[4097, 128, 512]``."""
    cfg = TransformerConfig(
        vocab=65536, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
        n_layers=4, layers=("shortconv", "mlp", "attn", "moe"), d_ff=7168,
        max_len=4096, norm="rmsnorm", pos="rope", rope_theta=1e6,
        qk_norm=True, mlp="swiglu", conv_kernel=3, moe_experts=32,
        moe_top_k=4, moe_d_ff=1792, moe_score="sigmoid", moe_bias=True,
    )
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg)),
    )
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        eng = Engine(params, cfg, EngineConfig(
            slots=_FEW, kv_block_len=128, kv_blocks=33, max_prefill_chunk=512,
        ))

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def sized(a):
        if a.shape[:2] == (33, 128):            # a pool: every slot's blocks
            return arg(a.dtype, 128 * 32 + 1, *a.shape[1:])
        return arg(a.dtype, *(128 if d == _FEW else d for d in a.shape))

    head = (
        jax.tree.map(lambda a: arg(a.dtype, *a.shape), params),
        jax.tree.map(sized, eng.state),
    )
    i32 = lambda *shape: arg(jnp.int32, *shape)  # noqa: E731
    programs = {
        "decode": (eng._decode, head),
        "prefill": (eng._prefill, head + (i32(), i32(512), i32(), i32())),
    }
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        texts = {
            name: jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
            .as_text()
            for name, (fn, args) in programs.items()
        }
    return eng, texts


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_rag_programs_keep_the_tail_in_place(rag_cell_on_tpu, program):
    """The short convolution's tick and chunk compile: the tail and the
    pools arrive and leave in one layout and alias their inputs, no
    copy of the tail leaves its layout, and the layer's operations lie
    under ``shortconv`` (``in_proj``, ``conv``, ``out_proj``) with no
    recurrent state beside the tail."""
    eng, texts = rag_cell_on_tpu
    assert "ssm" not in eng.state and len(eng.state["conv"]) == 1
    text = texts[program]
    header = text[:text.index("\n")]
    sides = re.search(
        r"entry_computation_layout=\{\((.*)\)->(.*)\}", header
    ).groups()
    for dims, n in ((r"bf16\[2,128,2048\]", 1), (r"bf16\[4097,128,512\]", 2)):
        arrive, leave = (
            re.findall(dims + r"(\{[^}]*\})", side) for side in sides
        )
        assert len(arrive) == n and arrive == leave, (dims, arrive, leave)
    assert header.count("-alias)") >= 3
    assert not re.findall(r"= bf16\[4097,128,512\]\S* copy\(", text)
    for layout in re.findall(r"= bf16\[2,128,2048\](\{[^}]*\})\S* copy\(", text):
        assert layout.startswith("{2,1,0:"), layout
    for scope in ("in_proj", "conv", "out_proj"):
        assert f"/blk0/shortconv/{scope}/" in text, scope


def test_rag_programs_take_their_forms(rag_cell_on_tpu):
    """The tick's attention is the paged kernel (4 query heads over each
    of 8 K/V heads of 64) and the chunk keeps its one-slot gather; a
    tick's 128 tokens over 32 experts stay dense, a chunk's 512 (64
    routed rows an expert) go grouped: THREE megablox calls, the
    experts' gate, up and down."""
    from singa_tpu.parallel.moe import choose_expert_form

    eng, texts = rag_cell_on_tpu
    assert eng.attend_choice == "fused"
    assert choose_expert_form(128, 32, 32, 4, "tpu").startswith("dense")
    assert eng.expert_forms["jit__prefill"].startswith("grouped: 512 tokens")
    kernel = re.findall(
        r'= [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        r'op_name="([^"]*)"', texts["decode"],
    )
    assert [k for k in kernel if "paged_attention" in k], kernel
    assert "jit(gmm)" not in texts["decode"]
    calls = re.findall(
        r'%gmm[.\d]* = [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        r'op_name="([^"]*)"', texts["prefill"],
    )
    assert len(calls) == 3 and all(
        c.startswith("jit(_prefill)/blk3/moe/") for c in calls
    ), calls
    chunk_kernels = re.findall(
        r'= [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        r'op_name="([^"]*)"', texts["prefill"],
    )
    assert not [k for k in chunk_kernels if "paged_attention" in k]
