"""Latent attention over a one-row latent paged cache, sigmoid-scored
experts with a selection bias, a scaling factor, a shared expert and a
share of the experts held, a leading dense SwiGLU layer and YaRN rotary
frequencies, at a small size on the CPU with seeded float32 weights:
the program (``TransformerConfig`` -> ``Engine`` -> ``Scheduler``) against
the plain reference (``benchmark/reference/kimi_k2.py``), at the level
of logits.

Tolerances are float32's: program and reference compute the same
equations in another order (the decode tick in the ABSORBED form, the
reference never), so logits of size 1-10 agree to some 1e-5; a router's
near-tie that went the other way, a latent cached before its norm or a
rotary key cached unrotated show as 1e-2 or more, so 2e-4 pins them.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.drivers import serve_kimi_k2 as drv
from benchmark.reference import kimi_k2 as ref
from singa_tpu.models import transformer
from singa_tpu.models.transformer import (
    TransformerConfig, _rope, init_lm, latent_attend, lm_apply,
)
from singa_tpu.parallel.moe import moe_topk_ffn, topk_gates
from singa_tpu.serve import Engine, EngineConfig, Request, Scheduler
from singa_tpu.serve.kv_pool import KVPool

TOL = 2e-4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


#: the shipped configuration with the rehearsal's tiny keys laid over it
#: (a YaRN table whose original length, 16, the sequences here pass)
CFG = load("benchmark", "configs", "kimi_k2_instruct.json") | load(
    "tests", "benchmark", "tiny", "configs", "kimi_k2_instruct.json"
)
MCFG = drv.model_config(CFG, {"max_model_len": 64})
SEED = 2**31 + 34


@pytest.fixture(scope="module")
def params():
    return weights.make(ref.specs(CFG), SEED)


def gaps(params, cfg, prompt, tokens):
    """How far each served token's logit lies under the reference's best
    at its position, and the reference's logits at those positions."""
    full = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    logits = np.asarray(ref.forward(params, jnp.asarray(full), cfg))
    lo, hi = len(prompt) - 1, len(full) - 1
    rows = logits[lo:hi]
    return rows.max(-1) - rows[np.arange(hi - lo), full[lo + 1:hi + 1]], rows


def serve(params, mcfg, shapes, *, slots=3, chunk=16, seed=0, **serving):
    """Requests of ``shapes`` (prompt length, tokens) through a
    scheduler: chunked prefill, then decoding through the latent pool."""
    engine = Engine(params, mcfg, EngineConfig(
        slots=slots, kv_block_len=8, max_prefill_chunk=chunk, **serving
    ))
    sched = Scheduler(engine)
    rng = np.random.default_rng(seed)
    for i, (n, m) in enumerate(shapes):
        sched.submit(Request(
            rid=i, prompt=rng.integers(0, mcfg.vocab, (n,)).astype(np.int32),
            max_new_tokens=m, temperature=0.0, seed=i,
        ))
    sched.serve()
    return sched, engine


def test_reference_specs_are_the_programs_parameters(params):
    mine = init_lm(jax.random.PRNGKey(0), MCFG)
    assert {k: v.shape for k, v in mine.items()} == {
        k: v.shape for k, v in params.items()
    }
    # layer 0 is dense and gated, the others hold the share's experts
    assert "blk0/mlp/gate" in mine and "blk0/moe/gate" not in mine
    assert mine["blk1/moe/gate"].shape == (32, 16)
    assert mine["blk1/moe/w_up"].shape == (4, 32, 16)
    assert "blk1/attn/qkv" not in mine and "embed/pos" not in mine


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "import singa_tpu" not in text and "from singa_tpu" not in text


@pytest.mark.parametrize("length", [9, 24, 41])
def test_lm_apply_against_the_reference_forward(params, length):
    toks = np.random.default_rng(length).integers(0, 200, (length,))
    toks = toks.astype(np.int32)
    got = lm_apply(params, jnp.asarray(toks)[None], MCFG)[0]
    want = ref.forward(params, jnp.asarray(toks), CFG)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_yarn_frequencies_and_scale_by_hand():
    """The published keys: pairs 0..19 of the 32 keep their frequency,
    pairs from 20 on take a 32nd of it (low 19, high 20, the blend's one
    step between them), and the softmax scale carries m squared."""
    big = load("benchmark", "configs", "kimi_k2_instruct.json")
    mcfg = drv.model_config(big, {"max_model_len": 12800})
    inv = np.asarray(transformer._yarn_inv_freq(64, 50000.0, mcfg.rope_yarn))
    f = 50000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv[:20], f[:20], rtol=1e-6)
    np.testing.assert_allclose(inv[20:], f[20:] / 32, rtol=1e-6)
    np.testing.assert_allclose(inv, ref.yarn_inv_freq(64, 50000.0, mcfg.rope_yarn), rtol=1e-6)
    m = 0.1 * np.log(32.0) + 1.0
    assert abs(m - 1.34657) < 1e-5
    assert abs(mcfg.attn_scale - 192 ** -0.5 * m * m) < 1e-9
    assert mcfg.latent_width == 576 and KVPool.latent_row(576) == 640
    # the tiny table blends: some pair lies strictly between
    tiny = np.asarray(transformer._yarn_inv_freq(4, 50000.0, MCFG.rope_yarn))
    assert tiny[0] == 1.0 and tiny[1] < 50000.0 ** -0.5


def test_absorbed_and_materialised_attention_agree():
    rng = np.random.default_rng(3)
    b, h, c, nq = 2, 4, 24, 3
    q = jnp.asarray(rng.normal(size=(b, h, nq, 12)), jnp.float32)
    lat = jnp.asarray(rng.normal(size=(b, c, 20)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, h * 16)), jnp.float32)
    limits = jnp.asarray([[5, 11, 23], [0, 7, 8]])
    plain = latent_attend(q, lat, w, limits, MCFG, absorbed=False)
    # a pool's rows end in zeros (KVPool.latent_row)
    padded = jnp.pad(lat, ((0, 0), (0, 0), (0, 12)))
    for cache in (lat, padded):
        got = latent_attend(q, cache, w, limits, MCFG, absorbed=True)
        np.testing.assert_allclose(got, plain, atol=2e-5, rtol=0)
    np.testing.assert_allclose(
        latent_attend(q, padded, w, limits, MCFG, absorbed=False), plain,
        atol=1e-6, rtol=0,
    )
    # what lies beyond a query's limit moves nothing
    junk = lat.at[0, 6:].set(99.0)
    np.testing.assert_array_equal(
        latent_attend(q, junk, w, limits, MCFG, absorbed=True)[0, :, 0],
        latent_attend(q, lat, w, limits, MCFG, absorbed=True)[0, :, 0],
    )


def test_key_blocks_of_the_materialised_form_change_nothing(monkeypatch):
    """The walk over blocks of cached positions with a running softmax
    is the one pass over the whole cache; it stops at the last block a
    query may see, whatever lies beyond."""
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(2, 4, 5, 12)), jnp.float32)
    lat = jnp.asarray(rng.normal(size=(2, 32, 20)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 4 * 16)), jnp.float32)
    limits = jnp.asarray([[0, 3, 8, 15, 17], [9, 9, 2, 1, 16]])
    whole = latent_attend(q, lat, w, limits, MCFG, absorbed=False)
    monkeypatch.setattr(transformer, "LATENT_KEY_BLOCK", 8)
    junk = lat.at[:, 24:].set(jnp.nan)     # a block no query reaches
    for cache in (lat, junk):
        np.testing.assert_allclose(
            latent_attend(q, cache, w, limits, MCFG, absorbed=False), whole,
            atol=2e-5, rtol=0,
        )


# -- the served path: chunked prefill, then absorbed decode -------------

#: prompts that end inside a block, on a block's edge (8) and a chunk's
#: (16), and past the YaRN table's original length (16); with their
#: answers every sequence decodes across a block's edge
SHAPES = [(37, 12), (5, 20), (16, 9), (44, 15), (24, 6)]


@pytest.fixture(scope="module")
def served(params):
    return serve(params, MCFG, SHAPES)


@pytest.mark.parametrize("rid", range(len(SHAPES)))
def test_chunked_prefill_then_absorbed_decode_against_the_reference(
    params, served, rid
):
    sched, engine = served
    (req,) = [r for r in sched.finished if r.rid == rid]
    assert len(req.tokens) == SHAPES[rid][1]
    gap, rows = gaps(params, CFG, req.prompt, req.tokens)
    # every served token is the reference's best to rounding: the
    # chunks' materialised keys, the pool's rows and the absorbed ticks
    # all stand behind the later ones
    assert gap.max() < TOL, gap
    assert np.ptp(rows, axis=-1).min() > 0.5    # logits that could differ


def test_the_latent_pool_is_one_row_a_token(served):
    _, engine = served
    assert engine.attend_choice == "reference: platform = cpu"
    assert len(engine.state["k"]) == 3 and engine.state["v"] == ()
    # 16 + 4 values a token, the row rounded up to whole tiles
    assert engine.state["k"][0].shape == (3 * 8 + 1, 8, 128)


def test_decode_counters_ride_the_pass(params, served):
    sched, engine = served
    assert engine.decode_counters == 5
    # 2 expert layers x 4 held experts bound a pass's hits, and a held
    # expert cannot take more tokens than there are slots
    assert 0 < sched.experts_hit <= sched.decode_ticks * 2 * 4
    assert 0 < sched.expert_max_load <= 3
    # a live slot's token chooses 4 of 16 experts a layer, a quarter of
    # them held on average
    tokens = sum(m for _, m in SHAPES) - len(SHAPES)
    assert 0 < sched.held_pairs <= tokens * 2 * 4
    assert 0.3 < sched.held_pairs / (tokens * 2) < 2.0
    prompt_tokens = sum(n for n, _ in SHAPES)
    assert 0 < sched.chunk_held_pairs <= prompt_tokens * 2 * 4
    # the pass that makes a request's j-th token after the first read
    # its prompt and the j tokens before; a lane whose request had
    # finished by the time its pass was read counted once more
    read = sum(n + j for n, m in SHAPES for j in range(1, m))
    assert read <= sched.cache_rows <= read + sched.lanes_unread * 64


@pytest.mark.parametrize("fault", ["latent_before_its_norm", "k_pe_unrotated"])
def test_a_wrong_cache_fails_the_same_comparison(params, monkeypatch, fault):
    """The control: the cache holding the latent BEFORE its norm, or the
    rotary key unrotated. A prefill chunk's own tokens and every later
    one read such rows, and the served tokens leave the reference's."""
    if fault == "k_pe_unrotated":
        rope = transformer._rope
        monkeypatch.setattr(
            transformer, "_rope",
            lambda x, *a: x if x.shape[1] == 1 else rope(x, *a),
        )
    else:
        rms = transformer._rmsnorm
        monkeypatch.setattr(
            transformer, "_rmsnorm",
            lambda x, scale, eps: x if x.shape[-1] == 16 else rms(x, scale, eps),
        )
    sched, _ = serve(params, MCFG, SHAPES[:2], slots=2)
    worst = max(
        gaps(params, CFG, r.prompt, r.tokens)[0].max()
        for r in sched.finished
    )
    assert worst > 100 * TOL, worst


def test_what_cannot_run_on_a_latent_cache_is_refused_by_name(params):
    for kw, what in (
        ({"spec_k": 2}, "speculate"), ({"prefix_cache": True}, "prefix_cache"),
    ):
        with pytest.raises(ValueError, match="kv_latent = 16") as e:
            Engine(params, MCFG, EngineConfig(
                slots=2, kv_block_len=8, max_prefill_chunk=16, **kw
            ))
        assert what in str(e.value)
    engine = Engine(params, MCFG, EngineConfig(
        slots=2, kv_block_len=8, max_prefill_chunk=16,
    ))
    for call in (lambda: engine.export_slot(0),
                 lambda: engine.export_blocks([1])):
        with pytest.raises(ValueError, match="kv_latent = 16"):
            call()
    with pytest.raises(ValueError, match="kv_latent"):
        transformer.generate(params, jnp.zeros((1, 4), jnp.int32), MCFG, 2)
    with pytest.raises(ValueError, match="kv_latent needs"):
        TransformerConfig(vocab=10, kv_latent=16)


# -- the paged latent kernel (interpreted here) -----------------------------


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
def test_latent_kernel_against_the_gathered_view(dtype, tol):
    from singa_tpu.ops.paged_attention import paged_latent_attention

    rng = np.random.default_rng(11)
    s, h, w, bl, mb = 4, 4, 128, 16, 6
    nb = s * mb + 1
    q = jnp.asarray(rng.normal(size=(s, h, w)), dtype)
    pool = jnp.asarray(rng.normal(size=(nb, bl, w)), dtype)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, nb)).reshape(s, mb), jnp.int32
    )
    # inside a block, on a block's last row, a chunk's edge, a dead lane
    pos = jnp.asarray([5, 31, 95, -1], jnp.int32)
    got = paged_latent_attention(
        q, pool, tables, pos, scale=0.3, out_width=96, interpret=True
    )
    view = pool[tables].reshape(s, mb * bl, w).astype(jnp.float32)
    scores = jnp.einsum("shk,sck->shc", q.astype(jnp.float32), view) * 0.3
    seen = jnp.arange(mb * bl)[None, None, :] <= pos[:, None, None]
    p = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    want = jnp.einsum("shc,sck->shk", p, view)[..., :96]
    assert got.shape == (s, h, 96) and got.dtype == dtype
    np.testing.assert_allclose(
        got[:3].astype(jnp.float32), want[:3], atol=tol, rtol=0
    )
    assert not np.asarray(got[3]).any()
    # blocks beyond a sequence's live range are never read
    poisoned = pool.at[tables[0, 1:]].set(jnp.nan)
    again = paged_latent_attention(
        q, poisoned, tables, pos, scale=0.3, out_width=96, interpret=True
    )
    np.testing.assert_array_equal(again[0], got[0])


def test_the_kernels_engine_serves_the_same_tokens(params, served):
    """``attend_impl = fused`` (on a TPU the engine's own choice): the
    decode tick reads the pool in place; tokens and their distance from
    the reference are the gather path's."""
    fused, eng = serve(params, MCFG, SHAPES, attend_impl="fused")
    assert eng.attend_choice == "fused"
    plain = {r.rid: r.tokens for r in served[0].finished}
    for r in fused.finished:
        assert r.tokens == plain[r.rid]
        assert gaps(params, CFG, r.prompt, r.tokens)[0].max() < TOL
    jaxpr = str(jax.make_jaxpr(eng._decode)(eng.params, eng.state))
    assert "name=paged_latent_attention" in jaxpr


def test_the_engine_chooses_the_latent_kernel_on_a_tpu():
    from singa_tpu.serve.engine import choose_attend

    serving = EngineConfig(slots=2, kv_block_len=16, max_prefill_chunk=16)
    assert choose_attend(MCFG, serving, None, "tpu") == "fused"
    assert choose_attend(MCFG, serving, None, "cpu") == (
        "reference: platform = cpu"
    )
    assert choose_attend(MCFG, serving, object(), "tpu") == (
        "reference: a tensor-parallel mesh"
    )
    with pytest.raises(ValueError, match="no multiple of 8 rows"):
        Engine(
            weights.make(ref.specs(CFG), 1), MCFG, EngineConfig(
                slots=2, kv_block_len=4, max_prefill_chunk=16,
                attend_impl="fused",
            ),
        )


# -- the router -----------------------------------------------------------


def router_case():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    p = {"gate": jnp.asarray(rng.normal(size=(32, 16)) * 0.3, jnp.float32),
         "bias": jnp.asarray(rng.normal(size=(16,)) * 0.3, jnp.float32)}
    return x, p


def test_the_bias_chooses_and_does_not_weigh():
    x, p = router_case()
    s = np.asarray(jax.nn.sigmoid(x @ p["gate"]))
    g, chosen = topk_gates(x, p, 4, "sigmoid", 2.5)
    g, chosen = np.asarray(g), np.asarray(chosen)
    unbiased, _ = topk_gates(x, {"gate": p["gate"]}, 4, "sigmoid", 2.5)
    by_bias = np.argsort(-(s + np.asarray(p["bias"])), axis=-1)[:, :4]
    by_score = np.argsort(-s, axis=-1)[:, :4]
    # a case worth having: the bias changes the choice for most tokens
    moved = [set(a) != set(b) for a, b in zip(by_bias, by_score)]
    assert sum(moved) > 32
    for t in range(64):
        assert set(np.flatnonzero(chosen[t])) == set(by_bias[t])
        want = np.zeros(16)
        want[by_bias[t]] = s[t, by_bias[t]] / s[t, by_bias[t]].sum() * 2.5
        np.testing.assert_allclose(g[t], want, atol=1e-6)
    # normalised, then scaled; the bias is nowhere in the weights
    np.testing.assert_allclose(g.sum(-1), 2.5, atol=1e-5)
    assert np.abs(g - np.asarray(unbiased)).max() > 0.05
    np.testing.assert_allclose(
        g, ref.gates(jnp.asarray(s), p["bias"], 4, 2.5), atol=1e-6
    )


def test_softmax_scoring_is_what_it_was():
    x, p = router_case()
    g, chosen = topk_gates(x, {"gate": p["gate"]}, 2)
    probs = np.asarray(jax.nn.softmax(x @ p["gate"], axis=-1))
    top = np.argsort(-probs, axis=-1)[:, :2]
    for t in range(64):
        want = np.zeros(16)
        want[top[t]] = probs[t, top[t]] / probs[t, top[t]].sum()
        np.testing.assert_allclose(np.asarray(g)[t], want, atol=1e-6)


def expert_layer(seed=6):
    cfg = dict(CFG, n_routed_experts=16, experts_held_from=0)
    spec = {
        k[len("blk1/"):]: v for k, v in ref.specs(cfg).items()
        if k.startswith("blk1/moe/")
    }
    lp = weights.make(spec, seed)
    x = jnp.asarray(
        np.random.default_rng(seed).normal(size=(2, 24, 32)), jnp.float32
    )
    return cfg, lp, x


def program_layer(lp, x, held_from, held, shared):
    """``moe_topk_ffn`` told its share: the router whole, the experts'
    weights cut to ``[held_from, held_from + held)``."""
    names = ("gate", "bias", "w_gate", "w_up", "w_down") + (
        ("s_gate", "s_up", "s_down") if shared else ()
    )
    p = {k: lp[f"moe/{k}"] for k in names}
    for k in ("w_gate", "w_up", "w_down"):
        p[k] = p[k][held_from:held_from + held]
    return moe_topk_ffn(
        x, p, CFG["num_experts_per_tok"], score="sigmoid",
        scale=CFG["routed_scaling_factor"], held_from=held_from,
    )


@pytest.mark.parametrize("held", [16, 8, 4, 2])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """Over every share of ``held`` experts: the routed parts summed,
    with the shared expert counted once, are the uncut reference's
    layer; and each share's part is the reference's for that share."""
    cfg, lp, x = expert_layer()
    h = x.reshape(-1, 32)
    ident = lambda a: a  # noqa: E731
    routed, shared = ref.expert_parts(lp, h, ref.Dims.of(cfg), ident)
    whole = np.asarray(routed + shared)
    total = np.zeros_like(whole)
    pairs = 0
    for first in range(0, 16, held):
        part, stats = program_layer(lp, x, first, held, shared=False)
        share = {
            k: (v[first:first + held] if k.startswith("moe/w_") else v)
            for k, v in lp.items()
        }
        want, _ = ref.expert_parts(
            share, h, ref.Dims.of(dict(cfg, experts_held_from=first)), ident
        )
        np.testing.assert_allclose(
            part.reshape(-1, 32), want, atol=TOL, rtol=0
        )
        total += np.asarray(part.reshape(-1, 32))
        pairs += int(stats[2])
        assert int(stats[0]) <= held and int(stats[1]) <= 48
    # every token's 4 experts lie in exactly one share each
    assert pairs == 48 * CFG["num_experts_per_tok"]
    np.testing.assert_allclose(total + shared, whole, atol=TOL, rtol=0)
    # and a share computed WITH the shared expert holds it once
    with_shared, _ = program_layer(lp, x, 0, held, shared=True)
    without, _ = program_layer(lp, x, 0, held, shared=False)
    np.testing.assert_allclose(
        (with_shared - without).reshape(-1, 32), shared, atol=TOL, rtol=0
    )
    assert np.abs(whole).max() > 0.1


def test_dense_swiglu_layer_by_hand(params):
    x = jnp.asarray(np.random.default_rng(8).normal(size=(1, 5, 32)), jnp.float32)
    cfg = TransformerConfig(
        vocab=10, d_model=32, n_heads=4, n_layers=1, d_ff=48, mlp="swiglu",
    )
    p = {k: v for k, v in params.items() if k.startswith("blk0/mlp/")}
    p |= {"blk0/ln1/scale": jnp.ones(32), "blk0/ln1/bias": jnp.zeros(32),
          "blk0/ln2/scale": jnp.ones(32), "blk0/ln2/bias": jnp.zeros(32),
          "blk0/attn/qkv": jnp.zeros((32, 96)), "blk0/attn/out": jnp.zeros((32, 32))}
    zero = lambda q, k, v: (jnp.zeros_like(q), None)  # noqa: E731
    got, _, _ = transformer._block_apply(p, "blk0", x, zero, cfg)
    h = transformer._layernorm(x, 1.0, 0.0)
    want = x + (
        jax.nn.silu(h @ p["blk0/mlp/gate"]) * (h @ p["blk0/mlp/up"])
    ) @ p["blk0/mlp/down"]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_rope_without_yarn_is_what_it_was():
    x = jnp.asarray(np.random.default_rng(9).normal(size=(1, 2, 6, 8)), jnp.float32)
    pos = jnp.arange(6)[None] * 7
    a = _rope(x, pos, 10000.0)
    b = _rope(x, pos, 10000.0, (1.0, 4096, 1, 1, 1.0, 1.0))
    # a factor of 1 divides nothing, and a sequence inside the original
    # length is rotated as ever
    np.testing.assert_allclose(a, b, atol=1e-6)


# -- the driver's arithmetic ----------------------------------------------


def test_token_flops_of_the_share_by_hand():
    big = load("benchmark", "configs", "kimi_k2_instruct.json")
    d, h = 7168, 64
    proj = d * 1536 + 1536 * h * 192 + d * 576 + h * 128 * d
    expert = 3 * d * 2048
    moe = d * 384 + (1 + 8 * 12 / 384) * expert
    dense = 3 * d * 18432
    absorb = h * 512 * 256
    want = (
        6 * (2 * (proj + absorb) + 2 * h * (2 * 512 + 64) * 1000)
        + 2 * dense + 5 * 2 * moe + 2 * d * 20480
    )
    assert drv.token_fwd_flops(big, 1000, True, 512) == pytest.approx(want)
    chunked = (
        6 * (2 * (proj + 512 * h * 256 * 1000 / 512) + 2 * h * 320 * 1000)
        + 2 * dense + 5 * 2 * moe
    )
    assert drv.token_fwd_flops(big, 1000, False, 512) == pytest.approx(chunked)


def test_the_configuration_keeps_every_published_width():
    big = load("benchmark", "configs", "kimi_k2_instruct.json")
    published = {
        "hidden_size": 7168, "num_attention_heads": 64,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "q_lora_rank": 1536, "kv_lora_rank": 512,
        "moe_intermediate_size": 2048, "intermediate_size": 18432,
        "n_router_outputs": 384, "num_experts_per_tok": 8,
        "routed_scaling_factor": 2.827, "first_k_dense_replace": 1,
    }
    assert {k: big[k] for k in published} == published
    assert sorted(big["reduced_from"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size",
    ]
    (entry,) = [
        c for c in load("BENCHMARK.json")["configs"]
        if c["name"] == "kimi_k2_instruct"
    ]
    assert sorted(entry["reduced"]) == sorted(big["reduced_from"])
    assert "32 chips share each layer" in big["deployment"]
    # the weights the cell holds, in bfloat16
    n = sum(int(np.prod(s["shape"])) for s in ref.specs(big).values())
    assert abs(2 * n / 1e9 - 8.35) < 0.01
