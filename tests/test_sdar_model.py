"""The block vocabulary beyond GPT-2's, at a small size on the CPU with
seeded random weights: RMSNorm, rotary positions, fewer K/V heads than
query heads, QK-norm, an untied head and drop-free top-k SwiGLU experts,
lowered by the ONE ``_block_apply``, against the plain reference
(``benchmark/reference/sdar_moe.py``).

Tolerances are float32's: program and reference compute the same
equations in float32 in another order, so logits of size 1-10 agree to
some 1e-5; a router's near-tie that went the other way would show as
1e-2 or more, so 2e-4 also pins the routing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.drivers import serve_blocks
from benchmark.reference import sdar_moe as ref
from singa_tpu.models.transformer import (
    TransformerConfig, cache_attend, init_lm, lm_apply,
)
from singa_tpu.parallel.moe import init_moe_topk, moe_topk_ffn

TOL = 2e-4

#: the rehearsal's tiny configuration (tests/benchmark/tiny/configs)
CFG = {
    "hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "num_experts": 8,
    "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "vocab_size": 200, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "tie_word_embeddings": False, "block_length": 4, "mask_token_id": 199,
    "initializer_range": 0.3, "torch_dtype": "float32",
}
MCFG = serve_blocks.model_config(CFG, {"max_model_len": 64})


@pytest.fixture(scope="module")
def params():
    return weights.make(ref.specs(CFG), 2**31 + 7)


def test_reference_specs_are_the_programs_parameters(params):
    mine = init_lm(jax.random.PRNGKey(0), MCFG)
    assert {k: v.shape for k, v in mine.items()} == {
        k: v.shape for k, v in params.items()
    }
    assert "embed/pos" not in mine and "blk0/ln1/bias" not in mine


@pytest.mark.parametrize("length", [12, 24, 41 // 4 * 4])
def test_lm_apply_against_the_reference_forward(params, length):
    rng = np.random.default_rng(length)
    toks = rng.integers(0, 199, (length,)).astype(np.int32)
    toks[-3:] = CFG["mask_token_id"]       # a block still being denoised
    got = lm_apply(params, jnp.asarray(toks)[None], MCFG)[0]
    want = ref.forward(params, jnp.asarray(toks), CFG)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_block_causal_mask_is_not_the_causal_one(params):
    """A token INSIDE a block changes the logits of the block's earlier
    positions, and a later block changes nothing before it."""
    toks = np.arange(16, dtype=np.int32)
    base = np.asarray(lm_apply(params, jnp.asarray(toks)[None], MCFG)[0])
    inside, later = toks.copy(), toks.copy()
    inside[7] = 150
    later[8] = 150
    moved = np.asarray(lm_apply(params, jnp.asarray(inside)[None], MCFG)[0])
    same = np.asarray(lm_apply(params, jnp.asarray(later)[None], MCFG)[0])
    assert np.abs(moved[4] - base[4]).max() > 1e-3     # same block
    np.testing.assert_array_equal(moved[:4], base[:4])  # the block before
    np.testing.assert_array_equal(same[:8], base[:8])


def all_experts(x, p, top_k):
    """Every expert on every token, one expert at a time, weighted by
    the renormalised top-k gate: the layer's equation, spelt out."""
    probs = jax.nn.softmax(x @ p["gate"], axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    y = jnp.zeros_like(x)
    load = np.zeros((probs.shape[-1],), np.int64)
    for e in range(probs.shape[-1]):
        hit = jnp.any(top_e == e, axis=-1)
        g = jnp.where(hit, probs[..., e], 0.0) / jnp.sum(top_p, axis=-1)
        out = (
            jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
        ) @ p["w_down"][e]
        y = y + g[..., None] * out
        load[e] = int(jnp.sum(hit))
    return y, load


@pytest.mark.parametrize("skew", [0.0, 6.0])
def test_topk_layer_drops_no_token_under_a_skewed_router(skew):
    """With ``skew`` one expert takes most tokens (the capacity a Switch
    layer would cut at is far exceeded): the result is still the whole
    sum, and the counters say how skewed it was."""
    d, f, e, k = 16, 8, 8, 2
    p = init_moe_topk(jax.random.PRNGKey(1), d, f, e)
    p["gate"] = p["gate"].at[:, 3].add(skew * jnp.sign(p["gate"][:, 3]))
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 16, d))
    x = x + skew * jnp.sign(p["gate"][:, 3]) / d  # push every token to 3
    y, stats = moe_topk_ffn(x, p, k)
    want, load = all_experts(x, p, k)
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=0)
    assert int(stats[0]) == int((load > 0).sum())
    assert int(stats[1]) == int(load.max())
    assert load.sum() == 3 * 16 * k                # no token dropped
    if skew:
        assert load[3] >= 40 and load.max() > 3 * (48 * k // e)
    # the counters count the tokens marked valid alone
    valid = jnp.zeros((3, 16), bool).at[0].set(True)
    _, some = moe_topk_ffn(x, p, k, valid=valid)
    assert int(some[1]) <= 16 and int(some[0]) <= int(stats[0])


@pytest.mark.parametrize("hq,hkv", [(4, 2), (8, 1), (4, 4)])
def test_grouped_cache_attend_is_attention_over_repeated_kv(hq, hkv):
    rng = jax.random.PRNGKey(hq * 10 + hkv)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (2, hq, 5, 8))
    k = jax.random.normal(kk, (2, hkv, 12, 8))
    v = jax.random.normal(kv, (2, hkv, 12, 8))
    limits = jnp.asarray([[3, 3, 3, 3, 7], [11, 11, 11, 11, 0]])
    got = cache_attend(q, k, v, limits)
    rep = hq // hkv
    want = cache_attend(
        q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1), limits
    )
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    assert got.shape == q.shape


@pytest.mark.parametrize("n_blocks", [1, 3, 6])
def test_two_stream_reference_is_the_literal_loop(params, n_blocks):
    """Scoring a request a pass index at a time — clean and noisy
    sequence side by side — gives, at every block, the logits of the
    literal step: the committed prefix, then the block as it stood."""
    b, mask = CFG["block_length"], CFG["mask_token_id"]
    rng = np.random.default_rng(n_blocks)
    clean = rng.integers(0, 199, (n_blocks * b,)).astype(np.int32)
    noisy = np.where(rng.random(clean.shape) < 0.5, mask, clean).astype(
        np.int32
    )
    both = ref.two_stream(
        params, jnp.asarray(clean), jnp.asarray(noisy), CFG
    )
    for blk in range(n_blocks):
        lo, hi = blk * b, (blk + 1) * b
        literal = ref.score_block(
            params, jnp.asarray(clean[:lo]), jnp.asarray(noisy[lo:hi]), CFG
        )
        np.testing.assert_allclose(both[lo:hi], literal, atol=TOL, rtol=0)


def test_gpt2_defaults_resolve_to_what_they_were():
    cfg = TransformerConfig(vocab=50, d_model=32, n_heads=4)
    assert (cfg.head_dim, cfg.n_kv_heads, cfg.qkv_width) == (8, 4, 96)
    assert not cfg.gqa and cfg.tied_head and cfg.norm == "layernorm"
    names = set(init_lm(jax.random.PRNGKey(0), cfg))
    assert {"embed/pos", "blk0/ln1/bias", "blk0/mlp/up"} <= names
    assert "head/out" not in names and "blk0/attn/q_norm" not in names
    with pytest.raises(ValueError, match="n_kv_heads"):
        TransformerConfig(vocab=50, d_model=32, n_heads=4, n_kv_heads=3)
    with pytest.raises(ValueError, match="moe_top_k"):
        TransformerConfig(vocab=50, moe_experts=4, moe_top_k=8, moe_d_ff=8)
