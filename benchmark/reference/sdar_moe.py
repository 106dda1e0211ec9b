"""Plain reference for a decoder of the ``sdar_moe`` type (a Qwen3-MoE
lineage block under a block-causal mask, generated by diffusion over
blocks): the equations of its configuration file in ``jax.numpy``,
float32 with products at ``highest`` — no cache, no pages, no batching
of slots, no kernel, an explicit mask and explicit positions, every
expert computed for every token and weighted by its gate (zero outside
the top k).

    x0 = Embed[tok]                                       (no position table)
    per layer:  h = RMSNorm(x; w1)
      q = h Wq -> (Hq, D);  k = h Wk -> (Hkv, D);  v = h Wv -> (Hkv, D)
      q = RoPE(RMSNorm_D(q; wq), pos);  k = RoPE(RMSNorm_D(k; wk), pos)
      a = softmax(q k_g^T / sqrt(D) + M) v_g    query head j reads K/V head j // (Hq / Hkv)
      x = x + concat(a) Wo
      h = RMSNorm(x; w2);  p = softmax(h Wr) over E;  T = top-k(p);  g_e = p_e / sum_T p
      x = x + sum_{e in T} g_e ((silu(h Wg_e) * (h Wu_e)) Wd_e)
    logits = RMSNorm(x_L; wf) Whead
    M[i, j] = 0 if j // B <= i // B else -inf             (block-causal)

The parameter names are the program's (``init_lm`` for this block's
fields): ``attn/qkv`` holds Wq, Wk, Wv side by side, ``moe/w_gate`` and
``moe/w_up`` hold the experts as (E, d, F). They stay in
memory as they are drawn (bfloat16 at the published size) and are
upcast a layer, and inside it a group of experts, at a time.

Generation is scored A PASS INDEX AT A TIME (``two_stream``): the clean
sequence and the sequence as it stood at pass s side by side in one
forward of twice the length, block b of the second seeing the blocks
before b of the first and itself — block diffusion's training-time mask.
``score_block`` is the literal step (the prefix, then the block alone);
``tests/test_sdar_model.py`` holds the two equal.

``arith`` is as in ``reference/confnet.py``: below float32 it rounds the
operands of every product (the controls).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark.reference.confnet import HI, rounder

#: experts upcast and computed together
EXPERT_GROUP = 8


def specs(cfg: dict) -> dict[str, dict]:
    """The served model's parameters: names, shapes and how they are
    drawn (normal 0.02 everywhere, norms at identity)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    std = cfg["initializer_range"]

    def normal(*shape):
        return {"shape": list(shape), "init": "normal", "std": std}

    def ones(*shape):
        return {"shape": list(shape), "init": "constant", "value": 1.0}

    out = {"embed/tok": normal(cfg["vocab_size"], d)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"blk{i}"
        out[f"{p}/ln1/scale"] = ones(d)
        out[f"{p}/attn/qkv"] = normal(d, (hq + 2 * hkv) * hd)
        out[f"{p}/attn/out"] = normal(hq * hd, d)
        out[f"{p}/attn/q_norm"] = ones(hd)
        out[f"{p}/attn/k_norm"] = ones(hd)
        out[f"{p}/ln2/scale"] = ones(d)
        out[f"{p}/moe/gate"] = normal(d, e)
        out[f"{p}/moe/w_gate"] = normal(e, d, f)
        out[f"{p}/moe/w_up"] = normal(e, d, f)
        out[f"{p}/moe/w_down"] = normal(e, f, d)
    out["ln_f/scale"] = ones(d)
    out["head/out"] = normal(d, cfg["vocab_size"])
    return out


class Dims(NamedTuple):
    """The numbers of a configuration that a layer's equations read."""

    hq: int
    hkv: int
    hd: int
    eps: float
    theta: float
    experts: int
    top_k: int

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        return cls(
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["rms_norm_eps"], float(cfg["rope_theta"]),
            cfg["num_experts"], cfg["num_experts_per_tok"],
        )


def block_causal(n: int, block: int):
    """see[i, j]: position i sees j iff ``j // block <= i // block``."""
    b = jnp.arange(n) // block
    return b[None, :] <= b[:, None]


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x (H, S, D), rotate-half: pair (i, i + D/2) by pos * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[None, :, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "arith"))
def _layer(lp: dict, x, positions, see, *, dims: Dims, arith: str):
    """One layer on x (S, d): ``lp`` holds the layer's parameters under
    their names without the ``blk<i>/`` prefix. Compiled once for all
    the layers of a model."""
    r = rounder(arith)
    f32 = jnp.float32
    hq, hkv, hd, eps = dims.hq, dims.hkv, dims.hd, dims.eps
    s = x.shape[0]

    def mm(a, b):
        return jnp.matmul(r(a), r(b), precision=HI)

    h = _rms(x, lp["ln1/scale"].astype(f32), eps)
    wqkv = lp["attn/qkv"].astype(f32)
    wq, wk, wv = jnp.split(wqkv, [hq * hd, (hq + hkv) * hd], axis=1)
    q = jnp.moveaxis(mm(h, wq).reshape(s, hq, hd), 1, 0)       # (Hq, S, D)
    k = jnp.moveaxis(mm(h, wk).reshape(s, hkv, hd), 1, 0)
    v = jnp.moveaxis(mm(h, wv).reshape(s, hkv, hd), 1, 0)
    q = _rope(_rms(q, lp["attn/q_norm"].astype(f32), eps), positions,
              dims.theta)
    k = _rope(_rms(k, lp["attn/k_norm"].astype(f32), eps), positions,
              dims.theta)
    group = hq // hkv
    heads = []
    for g in range(hkv):        # query head j reads K/V head j // group
        qg = q[g * group:(g + 1) * group]
        scores = jnp.einsum(
            "hqd,kd->hqk", r(qg), r(k[g]), precision=HI
        ) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(see[None], scores, -jnp.inf), axis=-1)
        heads.append(jnp.einsum("hqk,kd->hqd", r(w), r(v[g]), precision=HI))
    a = jnp.moveaxis(jnp.concatenate(heads, 0), 0, 1).reshape(s, hq * hd)
    x = x + mm(a, lp["attn/out"].astype(f32))

    h = _rms(x, lp["ln2/scale"].astype(f32), eps)
    p = jax.nn.softmax(mm(h, lp["moe/gate"].astype(f32)), axis=-1)   # (S, E)
    top_p, top_e = jax.lax.top_k(p, dims.top_k)
    chosen = jnp.zeros(p.shape, bool).at[
        jnp.arange(s)[:, None], top_e
    ].set(True)
    gates = jnp.where(chosen, p, 0.0) / jnp.sum(top_p, -1, keepdims=True)
    y = jnp.zeros_like(x)
    for e0 in range(0, dims.experts, EXPERT_GROUP):
        sl = slice(e0, e0 + EXPERT_GROUP)
        wg = lp["moe/w_gate"][sl].astype(f32)                  # (G, d, F)
        wu = lp["moe/w_up"][sl].astype(f32)
        wd = lp["moe/w_down"][sl].astype(f32)                  # (G, F, d)
        up = jnp.einsum("sd,gdf->sgf", r(h), r(wu), precision=HI)
        act = jax.nn.silu(
            jnp.einsum("sd,gdf->sgf", r(h), r(wg), precision=HI)
        ) * up
        out = jnp.einsum("sgf,gfd->sgd", r(act), r(wd), precision=HI)
        y = y + jnp.einsum("sg,sgd->sd", gates[:, sl], out, precision=HI)
    return x + y


def forward(params: dict, tokens, cfg: dict, arith: str = "float32", *,
            positions=None, see=None, rows=None):
    """tokens (S,) int32 -> logits (S, vocab), row t scoring the token
    AT t. ``positions`` (S,) default to 0..S-1 and ``see`` (S, S) to the
    block-causal mask of ``cfg["block_length"]``; ``rows`` keeps only
    those rows of the logits (the head is the widest product)."""
    f32 = jnp.float32
    r = rounder(arith)
    s = tokens.shape[0]
    if positions is None:
        positions = jnp.arange(s)
    if see is None:
        see = block_causal(s, cfg["block_length"])
    dims = Dims.of(cfg)
    with jax.default_matmul_precision("highest"):
        x = params["embed/tok"][tokens].astype(f32)
        for i in range(cfg["num_hidden_layers"]):
            pre = f"blk{i}/"
            lp = {k[len(pre):]: v for k, v in params.items()
                  if k.startswith(pre)}
            x = _layer(lp, x, positions, see, dims=dims, arith=arith)
        if rows is not None:
            x = x[rows]
        x = _rms(x, params["ln_f/scale"].astype(f32), cfg["rms_norm_eps"])
        return jnp.matmul(
            r(x), r(params["head/out"].astype(f32)), precision=HI
        )


def two_stream(params: dict, clean, noisy, cfg: dict,
               arith: str = "float32"):
    """Scores of one pass index for a whole request, in ONE forward.

    ``clean`` (S,) is the sequence as it ends up, ``noisy`` (S,) the
    sequence as it stood at that pass (the tokens fixed before it in
    place, the mask id elsewhere); S is a whole number of blocks. Both
    go through the model side by side: a clean position sees the clean
    blocks up to its own (the block-causal mask); block b of the noisy
    stream sees the clean blocks BEFORE b and the noisy block b itself.
    That is what a block's pass saw: the committed prefix through the
    cache, and the block as it stood. -> logits (S, vocab) of the noisy
    stream."""
    s, block = clean.shape[0], cfg["block_length"]
    b = jnp.arange(s) // block
    see = jnp.block([
        [b[None, :] <= b[:, None], jnp.zeros((s, s), bool)],
        [b[None, :] < b[:, None], b[None, :] == b[:, None]],
    ])
    pos = jnp.arange(s)
    return forward(
        params, jnp.concatenate([clean, noisy]), cfg, arith,
        positions=jnp.concatenate([pos, pos]), see=see,
        rows=slice(s, 2 * s),
    )


def score_block(params: dict, prefix, block_tokens, cfg: dict,
                arith: str = "float32"):
    """The literal step: the committed ``prefix`` (whole blocks) and
    then the block as it stands, alone -> logits (B, vocab) of the
    block's positions."""
    n = block_tokens.shape[0]
    return forward(
        params, jnp.concatenate([prefix, block_tokens]), cfg, arith
    )[-n:]
