"""Plain reference for a decoder of the ``lfm2_moe`` type (gated
short-convolution layers and attention layers, one operator a layer,
each followed by a dense SwiGLU MLP or sigmoid-scored SwiGLU experts):
the equations of its configuration file in ``jax.numpy``, float32 with
products at ``highest`` — no cache, no tail carried between calls, no
batching of slots, no kernel: the convolution is written as its sum over
the taps, attention goes a K/V head's group of query heads at a time
under an explicit causal mask, the experts one after another.

    x0 = Embed[tok]                                    no positional term
    per layer l of layer_types:   h = x + op_l(RMSNorm(x; w1))
                                  x = h + ffn_l(RMSNorm(h; w2))
      conv   [B | C | x~] = u W_in           three widths of d, in that order
             v_t = B_t * x~_t
             w_t = sum_{j=0..K-1} k_j * v_{t-K+1+j}      per channel; zeros before the sequence
             out = (C * w) W_out                          no bias, no other nonlinearity
      attn   q, k, v = split(u W_qkv)        Hq heads on Hkv of d_h; head j reads K/V head j // (Hq / Hkv)
             q = RoPE(RMSNorm_dh(q; wq));  k = RoPE(RMSNorm_dh(k; wk))     rotate-half, rope_theta
             out = concat(softmax(q k^T / sqrt(d_h) + causal) v) W_o
      ffn    l < num_dense_layers:  (silu(u Wg) * (u Wu)) Wd             intermediate_size wide
             else  s = sigmoid(u W_r) over num_experts;  T = top-k(s + b)
                   g_e = s_e / (sum_T s + 1e-6) * routed_scaling_factor
                   out = sum_{e in T} g_e (silu(u Wg_e) * (u Wu_e)) Wd_e
    logits = RMSNorm(x_L; wf) Embed^T                  tied head; row t scores the token at t + 1

``b`` (the expert bias) chooses and does not weigh. The 1e-6 in the
gates' sum is transformers' ``route_tokens_to_experts``; the program
keeps the sum off zero by 1e-20 (``parallel/moe.py`` ``topk_gates``), a
gate's difference under 1e-6 relative where four sigmoid scores sum to
one or more (the configuration's ``departures``).

The parameter names are the program's (``init_lm`` for a model whose
``layers`` hold two one-mixer blocks a published layer: the operator's,
``blk<2l>``, then the MLP's or the experts', ``blk<2l+1>``), drawn by
``draw`` (``benchmark/weights.py``, then the convolution's taps shaped
uniform) and rounded to the stored type once. They stay
in memory as drawn and are upcast a layer, and inside an expert layer
an expert, at a time; a layer is a compiled call of its own, so that
4,096 positions at the published widths fit beside the weights.
``arith`` is as in ``reference/confnet.py``: below float32 it rounds
the operands of every product, the convolution's taps and what they
read among them (the control). Imports nothing of ``singa_tpu/``.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.reference.confnet import HI, rounder

#: a published ``layer_types`` entry -> the program's operator kind
OPERATORS = {"conv": "shortconv", "full_attention": "attn"}


def layer_kinds(cfg: dict) -> tuple:
    """The program's blocks: the operator of each published layer, then
    its dense MLP (the first ``num_dense_layers``) or its experts."""
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"layer_types names {len(types)} layers, num_hidden_layers "
            f"{cfg['num_hidden_layers']}"
        )
    return tuple(
        kind
        for i, t in enumerate(types)
        for kind in (
            OPERATORS[t], "mlp" if i < cfg["num_dense_layers"] else "moe"
        )
    )


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def specs(cfg: dict) -> dict[str, dict]:
    """The served model's parameters: names, shapes and how
    ``weights.make`` draws them (normal ``initializer_range``
    everywhere; the expert bias normal ``expert_bias_std``; norms one;
    the convolution's taps standard normals that ``draw`` shapes)."""
    d, k = cfg["hidden_size"], cfg["conv_L_cache"]
    hq, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    std = cfg["initializer_range"]

    def normal(*shape, s=std):
        return {"shape": list(shape), "init": "normal", "std": s}

    def ones(*shape):
        return {"shape": list(shape), "init": "constant", "value": 1.0}

    out = {"embed/tok": normal(cfg["vocab_size"], d)}
    for i, kind in enumerate(layer_kinds(cfg)):
        p = f"blk{i}"
        out[f"{p}/ln1/scale"] = ones(d)
        if kind == "shortconv":
            out[f"{p}/shortconv/in_proj"] = normal(d, 3 * d)
            out[f"{p}/shortconv/conv_w"] = normal(k, d, s=1.0)
            out[f"{p}/shortconv/out_proj"] = normal(d, d)
        elif kind == "attn":
            out[f"{p}/attn/qkv"] = normal(d, (hq + 2 * hkv) * dh)
            out[f"{p}/attn/out"] = normal(hq * dh, d)
            out[f"{p}/attn/q_norm"] = ones(dh)
            out[f"{p}/attn/k_norm"] = ones(dh)
        elif kind == "mlp":
            out[f"{p}/mlp/gate"] = normal(d, cfg["intermediate_size"])
            out[f"{p}/mlp/up"] = normal(d, cfg["intermediate_size"])
            out[f"{p}/mlp/down"] = normal(cfg["intermediate_size"], d)
        else:
            out[f"{p}/moe/gate"] = normal(d, e)
            out[f"{p}/moe/w_gate"] = normal(e, d, f)
            out[f"{p}/moe/w_up"] = normal(e, d, f)
            out[f"{p}/moe/w_down"] = normal(e, f, d)
            out[f"{p}/moe/bias"] = normal(e, s=cfg["expert_bias_std"])
    out["ln_f/scale"] = ones(d)
    return out


def draw(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    """The seeded weights both sides read: ``weights.make`` of
    ``specs``, then each short convolution's taps uniform in
    +-1/sqrt(K) from their standard normals ``z`` (``2 Phi(z) - 1`` is
    uniform in +-1): a depthwise Conv1d's default, whose fan-in is its
    K taps, as the program's ``init_lm`` and ``nemotron_h.draw`` have
    it (at ``initializer_range`` a short convolution would add some
    0.02 to a residual of order one, and no served token could show
    what its tail holds). Rounded to ``dtype`` once."""
    params = weights.make(specs(cfg), seed, dtype)
    bound = 1.0 / math.sqrt(cfg["conv_L_cache"])

    @jax.jit
    def uniform(z):
        u = 2.0 * jax.scipy.special.ndtr(z.astype(jnp.float32)) - 1.0
        return (u * bound).astype(dtype)

    for i, kind in enumerate(layer_kinds(cfg)):
        if kind == "shortconv":
            name = f"blk{i}/shortconv/conv_w"
            params[name] = uniform(params[name])
    return params


class Dims(NamedTuple):
    """The numbers of a configuration that a layer's equations read."""

    eps: float
    heads: int
    kv_heads: int
    head_dim: int
    theta: float
    top_k: int
    route_scale: float

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        if not cfg["norm_topk_prob"] or cfg["conv_bias"]:
            raise ValueError("lfm2_moe: norm_topk_prob true, conv_bias false")
        return cls(
            cfg["norm_eps"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], head_dim(cfg),
            float(cfg["rope_theta"]), cfg["num_experts_per_tok"],
            float(cfg["routed_scaling_factor"]),
        )


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (H, S, D) at positions 0..S-1, rotate-half: pair (i, i + D/2)
    turns by pos * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[None, :, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def short_conv(lp: dict, u, r):
    """The gated short convolution on u (S, d), from zeros."""
    f32 = jnp.float32
    s, d = u.shape
    bcx = jnp.matmul(r(u), r(lp["shortconv/in_proj"].astype(f32)),
                     precision=HI)
    b, c, xs = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    v = r(b * xs)
    taps = r(lp["shortconv/conv_w"].astype(f32))               # (K, d)
    k = taps.shape[0]
    padded = jnp.pad(v, ((k - 1, 0), (0, 0)))
    w = sum(taps[j] * padded[j:j + s] for j in range(k))
    return jnp.matmul(r(c * w), r(lp["shortconv/out_proj"].astype(f32)),
                      precision=HI)


def attention(lp: dict, u, dims: Dims, r):
    """Causal attention on u (S, d) with QK-norm and rotary positions,
    the query heads of one K/V head at a time."""
    f32 = jnp.float32
    s = u.shape[0]
    hq, hkv, dh = dims.heads, dims.kv_heads, dims.head_dim
    qkv = jnp.matmul(r(u), r(lp["attn/qkv"].astype(f32)), precision=HI)
    q, k, v = (
        jnp.moveaxis(part.reshape(s, -1, dh), 1, 0)
        for part in jnp.split(qkv, [hq * dh, (hq + hkv) * dh], axis=1)
    )
    q = _rope(_rms(q, lp["attn/q_norm"].astype(f32), dims.eps), dims.theta)
    k = _rope(_rms(k, lp["attn/k_norm"].astype(f32), dims.eps), dims.theta)
    see = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    group = hq // hkv
    heads = []
    for g in range(hkv):        # query head j reads K/V head j // group
        scores = jnp.einsum(
            "hqd,kd->hqk", r(q[g * group:(g + 1) * group]), r(k[g]),
            precision=HI,
        ) / math.sqrt(dh)
        w = jax.nn.softmax(jnp.where(see[None], scores, -jnp.inf), axis=-1)
        heads.append(jnp.einsum("hqk,kd->hqd", r(w), r(v[g]), precision=HI))
    a = jnp.moveaxis(jnp.concatenate(heads, 0), 0, 1).reshape(s, hq * dh)
    return jnp.matmul(r(a), r(lp["attn/out"].astype(f32)), precision=HI)


def _swiglu(u, wg, wu, wd, r):
    f32 = jnp.float32

    def mm(a, b):
        return jnp.matmul(r(a), r(b.astype(f32)), precision=HI)

    return mm(jax.nn.silu(mm(u, wg)) * mm(u, wu), wd)


def gates(s, bias, top_k: int, route_scale: float):
    """Scores ``s`` (S, E) of the router -> gates (S, E), zero outside
    each token's top k of ``s + bias``: the bias chooses, ``s`` weighs,
    normalised over the chosen as transformers does it."""
    _, top_e = jax.lax.top_k(s + bias, top_k)
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], top_e
    ].set(True)
    picked = jnp.where(chosen, s, 0.0)
    return picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-6
    ) * route_scale


def experts(lp: dict, u, dims: Dims, r):
    """The expert layer on u (S, d): every expert on every token, each
    weighted by its gate (zero outside the top k)."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(
        jnp.matmul(u, lp["moe/gate"].astype(f32), precision=HI)
    )
    g = gates(s, lp["moe/bias"].astype(f32), dims.top_k, dims.route_scale)

    def one(total, e):
        mine = jax.lax.dynamic_index_in_dim(g, e, 1)
        return total + mine * _swiglu(
            u, lp["moe/w_gate"][e], lp["moe/w_up"][e], lp["moe/w_down"][e],
            r,
        ), None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(u), jnp.arange(lp["moe/w_up"].shape[0])
    )
    return total


@functools.partial(jax.jit, static_argnames=("kind", "dims", "arith"))
def _block(lp: dict, x, *, kind: str, dims: Dims, arith: str):
    """One block on x (S, d), ``x + mixer(RMSNorm(x))``: ``lp`` holds its
    parameters under their names without the ``blk<i>/`` prefix.
    Compiled once a kind."""
    r = rounder(arith)
    f32 = jnp.float32
    u = _rms(x, lp["ln1/scale"].astype(f32), dims.eps)
    if kind == "shortconv":
        return x + short_conv(lp, u, r)
    if kind == "attn":
        return x + attention(lp, u, dims, r)
    if kind == "mlp":
        return x + _swiglu(
            u, lp["mlp/gate"], lp["mlp/up"], lp["mlp/down"], r
        )
    return x + experts(lp, u, dims, r)


def forward(params: dict, tokens, cfg: dict, arith: str = "float32"):
    """tokens (S,) int32 -> logits (S, vocab), row t scoring the token
    at t + 1. A block a compiled call."""
    f32 = jnp.float32
    r = rounder(arith)
    dims = Dims.of(cfg)
    with jax.default_matmul_precision("highest"):
        x = params["embed/tok"][tokens].astype(f32)
        for i, kind in enumerate(layer_kinds(cfg)):
            pre = f"blk{i}/"
            lp = {k[len(pre):]: v for k, v in params.items()
                  if k.startswith(pre)}
            x = _block(lp, x, kind=kind, dims=dims, arith=arith)
        x = _rms(x, params["ln_f/scale"].astype(f32), cfg["norm_eps"])
        return jnp.matmul(
            r(x), r(params["embed/tok"].astype(f32)).T, precision=HI
        )
