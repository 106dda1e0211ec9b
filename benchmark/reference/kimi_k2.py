"""Plain reference for a decoder of the ``kimi_k2`` type (the DeepSeek-V3
modelling code: latent attention, sigmoid-scored experts with a
selection bias and a shared expert, a leading dense SwiGLU layer, YaRN
rotary frequencies): the equations of its configuration file in
``jax.numpy``, float32 with products at ``highest`` — no cache, no pages,
no batching of slots, no kernel, NO ABSORPTION: keys and values of every
head are made from the latents of the whole sequence and attended to
under an explicit causal mask, one head at a time so that 12,800
positions fit.

    x0 = Embed[tok]
    per layer:  h = RMSNorm(x; w1)
      c_q = RMSNorm(h W_qa; w_q);   [q_nope | q_pe]_h = c_q W_qb        (H heads of d_nope + d_rope)
      [c | k_pe] = h W_kva;  c = RMSNorm(c; w_kv);  [k_nope | v]_h = c W_kvb
      q_pe, k_pe = RoPE_yarn(., pos)        k_pe is ONE vector a token, shared by every head
      a_h = softmax(scale (q_nope_h k_nope_h^T + q_pe_h k_pe^T) + causal) v_h;   x = x + concat(a) W_o
      h = RMSNorm(x; w2)
      layer < first_k_dense_replace:  x = x + (silu(h W_g) * (h W_u)) W_d
      else:  s = sigmoid(h W_r) over ALL n_router_outputs;  T = top-k(s + b)
             g_e = s_e / (sum_T s + 1e-20) * routed_scaling_factor
             x = x + sum_{e in T, e held} g_e E_e(h) + S(h)           E_e, S SwiGLU
    logits = RMSNorm(x_L; wf) W_head                     row t scores the token at t + 1
    scale = (d_nope + d_rope) ** -0.5 * m * m,   m = 0.1 * mscale_all_dim * ln(factor) + 1

THE SHARE. The file's ``n_routed_experts`` experts from
``experts_held_from`` on are held here; the router is
``n_router_outputs`` wide. T and g are computed over all of them and the
sum runs over T's held members: what the others would add is left out,
as in the program (``expert_parts`` returns the routed part and the
shared expert apart, so that a test can add the shares up).

The parameter names are the program's (``init_lm`` for these fields).
They stay in memory as drawn (bfloat16 at the published size) and are
upcast a matrix at a time. ``arith`` is as in ``reference/confnet.py``:
below float32 it rounds the operands of every product (the controls).
Imports nothing of ``singa_tpu/``.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark.reference.confnet import HI, rounder


def specs(cfg: dict) -> dict[str, dict]:
    """The served model's parameters: names, shapes and how they are
    drawn (normal ``initializer_range`` everywhere, the selection bias
    normal ``router_bias_std``, norms at identity)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    held, width = cfg["n_routed_experts"], cfg["n_router_outputs"]
    f = cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    std = cfg["initializer_range"]

    def normal(*shape, s=std):
        return {"shape": list(shape), "init": "normal", "std": s}

    def ones(*shape):
        return {"shape": list(shape), "init": "constant", "value": 1.0}

    out = {"embed/tok": normal(cfg["vocab_size"], d)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"blk{i}"
        out[f"{p}/ln1/scale"] = ones(d)
        out[f"{p}/attn/q_a"] = normal(d, rq)
        out[f"{p}/attn/q_a_norm"] = ones(rq)
        out[f"{p}/attn/q_b"] = normal(rq, h * (dn + dr))
        out[f"{p}/attn/kv_a"] = normal(d, rkv + dr)
        out[f"{p}/attn/kv_a_norm"] = ones(rkv)
        out[f"{p}/attn/kv_b"] = normal(rkv, h * (dn + dv))
        out[f"{p}/attn/out"] = normal(h * dv, d)
        out[f"{p}/ln2/scale"] = ones(d)
        if i < cfg["first_k_dense_replace"]:
            out[f"{p}/mlp/gate"] = normal(d, cfg["intermediate_size"])
            out[f"{p}/mlp/up"] = normal(d, cfg["intermediate_size"])
            out[f"{p}/mlp/down"] = normal(cfg["intermediate_size"], d)
            continue
        out[f"{p}/moe/gate"] = normal(d, width)
        out[f"{p}/moe/bias"] = normal(width, s=cfg["router_bias_std"])
        out[f"{p}/moe/w_gate"] = normal(held, d, f)
        out[f"{p}/moe/w_up"] = normal(held, d, f)
        out[f"{p}/moe/w_down"] = normal(held, f, d)
        out[f"{p}/moe/s_gate"] = normal(d, fs)
        out[f"{p}/moe/s_up"] = normal(d, fs)
        out[f"{p}/moe/s_down"] = normal(fs, d)
    out["ln_f/scale"] = ones(d)
    out["head/out"] = normal(d, cfg["vocab_size"])
    return out


class Dims(NamedTuple):
    """The numbers of a configuration that a layer's equations read."""

    heads: int
    d_nope: int
    d_rope: int
    d_v: int
    r_kv: int
    eps: float
    theta: float
    yarn: tuple        # factor, original length, beta_fast, beta_slow, mscale, mscale_all_dim
    top_k: int
    route_scale: float
    held_from: int

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        y = cfg["rope_scaling"]
        return cls(
            cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"],
            cfg["rms_norm_eps"], float(cfg["rope_theta"]),
            (float(y["factor"]), y["original_max_position_embeddings"],
             y["beta_fast"], y["beta_slow"], y["mscale"],
             y["mscale_all_dim"]),
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["experts_held_from"],
        )


def mscale_of(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(dims: Dims) -> float:
    scale = (dims.d_nope + dims.d_rope) ** -0.5
    if dims.yarn[5]:
        scale *= mscale_of(dims.yarn[0], dims.yarn[5]) ** 2
    return scale


def yarn_inv_freq(dim: int, theta: float, yarn: tuple):
    """``f_i = theta ** (-2i / dim)``; pair i keeps it below ``low``,
    takes ``f_i / factor`` above ``high``, a linear blend between
    (module docstring of the configuration's issue: ``cd``, ``keep``)."""
    factor, orig, beta_fast, beta_slow = yarn[:4]

    def cd(n):
        return dim * math.log(orig / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(cd(beta_fast)), 0)
    high = min(math.ceil(cd(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / dim)
    keep = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (f / factor) * (1.0 - keep) + f * keep


def _rope(x, positions, dims: Dims):
    """x (..., S, d_rope), rotate-half: pair (i, i + d_rope / 2) turns by
    ``pos * inv_freq_i``; cos and sin carry the ratio of the two
    magnitude corrections (1 where mscale == mscale_all_dim)."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(
        2 * half, dims.theta, dims.yarn
    )
    m = mscale_of(dims.yarn[0], dims.yarn[4]) / mscale_of(
        dims.yarn[0], dims.yarn[5]
    )
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def gates(s, bias, top_k: int, route_scale: float):
    """Scores ``s`` (S, E) of the router -> gates (S, E), zero outside
    each token's top k of ``s + bias``: the bias chooses, ``s`` weighs."""
    _, top_e = jax.lax.top_k(s + bias, top_k)
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], top_e
    ].set(True)
    picked = jnp.where(chosen, s, 0.0)
    return picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20
    ) * route_scale


def expert_parts(lp: dict, h, dims: Dims, r):
    """The expert layer on h (S, d) -> (what the held routed experts
    give, what the shared expert gives)."""
    f32 = jnp.float32

    def mm(a, b):
        return jnp.matmul(r(a), r(b.astype(f32)), precision=HI)

    def swiglu(wg, wu, wd):
        return mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)

    s = jax.nn.sigmoid(
        jnp.matmul(h, lp["moe/gate"].astype(f32), precision=HI)
    )
    g = gates(s, lp["moe/bias"].astype(f32), dims.top_k, dims.route_scale)
    routed = jnp.zeros_like(h)
    for e in range(lp["moe/w_gate"].shape[0]):
        routed = routed + g[:, dims.held_from + e, None] * swiglu(
            lp["moe/w_gate"][e], lp["moe/w_up"][e], lp["moe/w_down"][e]
        )
    return routed, swiglu(lp["moe/s_gate"], lp["moe/s_up"], lp["moe/s_down"])


@functools.partial(jax.jit, static_argnames=("dims", "arith"))
def _layer(lp: dict, x, positions, *, dims: Dims, arith: str):
    """One layer on x (S, d): ``lp`` holds the layer's parameters under
    their names without the ``blk<i>/`` prefix; a layer with ``mlp/up``
    is a dense one. Compiled once for each kind of layer."""
    r = rounder(arith)
    f32 = jnp.float32
    hq, dn, dv, rkv, eps = (dims.heads, dims.d_nope, dims.d_v, dims.r_kv,
                            dims.eps)
    s = x.shape[0]

    def mm(a, b):
        return jnp.matmul(r(a), r(b.astype(f32)), precision=HI)

    h = _rms(x, lp["ln1/scale"].astype(f32), eps)
    cq = _rms(mm(h, lp["attn/q_a"]), lp["attn/q_a_norm"].astype(f32), eps)
    q = jnp.moveaxis(mm(cq, lp["attn/q_b"]).reshape(s, hq, -1), 1, 0)
    kv = mm(h, lp["attn/kv_a"])
    c = _rms(kv[:, :rkv], lp["attn/kv_a_norm"].astype(f32), eps)
    k_pe = _rope(kv[:, rkv:], positions, dims)                  # (S, d_rope)
    kvb = jnp.moveaxis(mm(c, lp["attn/kv_b"]).reshape(s, hq, dn + dv), 1, 0)
    scale = softmax_scale(dims)
    see = positions[None, :] <= positions[:, None]

    def head(args):
        qh, kvh = args                          # (S, d_nope + d_rope), (S, d_nope + d_v)
        scores = scale * (
            jnp.matmul(r(qh[:, :dn]), r(kvh[:, :dn]).T, precision=HI)
            + jnp.matmul(
                r(_rope(qh[:, dn:], positions, dims)), r(k_pe).T,
                precision=HI,
            )
        )
        w = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
        return jnp.matmul(r(w), r(kvh[:, dn:]), precision=HI)

    a = jnp.moveaxis(jax.lax.map(head, (q, kvb)), 0, 1).reshape(s, hq * dv)
    x = x + mm(a, lp["attn/out"])

    h = _rms(x, lp["ln2/scale"].astype(f32), eps)
    if "mlp/up" in lp:
        return x + mm(
            jax.nn.silu(mm(h, lp["mlp/gate"])) * mm(h, lp["mlp/up"]),
            lp["mlp/down"],
        )
    routed, shared = expert_parts(lp, h, dims, r)
    return x + routed + shared


def forward(params: dict, tokens, cfg: dict, arith: str = "float32"):
    """tokens (S,) int32 -> logits (S, vocab), row t scoring the token
    at t + 1."""
    f32 = jnp.float32
    r = rounder(arith)
    dims = Dims.of(cfg)
    positions = jnp.arange(tokens.shape[0])
    with jax.default_matmul_precision("highest"):
        x = params["embed/tok"][tokens].astype(f32)
        for i in range(cfg["num_hidden_layers"]):
            pre = f"blk{i}/"
            lp = {k[len(pre):]: v for k, v in params.items()
                  if k.startswith(pre)}
            x = _layer(lp, x, positions, dims=dims, arith=arith)
        x = _rms(x, params["ln_f/scale"].astype(f32), cfg["rms_norm_eps"])
        return jnp.matmul(
            r(x), r(params["head/out"].astype(f32)), precision=HI
        )
