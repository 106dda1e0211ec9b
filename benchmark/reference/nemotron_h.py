"""Plain reference for a decoder of the ``nemotron_h`` type (Mamba-2
layers, attention layers WITHOUT positions and latent expert layers, ONE
mixer a layer): the equations of its configuration file in
``jax.numpy``, float32 with products at ``highest`` — no cache, no state
carried between calls, no batching of slots, no kernel, NO CHUNKED SCAN:
the recurrence runs a position at a time, the convolution is its
four-tap sum, attention goes one head at a time under an explicit causal
mask, the experts one after another.

    x0 = Embed[tok]                                   no positional term
    per layer l of hybrid_override_pattern:  x = x + mixer_l(RMSNorm(x; w_l))
      M  [z | xBC | dt] = h W_in                      widths H P | H P + 2 G N | H
         xBC_t = silu(b + sum_k w_k xBC_{t-K+1+k})    per channel; zeros before the sequence
         x (H, P), B (G, N), C (G, N) = split(xBC_t)  head i reads group i // (H / G)
         dt_t = softplus(dt_t + dt_bias);  a_t = exp(dt_t A),  A = -exp(A_log)
         S_t = a_t S_{t-1} + dt_t x_t (x) B_t,  S_0 = 0;   y_t = S_t C_t + D x_t
         y = RMSNorm_grouped(y * silu(z); w_n)  over G groups;   out = y W_out
      *  q, k, v = split(h W_qkv)   Hq heads on Hkv of d_h;  head j reads K/V head j // (Hq / Hkv)
         a_j = softmax(q_j k^T / sqrt(d_h) + causal) v;   out = concat(a) W_o      NO rotary
      E  s = sigmoid(h W_r) over ALL n_router_outputs;  T = top-k(s + b)
         g_e = s_e / (sum_T s + 1e-20) * routed_scaling_factor
         v = h W_1;  f_e(v) = relu(v U_e)^2 D_e;   out = (sum_{e in T, e held} g_e f_e(v)) W_2
                                                         + relu(h U_s)^2 D_s
      -  out = relu(h U)^2 D
    logits = RMSNorm(x_L; wf) W_head                  row t scores the token at t + 1

THE SHARE. The file's ``n_routed_experts`` experts from
``experts_held_from`` on are held here; the router is
``n_router_outputs`` wide. T and g are computed over all of them and the
sum runs over T's held members: what the others would add is left out,
as in the program. ``W_2`` is linear, so the shares' routed parts add up
(``expert_parts`` returns the routed part and the shared expert apart,
so that a test can add them).

The parameter names are the program's (``init_lm`` for these fields).
``draw`` makes them from a seed: what ``benchmark/weights.py`` draws,
and on top of it the three the lineage initialises otherwise (``A_log``,
``dt_bias``, the convolution), all rounded to the stored type once.
They stay in memory as drawn and are upcast a matrix at a time.
``arith`` is as in ``reference/confnet.py``: below float32 it rounds the
operands of every product AND the recurrent state after every step (the
control). Imports nothing of ``singa_tpu/``.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.reference.confnet import HI, rounder

#: a letter of ``hybrid_override_pattern`` -> the layer's kind
KINDS = {"M": "mamba", "*": "attn", "E": "moe", "-": "mlp"}


def layer_kinds(cfg: dict) -> tuple:
    """The layers' kinds, one a letter of the pattern."""
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"hybrid_override_pattern {pattern!r} names {len(pattern)} "
            f"layers, num_hidden_layers {cfg['num_hidden_layers']}"
        )
    return tuple(KINDS[c] for c in pattern)


def specs(cfg: dict) -> dict[str, dict]:
    """The served model's parameters: names, shapes and how
    ``weights.make`` draws them (normal ``initializer_range``
    everywhere, the selection bias normal ``router_bias_std``, norms and
    ``D`` one; ``A_log``, ``dt_bias`` and ``conv_w`` standard normals
    that ``draw`` shapes)."""
    d = cfg["hidden_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, k = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    d_in, conv = h * p, h * p + 2 * g * n
    hq, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    held, width = cfg["n_routed_experts"], cfg["n_router_outputs"]
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"] * cfg["n_shared_experts"]
    std = cfg["initializer_range"]

    def normal(*shape, s=std):
        return {"shape": list(shape), "init": "normal", "std": s}

    def const(*shape, value=1.0):
        return {"shape": list(shape), "init": "constant", "value": value}

    out = {"embed/tok": normal(cfg["vocab_size"], d)}
    for i, kind in enumerate(layer_kinds(cfg)):
        p_ = f"blk{i}"
        out[f"{p_}/ln1/scale"] = const(d)
        if kind == "mamba":
            out[f"{p_}/mamba/in_proj"] = normal(d, d_in + conv + h)
            out[f"{p_}/mamba/conv_w"] = normal(k, conv, s=1.0)
            out[f"{p_}/mamba/conv_b"] = const(conv, value=0.0)
            out[f"{p_}/mamba/dt_bias"] = normal(h, s=1.0)
            out[f"{p_}/mamba/A_log"] = const(h, value=0.0)
            out[f"{p_}/mamba/D"] = const(h)
            out[f"{p_}/mamba/norm"] = const(d_in)
            out[f"{p_}/mamba/out_proj"] = normal(d_in, d)
        elif kind == "attn":
            out[f"{p_}/attn/qkv"] = normal(d, (hq + 2 * hkv) * dh)
            out[f"{p_}/attn/out"] = normal(hq * dh, d)
        elif kind == "moe":
            out[f"{p_}/moe/gate"] = normal(d, width)
            out[f"{p_}/moe/w_up"] = normal(held, lat, f)
            out[f"{p_}/moe/w_down"] = normal(held, f, lat)
            out[f"{p_}/moe/bias"] = normal(width, s=cfg["router_bias_std"])
            out[f"{p_}/moe/s_up"] = normal(d, fs)
            out[f"{p_}/moe/s_down"] = normal(fs, d)
            out[f"{p_}/moe/lat_down"] = normal(d, lat)
            out[f"{p_}/moe/lat_up"] = normal(lat, d)
        else:
            out[f"{p_}/mlp/up"] = normal(d, cfg["intermediate_size"])
            out[f"{p_}/mlp/down"] = normal(cfg["intermediate_size"], d)
    out["ln_f/scale"] = const(d)
    out["head/out"] = normal(d, cfg["vocab_size"])
    return out


def draw(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    """The seeded weights both sides read: ``weights.make`` of
    ``specs``, then per Mamba layer what the lineage initialises
    otherwise, each from its standard normal ``z`` (``u = Phi(z)`` is
    uniform): ``A_log = log(1..H)``; ``dt_bias`` the inverse softplus of
    ``dt = exp(u (ln time_step_max - ln time_step_min) + ln
    time_step_min)`` floored at ``time_step_floor``; the convolution's
    weights uniform in +-1/sqrt(K). Rounded to ``dtype`` once."""
    params = weights.make(specs(cfg), seed, dtype)
    lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
    k, h = cfg["conv_kernel"], cfg["mamba_num_heads"]

    @jax.jit
    def shape(z_dt, z_conv):
        f32 = jnp.float32
        u = jax.scipy.special.ndtr(z_dt.astype(f32))
        dt = jnp.maximum(jnp.exp(u * (hi - lo) + lo), cfg["time_step_floor"])
        conv = (2.0 * jax.scipy.special.ndtr(z_conv.astype(f32)) - 1.0)
        return (
            (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            (conv / math.sqrt(k)).astype(dtype),
            jnp.log(jnp.arange(1, h + 1, dtype=f32)).astype(dtype),
        )

    for i, kind in enumerate(layer_kinds(cfg)):
        if kind == "mamba":
            m = f"blk{i}/mamba/"
            (params[m + "dt_bias"], params[m + "conv_w"],
             params[m + "A_log"]) = shape(
                params[m + "dt_bias"], params[m + "conv_w"]
            )
    return params


class Dims(NamedTuple):
    """The numbers of a configuration that a layer's equations read."""

    eps: float
    m_heads: int
    m_head_dim: int
    state: int
    groups: int
    heads: int
    kv_heads: int
    head_dim: int
    top_k: int
    route_scale: float
    held_from: int

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
            raise ValueError("nemotron_h: one expert group")
        return cls(
            cfg["layer_norm_epsilon"], cfg["mamba_num_heads"],
            cfg["mamba_head_dim"], cfg["ssm_state_size"], cfg["n_groups"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["num_experts_per_tok"],
            float(cfg["routed_scaling_factor"]), cfg["experts_held_from"],
        )


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


def _relu2(h, up, down, mm):
    return mm(jnp.square(jax.nn.relu(mm(h, up))), down)


def expert_parts(lp: dict, h, dims: Dims, r):
    """The expert layer on h (S, d) -> (what the held routed experts
    give, back in the model's width; what the shared expert gives)."""
    f32 = jnp.float32

    def mm(a, b):
        return jnp.matmul(r(a), r(b.astype(f32)), precision=HI)

    s = jax.nn.sigmoid(
        jnp.matmul(h, lp["moe/gate"].astype(f32), precision=HI)
    )
    g = gates(s, lp["moe/bias"].astype(f32), dims.top_k, dims.route_scale)
    held = lp["moe/w_up"].shape[0]
    v = mm(h, lp["moe/lat_down"])

    def one(total, e):
        mine = jax.lax.dynamic_index_in_dim(g, dims.held_from + e, 1)
        return total + mine * _relu2(
            v, lp["moe/w_up"][e], lp["moe/w_down"][e], mm
        ), None

    total, _ = jax.lax.scan(one, jnp.zeros_like(v), jnp.arange(held))
    return mm(total, lp["moe/lat_up"]), _relu2(
        h, lp["moe/s_up"], lp["moe/s_down"], mm
    )


def mamba(lp: dict, h, dims: Dims, r):
    """The Mamba-2 mixer on h (S, d), the recurrence one position at a
    time from a zero state."""
    f32 = jnp.float32
    s = h.shape[0]
    hh, p, n, g = dims.m_heads, dims.m_head_dim, dims.state, dims.groups
    d_in = hh * p

    def mm(a, b):
        return jnp.matmul(r(a), r(b.astype(f32)), precision=HI)

    zxbcdt = mm(h, lp["mamba/in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [d_in, zxbcdt.shape[1] - hh], axis=1)
    w = lp["mamba/conv_w"].astype(f32)                     # (K, C)
    k = w.shape[0]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(lp["mamba/conv_b"].astype(f32) + sum(
        w[j] * padded[j:j + s] for j in range(k)
    ))
    x = xbc[:, :d_in].reshape(s, hh, p)
    b = xbc[:, d_in:d_in + g * n].reshape(s, g, n)
    c = xbc[:, d_in + g * n:].reshape(s, g, n)
    # head i reads group i // (H / G)
    b, c = (jnp.repeat(v, hh // g, axis=1) for v in (b, c))
    dt = jax.nn.softplus(dt + lp["mamba/dt_bias"].astype(f32))   # (S, H)
    a = jnp.exp(dt * -jnp.exp(lp["mamba/A_log"].astype(f32)))

    def step(state, t):
        x_t, b_t, c_t, dt_t, a_t = t
        state = a_t[:, None, None] * state + (
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        )
        state = r(state)
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((hh, p, n), f32), (x, b, c, dt, a))
    y = y + lp["mamba/D"].astype(f32)[None, :, None] * x
    y = y.reshape(s, d_in) * jax.nn.silu(z)
    y = _rms(
        y.reshape(s, g, d_in // g), 1.0, dims.eps
    ).reshape(s, d_in) * lp["mamba/norm"].astype(f32)
    return mm(y, lp["mamba/out_proj"])


def attention(lp: dict, h, dims: Dims, r):
    """Causal attention on h (S, d) with no positional term, one query
    head at a time."""
    f32 = jnp.float32
    s = h.shape[0]
    hq, hkv, dh = dims.heads, dims.kv_heads, dims.head_dim

    def mm(a, b):
        return jnp.matmul(r(a), r(b.astype(f32)), precision=HI)

    qkv = mm(h, lp["attn/qkv"])
    q, k, v = (
        jnp.moveaxis(part.reshape(s, -1, dh), 1, 0)
        for part in jnp.split(qkv, [hq * dh, (hq + hkv) * dh], axis=1)
    )
    see = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def head(args):
        qh, j = args
        kh = jax.lax.dynamic_index_in_dim(k, j // (hq // hkv), 0, False)
        vh = jax.lax.dynamic_index_in_dim(v, j // (hq // hkv), 0, False)
        scores = jnp.matmul(r(qh), r(kh).T, precision=HI) / math.sqrt(dh)
        wts = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
        return jnp.matmul(r(wts), r(vh), precision=HI)

    a = jax.lax.map(head, (q, jnp.arange(hq)))
    return mm(jnp.moveaxis(a, 0, 1).reshape(s, hq * dh), lp["attn/out"])


@functools.partial(jax.jit, static_argnames=("kind", "dims", "arith"))
def _layer(lp: dict, x, *, kind: str, dims: Dims, arith: str):
    """One layer on x (S, d): ``lp`` holds the layer's parameters under
    their names without the ``blk<i>/`` prefix. Compiled once a kind."""
    r = rounder(arith)
    f32 = jnp.float32
    h = _rms(x, lp["ln1/scale"].astype(f32), dims.eps)
    if kind == "mamba":
        return x + mamba(lp, h, dims, r)
    if kind == "attn":
        return x + attention(lp, h, dims, r)
    if kind == "moe":
        routed, shared = expert_parts(lp, h, dims, r)
        return x + routed + shared
    return x + _relu2(
        h, lp["mlp/up"], lp["mlp/down"],
        lambda a, b: jnp.matmul(r(a), r(b.astype(f32)), precision=HI),
    )


def forward(params: dict, tokens, cfg: dict, arith: str = "float32"):
    """tokens (S,) int32 -> logits (S, vocab), row t scoring the token
    at t + 1. A layer a compiled call, so that a long sequence at the
    published widths fits."""
    f32 = jnp.float32
    r = rounder(arith)
    dims = Dims.of(cfg)
    with jax.default_matmul_precision("highest"):
        x = params["embed/tok"][tokens].astype(f32)
        for i, kind in enumerate(layer_kinds(cfg)):
            pre = f"blk{i}/"
            lp = {k[len(pre):]: v for k, v in params.items()
                  if k.startswith(pre)}
            x = _layer(lp, x, kind=kind, dims=dims, arith=arith)
        x = _rms(x, params["ln_f/scale"].astype(f32),
                 cfg["layer_norm_epsilon"])
        return jnp.matmul(
            r(x), r(params["head/out"].astype(f32)), precision=HI
        )
