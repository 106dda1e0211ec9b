"""Plain reference for the served LM: one full causal forward of a
GPT-2 style decoder in ``jax.numpy`` float32 with products at
``highest`` — no cache, no pages, no batching of slots. Prefill and then
decoding through the program's paged cache must agree with it.

The parameter names are the ones ``benchmark/drivers/serve.py`` draws
(``lm_specs``): tied head, no biases on the projections or the MLP — the
departures of the program's code-API LM, stated in
``benchmark/configs/gpt2_medium.json``. ``arith`` is as in
``benchmark/reference/confnet.py``: the lower ones are the controls.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.reference.confnet import (
    HI, _layernorm, causal_attention, gelu_tanh, rounder,
)


def lm_specs(cfg: dict) -> dict[str, dict]:
    """The served LM's parameters: names, shapes and how they are drawn
    (GPT-2's: normal 0.02, residual projections scaled by
    1/sqrt(2 n_layer), LayerNorm at identity)."""
    d, f, n = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    std = cfg["initializer_range"]
    res = std / (2.0 * n) ** 0.5

    def normal(shape, s):
        return {"shape": list(shape), "init": "normal", "std": s}

    def const(shape, v):
        return {"shape": list(shape), "init": "constant", "value": v}

    specs = {
        "embed/tok": normal((cfg["vocab_size"], d), std),
        "embed/pos": normal((cfg["n_positions"], d), std),
    }
    for i in range(n):
        p = f"blk{i}"
        specs[f"{p}/ln1/scale"] = const((d,), 1.0)
        specs[f"{p}/ln1/bias"] = const((d,), 0.0)
        specs[f"{p}/attn/qkv"] = normal((d, 3 * d), std)
        specs[f"{p}/attn/out"] = normal((d, d), res)
        specs[f"{p}/ln2/scale"] = const((d,), 1.0)
        specs[f"{p}/ln2/bias"] = const((d,), 0.0)
        specs[f"{p}/mlp/up"] = normal((d, f), std)
        specs[f"{p}/mlp/down"] = normal((f, d), res)
    specs["ln_f/scale"] = const((d,), 1.0)
    specs["ln_f/bias"] = const((d,), 0.0)
    return specs


def forward(params: dict, tokens, cfg: dict, arith: str = "float32"):
    """tokens (S,) int32 -> logits (S, vocab): position t's row scores
    the token at t + 1."""
    r = rounder(arith)
    eps, h = cfg["layer_norm_epsilon"], cfg["n_head"]
    s = tokens.shape[0]
    x = (params["embed/tok"][tokens] + params["embed/pos"][:s])[None]
    d = x.shape[-1]
    for i in range(cfg["n_layer"]):
        p = f"blk{i}"
        y = _layernorm(x, params[f"{p}/ln1/scale"], params[f"{p}/ln1/bias"], eps)
        qkv = r.out(jnp.matmul(r(y), r(params[f"{p}/attn/qkv"]), precision=HI))
        qkv = qkv.reshape(1, s, 3, h, d // h)
        q, k, v = (jnp.moveaxis(qkv[:, :, j], 2, 1) for j in range(3))
        o = jnp.moveaxis(causal_attention(q, k, v, r), 1, 2).reshape(1, s, d)
        x = x + jnp.matmul(r(o), r(params[f"{p}/attn/out"]), precision=HI)
        y = _layernorm(x, params[f"{p}/ln2/scale"], params[f"{p}/ln2/bias"], eps)
        y = gelu_tanh(jnp.matmul(r(y), r(params[f"{p}/mlp/up"]), precision=HI))
        x = x + jnp.matmul(r(y), r(params[f"{p}/mlp/down"]), precision=HI)
    x = _layernorm(x, params["ln_f/scale"], params["ln_f/bias"], eps)
    return jnp.matmul(r(x), r(params["embed/tok"]).T, precision=HI)[0]
