"""GPT-2 (Radford et al. 2019) as a layer list for the conf trainer:
the block of ``examples/lm/tinylm_d128.conf`` at the widths of the
configuration file. What this repo's conf path departs from the
published model in (no bias on qkv/out, an untied head) is stated in
``benchmark/configs/gpt2_medium.json``; the reference walks this same
list, so it makes the same departures.
"""

from __future__ import annotations

import math


def _ln(name, src, d, eps):
    return {
        "name": name, "type": "kLayerNorm", "src": [src], "eps": eps,
        "params": {
            "scale": {"shape": [d], "init": "constant", "value": 1.0},
            "bias": {"shape": [d], "init": "constant", "value": 0.0},
        },
    }


def _dense(name, src, d_in, d_out, std, activation=None, bias=True):
    layer = {
        "name": name, "type": "kDense", "src": [src], "num_output": d_out,
        "bias_term": bias,
        "params": {"weight": {"shape": [d_in, d_out], "init": "normal",
                              "std": std}},
    }
    if activation:
        layer["activation"] = activation
    if bias:
        layer["params"]["bias"] = {
            "shape": [d_out], "init": "constant", "value": 0.0
        }
    return layer


def build(cfg: dict, traffic: dict, shard: str) -> list[dict]:
    d, f, n = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    std = cfg["initializer_range"]
    # GPT-2 scales the residual projections by 1/sqrt(2 * n_layer)
    res_std = std / math.sqrt(2.0 * n)
    layers = [
        {"name": "data", "type": "kSequenceData", "path": shard,
         "batchsize": traffic["batch"]},
        {"name": "embed", "type": "kEmbedding", "src": ["data"],
         "vocab_size": cfg["vocab_size"], "embedding_dim": d,
         "params": {
             "tok": {"shape": [cfg["vocab_size"], d], "init": "normal",
                     "std": std},
             "pos": {"shape": [traffic["seq_len"], d], "init": "normal",
                     "std": std},
         }},
    ]
    x = "embed"
    for i in range(n):
        p = f"b{i}"
        layers += [
            _ln(f"{p}_ln1", x, d, cfg["layer_norm_epsilon"]),
            {"name": f"{p}_attn", "type": "kAttention", "src": [f"{p}_ln1"],
             "num_heads": cfg["n_head"], "mode": cfg["train_attention_mode"],
             "params": {
                 "qkv": {"shape": [d, 3 * d], "init": "normal", "std": std},
                 "out": {"shape": [d, d], "init": "normal", "std": res_std},
             }},
            {"name": f"{p}_res1", "type": "kAdd", "src": [x, f"{p}_attn"]},
            _ln(f"{p}_ln2", f"{p}_res1", d, cfg["layer_norm_epsilon"]),
            _dense(f"{p}_up", f"{p}_ln2", d, f, std, activation="gelu"),
            _dense(f"{p}_down", f"{p}_up", f, d, res_std),
            {"name": f"{p}_res2", "type": "kAdd",
             "src": [f"{p}_res1", f"{p}_down"]},
        ]
        x = f"{p}_res2"
    layers += [
        _ln("ln_f", x, d, cfg["layer_norm_epsilon"]),
        _dense("head", "ln_f", d, cfg["vocab_size"], std, bias=False),
        {"name": "loss", "type": "kLMLoss", "src": ["head", "data"]},
    ]
    # groups the reference recomputes as one: each block, and the head
    # with its loss (the logits are the largest tensor of the step)
    for layer in layers:
        tail = layer["name"] in ("ln_f", "head", "loss")
        layer["group"] = "tail" if tail else layer["name"].split("_")[0]
    return layers
