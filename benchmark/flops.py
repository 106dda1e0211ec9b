"""Model FLOPs of a step, worked out from shapes: the yardstick's own
count, and since PR 30 the repo's only one: the arithmetic came from
``singa_tpu/utils/flops.py``, which that PR deleted, and lives here,
where a later change to the program cannot move it.

Conventions (the usual MFU accounting, e.g. the PaLM appendix): only
matrix-product FLOPs count — convolutions, dense and inner-product
layers, attention's projections and its score and value products; a
multiply-add is 2; elementwise work, normalisation, pooling and softmax
count nothing; the backward pass is twice the forward, so a training
step is 3x the forward walk; causal attention's scores count at half
density (the flash kernel skips the upper triangle). Recomputed
operations never count.
"""

from __future__ import annotations

import json
import math
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def _pooled(size: int, kernel: int, stride: int) -> int:
    return -((size - kernel) // -stride) + 1


def layer_shapes(layers: list[dict], traffic: dict,
                 record_shape: tuple) -> dict[str, tuple]:
    """Output shape of every layer, for a batch of ``traffic["batch"]``
    records of ``record_shape``."""
    b = traffic["batch"]
    shapes: dict[str, tuple] = {}
    for l in layers:
        t = l["type"]
        src = [shapes[s] for s in l.get("src", ())]
        if t in ("kShardData", "kSequenceData"):
            out = (b, *record_shape)
        elif t == "kRGBImage":
            out = (b, src[0][1], l["cropsize"], l["cropsize"])
        elif t == "kLabel":
            out = (b,)
        elif t == "kConvolution":
            _, _, h, w = src[0]
            k, s, p = l["kernel"], l["stride"], l["pad"]
            out = (b, l["num_filters"],
                   (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1)
        elif t == "kPooling":
            _, c, h, w = src[0]
            out = (b, c, _pooled(h, l["kernel"], l["stride"]),
                   _pooled(w, l["kernel"], l["stride"]))
        elif t == "kGlobalPooling":
            out = src[0][:2]
        elif t == "kInnerProduct":
            out = (b, l["num_output"])
        elif t == "kEmbedding":
            out = (*src[0], l["embedding_dim"])
        elif t == "kDense":
            out = (*src[0][:-1], l["num_output"])
        else:  # elementwise, normalisation, attention, losses
            out = src[0]
        shapes[l["name"]] = out
    return shapes


def layer_fwd_flops(layer: dict, src: list[tuple], out: tuple) -> float:
    """Matrix-product FLOPs of one layer's forward pass for a batch."""
    t = layer["type"]
    if t == "kConvolution":
        b, f, h, w = out
        return 2.0 * b * f * h * w * src[0][1] * layer["kernel"] ** 2
    if t == "kInnerProduct":
        return 2.0 * src[0][0] * math.prod(src[0][1:]) * out[-1]
    if t == "kDense":
        return 2.0 * math.prod(out[:-1]) * src[0][-1] * out[-1]
    if t == "kAttention":
        b, s, d = src[0]
        proj = 8.0 * b * s * d * d          # qkv (6bsd^2) + out (2bsd^2)
        scores = 4.0 * b * s * s * d        # QK^T + PV
        return proj + scores / 2.0          # causal: half the blocks run
    return 0.0


def net_fwd_flops(layers, traffic, record_shape) -> tuple[float, dict]:
    shapes = layer_shapes(layers, traffic, record_shape)
    per = {}
    for l in layers:
        f = layer_fwd_flops(
            l, [shapes[s] for s in l.get("src", ())], shapes[l["name"]]
        )
        if f:
            per[l["name"]] = f
    return sum(per.values()), per


def record_shape(layers, traffic) -> tuple:
    first = layers[0]
    if first["type"] == "kSequenceData":
        return (traffic["seq_len"],)
    rgb = next(l for l in layers if l["type"] == "kRGBImage")
    return (3, rgb["cropsize"], rgb["cropsize"])


def train_step_flops(layers, traffic) -> float:
    """Forward and backward of one training step: 3x the forward."""
    total, _ = net_fwd_flops(layers, traffic, record_shape(layers, traffic))
    return 3.0 * total


def lm_matmul_params(cfg: dict) -> int:
    """Parameters of a GPT-2 style LM that a token's forward multiplies
    by: per block qkv (3d^2), out (d^2), up and down (2 d f); and the
    head (d x vocab, tied or not). Embedding lookups are not products."""
    d, f = cfg["n_embd"], cfg["n_inner"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * f) + d * cfg["vocab_size"]


def lm_token_fwd_flops(cfg: dict, cached_positions: float) -> float:
    """Forward FLOPs of ONE token whose attention reads
    ``cached_positions`` positions: 2 x matmul parameters, plus the
    score and value products over the positions really cached
    (4 x d x positions a block)."""
    return (
        2.0 * lm_matmul_params(cfg)
        + 4.0 * cfg["n_layer"] * cfg["n_embd"] * cached_positions
    )


def peak_flops(device_kind: str) -> float:
    """bf16 peak FLOP/s of one chip from ``peaks.json``, keyed by a
    substring of ``device_kind``. A kind that is not in the table is an
    error, not a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    kind = device_kind.lower()
    for row in table["peaks"]:
        if row["match"] in kind:
            return float(row["bf16_flops"])
    raise ValueError(
        f"no peak on record for device_kind {device_kind!r}: add it to "
        "benchmark/peaks.json with its source"
    )


def mfu_percent(flops_per_s: float, chips: int, device_kind: str) -> float:
    return 100.0 * flops_per_s / (chips * peak_flops(device_kind))
