"""Analytic model-FLOPs accounting for MFU reporting.

The reference has no FLOPs accounting at all — its perf surface is the
per-phase millisecond timers (include/worker/worker.h:91-114). Matching
"fast vs yesterday" is not "fast vs the chip", so bench.py pairs those
timers with an analytic FLOPs walk over the built Net and reports
model-FLOPs utilization (MFU) against the device's peak.

Conventions (the standard MFU accounting, e.g. the PaLM appendix):
only matmul-class FLOPs are counted (convs, dense/inner-product layers,
attention projections and score/value matmuls); elementwise ops,
normalizations, pooling, and softmax are omitted. A multiply-add is 2
FLOPs. The backward pass is 2x the forward (one matmul each for the
input grad and the weight grad), so one train step costs 3x the forward
walk. Causal attention scores count at half density — the flash kernel
(ops/attention.py) really does skip the upper-triangle blocks.
"""

from __future__ import annotations

import math


def layer_fwd_flops(layer, src_shapes: list[tuple]) -> float:
    """Matmul FLOPs of one layer's forward pass for a full batch."""
    t = layer.TYPE
    out = layer.out_shape
    if t == "kConvolution":
        b, f, h, w = out
        # setup() resolved the channel count (3-D sources are implicit
        # single-channel, layers/neuron.py) — don't re-derive from shape
        c = layer.channels
        return 2.0 * b * f * h * w * c * layer.kernel * layer.kernel
    if t in ("kInnerProduct", "kRBM"):
        b = src_shapes[0][0]
        fan_in = math.prod(src_shapes[0][1:])
        return 2.0 * b * fan_in * out[-1]
    if t == "kDense":
        d = src_shapes[0][-1]
        return 2.0 * math.prod(out[:-1]) * d * out[-1]
    if t == "kAttention":
        b, s, d = src_shapes[0]
        proj = 8.0 * b * s * d * d  # qkv (6bsd^2) + out (2bsd^2)
        scores = 4.0 * b * s * s * d  # QK^T + PV
        return proj + scores / 2.0  # causal: half the blocks run
    if t == "kMoE":
        # per token: router (negligible) + ONE routed expert's 2-layer FFN
        b, s, d = src_shapes[0]
        d_ff = getattr(layer, "d_ff", d)
        return 2.0 * b * s * (d * d_ff + d_ff * d)
    return 0.0


def net_fwd_flops(net) -> tuple[float, dict[str, float]]:
    """-> (total forward matmul FLOPs per batch, per-layer breakdown)."""
    per: dict[str, float] = {}
    for layer in net.layers:
        srcs = [net.name2layer[s].out_shape for s in layer.srclayers]
        f = layer_fwd_flops(layer, srcs)
        if f:
            per[layer.name] = f
    return sum(per.values()), per


def train_step_flops(net) -> float:
    """Model FLOPs of one forward+backward train step (3x forward)."""
    total, _ = net_fwd_flops(net)
    return 3.0 * total


def cd_step_flops(net) -> float:
    """Model FLOPs of one greedy-layerwise CD-k train step (CDTrainer).

    The 3x-forward backprop convention does not apply: CD has no
    backward pass. Per RBM, one step runs the positive-phase up-prop
    (2bvh), cd_k Gibbs iterations (down + up, 4bvh each), and the two
    gradient outer products v0^T h0 and vk^T hk (2bvh each) — all
    matmul-class, everything else (sigmoids, Bernoulli draws, bias
    grads) omitted per the MFU convention above. Non-RBM layers in the
    chain (parsers) contribute their forward cost once."""
    total = 0.0
    for layer in net.layers:
        srcs = [net.name2layer[s].out_shape for s in layer.srclayers]
        if layer.TYPE != "kRBM":
            total += layer_fwd_flops(layer, srcs)
            continue
        b = srcs[0][0]
        v = math.prod(srcs[0][1:])
        h = layer.hdim
        bvh = 2.0 * b * v * h
        total += bvh * (1 + 2 * layer.cd_k + 2)
    return total


#: bf16 matmul peak per chip, by device_kind substring (first match wins).
#: Sources: public TPU system specs (cloud.google.com/tpu/docs/system-*).
_PEAKS = (
    ("v5 lite", 197e12),  # v5e
    ("v5e", 197e12),
    ("v6 lite", 918e12),  # v6e / Trillium
    ("v6e", 918e12),
    ("v5p", 459e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
)


def device_peak_flops(device=None) -> float | None:
    """bf16 peak FLOP/s of one chip; None on the CPU platform (no
    utilization is ever quoted against it). An accelerator whose
    ``device_kind`` is not in the table is an error, not a default: a
    utilization against a guessed peak is worse than none."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = device.device_kind.lower()
    for key, peak in _PEAKS:
        if key in kind:
            return peak
    raise ValueError(
        f"no bf16 peak on record for {device.platform} device_kind "
        f"{device.device_kind!r}: add it to utils/flops._PEAKS with its "
        "source before quoting a utilization"
    )
