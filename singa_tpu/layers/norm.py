"""Normalization + residual layers (singa-tpu extensions).

The reference predates batch normalization and residual networks (its
layer registry tops out at LRN, src/worker/neuralnet.cc:13-33); these
layers extend the same config surface so ImageNet ResNet-50 is
expressible as a plain job file.

kBatchNorm's running statistics are the framework's first *buffers*:
non-trainable state updated by the layer inside the jitted step and
carried between steps by the trainer (layers/base.py BufferSpec). Under a
data-sharded batch, GSPMD turns the batch-mean reductions into cross-chip
psums automatically — i.e. sync BatchNorm over the whole global batch,
with no BN-specific communication code.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from ..config.schema import ConfigError
from .base import Layer, Shape, require_one_src


class BatchNormLayer(Layer):
    """kBatchNorm: per-channel batch normalization (NCHW axis 1, or the
    feature axis of 2-D inputs).

    Training normalizes by batch statistics and folds them into running
    stats with Caffe's momentum convention
    (running = momentum * running + (1 - momentum) * batch);
    eval normalizes by the running stats.
    """

    TYPE = "kBatchNorm"

    def setup(self, src_shapes: Sequence[Shape], batchsize: int) -> Shape:
        p = self.cfg.batchnorm_param
        self.momentum = p.momentum if p else 0.9
        self.eps = p.eps if p else 1e-5
        self.stats_stride = p.stats_sample_stride if p else 1
        if self.stats_stride < 1:
            raise ConfigError(
                f"layer {self.name!r}: stats_sample_stride must be >= 1"
            )
        # leave at least 8 sample rows in the stats subsample: a stride
        # that reduces stats to 1-2 rows drives per-channel variance
        # toward 0 and inv toward rsqrt(eps) ~ 316 — silent divergence,
        # not a perf knob
        if self.stats_stride > 1 and batchsize // self.stats_stride < 8:
            raise ConfigError(
                f"layer {self.name!r}: stats_sample_stride "
                f"{self.stats_stride} leaves "
                f"{max(batchsize // self.stats_stride, 0)} of {batchsize} "
                "sample rows for the batch moments (need >= 8)"
            )
        src = require_one_src(self, src_shapes)
        if len(src) not in (2, 4):
            raise ConfigError(
                f"layer {self.name!r}: kBatchNorm needs (N,C,H,W) or (N,F) "
                f"input, got {src}"
            )
        c = src[1]
        self.gname = self._declare_param(
            0, "gamma", (c,), neuron_axis=0
        )
        self.bname = self._declare_param(1, "beta", (c,), neuron_axis=0)
        self.mean_buf = self._declare_buffer("running_mean", (c,), 0.0)
        self.var_buf = self._declare_buffer("running_var", (c,), 1.0)
        return src

    def apply_stateful(self, params, buffers, inputs, *, training, rng=None):
        from .. import ops

        x = inputs[0]
        if training:
            # running mean anchors the one-pass moments: a free
            # independent input (an anchor computed from x costs
            # ~2.5ms/step on ResNet-50 — ops/norm.py docstring)
            anchor = jax.lax.stop_gradient(buffers[self.mean_buf])
            if self.stats_stride > 1:
                # OPT-IN subsample-stats + straight-through backward
                # (different math; ops/norm.py batch_norm_train_sampled)
                y, mean, var = ops.batch_norm_train_sampled(
                    x,
                    params[self.gname],
                    params[self.bname],
                    self.eps,
                    self.stats_stride,
                    shift=anchor,
                )
            else:
                # fused one-pass BN (ops/norm.py custom VJP — stats in
                # fp32, minimal HBM traffic)
                y, mean, var = ops.batch_norm_train(
                    x,
                    params[self.gname],
                    params[self.bname],
                    self.eps,
                    shift=anchor,
                )
            # running stats are a detached side effect
            mean = jax.lax.stop_gradient(mean)
            var = jax.lax.stop_gradient(var)
            m = self.momentum
            updates = {
                self.mean_buf: m * buffers[self.mean_buf] + (1 - m) * mean,
                self.var_buf: m * buffers[self.var_buf] + (1 - m) * var,
            }
            return y, updates
        y = ops.batch_norm_infer(
            x,
            params[self.gname],
            params[self.bname],
            buffers[self.mean_buf],
            buffers[self.var_buf],
            self.eps,
        )
        return y, {}

    def apply(self, params, inputs, *, training, rng=None):
        raise RuntimeError(
            f"layer {self.name!r}: kBatchNorm is stateful; the net must "
            "call apply_stateful (buffers plumbing)"
        )


class AddLayer(Layer):
    """kAdd: elementwise sum of all srclayers — the residual connection.
    Shapes must match exactly (use a projection conv on the shortcut when
    they don't, like standard ResNet type-B shortcuts)."""

    TYPE = "kAdd"
    decode_positionwise = True  # elementwise: serving decode reuses apply

    def setup(self, src_shapes: Sequence[Shape], batchsize: int) -> Shape:
        if len(src_shapes) < 2:
            raise ConfigError(
                f"layer {self.name!r}: kAdd needs >= 2 srclayers"
            )
        first = src_shapes[0]
        for s in src_shapes[1:]:
            if tuple(s) != tuple(first):
                raise ConfigError(
                    f"layer {self.name!r}: kAdd shape mismatch {first} vs {s}"
                )
        return first

    def apply(self, params, inputs, *, training, rng=None):
        out = inputs[0]
        for x in inputs[1:]:
            out = out + x
        return out


class GlobalPoolingLayer(Layer):
    """kGlobalPooling: mean (AVE, default) or max over the spatial dims of
    an NCHW input -> (N, C)."""

    TYPE = "kGlobalPooling"

    def setup(self, src_shapes: Sequence[Shape], batchsize: int) -> Shape:
        p = self.cfg.globalpooling_param
        self.pool = p.pool if p else "AVE"
        src = require_one_src(self, src_shapes)
        if len(src) != 4:
            raise ConfigError(
                f"layer {self.name!r}: kGlobalPooling needs NCHW input"
            )
        return (src[0], src[1])

    def apply(self, params, inputs, *, training, rng=None):
        x = inputs[0]
        if self.pool == "MAX":
            return jnp.max(x, axis=(2, 3))
        return jnp.mean(x, axis=(2, 3))
