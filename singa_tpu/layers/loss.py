"""Loss layers (reference: SoftmaxLossLayer, src/worker/layer.cc:704-764)."""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

from .. import ops
from ..config.schema import ConfigError
from .base import Layer, Shape, feature_dim


class SoftmaxLossLayer(Layer):
    """kSoftmaxLoss: softmax + cross-entropy + top-k precision.

    Takes two srclayers (logits, label). apply returns (loss, metrics); the
    graph accumulates the loss term and the trainer averages metrics like
    the reference's Performance class (worker.cc:350-386). Refuses
    kLayerPartition like the reference (layer.h:216-221).
    """

    TYPE = "kSoftmaxLoss"
    is_losslayer = True

    def setup(self, src_shapes: Sequence[Shape], batchsize: int) -> Shape:
        if len(src_shapes) != 2:
            raise ConfigError(
                f"layer {self.name!r}: kSoftmaxLoss needs (logits, label) "
                f"srclayers, got {len(src_shapes)}"
            )
        if self.cfg.partition_type == "kLayerPartition":
            raise ConfigError(
                f"layer {self.name!r}: kSoftmaxLoss cannot be layer-partitioned"
            )
        if self.partition_type == "kLayerPartition":
            # net-level kLayerPartition downgrades to kNone here, like the
            # reference forcing the loss layer out of the neuron split
            # (layer.h:216-221)
            self.partition_type = "kNone"
        p = self.cfg.softmaxloss_param
        self.topk = p.topk if p else 1
        self.scale = p.scale if p else 1.0
        return src_shapes[0]

    def apply(self, params, inputs, *, training, rng=None):
        logits, labels = inputs
        return ops.softmax_loss(
            logits, labels, topk=self.topk, scale=self.scale
        )


class EuclideanLossLayer(Layer):
    """kEuclideanLoss: 0.5 * mean squared reconstruction error.

    singa-tpu extension (no counterpart in this reference snapshot): the
    regression/autoencoder loss needed by the deep autoencoder
    (examples/mnist/autoencoder.conf), where the target srclayer is the input image itself.
    Takes (prediction, target) srclayers; both are flattened to
    (batch, -1). loss = 0.5/batch * sum((pred - target)^2).
    """

    TYPE = "kEuclideanLoss"
    is_losslayer = True

    def setup(self, src_shapes: Sequence[Shape], batchsize: int) -> Shape:
        if len(src_shapes) != 2:
            raise ConfigError(
                f"layer {self.name!r}: kEuclideanLoss needs (prediction, "
                f"target) srclayers, got {len(src_shapes)}"
            )
        pdim = feature_dim(src_shapes[0])
        tdim = feature_dim(src_shapes[1])
        if pdim != tdim:
            raise ConfigError(
                f"layer {self.name!r}: prediction size {pdim} != target "
                f"size {tdim}"
            )
        return src_shapes[0]

    def apply(self, params, inputs, *, training, rng=None):
        # accumulate the reduction in fp32 even under bf16 compute
        pred = inputs[0].reshape(inputs[0].shape[0], -1).astype(jnp.float32)
        target = inputs[1].reshape(inputs[1].shape[0], -1).astype(jnp.float32)
        loss = 0.5 * jnp.mean(jnp.sum(jnp.square(pred - target), axis=1))
        return loss, {"loss": loss}
