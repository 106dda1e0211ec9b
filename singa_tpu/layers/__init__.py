"""Layer registry.

String-keyed factory mirroring the reference's Factory<Layer> +
NeuralNet::RegistryLayers 18 built-ins (src/worker/neuralnet.cc:13-33,
include/utils/factory.h:22-56). ``register_layer`` lets user code add types,
like the reference's factory Register calls.
"""

from __future__ import annotations

from ..config.schema import ConfigError, LayerConfig
from .base import Layer
from .connector import (
    BridgeDstLayer,
    BridgeSrcLayer,
    ConcateLayer,
    SliceLayer,
    SplitLayer,
)
from .data import (
    LabelLayer,
    LMDBDataLayer,
    MnistImageLayer,
    RGBImageLayer,
    ShardDataLayer,
)
from .loss import EuclideanLossLayer, SoftmaxLossLayer
from .norm import AddLayer, BatchNormLayer, GlobalPoolingLayer
from .rbm import RBMLayer
from .sequence import (
    AttentionLayer,
    DenseLayer,
    EmbeddingLayer,
    LayerNormLayer,
    LMLossLayer,
    MoELayer,
    SequenceDataLayer,
)
from .neuron import (
    ConvolutionLayer,
    DropoutLayer,
    InnerProductLayer,
    LRNLayer,
    PoolingLayer,
    ReLULayer,
    SigmoidLayer,
    TanhLayer,
)

_REGISTRY: dict[str, type[Layer]] = {}


def register_layer(cls: type[Layer]) -> type[Layer]:
    if not cls.TYPE:
        raise ValueError(f"{cls.__name__} has no TYPE")
    _REGISTRY[cls.TYPE] = cls
    return cls


def create_layer(cfg: LayerConfig, net_partition: str = "kNone") -> Layer:
    try:
        cls = _REGISTRY[cfg.type]
    except KeyError:
        raise ConfigError(
            f"unknown layer type {cfg.type!r} (registered: {sorted(_REGISTRY)})"
        ) from None
    return cls(cfg, net_partition)


def registered_types() -> list[str]:
    return sorted(_REGISTRY)


# the reference's 18 built-ins (neuralnet.cc:13-33) + extensions:
# kSigmoid, kRBM + kEuclideanLoss (the CD/autoencoder path),
# kBatchNorm/kAdd/kGlobalPooling (the ResNet vocabulary),
# kSequenceData/kEmbedding/kLayerNorm/kAttention/kDense/kLMLoss/kMoE (the
# transformer-LM vocabulary — long-context + MoE as config citizens)
for _cls in (
    RBMLayer,
    EuclideanLossLayer,
    AddLayer,
    BatchNormLayer,
    GlobalPoolingLayer,
    SequenceDataLayer,
    EmbeddingLayer,
    LayerNormLayer,
    AttentionLayer,
    DenseLayer,
    MoELayer,
    LMLossLayer,
    ConvolutionLayer,
    ConcateLayer,
    DropoutLayer,
    InnerProductLayer,
    RGBImageLayer,
    LabelLayer,
    LMDBDataLayer,
    LRNLayer,
    MnistImageLayer,
    BridgeDstLayer,
    BridgeSrcLayer,
    PoolingLayer,
    ReLULayer,
    ShardDataLayer,
    SliceLayer,
    SoftmaxLossLayer,
    SplitLayer,
    TanhLayer,
    SigmoidLayer,
):
    register_layer(_cls)

__all__ = [
    "Layer",
    "create_layer",
    "register_layer",
    "registered_types",
]
