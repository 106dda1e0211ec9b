"""ResNet (He et al., arXiv:1512.03385) as a layer list: bottleneck
blocks, stride 2 at each stage's entry in the first 1x1 convolution (the
paper's placement, as the shipped conf has it), type-B
projection shortcuts — the net of ``examples/imagenet/resnet50.conf``,
written out again here so that the yardstick does not move with the
program's generator (tests/benchmark pins the two against each other).
"""

from __future__ import annotations

import math


def _conv(name, src, cin, filters, kernel, stride, pad):
    fan_in = cin * kernel * kernel
    return {
        "name": name, "type": "kConvolution", "src": [src],
        "num_filters": filters, "kernel": kernel, "stride": stride,
        "pad": pad, "bias_term": False, "channels": cin,
        "params": {"weight": {
            "shape": [filters, fan_in], "init": "normal",
            "std": math.sqrt(2.0 / fan_in),
        }},
    }


def _bn(name, src, c, cfg):
    return {
        "name": name, "type": "kBatchNorm", "src": [src],
        "momentum": cfg["bn_momentum"], "eps": cfg["bn_eps"],
        "params": {
            "gamma": {"shape": [c], "init": "constant", "value": 1.0},
            "beta": {"shape": [c], "init": "constant", "value": 0.0},
        },
    }


def _relu(name, src):
    return {"name": name, "type": "kReLU", "src": [src]}


def build(cfg: dict, traffic: dict, shard: str) -> list[dict]:
    """The layer list of ``cfg`` (a file of ``benchmark/configs``) fed
    ``traffic["batch"]`` records a step from the shard at ``shard``."""
    layers = [
        {"name": "data", "type": "kShardData", "path": shard,
         "batchsize": traffic["batch"], "random_skip": 0},
        {"name": "rgb", "type": "kRGBImage", "src": ["data"],
         "cropsize": cfg["crop"], "mirror": cfg["mirror"],
         "scale": cfg["pixel_scale"]},
        {"name": "label", "type": "kLabel", "src": ["data"]},
        _conv("conv1", "rgb", 3, cfg["stem_width"], 7, 2, 3),
        _bn("bn1", "conv1", cfg["stem_width"], cfg),
        _relu("relu1", "bn1"),
        {"name": "pool1", "type": "kPooling", "src": ["relu1"],
         "pool": "MAX", "kernel": 3, "stride": 2},
    ]
    src, cin = "pool1", cfg["stem_width"]
    for s, (nblocks, width) in enumerate(
        zip(cfg["blocks"], cfg["widths"]), start=1
    ):
        cout = width * cfg["expansion"]
        for b in range(1, nblocks + 1):
            p = f"s{s}b{b}"
            stride = 2 if (b == 1 and s > 1) else 1
            layers += [
                _conv(f"{p}_a_conv", src, cin, width, 1, stride, 0),
                _bn(f"{p}_a_bn", f"{p}_a_conv", width, cfg),
                _relu(f"{p}_a_relu", f"{p}_a_bn"),
                _conv(f"{p}_b_conv", f"{p}_a_relu", width, width, 3, 1, 1),
                _bn(f"{p}_b_bn", f"{p}_b_conv", width, cfg),
                _relu(f"{p}_b_relu", f"{p}_b_bn"),
                _conv(f"{p}_c_conv", f"{p}_b_relu", width, cout, 1, 1, 0),
                _bn(f"{p}_c_bn", f"{p}_c_conv", cout, cfg),
            ]
            short = src
            if b == 1:
                layers += [
                    _conv(f"{p}_proj_conv", src, cin, cout, 1, stride, 0),
                    _bn(f"{p}_proj_bn", f"{p}_proj_conv", cout, cfg),
                ]
                short = f"{p}_proj_bn"
            layers += [
                {"name": f"{p}_add", "type": "kAdd",
                 "src": [f"{p}_c_bn", short]},
                _relu(f"{p}_out", f"{p}_add"),
            ]
            src, cin = f"{p}_out", cout
    layers += [
        {"name": "gap", "type": "kGlobalPooling", "src": [src]},
        {"name": "fc", "type": "kInnerProduct", "src": ["gap"],
         "num_output": cfg["classes"],
         "params": {
             "weight": {"shape": [cin, cfg["classes"]], "init": "normal",
                        "std": 0.01},
             "bias": {"shape": [cfg["classes"]], "init": "constant",
                      "value": 0.0},
         }},
        {"name": "loss", "type": "kSoftmaxLoss", "src": ["fc", "label"],
         "topk": 1},
    ]
    # groups the reference recomputes as one (it saves only what
    # crosses a group's edge): the stem, each block, the classifier
    for layer in layers:
        stem = layer["name"] in ("conv1", "bn1", "relu1", "pool1")
        tail = layer["name"] in ("gap", "fc", "loss")
        layer["group"] = (
            "stem" if stem else "tail" if tail
            else layer["name"].split("_")[0]
        )
    return layers
