"""Layer lists: the one description a conf-trained model has here.

A model generator (``resnet_conf``, ``gpt2_conf``) returns a list of
plain dicts, one per layer: ``name``, ``type`` (the program's layer
vocabulary), ``src`` (names of the layers it reads) and the layer's own
sizes. Two readers share it and nothing else: ``render`` writes the
text-proto job file the program is started from, and
``benchmark/reference/confnet.py`` walks the same list in plain
``jax.numpy``. ``init`` on a parameter says how ``benchmark/weights.py``
draws it from the seed; the job file carries constants as placeholders
because the benchmark installs its own weights.
"""

from __future__ import annotations


def _param_lines(layer: dict) -> str:
    return "".join(
        f'    param {{ name: "{p}" init_method: "kConstant" value: 0 }}\n'
        for p in layer.get("params", {})
    )


def _block(kind: str, fields: dict) -> str:
    body = " ".join(
        f"{k}: {_scalar(v)}" for k, v in fields.items() if v is not None
    )
    return f"    {kind} {{ {body} }}\n"


def _scalar(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, str):
        return f'"{v}"'
    return repr(v)


#: layer type -> (text-proto block name, keys copied into it)
_BLOCKS = {
    "kShardData": ("data_param", ("path", "batchsize", "random_skip")),
    "kSequenceData": ("data_param", ("path", "batchsize")),
    "kRGBImage": ("rgbimage_param", ("cropsize", "mirror", "scale")),
    "kConvolution": (
        "convolution_param",
        ("num_filters", "kernel", "stride", "pad", "bias_term"),
    ),
    "kBatchNorm": ("batchnorm_param", ("momentum", "eps")),
    "kPooling": ("pooling_param", ("pool", "kernel", "stride")),
    "kInnerProduct": ("inner_product_param", ("num_output",)),
    "kSoftmaxLoss": ("softmaxloss_param", ("topk",)),
    "kEmbedding": ("embedding_param", ("vocab_size", "embedding_dim")),
    "kLayerNorm": ("layernorm_param", ("eps",)),
    "kAttention": ("attention_param", ("num_heads", "mode")),
    "kDense": ("dense_param", ("num_output", "activation", "bias_term")),
}


def render_layer(layer: dict) -> str:
    srcs = " ".join(f'srclayers: "{s}"' for s in layer.get("src", ()))
    text = f'  layer {{ name: "{layer["name"]}" type: "{layer["type"]}" {srcs}\n'
    if layer["type"] in _BLOCKS:
        kind, keys = _BLOCKS[layer["type"]]
        fields = {k: layer[k] for k in keys if k in layer}
        if fields:
            text += _block(kind, fields)
    text += _param_lines(layer)
    return text + "  }\n"


def render(name: str, layers: list[dict], updater: dict, compute_dtype: str,
           tail: str = "") -> str:
    """The job file: the net, the updater and the compute dtype. ``tail``
    holds the cadences the driver sets (text-format scalars take their
    last occurrence)."""
    up = "\n".join(f"  {k}: {_scalar(v)}" for k, v in updater.items())
    net = "".join(render_layer(l) for l in layers)
    return (
        f'name: "{name}"\n'
        + (f'compute_dtype: "{compute_dtype}"\n' if compute_dtype else "")
        + f"updater {{\n{up}\n}}\nneuralnet {{\n{net}}}\n{tail}\n"
    )


def param_specs(layers: list[dict]) -> dict[str, dict]:
    """{"<layer>/<param>": {"shape", "init", ...}} in list order — the
    names the program gives its parameters."""
    out = {}
    for layer in layers:
        for pname, spec in layer.get("params", {}).items():
            out[f'{layer["name"]}/{pname}'] = spec
    return out
