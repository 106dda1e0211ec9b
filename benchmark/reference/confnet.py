"""Plain reference for conf-trained nets: one walk over a layer list
(``benchmark/models/confnet.py``) in ``jax.numpy`` float32, its loss,
its gradients by ``jax.grad`` and momentum SGD written out. No kernels,
no scan, no device cache; it imports nothing of the program and is given
the benchmark's own weights and records.

``arith`` names the precision the matrix products and convolutions see
their operands in. ``float32`` is the reference; the lower ones are the
controls of "How correct is decided": the same walk with every operand
of every product rounded first, forward and backward (accumulation,
normalisation, the loss and the update stay float32).

    float32       operands as they are, products at ``highest``
    bfloat16      product operands rounded to bfloat16
    float8        product operands scaled per tensor to e4m3 and rounded
    bfloat16_all  as bfloat16, and every layer's output stored in it

``bfloat16_all`` is what "computed in that precision" means for a
trainer whose ``compute_dtype`` holds every activation and every
backward signal in the type, as the program's bfloat16 does: it is the
second witness for what that type's rounding alone does to a number.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
ARITHS = ("float32", "bfloat16", "float8", "bfloat16_all")


def rounder(arith: str):
    """-> r: ``r(x)`` is an operand as the products of ``arith`` see it
    (the gradient passes straight through the rounding). ``r.out(y)``
    marks a tensor the step keeps: its cotangent is rounded on the way
    back (the backward products take low-precision operands on both
    sides), and under an ``_all`` arith the tensor itself is rounded
    too, as a program that STORES its activations in that type does."""
    if arith == "float32":
        ident = lambda x: x  # noqa: E731
        ident.out = ident
        return ident
    if arith not in ARITHS:
        raise ValueError(f"arith {arith!r} not one of {ARITHS}")
    base, _, scope = arith.partition("_")
    if base == "bfloat16":
        def q(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        def q(x):
            amax = jnp.max(jnp.abs(x))
            scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
            return (x * scale).astype(jnp.float8_e4m3fn).astype(
                jnp.float32
            ) / scale

    def r(x):
        return x + lax.stop_gradient(q(x) - x)

    @jax.custom_vjp
    def out(y):
        return q(y) if scope else y

    out.defvjp(lambda y: (out(y), None), lambda _, g: (q(g),))
    r.out = out
    return r


def _ceil_pool_pad(size: int, kernel: int, stride: int) -> int:
    out = -((size - kernel) // -stride) + 1
    return (out - 1) * stride + kernel - size


def _layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale + bias


def gelu_tanh(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def causal_attention(q, k, v, r):
    """(B, H, S, D) each -> (B, H, S, D); dense scores, causal mask."""
    s = q.shape[2]
    scores = r.out(jnp.einsum(
        "bhqd,bhkd->bhqk", r(q), r(k), precision=HI
    )) / math.sqrt(q.shape[-1])
    mask = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    return r.out(jnp.einsum("bhqk,bhkd->bhqd", r(p), r(v), precision=HI))


def apply_layer(layer: dict, src: list, params: dict, batch: dict, r):
    """One layer's training-mode forward. ``src`` are its sources'
    outputs in order; a loss layer returns the scalar loss."""
    t, n = layer["type"], layer["name"]
    if t in ("kShardData", "kSequenceData"):
        return batch
    if t == "kRGBImage":
        x = src[0]["image"].astype(jnp.float32)
        if x.shape[-1] != layer["cropsize"]:
            raise ValueError(
                f"{n}: records of edge {x.shape[-1]} need a random crop to "
                f"{layer['cropsize']}, which no reference can follow"
            )
        if layer["mirror"]:
            raise ValueError(f"{n}: random mirror has no reference")
        return x * layer["scale"]
    if t == "kLabel":
        return src[0]["label"].astype(jnp.int32)
    if t == "kConvolution":
        k, c = layer["kernel"], layer["channels"]
        w = params[f"{n}/weight"].reshape(layer["num_filters"], c, k, k)
        pad = layer["pad"]
        return lax.conv_general_dilated(
            r(src[0]), r(w), (layer["stride"],) * 2, [(pad, pad)] * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HI,
        )
    if t == "kBatchNorm":
        x = src[0]
        mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
        var = jnp.mean((x - mean) ** 2, axis=(0, 2, 3), keepdims=True)
        g = params[f"{n}/gamma"].reshape(1, -1, 1, 1)
        b = params[f"{n}/beta"].reshape(1, -1, 1, 1)
        return (x - mean) * lax.rsqrt(var + layer["eps"]) * g + b
    if t == "kReLU":
        return jnp.maximum(src[0], 0.0)
    if t == "kPooling":
        x, k, s = src[0], layer["kernel"], layer["stride"]
        if layer["pool"] != "MAX":
            raise ValueError(f"{n}: only MAX pooling has a reference")
        return lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 1, k, k), (1, 1, s, s),
            [(0, 0), (0, 0),
             (0, _ceil_pool_pad(x.shape[2], k, s)),
             (0, _ceil_pool_pad(x.shape[3], k, s))],
        )
    if t == "kAdd":
        return sum(src[1:], src[0])
    if t == "kGlobalPooling":
        return jnp.mean(src[0], axis=(2, 3))
    if t == "kInnerProduct":
        x = src[0].reshape(src[0].shape[0], -1)
        return (
            jnp.matmul(r(x), r(params[f"{n}/weight"]), precision=HI)
            + params[f"{n}/bias"]
        )
    if t == "kSoftmaxLoss":
        logp = jax.nn.log_softmax(src[0], axis=-1)
        return -jnp.mean(
            jnp.take_along_axis(logp, src[1][:, None], axis=-1)
        )
    if t == "kEmbedding":
        tokens = src[0]["image"].astype(jnp.int32)
        return (
            params[f"{n}/tok"][tokens]
            + params[f"{n}/pos"][: tokens.shape[1]]
        )
    if t == "kLayerNorm":
        return _layernorm(
            src[0], params[f"{n}/scale"], params[f"{n}/bias"], layer["eps"]
        )
    if t == "kAttention":
        x = src[0]
        b, s, d = x.shape
        h = layer["num_heads"]
        qkv = r.out(jnp.matmul(r(x), r(params[f"{n}/qkv"]), precision=HI))
        qkv = qkv.reshape(b, s, 3, h, d // h)
        q, k, v = (jnp.moveaxis(qkv[:, :, j], 2, 1) for j in range(3))
        o = causal_attention(q, k, v, r)
        o = jnp.moveaxis(o, 1, 2).reshape(b, s, d)
        return jnp.matmul(r(o), r(params[f"{n}/out"]), precision=HI)
    if t == "kDense":
        y = jnp.matmul(r(src[0]), r(params[f"{n}/weight"]), precision=HI)
        if layer.get("bias_term", True):
            y = y + params[f"{n}/bias"]
        act = layer.get("activation")
        if act == "gelu":
            y = gelu_tanh(y)
        elif act:
            raise ValueError(f"{n}: activation {act!r} has no reference")
        return y
    if t == "kLMLoss":
        logits, tokens = src[0], src[1]["image"].astype(jnp.int32)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return -jnp.mean(ll)
    raise ValueError(f"layer {n!r}: type {t!r} has no reference")


def _groups(layers: list[dict]) -> list[list[dict]]:
    out: list[list[dict]] = []
    for layer in layers:
        g = layer.get("group", layer["name"])
        if out and out[-1][0].get("group", out[-1][0]["name"]) == g:
            out[-1].append(layer)
        else:
            out.append([layer])
    return out


def loss_fn(layers: list[dict], params: dict, batch: dict,
            arith: str = "float32"):
    """The training loss of one batch. Each group of layers is
    recomputed in the backward pass (``jax.checkpoint``), so only what
    crosses a group's edge is kept: that is what lets a float32 step at
    the cell's own batch fit beside nothing else on the chip."""
    r = rounder(arith)
    groups = _groups(layers)
    vals: dict = {}
    for gi, group in enumerate(groups):
        inside = {l["name"] for l in group}
        later = {
            s for g in groups[gi + 1:] for l in g for s in l.get("src", ())
        }
        need = sorted(
            {s for l in group for s in l.get("src", ())} - inside
        )
        keep = [l["name"] for l in group if l["name"] in later]
        own = {
            k: v for k, v in params.items() if k.split("/")[0] in inside
        }

        def run(own, ins, group=group, keep=keep):
            local = dict(ins)
            for l in group:
                y = apply_layer(
                    l, [local[s] for s in l.get("src", ())], own, batch, r
                )
                # every tensor a layer hands on is one the step keeps
                kept_tensor = not isinstance(y, dict) and jnp.issubdtype(
                    y.dtype, jnp.floating
                ) and y.ndim > 0
                local[l["name"]] = r.out(y) if kept_tensor else y
            last = group[-1]["name"]
            return {k: local[k] for k in keep}, local[last]

        kept, last = jax.checkpoint(run)(own, {k: vals[k] for k in need})
        vals = {k: v for k, v in vals.items() if k in later}
        vals.update(kept)
    return last


def learning_rate(up: dict, step: int) -> float:
    base, method = up["base_learning_rate"], up.get(
        "learning_rate_change_method", "kFixed"
    )
    if method == "kFixed":
        return base
    if method == "kStep":
        return base * up["gamma"] ** (
            step // up["learning_rate_change_frequency"]
        )
    if method == "kLinear":
        rr = step / up["learning_rate_change_frequency"]
        return (1.0 - rr) * base + rr * up["final_learning_rate"]
    raise ValueError(f"learning-rate schedule {method!r} has no reference")


def sgd_step(up: dict, lr, params, grads, history):
    """Momentum SGD with L2: h = m h + lr (g + wd p); p = p - h."""
    wd, m = up.get("weight_decay", 0.0), up.get("momentum", 0.0)
    new_h = {
        k: m * history[k] + lr * (grads[k] + wd * params[k]) for k in params
    }
    return {k: params[k] - new_h[k] for k in params}, new_h


def train_steps(layers, up: dict, params: dict, batches: list[dict],
                arith: str = "float32"):
    """Follow the trainer through ``len(batches)`` steps from
    ``params`` and zero momentum. -> (losses, history after step 0,
    params after step 0, params after the last step), all on the
    device."""
    if up.get("type", "kSGD") != "kSGD":
        raise ValueError(f"updater {up.get('type')!r} has no reference")

    @jax.jit
    def grad_fn(params, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(layers, p, batch, arith)
        )(params)

    update = jax.jit(lambda lr, p, g, h: sgd_step(up, lr, p, g, h))
    history = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, first_history, first_params = [], None, None
    for s, batch in enumerate(batches):
        loss, grads = grad_fn(params, batch)
        params, history = update(
            jnp.float32(learning_rate(up, s)), params, grads, history
        )
        losses.append(loss)
        if s == 0:
            first_history, first_params = history, params
    return losses, first_history, first_params, params
