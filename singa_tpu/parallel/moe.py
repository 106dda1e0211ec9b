"""Mixture-of-experts FFN with expert parallelism.

The reference has no MoE (pre-transformer); this extension completes the
framework's parallelism vocabulary (dp/tp/sp/ep). The design is
GShard/Switch-style top-1 routing with a capacity limit, executed the
TPU way: routing builds a dense dispatch tensor (no ragged scatter — the
MXU sees einsums), experts' weights shard over a mesh axis, and the
combine is one psum over that axis. Under shard_map each device:

  1. computes gating for its (possibly data-sharded) tokens,
  2. dispatches tokens into its LOCAL experts' (capacity, d) buffers,
  3. runs the local experts' FFN,
  4. un-dispatches and psums partial outputs across the expert axis.

Dropped tokens (over capacity) pass through on the residual path, like
Switch Transformer. Routing/combine math stays fp32 under bf16 compute.

Beside it, for serving, ``moe_topk_ffn``: top-k of a softmax or of
sigmoid scores over all experts, renormalised, SwiGLU experts, NO
capacity and no dropped token (the layers of the Qwen3-MoE and the
DeepSeek-V3 lineages: the latter adds a selection bias, a scaling factor
and a shared expert). It is one device's layer: it routes over every
expert and computes those it is told it holds, all of them by default.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import axis_pair_mesh

EXPERT_AXIS = "expert"


def build_ep_mesh(ndata: int = 1, nexpert: int = 1, devices=None) -> Mesh:
    """A (data, expert) mesh: batch shards over data, experts over expert."""
    return axis_pair_mesh(ndata, nexpert, EXPERT_AXIS, devices, "ep mesh")


def init_moe(
    rng: jax.Array, d_model: int, d_ff: int, n_experts: int
) -> dict:
    """Param pytree: gate (D, E), experts' up (E, D, F) / down (E, F, D)."""
    kg, ku, kd = jax.random.split(rng, 3)
    s = 1.0 / np.sqrt(d_model)
    return {
        "gate": s * jax.random.normal(kg, (d_model, n_experts)),
        "up": s * jax.random.normal(ku, (n_experts, d_model, d_ff)),
        "down": (1.0 / np.sqrt(d_ff))
        * jax.random.normal(kd, (n_experts, d_ff, d_model)),
    }


#: the top-k layer's parameters: router ``gate`` (D, E); per HELD expert
#: the SwiGLU gate and up projections ``w_gate`` and ``w_up`` (H, D, F)
#: and ``w_down`` (H, F, D), H = E unless the layer is told its share.
#: Expert-major, as the TPU compiler lays the three products out: stored
#: (D, E, F) it copies 0.4 GB a matrix into this order in every pass
#: (read off the compiled text, PERF.md PR 28)
MOE_TOPK_PARAMS = ("gate", "w_gate", "w_up", "w_down")
#: the router's selection bias (E,), where the model has one
MOE_BIAS_PARAM = "bias"
#: the shared expert's SwiGLU, (D, Fs), (D, Fs) and (Fs, D)
MOE_SHARED_PARAMS = ("s_gate", "s_up", "s_down")


def init_moe_topk(
    rng: jax.Array, d_model: int, d_ff: int, n_experts: int, *,
    held: int = 0, bias: bool = False, shared_d_ff: int = 0,
) -> dict:
    """Param pytree of ``moe_topk_ffn`` (names: ``MOE_TOPK_PARAMS``, and
    ``MOE_BIAS_PARAM`` / ``MOE_SHARED_PARAMS`` where asked for). The
    router is ``n_experts`` wide; ``held`` experts have weights here
    (0 = all)."""
    kr, kg, ku, kd = jax.random.split(rng, 4)
    s = 1.0 / np.sqrt(d_model)
    h = held or n_experts
    out = {
        "gate": s * jax.random.normal(kr, (d_model, n_experts)),
        "w_gate": s * jax.random.normal(kg, (h, d_model, d_ff)),
        "w_up": s * jax.random.normal(ku, (h, d_model, d_ff)),
        "w_down": (1.0 / np.sqrt(d_ff))
        * jax.random.normal(kd, (h, d_ff, d_model)),
    }
    if bias:
        out[MOE_BIAS_PARAM] = 0.1 * jax.random.normal(
            jax.random.fold_in(rng, 1), (n_experts,)
        )
    if shared_d_ff:
        k1, k2, k3 = jax.random.split(jax.random.fold_in(rng, 2), 3)
        out["s_gate"] = s * jax.random.normal(k1, (d_model, shared_d_ff))
        out["s_up"] = s * jax.random.normal(k2, (d_model, shared_d_ff))
        out["s_down"] = (1.0 / np.sqrt(shared_d_ff)) * jax.random.normal(
            k3, (shared_d_ff, d_model)
        )
    return out


def topk_gates(x2d, params: dict, top_k: int, score: str = "softmax",
               scale: float = 1.0):
    """The router of ``moe_topk_ffn``: x2d (N, D) -> (gates (N, E)
    float32, zero outside each token's top k; chosen (N, E) bool).

        s = softmax_f32(x Wr) or sigmoid_f32(x Wr), over ALL experts
        T = top-k(s + b)       b the selection bias, where there is one
        g_e = s_e / sum_{T} s * scale   for e in T, else 0

    The bias CHOOSES and does not weigh: the gates are made of ``s``
    alone. Sigmoid scores need not sum to anything, so their sum over T
    is kept off zero by 1e-20, as the lineage's modelling code does."""
    n, e = x2d.shape[0], params["gate"].shape[1]
    logits = jnp.matmul(
        x2d.astype(jnp.float32), params["gate"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)                  # (N, E)
    elif score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"moe score {score!r}: softmax or sigmoid")
    bias = params.get(MOE_BIAS_PARAM)
    if bias is None:
        top_s, top_e = jax.lax.top_k(scores, top_k)
    else:
        _, top_e = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    chosen = jnp.zeros((n, e), bool).at[
        jnp.arange(n)[:, None], top_e
    ].set(True)
    total = jnp.sum(top_s, axis=-1, keepdims=True)
    if score == "sigmoid":
        total = total + 1e-20
    gates = jnp.where(chosen, scores, 0.0) / total
    if scale != 1.0:
        gates = gates * scale
    return gates, chosen


def moe_topk_ffn(x: jnp.ndarray, params: dict, top_k: int, valid=None, *,
                 score: str = "softmax", scale: float = 1.0,
                 held_from: int = 0):
    """Drop-free top-k SwiGLU experts: x (B, S, D) -> (y (B, S, D),
    int32 [held experts hit, most tokens one held expert took,
    token-expert pairs routed to held experts]).

        g = topk_gates(x)          (softmax or sigmoid, bias, scale)
        y = sum_{e held} g_e * ((silu(x Wg_e) * (x Wu_e)) Wd_e)  +  S(x)

    THE SHARE: the router is as wide as the model's expert count E; the
    expert weights hold H <= E experts, ``[held_from, held_from + H)``
    of them. The gates are computed over all E and the sum runs over the
    experts held: what the others would have added is left out (their
    chips' part of an expert-parallel layer; no exchange stands in for
    it here). H = E, the default, is the whole layer. ``S`` is the
    shared expert (``MOE_SHARED_PARAMS``), computed here in full for
    every token, where the model has one.

    HOW: every held expert runs on every token and ``g`` (zero outside
    T) weights the sum — two products (N, D) x (H, D, F) batched over
    the experts and one contraction over (H, F) jointly, so the
    (N, H, D) per-expert outputs are never formed. There is no capacity,
    no sort and no dispatch
    buffer, so no routing pattern can drop a token or leave a term out,
    and a skewed router costs what a flat one does. It is the form for
    a serving pass of a few hundred tokens over many narrow experts:
    there every expert's weights are read whatever is done (256 tokens x
    top-8 over 128 experts leave no expert idle), and at N tokens the
    products cost N FLOPs a weight byte — at N = 256 about the v5e's
    own ratio of FLOPs to bytes (240), so the idle products ride on the
    weight reads: 1.80 ms a layer at the published size against 1.64 ms
    for reading the layer's experts and doing nothing (PERF.md, PR 28).
    At thousands of tokens a pass a sorted, grouped product is the form
    to write instead.

    Router, scores, gates and the SwiGLU are float32; the products take
    ``x`` and the weights as stored, accumulate in float32 and come out
    in ``x``'s type. ``valid``
    (B, S) marks the tokens the counters count (None = all)."""
    b, s, d = x.shape
    n = b * s
    x2d = x.reshape(n, d)
    held = params["w_gate"].shape[0]
    with jax.named_scope("route"):
        gates, chosen = topk_gates(x2d, params, top_k, score, scale)
        if held != chosen.shape[1]:
            gates = gates[:, held_from:held_from + held]
            chosen = chosen[:, held_from:held_from + held]
        counted = chosen if valid is None else (
            chosen & valid.reshape(n, 1)
        )
        load = jnp.sum(counted, axis=0, dtype=jnp.int32)          # (H,)
        stats = jnp.stack([
            jnp.sum(load > 0, dtype=jnp.int32), jnp.max(load), jnp.sum(load),
        ])
    with jax.named_scope("experts"):
        f32 = jnp.float32
        a = jnp.einsum("nd,edf->enf", x2d, params["w_gate"]).astype(f32)
        u = jnp.einsum("nd,edf->enf", x2d, params["w_up"]).astype(f32)
        h = (jax.nn.silu(a) * u * gates.T[:, :, None]).astype(x.dtype)
    with jax.named_scope("combine"):
        y = jnp.einsum("enf,efd->nd", h, params["w_down"])
    if "s_gate" in params:
        with jax.named_scope("shared"):
            a = jnp.matmul(x2d, params["s_gate"]).astype(f32)
            u = jnp.matmul(x2d, params["s_up"]).astype(f32)
            y = y + jnp.matmul(
                (jax.nn.silu(a) * u).astype(x.dtype), params["s_down"]
            )
    return y.reshape(b, s, d), stats


def _route(x2d: jnp.ndarray, gate_w: jnp.ndarray, capacity: int):
    """Top-1 routing -> (dispatch (N, E, C) one-hot, combine weights,
    aux load-balancing loss, per-expert routed fraction, per-expert mean
    prob). All fp32. frac/mean_prob are the aux's ingredients — the
    all-to-all formulation pmeans them across token shards before the
    (nonlinear) product so its aux equals the global-batch value."""
    logits = x2d.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (N, E)
    expert = jnp.argmax(probs, axis=-1)  # (N,)
    onehot = jax.nn.one_hot(expert, gate_w.shape[1], dtype=jnp.float32)
    # each token's position in its expert's queue (0-based)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1.0) * onehot, axis=-1)
    kept = pos < capacity  # over-capacity tokens drop to the residual
    slot = jax.nn.one_hot(
        pos.astype(jnp.int32), capacity, dtype=jnp.float32
    ) * kept[:, None]
    dispatch = onehot[:, :, None] * slot[:, None, :]  # (N, E, C)
    gate_val = jnp.sum(probs * onehot, axis=-1)  # (N,)
    combine = dispatch * gate_val[:, None, None]
    # Switch load-balancing aux: mean fraction-routed x mean prob per expert
    frac = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = gate_w.shape[1] * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux, frac, mean_prob


def moe_ffn_dense(x: jnp.ndarray, params: dict, capacity_factor: float = 1.25):
    """Single-device reference MoE: x (B, S, D) -> (y, aux_loss)."""
    b, s, d = x.shape
    n = b * s
    e = params["gate"].shape[1]
    capacity = max(1, int(capacity_factor * n / e))
    x2d = x.reshape(n, d)
    dispatch, combine, aux, _, _ = _route(x2d, params["gate"], capacity)
    expert_in = jnp.einsum(
        "nec,nd->ecd", dispatch, x2d.astype(jnp.float32)
    )
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, params["up"]))
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["down"])
    y = jnp.einsum("nec,ecd->nd", combine, expert_out)
    return y.reshape(b, s, d).astype(x.dtype), aux


def moe_ffn(
    x: jnp.ndarray,
    params: dict,
    mesh: Mesh,
    *,
    capacity_factor: float = 1.25,
    axis: str = EXPERT_AXIS,
):
    """Expert-parallel MoE over ``mesh``'s expert axis.

    x (B, S, D) with batch optionally sharded over "data"; expert weights
    (E, ...) sharded over ``axis``. Each shard routes its local tokens,
    computes only its local experts, and the combine psums partial
    outputs across the expert axis. With an unsharded batch (ndata == 1)
    this is numerically identical to moe_ffn_dense; under data sharding,
    capacity and queue order are per data shard, so over-capacity DROP
    decisions can differ from the global dense reference (outputs for
    kept tokens are identical either way).
    """
    nexp = mesh.shape[axis]
    if nexp == 1:
        return moe_ffn_dense(x, params, capacity_factor)
    data = "data" if "data" in mesh.shape else None

    def local(x, gate_w, up, down):
        b, s, d = x.shape
        n = b * s
        e_total = gate_w.shape[1]
        capacity = max(1, int(capacity_factor * n / e_total))
        x2d = x.reshape(n, d)
        dispatch, combine, aux, _, _ = _route(x2d, gate_w, capacity)
        # this shard owns experts [my*e_local, (my+1)*e_local)
        e_local = up.shape[0]
        my = jax.lax.axis_index(axis)
        lo = my * e_local
        dsp = jax.lax.dynamic_slice_in_dim(dispatch, lo, e_local, axis=1)
        cmb = jax.lax.dynamic_slice_in_dim(combine, lo, e_local, axis=1)
        expert_in = jnp.einsum("nec,nd->ecd", dsp, x2d.astype(jnp.float32))
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, up))
        expert_out = jnp.einsum("ecf,efd->ecd", h, down)
        y = jnp.einsum("nec,ecd->nd", cmb, expert_out)
        y = jax.lax.psum(y, axis)  # combine partial expert outputs
        # aux is identical on every expert shard (gating is replicated);
        # shape (1,) so the data axis can stack shards' values
        return y.reshape(b, s, d).astype(x.dtype), aux.reshape(1)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(data, None, None),      # x: batch over data, replicated on ep
            P(),                       # gate replicated
            P(axis, None, None),       # up sharded over experts
            P(axis, None, None),       # down sharded over experts
        ),
        out_specs=(P(data, None, None), P(data)),
    )
    y, aux = fn(x, params["gate"], params["up"], params["down"])
    return y, jnp.mean(aux)


def moe_ffn_a2a(
    x: jnp.ndarray,
    params: dict,
    mesh: Mesh,
    *,
    capacity_factor: float = 1.25,
    axis: str = EXPERT_AXIS,
):
    """Expert-parallel MoE with GShard-style all-to-all dispatch.

    Tokens shard over BOTH the data and expert axes (the expert axis
    doubles as extra data parallelism outside the MoE); each device
    routes only its n/(ndata*E_shards) local tokens, ships per-expert
    capacity buffers to the experts' owners with one all_to_all, runs
    its local experts, and a second all_to_all returns the outputs.

    **Comm volume per device**: 2 x cf * n_local * d — the two all_to_alls move only the
    capacity buffers. The psum formulation (moe_ffn) replicates every
    token over the expert axis, so each device routes/dispatches
    E-fold more tokens and the combine all-reduces a FULL (n, d)
    activation: ~2 * n * d comm per device plus E-fold redundant
    routing/dispatch compute. At E experts the all-to-all form does
    O(1/E) of both.

    **Semantics vs moe_ffn/moe_ffn_dense**: the capacity limit is per
    (source shard, expert) — cf * n_local / E slots — the standard
    GShard/Switch local-capacity semantics. Aggregate capacity matches
    the dense reference, and with ample capacity (no drops anywhere)
    outputs are exactly equal (pinned by tests/test_moe.py); when a
    local queue overflows, DROP decisions differ from the global dense
    queue. The aux loss is exactly the global-batch value in all cases
    (frac/mean_prob pmean across token shards before the product).
    moe_ffn (psum) remains the default for dense-equivalence; select
    this with moe_param.dispatch: "alltoall".
    """
    nexp = mesh.shape[axis]
    if nexp == 1:
        return moe_ffn_dense(x, params, capacity_factor)
    data = "data" if "data" in mesh.shape else None
    token_axes = (data, axis) if data else (axis,)

    def local(x, gate_w, up, down):
        b, s, d = x.shape
        n = b * s
        e_total = gate_w.shape[1]
        e_local = up.shape[0]
        cap = max(1, int(capacity_factor * n / e_total))
        x2d = x.reshape(n, d)
        dispatch, combine, _, frac, mean_prob = _route(x2d, gate_w, cap)
        # send buffers: slot-addressed tokens for EVERY expert
        send = jnp.einsum("nec,nd->ecd", dispatch, x2d.astype(jnp.float32))
        # all_to_all over the expert axis: chunk k of the leading
        # (E_total = E_shards * e_local) dim goes to shard k; received
        # rows [j*e_local + i] are source shard j's buffer for my
        # local expert i
        recv = jax.lax.all_to_all(
            send, axis, split_axis=0, concat_axis=0, tiled=True
        )
        nshards = e_total // e_local
        expert_in = (
            recv.reshape(nshards, e_local, cap, d)
            .transpose(1, 0, 2, 3)
            .reshape(e_local, nshards * cap, d)
        )
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, up))
        out = jnp.einsum("ecf,efd->ecd", h, down)
        # reverse exchange: outputs back to the tokens' source shards
        back = (
            out.reshape(e_local, nshards, cap, d)
            .transpose(1, 0, 2, 3)
            .reshape(e_total, cap, d)
        )
        ret = jax.lax.all_to_all(
            back, axis, split_axis=0, concat_axis=0, tiled=True
        )
        y = jnp.einsum("nec,ecd->nd", combine, ret)
        # aux: exact global-batch value (see _route docstring)
        frac_g = jax.lax.pmean(frac, token_axes)
        mp_g = jax.lax.pmean(mean_prob, token_axes)
        aux = e_total * jnp.sum(frac_g * mp_g)
        return y.reshape(b, s, d).astype(x.dtype), aux.reshape(1)

    token_spec = P(token_axes if data else axis, None, None)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            token_spec,                # x: batch over data AND expert
            P(),                       # gate replicated
            P(axis, None, None),       # up sharded over experts
            P(axis, None, None),       # down sharded over experts
        ),
        # aux is pmean'ed identical everywhere; expose one copy
        out_specs=(token_spec, P(None)),
    )
    y, aux = fn(x, params["gate"], params["up"], params["down"])
    return y, jnp.mean(aux)


def moe_param_shardings(mesh: Mesh, axis: str = EXPERT_AXIS) -> dict:
    """Placement for init_moe params on an ep mesh."""
    return {
        "gate": NamedSharding(mesh, P()),
        "up": NamedSharding(mesh, P(axis, None, None)),
        "down": NamedSharding(mesh, P(axis, None, None)),
    }
