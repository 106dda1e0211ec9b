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
expert and computes those it is told it holds, all of them by default,
in one of two forms that ``choose_expert_form`` picks from the pass's
shapes: every held expert on every token, or the routed token-expert
pairs alone, sorted by expert, through a grouped product.
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
#: the projections into and out of the latent the routed experts live
#: in, (D, L) and (L, D), where the model has one: the experts' matrices
#: are then (H, L, F) and (H, F, L)
MOE_LATENT_PARAMS = ("lat_down", "lat_up")


def topk_param_names(act: str = "swiglu", bias: bool = False,
                     shared: bool = False, latent: bool = False) -> tuple:
    """The names of a top-k layer's parameters: a "relu2" expert
    (``relu(x U)^2 D``) has no gate matrix, routed or shared."""
    names = MOE_TOPK_PARAMS + ((MOE_BIAS_PARAM,) if bias else ()) + (
        MOE_SHARED_PARAMS if shared else ()
    ) + (MOE_LATENT_PARAMS if latent else ())
    if act == "relu2":
        names = tuple(n for n in names if n not in ("w_gate", "s_gate"))
    return names


def init_moe_topk(
    rng: jax.Array, d_model: int, d_ff: int, n_experts: int, *,
    held: int = 0, bias: bool = False, shared_d_ff: int = 0,
    act: str = "swiglu", latent: int = 0,
) -> dict:
    """Param pytree of ``moe_topk_ffn`` (``topk_param_names``). The
    router is ``n_experts`` wide; ``held`` experts have weights here
    (0 = all); with a ``latent`` the routed experts are that wide and
    the two projections stand beside them."""
    kr, kg, ku, kd = jax.random.split(rng, 4)
    s = 1.0 / np.sqrt(d_model)
    h = held or n_experts
    width = latent or d_model
    out = {
        "gate": s * jax.random.normal(kr, (d_model, n_experts)),
        "w_gate": (1.0 / np.sqrt(width))
        * jax.random.normal(kg, (h, width, d_ff)),
        "w_up": (1.0 / np.sqrt(width))
        * jax.random.normal(ku, (h, width, d_ff)),
        "w_down": (1.0 / np.sqrt(d_ff))
        * jax.random.normal(kd, (h, d_ff, width)),
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
    if latent:
        k1, k2 = jax.random.split(jax.random.fold_in(rng, 3))
        out["lat_down"] = s * jax.random.normal(k1, (d_model, latent))
        out["lat_up"] = (1.0 / np.sqrt(latent)) * jax.random.normal(
            k2, (latent, d_model)
        )
    names = topk_param_names(act, bias, bool(shared_d_ff), bool(latent))
    return {k: out[k] for k in names}


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


#: rows of the grouped product's row tile (megablox ``tm``): a held
#: expert with any pair in a window of pairs costs whole tiles of rows
PAIR_TILE = 128
#: tokens a pass up to which the dense product rides on the weight
#: reads of a v5e: N tokens do N FLOPs a byte of bfloat16 weights and
#: the chip has 240 FLOPs a byte; a quarter over that the grouped
#: form's sort, gather and combine are paid for (``moe_topk_ffn``, HOW)
DENSE_TOKENS = 300
#: tokens a pass beyond which no chip reading holds: the grouped form's
#: combine is a 0/1 matrix product, quadratic in the pass's tokens
GROUPED_TOKENS = 2048
#: share of the held experts a flat router must leave without a token
#: before skipping their weights pays for the grouped form's overhead
IDLE_SHARE = 0.25


def choose_expert_form(n: int, held: int, experts: int, top_k: int,
                       platform: str) -> str:
    """Which form ``moe_topk_ffn`` computes the held experts in, from
    what a trace can see: ``"dense: <why>"`` or ``"grouped: <why>"``
    for a pass of ``n`` tokens over ``held`` of ``experts`` experts,
    ``top_k`` a token, on ``platform``. A pure function (as
    ``serve/engine.py`` ``choose_attend`` is), so the engine can record
    what each of its programs compiled with and a CPU test can ask it
    about a TPU.

    Dense off a TPU (the grouped kernel would run through the Pallas
    interpreter, a grid step at a time). On one, grouped for either of
    the two things it saves. ARITHMETIC: the dense product is bound by
    it (``n`` over ``DENSE_TOKENS``) and the rows the grouped product
    computes an expert, its ``n * top_k / experts`` routed pairs under
    a flat router and a row tile's rounding, are under half of the
    ``n`` the dense one computes. WEIGHT READS: the dense product reads
    every held expert, the grouped one those that drew a token, and a
    flat router leaves ``(1 - top_k / experts) ** n`` of them without
    (``IDLE_SHARE`` or more). Dense everywhere else: there every
    expert's weights are read whatever is done and the idle products
    ride on the reads."""
    if platform != "tpu":
        return f"dense: platform = {platform}"
    routed = n * top_k / experts
    idle = (1.0 - top_k / experts) ** n
    if n > GROUPED_TOKENS:
        return f"dense: {n} tokens a pass, over {GROUPED_TOKENS}"
    if n > DENSE_TOKENS and 2 * (routed + PAIR_TILE) < n:
        return (
            f"grouped: {n} tokens a pass are over {DENSE_TOKENS}, "
            f"{routed:.1f} routed rows an expert for {n} dense "
            f"({n * top_k * held // experts} pairs for {n * held})"
        )
    if idle >= IDLE_SHARE:
        return (
            f"grouped: a flat router leaves {100 * idle:.0f} % of the "
            f"held experts without one of {n} tokens"
        )
    if n <= DENSE_TOKENS:
        return (
            f"dense: {n} tokens a pass ride on the weight reads "
            f"(<= {DENSE_TOKENS})"
        )
    return (
        f"dense: {routed:.1f} routed rows an expert and a tile of "
        f"{PAIR_TILE} are half of {n} or more"
    )


def _grouped_tiling(k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """(tm, tk, tn) of one grouped product (P, k) x (H, k, n): whole
    lanes, the widest n-tile up to 2048 and the deepest k-tile that
    keep a weight block at 4 MiB or under — two of them in flight, the
    row tiles and the float32 accumulator fit the kernel's default
    16 MiB of VMEM (12.3 MB at the published widths), and a block is
    long enough to stream at the HBM's rate."""
    def widest(size, limit):
        fits = [
            t for t in range(128, min(size, limit) + 1, 128)
            if size % t == 0
        ]
        return fits[-1] if fits else size

    tn = widest(n, 2048)
    tk = widest(k, max(128, (4 << 20) // (tn * itemsize)))
    return PAIR_TILE, tk, tn


def _expert_act(x, params, product, gate: str, up: str):
    """What an expert's first matrices make of its rows, float32:
    ``silu(x Wg) * (x Wu)`` where the layer has a gate matrix, else
    ``relu(x U)^2``. ``product(x, w)`` is the form's own."""
    f32 = jnp.float32
    if gate in params:
        a = product(x, params[gate]).astype(f32)
        u = product(x, params[up]).astype(f32)
        return jax.nn.silu(a) * u
    return jnp.square(jax.nn.relu(product(x, params[up]).astype(f32)))


def _experts_dense(x2d, params, gates):
    """Every held expert on every token, ``gates`` (N, H) weighting the
    sum: two products (one for experts without a gate matrix) batched
    over the experts and one contraction over (H, F) jointly, so no
    (N, H, D) per-expert output is formed."""
    with jax.named_scope("experts"):
        h = _expert_act(
            x2d, params, lambda x, w: jnp.einsum("nd,edf->enf", x, w),
            "w_gate", "w_up",
        )
        h = (h * gates.T[:, :, None]).astype(x2d.dtype)
    with jax.named_scope("combine"):
        return jnp.einsum("enf,efd->nd", h, params["w_down"])


def _experts_grouped(x2d, params, gates, pairs, top_k: int, start=None):
    """The token-expert ``pairs`` (N, H) alone: the list of pairs sorted
    by expert, walked a WINDOW of R = N (a whole number of row tiles)
    pairs at a time; a window gathers its pairs' rows of ``x``, runs
    the three grouped products (megablox ``gmm``: it visits the row
    tiles the group sizes cover and skips an expert with no pair) and
    adds each pair's output, times its gate, into its token's row in
    float32, on top of ``start`` (N, D) where there is one.
    ``min(top_k, H)`` windows hold the worst case (every token on its
    full count of held experts); a window past the last pair is skipped
    by a ``lax.cond``, so the work follows the router and nothing is
    dropped. A flat router fills a quarter of the first window at 12 of
    384 experts held."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    n, d = x2d.shape
    held = pairs.shape[1]
    f32 = jnp.float32
    interpret = jax.default_backend() != "tpu"
    rows = -(-n // PAIR_TILE) * PAIR_TILE
    windows = -(-n * min(top_k, held) // rows)

    def product(lhs, w, sizes):
        return megablox.gmm(
            lhs, w, sizes, f32,
            _grouped_tiling(w.shape[1], w.shape[2], w.dtype.itemsize),
            interpret=interpret,
        )

    with jax.named_scope("route"):
        flat = pairs.T.reshape(-1)                    # expert-major
        # the pairs first, by expert and then by token: what a stable
        # sort of "is no pair" leaves in front
        order = jnp.argsort(~flat, stable=True).astype(jnp.int32)
        order = jnp.pad(order, (0, max(0, rows * windows - n * held)))
        pair_gate = gates.T.reshape(-1)
        ends = jnp.cumsum(jnp.sum(pairs, axis=0, dtype=jnp.int32))
        starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
        total = ends[-1]

    def window(w, y):
        lo = w * rows

        def run(y):
            with jax.named_scope("route"):
                at = jax.lax.dynamic_slice(order, (lo,), (rows,))
                tok = at % n
                live = (lo + jnp.arange(rows) < total)[:, None]
                sizes = (
                    jnp.clip(ends - lo, 0, rows)
                    - jnp.clip(starts - lo, 0, rows)
                )
            with jax.named_scope("experts"):
                xs = jnp.where(live, x2d[tok], 0)
                h = _expert_act(
                    xs, params, lambda x, w: product(x, w, sizes),
                    "w_gate", "w_up",
                ).astype(x2d.dtype)
            with jax.named_scope("combine"):
                out = product(h, params["w_down"], sizes)
                # rows past the last pair are the kernel's to leave
                # unwritten, here and in its gradients: they are
                # selected out on both sides, never multiplied out
                out = jnp.where(live, out, 0.0) * pair_gate[at][:, None]
                # each row into its token's: a 0/1 matrix on the MXU in
                # three bfloat16 passes (0.04 ms at 512 x 512 x 7168
                # where a scatter-add of 512 rows reads 0.44: PERF.md,
                # PR 35)
                put = (jnp.arange(n)[:, None] == tok[None]).astype(f32)
                return y + jnp.matmul(
                    put, out, precision=jax.lax.Precision.HIGH
                )

        return jax.lax.cond(lo < total, run, lambda y: y, y)

    y = jnp.zeros((n, d), f32) if start is None else start
    return jax.lax.fori_loop(0, windows, window, y).astype(x2d.dtype)


def moe_topk_ffn(x: jnp.ndarray, params: dict, top_k: int, valid=None, *,
                 score: str = "softmax", scale: float = 1.0,
                 held_from: int = 0):
    """Drop-free top-k experts: x (B, S, D) -> (y (B, S, D),
    int32 [held experts hit, most tokens one held expert took,
    token-expert pairs routed to held experts]).

        g = topk_gates(x)          (softmax or sigmoid, bias, scale)
        y = sum_{e held} g_e * ((silu(x Wg_e) * (x Wu_e)) Wd_e)  +  S(x)

    An expert is a SwiGLU, or, where the layer's parameters hold no
    gate matrix (``topk_param_names`` of "relu2"), ``relu(x U_e)^2
    D_e``; the shared expert likewise. IN A LATENT
    (``MOE_LATENT_PARAMS``): the routed experts read ``x W_1`` (D -> L)
    and their weighted sum goes back through ``W_2`` (L -> D), while the
    router and the shared expert read the full width:

        y = (sum_{e held} g_e f_e(x W_1)) W_2  +  S(x)

    ``W_2`` is linear, so the shares' routed parts still add up.

    THE SHARE: the router is as wide as the model's expert count E; the
    expert weights hold H <= E experts, ``[held_from, held_from + H)``
    of them. The gates are computed over all E and the sum runs over the
    experts held: what the others would have added is left out (their
    chips' part of an expert-parallel layer; no exchange stands in for
    it here). H = E, the default, is the whole layer. ``S`` is the
    shared expert (``MOE_SHARED_PARAMS``), computed here in full for
    every token, where the model has one.

    HOW, in one of two forms that ``choose_expert_form`` picks at trace
    time from the pass's static shapes and the platform. DENSE
    (``_experts_dense``): every held expert runs on every token and
    ``g`` (zero outside T) weights the sum — two products
    (N, D) x (H, D, F) batched over the experts and one contraction
    over (H, F) jointly. No sort and no dispatch buffer, and a skewed
    router costs what a flat one does. It is the form for a serving
    pass of a few hundred tokens over many narrow experts: there every
    expert's weights are read whatever is done (256 tokens x top-8 over
    128 experts leave no expert idle), and at N tokens the products
    cost N FLOPs a weight byte — at N = 256 about the v5e's own ratio
    of FLOPs to bytes (240), so the idle products ride on the weight
    reads. GROUPED (``_experts_grouped``): the routed (token, held
    expert) pairs alone, sorted by expert — a gather of their rows of
    ``x``, three grouped products (P, D) x (H, D, F) / (P, F) x
    (H, F, D) through the Pallas grouped-matmul kernel that ships with
    jax (megablox ``gmm``, which walks the row tiles the group sizes
    cover and skips an expert with no pair), each pair's row times its
    gate summed into its token's row in float32. It is the form where
    the dense product is bound by arithmetic, and where a pass leaves
    many held experts without a token, whose weights it does not read.
    NEITHER has a capacity: the grouped form walks the sorted list a
    window of N pairs at a time for as many windows as the worst
    routing fills (``min(top_k, H)``: every token on its full count of
    held experts) and skips the empty ones, so no routing pattern can
    drop a token or leave a term out. Pairs of tokens that ``valid``
    marks out are not computed there (their rows of ``y`` hold the
    shared expert's output alone).

    The two chip readings the rule's margin rests on (one layer alone
    on a v5e, host clock, bfloat16; PERF.md, PR 35): 256 tokens over
    128 of 128 experts of 2048 x 768, dense 1.87 ms against grouped
    2.33 (and 1.64 for reading the layer's experts and doing nothing,
    PR 28): stays dense. 512 tokens over 12 of 384 experts of
    7168 x 2048, 128 pairs for 6,144 dense rows: dense 3.41 ms, at 92 %
    of the MXU's peak, against grouped 2.20, its three products 1.53
    for 1.44 of reading their weights: goes grouped. ``jax.lax.
    ragged_dot`` is compiled by the TPU compiler to a grouped kernel of
    its own whose row tile is min(P, 512) and cannot be set: 3.50 ms
    for the three products at P = 512, dense's 3.02 and more.

    Router, scores, gates and the SwiGLU are float32; the products take
    ``x`` and the weights as stored, accumulate in float32 and come out
    in ``x``'s type. ``valid``
    (B, S) marks the tokens the counters count (None = all). The
    products of both forms have derivatives (``gmm`` carries its own),
    so the layer trains in whichever form a pass takes."""
    b, s, d = x.shape
    n = b * s
    x2d = x.reshape(n, d)
    held = params["w_up"].shape[0]
    with jax.named_scope("route"):
        gates, chosen = topk_gates(x2d, params, top_k, score, scale)
        form = choose_expert_form(
            n, held, chosen.shape[1], top_k, jax.default_backend()
        )
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

    def shared(out_type=None):
        with jax.named_scope("shared"):
            h = _expert_act(x2d, params, jnp.matmul, "s_gate", "s_up")
            return jnp.matmul(
                h.astype(x.dtype), params["s_down"],
                preferred_element_type=out_type,
            )

    has_shared, latent = "s_up" in params, "lat_down" in params
    grouped = form.startswith("grouped")
    xe = x2d
    if latent:
        with jax.named_scope("latent_down"):
            xe = jnp.matmul(x2d, params["lat_down"])
    if grouped:
        # the windows' float32 sums start from the shared expert's,
        # where both are as wide as the model
        y = _experts_grouped(
            xe, params, gates, counted, top_k,
            shared(jnp.float32) if has_shared and not latent else None,
        )
    else:
        y = _experts_dense(xe, params, gates)
    if latent:
        with jax.named_scope("latent_up"):
            y = jnp.matmul(y, params["lat_up"])
    if has_shared and (latent or not grouped):
        y = y + shared()
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
