"""The Mamba-2 mixer: one recurrence in three forms that agree.

A state-space layer of the Mamba-2 kind (the ``nemotron_h`` lineage's
``M`` layers) reads a token's input ``u`` (d) and keeps, per sequence, a
state ``S`` (H heads x P x N) and the last ``K - 1`` inputs of a short
causal depthwise convolution:

    [z | xBC | dt] = u W_in          widths H P | H P + 2 G N | H
    xBC_t <- silu(b + sum_k w_k * xBC_{t-K+1+k})      per channel, zeros
                                     before the sequence
    x (H, P), B (G, N), C (G, N) = split(xBC_t)       head i reads group
                                     i // (H / G)
    dt_t = softplus(dt_t + dt_bias);   a_t = exp(dt_t * A),  A = -exp(A_log)
    S_t = a_t S_{t-1} + dt_t x_t (x) B_t;     y_t = S_t C_t + D x_t
    y <- RMSNorm_grouped(y * silu(z)) * scale         groups of H P / G
    out = y W_out

THE THREE FORMS (``ssd_chunked`` and ``ssd_step`` under ``mamba2_mixer``):

- a whole sequence from a zero state (``lm_apply``): the chunked form
  with nothing carried in;
- a prefill chunk that starts from a slot's state: the CHUNKED form
  (SSD): the positions in blocks of ``block`` (128), matrix products
  inside a block (``C B^T`` masked by the decays, times ``dt x``), each
  block's own contribution to the state as one more product, and the
  state carried from block to block by a short scan;
- a decode tick's ONE step a slot: the recurrence as it is written,
  elementwise over the state.

``choose_mamba_form`` says which a pass of ``s`` positions takes, from
its static shape alone. Positions that ``valid`` marks out (a chunk's
padding, a tick's dead lanes) take ``dt = 0``: they decay nothing and
add nothing, the state that comes back is selected from the one that
went in, and the convolution's tail is cut where the valid positions
end, so state and tail are exactly as they were.

Precision: the products take their operands as stored (the compute
type, bfloat16 on the chip) and accumulate in float32; ``z``, ``dt``,
its softplus, the decays, the cumulative sums, the state, every product
that reads the state, the gate and the norm are float32.

Beside it, ``short_conv``: the gated short convolution of the LFM2
lineage, whose only state is the convolution's tail, carried by the
same ``causal_conv`` with no bias and no silu.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: a Mamba-2 layer's parameters: ``in_proj`` (d, 2 H P + 2 G N + H),
#: ``conv_w`` (K, H P + 2 G N) and ``conv_b``, per head ``dt_bias``,
#: ``A_log`` and ``D``, the gated norm's ``norm`` (H P) and ``out_proj``
#: (H P, d)
MAMBA_PARAMS = (
    "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm",
    "out_proj",
)

_HI = jax.lax.Precision.HIGHEST


def choose_mamba_form(s: int, block: int, carried: bool) -> str:
    """Which form a pass of ``s`` positions a sequence computes the
    recurrence in, with its reason: one ``step`` for a tick that carries
    a state, else ``chunked`` in blocks of ``block``. A pure function of
    the pass's static shape, so the engine can record what each of its
    programs compiled with (as ``choose_expert_form``)."""
    if s == 1 and carried:
        return "step: one position a sequence from its carried state"
    n = -(-s // block)
    return (
        f"chunked: {s} positions in {n} block{'s' if n > 1 else ''} of "
        f"{block}" + (", the state carried in" if carried else "")
    )


@jax.named_scope("conv")
def causal_conv(xbc, tail, w, b, n_valid, silu: bool = True):
    """The depthwise causal convolution with its carried tail.

    ``xbc`` (B, S, C) the sequence's inputs, ``tail`` (B, K - 1, C) the
    inputs before it (zeros at a sequence's start), ``w`` (K, C), ``b``
    (C,) or None for none, ``n_valid`` (B,) how many of the S positions
    count (a prefix). -> (silu(b + sum_k w_k x_{t-K+1+k}) (B, S, C) in
    ``xbc``'s type — the sum alone where ``silu`` is False — and the new
    tail: the last K - 1 inputs up to the valid ones' end, which is the
    old tail itself where none is valid). Sums and the silu are
    float32."""
    k = w.shape[0]
    s = xbc.shape[1]
    f32 = jnp.float32
    full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    acc = None if b is None else b.astype(f32)
    for j in range(k):
        tap = w[j].astype(f32) * full[:, j:j + s].astype(f32)
        acc = tap if acc is None else acc + tap
    at = n_valid[:, None] + jnp.arange(k - 1)[None, :]          # (B, K-1)
    new_tail = jnp.take_along_axis(full, at[:, :, None], axis=1)
    out = jax.nn.silu(acc) if silu else acc
    return out.astype(xbc.dtype), new_tail.astype(tail.dtype)


@jax.named_scope("step")
def ssd_step(x, dt, a_neg, b, c, d_skip, state):
    """One step of the recurrence for every sequence of the batch.

    ``x`` (B, H, P), ``dt`` (B, H) float32 after its softplus (0 where
    the lane does not count), ``a_neg`` (H,) = -exp(A_log), ``b``, ``c``
    (B, G, N), ``d_skip`` (H,), ``state`` (B, H, P, N) float32.
    -> (y (B, H, P) float32, the new state). Elementwise over the state
    in float32: a tick reads and writes it once."""
    f32 = jnp.float32
    bsz, h, p = x.shape
    g = b.shape[1]
    x32 = x.astype(f32)
    decay = jnp.exp(dt * a_neg)                                  # (B, H)
    st = state.reshape(bsz, g, h // g, p, -1)
    dx = (dt[..., None] * x32).reshape(bsz, g, h // g, p)
    new = (
        decay.reshape(bsz, g, h // g, 1, 1) * st
        + dx[..., None] * b.astype(f32)[:, :, None, None, :]
    )
    y = jnp.sum(new * c.astype(f32)[:, :, None, None, :], axis=-1)
    y = y.reshape(bsz, h, p) + d_skip.astype(f32)[None, :, None] * x32
    return y, new.reshape(state.shape)


@jax.named_scope("scan")
def ssd_chunked(x, dt, a_neg, b, c, d_skip, state, block: int):
    """The recurrence over a sequence in blocks (the SSD form).

    ``x`` (B, S, H, P), ``dt`` (B, S, H) float32 after its softplus (0
    where a position does not count), ``a_neg`` (H,), ``b``, ``c``
    (B, S, G, N), ``d_skip`` (H,), ``state`` (B, H, P, N) float32: what
    the sequence starts from. -> (y (B, S, H, P) float32, the state
    after the last position).

    With ``cum_i`` the running sum of ``dt A`` inside a block:

        y_i = sum_{j <= i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j      inside
            + exp(cum_i) C_i S_start                                    carried
        S_end = exp(cum_last) S_start + sum_j exp(cum_last - cum_j) dt_j x_j (x) B_j

    the first and the last sums as matrix products a block, ``S_start``
    from block to block by a scan. S is padded up to whole blocks with
    ``dt = 0``."""
    f32 = jnp.float32
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    pad = -s % block
    if pad:
        x, dt, b, c = (
            jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            for v in (x, dt, b, c)
        )
    nc = (s + pad) // block
    ctype = x.dtype
    x32 = x.astype(f32)
    xg = x32.reshape(bsz, nc, block, g, r, p)
    dtg = dt.reshape(bsz, nc, block, g, r)
    bc = b.reshape(bsz, nc, block, g, n)
    cc = c.reshape(bsz, nc, block, g, n)
    cum = jnp.cumsum(dtg * a_neg.reshape(g, r), axis=2)         # (B,nc,L,G,R)
    dx = (dtg[..., None] * xg)                                   # dt_j x_j
    # inside a block: (C_i . B_j) masked by the decay from j to i
    cb = jnp.einsum(
        "zlign,zljgn->zlgij", cc, bc, preferred_element_type=f32
    )
    diff = (
        jnp.moveaxis(cum, 2, -1)[..., :, None]
        - jnp.moveaxis(cum, 2, -1)[..., None, :]
    )                                                            # (B,nc,G,R,i,j)
    tri = jnp.tril(jnp.ones((block, block), bool))
    m = jnp.exp(jnp.where(tri, diff, -jnp.inf)) * cb[:, :, :, None]
    y = jnp.einsum(
        "zlgrij,zljgrp->zligrp", m.astype(ctype), dx.astype(ctype),
        preferred_element_type=f32,
    )
    # each block's own contribution to the state at its end
    last = cum[:, :, -1]                                         # (B,nc,G,R)
    to_end = jnp.exp(last[:, :, None] - cum)                     # (B,nc,L,G,R)
    own = jnp.einsum(
        "zljgrp,zljgn->zlgrpn", (dx * to_end[..., None]).astype(ctype), bc,
        preferred_element_type=f32,
    )

    def carry(st, blk):
        own_l, decay_l = blk
        return decay_l[..., None, None] * st + own_l, st

    final, starts = jax.lax.scan(
        carry, state.reshape(bsz, g, r, p, n).astype(f32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(jnp.exp(last), 1, 0)),
    )
    starts = jnp.moveaxis(starts, 0, 1)                          # (B,nc,G,R,P,N)
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "zlign,zlgrpn->zligrp", cc.astype(f32), starts, precision=_HI,
    )
    y = y + d_skip.astype(f32).reshape(g, r, 1) * xg
    y = y.reshape(bsz, nc * block, h, p)[:, :s]
    return y, final.reshape(state.shape)


@jax.named_scope("gate_norm")
def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``RMSNorm(y * silu(z))`` over ``groups`` groups of the last axis,
    times ``scale``: float32 in, float32 out."""
    f32 = jnp.float32
    v = y * jax.nn.silu(z)
    vg = v.reshape(*v.shape[:-1], groups, -1)
    vg = vg * jax.lax.rsqrt(jnp.mean(vg * vg, axis=-1, keepdims=True) + eps)
    return vg.reshape(v.shape) * scale.astype(f32)


def mamba2_mixer(w: dict, u, *, heads: int, head_dim: int, state_dim: int,
                 groups: int, block: int, eps: float, carried=None,
                 valid=None):
    """The Mamba-2 mixer on ``u`` (B, S, d) -> (out (B, S, d), (the
    state after the valid positions (B, H, P, N) float32, the
    convolution's tail (B, K - 1, C))).

    ``w`` holds ``MAMBA_PARAMS``. ``carried`` is what each sequence
    starts from, (state, tail), or None for a sequence's start (zeros);
    ``valid`` (B, S) bool marks the positions that count, a prefix of
    each row (None = all). In a trace its operations lie under
    ``in_proj``, ``conv``, ``scan`` (chunked) or ``step``, ``gate_norm``
    and ``out_proj``."""
    f32 = jnp.float32
    bsz, s, _ = u.shape
    h, p, n, g = heads, head_dim, state_dim, groups
    d_in, k = h * p, w["conv_w"].shape[0]
    conv_dim = d_in + 2 * g * n
    if valid is None:
        valid = jnp.ones((bsz, s), bool)
    if carried is None:
        state = jnp.zeros((bsz, h, p, n), f32)
        tail = jnp.zeros((bsz, k - 1, conv_dim), u.dtype)
    else:
        state, tail = carried
    with jax.named_scope("in_proj"):
        zxbcdt = jnp.matmul(u, w["in_proj"], preferred_element_type=f32)
        z = zxbcdt[..., :d_in]
        xbc = zxbcdt[..., d_in:d_in + conv_dim].astype(u.dtype)
        dt = jax.nn.softplus(
            zxbcdt[..., d_in + conv_dim:] + w["dt_bias"].astype(f32)
        )
        dt = jnp.where(valid[..., None], dt, 0.0)
    xbc, new_tail = causal_conv(
        xbc, tail, w["conv_w"], w["conv_b"],
        jnp.sum(valid, axis=1, dtype=jnp.int32),
    )
    x = xbc[..., :d_in].reshape(bsz, s, h, p)
    b = xbc[..., d_in:d_in + g * n].reshape(bsz, s, g, n)
    c = xbc[..., d_in + g * n:].reshape(bsz, s, g, n)
    a_neg = -jnp.exp(w["A_log"].astype(f32))
    if choose_mamba_form(s, block, carried is not None).startswith("step"):
        y, new_state = ssd_step(
            x[:, 0], dt[:, 0], a_neg, b[:, 0], c[:, 0], w["D"], state
        )
        y = y[:, None]
    else:
        y, new_state = ssd_chunked(x, dt, a_neg, b, c, w["D"], state, block)
    # a sequence with no valid position keeps its state bit for bit
    new_state = jnp.where(
        jnp.any(valid, axis=1)[:, None, None, None], new_state, state
    )
    y = gated_group_norm(y.reshape(bsz, s, d_in), z, w["norm"], g, eps)
    with jax.named_scope("out_proj"):
        out = y.astype(u.dtype) @ w["out_proj"]
    return out, (new_state, new_tail)


#: a gated short convolution's parameters: ``in_proj`` (d, 3 d), its
#: columns B, C and x in that order, ``conv_w`` (K, d), one filter a
#: channel and no bias, and ``out_proj`` (d, d)
SHORTCONV_PARAMS = ("in_proj", "conv_w", "out_proj")


def short_conv(w: dict, u, *, carried=None, valid=None):
    """The gated short convolution (the LFM2 lineage's ``conv`` layers)
    on ``u`` (B, S, d) -> (out (B, S, d), the new tail (B, K - 1, d)).

        [B | C | x] = u W_in              three widths of d
        v_t = B_t * x_t
        w_t = sum_k k_k * v_{t-K+1+k}     per channel, no bias, zeros
                                          before the sequence
        out = (C * w) W_out

    No other nonlinearity, and no state but the last K - 1 rows of ``v``,
    which ``carried`` (B, K - 1, d) brings in (None: a sequence's start)
    and the second result hands on, cut where the positions ``valid``
    (B, S) marks end (``causal_conv``): a lane with none keeps its tail
    bit for bit. ``w`` holds ``SHORTCONV_PARAMS``. ``v`` is rounded to
    ``u``'s type, the type the tail is kept in, before the taps read it,
    so that a sequence's rows meet the same values whether a chunk or a
    tail brings them; the product accumulates in float32 and the gates
    are float32. In a trace its operations lie under ``in_proj``,
    ``conv`` and ``out_proj``."""
    f32 = jnp.float32
    bsz, s, d = u.shape
    k = w["conv_w"].shape[0]
    if valid is None:
        valid = jnp.ones((bsz, s), bool)
    tail = jnp.zeros((bsz, k - 1, d), u.dtype) if carried is None else carried
    with jax.named_scope("in_proj"):
        bcx = jnp.matmul(u, w["in_proj"], preferred_element_type=f32)
        gate_b, gate_c, xs = jnp.split(bcx, 3, axis=-1)
        v = (gate_b * xs).astype(u.dtype)
    conv, new_tail = causal_conv(
        v, tail, w["conv_w"], None,
        jnp.sum(valid, axis=1, dtype=jnp.int32), silu=False,
    )
    with jax.named_scope("out_proj"):
        y = (gate_c * conv.astype(f32)).astype(u.dtype)
        out = y @ w["out_proj"]
    return out, new_tail
