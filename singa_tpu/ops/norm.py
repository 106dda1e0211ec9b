"""Fused batch normalization with a hand-written VJP.

Why this exists (round-4 perf work): autodiff through
``jnp.mean``/``jnp.var`` plus fp32 casts generated 4-6 extra
full-activation passes per BatchNorm, and ResNet-50 has 53 of them on
activation-sized tensors. This implementation does the
information-theoretic minimum of HBM traffic:

  fwd:  one fused read of x for both moments (sum and sum-of-squares
        accumulated in fp32 inside the reduction — no materialized fp32
        copy), then one read+write for the normalize.
  bwd:  one fused read of (dy, x) for the two reductions
        (sum(dy), sum(dy*xhat)), one read of (dy, x) + write for dx.

Total: 8 activation-sized bf16 touches for fwd+bwd, vs ~14 (some fp32)
from autodiff of the naive formula.

The reference has no batch normalization (its registry tops out at LRN,
/root/reference/src/worker/neuralnet.cc:13-33); this op backs the
kBatchNorm extension layer (singa_tpu/layers/norm.py) that the ResNet
configs are built from.

``batch_norm_train`` returns (y, mean, var). The y-cotangent math is
the standard BN backward:

  dgamma = sum(dy * xhat),  dbeta = sum(dy)
  dx     = gamma*inv * (dy - dbeta/n - xhat * dgamma/n)

and the mean/var cotangents contribute dmean/n + 2*dvar*(x-mean)/n,
folded into the same dx pass (free when they are the usual structural
zeros — XLA constant-folds them away).

Numerics: one-pass moments E[x^2]-E[x]^2 cancel catastrophically when
|mean|/std exceeds ~3e3 in fp32 (ulp 6e-8 of mean^2 swamps std^2).
Two defenses, both costless on the hot path:

  1. an optional per-channel ``shift`` anchor subtracted inside the
     pass (layers/norm.py passes its running-mean buffer — a free
     independent input, unlike anchors computed from x, which measured
     +2.5ms/step on ResNet-50 by serializing ahead of every stats
     reduction);
  2. a lax.cond rescue: when any channel's one-pass variance is within
     10x of the cancellation noise floor (var < 1e-5 * mean_shifted^2,
     i.e. |mean|/std > ~316 in the anchored frame), a second,
     cancellation-free pass E[(x - s - m)^2] recomputes the exact
     variance. The predicate is false in any sane training regime, so
     the branch never runs — but step 0 with a cold anchor and a
     pathologically offset input is still *correct*, just one pass
     slower. (Under vmap, cond lowers to select and both branches pay —
     don't vmap this op; the trainer never does.)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _axes_shape(x: jnp.ndarray):
    """Reduction axes and broadcast shape for (N, C) or (N, C, H, W)."""
    if x.ndim == 2:
        return (0,), (1, -1)
    return (0, 2, 3), (1, -1, 1, 1)


def _moments(x: jnp.ndarray, axes, shape, n: int, shift):
    """Single-pass fp32 batch moments of the shifted data: with
    s = shift (a per-channel mean estimate), E[x] = E[x-s] + s and
    Var[x] = E[(x-s)^2] - E[x-s]^2. The elementwise cast, subtract, and
    square all fuse into the two reductions, so x is read once from HBM
    and no fp32 copy is materialized. See the module docstring for the
    cancellation rescue."""
    sf = None if shift is None else shift.astype(jnp.float32).reshape(shape)

    def shifted(xx):
        xxf = xx.astype(jnp.float32)
        return xxf if sf is None else xxf - sf

    xf = shifted(x)
    s1 = jnp.sum(xf, axes)
    s2 = jnp.sum(xf * xf, axes)
    m = s1 / n
    var = jnp.maximum(s2 / n - m * m, 0.0)

    def exact_var():
        # cancellation-free second pass around the now-known exact mean.
        # Recompute the shifted cast from x INSIDE the branch: closing
        # over xf would force XLA to materialize the fp32 copy in HBM
        # for the branch operand (measured +4ms/step on ResNet-50 even
        # with the branch never taken)
        d = shifted(x) - m.reshape(shape)
        return jnp.sum(d * d, axes) / n

    suspect = jnp.any(var * 1e5 < m * m)
    var = jax.lax.cond(suspect, exact_var, lambda: var)
    mean = m if shift is None else m + shift.astype(jnp.float32)
    return mean, var


def _apply(x, gamma, beta, eps, shift):
    axes, shape = _axes_shape(x)
    n = x.size // x.shape[1]
    mean, var = _moments(x, axes, shape, n, shift)
    inv = jax.lax.rsqrt(var + eps)
    scale = gamma.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - scale * mean
    y = (
        x * scale.astype(x.dtype).reshape(shape)
        + shift.astype(x.dtype).reshape(shape)
    )
    return y, mean, var, inv


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def batch_norm_train(x, gamma, beta, eps=1e-5, shift=None):
    """-> (y, mean, var). Batch stats are fp32; y stays in x.dtype.

    ``shift`` (optional, (C,)) is a numerical-stability anchor for the
    one-pass moments — pass a running-mean estimate; it does not change
    the math and receives a zero gradient."""
    y, mean, var, _ = _apply(x, gamma, beta, eps, shift)
    return y, mean, var


def _bn_fwd(x, gamma, beta, eps, shift):
    y, mean, var, inv = _apply(x, gamma, beta, eps, shift)
    return (y, mean, var), (x, gamma, beta, mean, inv, shift)


def _bn_bwd(eps, res, cts):
    dy, dmean, dvar = cts
    x, gamma, beta, mean, inv, shift = res
    axes, shape = _axes_shape(x)
    n = x.size // x.shape[1]
    dyf = dy.astype(jnp.float32)
    xc = x.astype(jnp.float32) - mean.reshape(shape)
    xhat = xc * inv.reshape(shape)
    dbeta = jnp.sum(dyf, axes)
    dgamma = jnp.sum(dyf * xhat, axes)
    k = (gamma.astype(jnp.float32) * inv).reshape(shape)
    dxf = k * (
        dyf - (dbeta / n).reshape(shape) - xhat * (dgamma / n).reshape(shape)
    )
    # mean/var output cotangents: usually structural zeros (running-stat
    # updates are detached); the terms fuse into the same dx pass and
    # XLA folds them away when zero, so generality costs nothing
    dxf = dxf + (dmean / n).reshape(shape) + xc * (2.0 / n * dvar).reshape(shape)
    dx = dxf.astype(x.dtype)
    # shift is a stability anchor that cancels out of the math — zero
    # gradient (None when the arg was None, matching its pytree)
    dshift = None if shift is None else jnp.zeros_like(shift)
    return (
        dx,
        dgamma.astype(gamma.dtype),
        dbeta.astype(beta.dtype),
        dshift,
    )


batch_norm_train.defvjp(_bn_fwd, _bn_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def batch_norm_train_sampled(x, gamma, beta, eps, stride, shift=None):
    """Subsample-stats BatchNorm (OPT-IN, different math — r5 knob).

    Batch moments are computed from the first ``batch/stride`` sample
    rows (a contiguous prefix — see _apply_sampled for why not a
    strided slice) and the backward treats them as DETACHED constants:

        dx = gamma * inv * dy          (no reduction dependency)
        dgamma/dbeta exact as usual

    Two deliberate approximations vs batch_norm_train:
      * stats see batch/stride samples (an unbiased but noisier moment
        estimate — large batches tolerate this the way ghost/virtual BN
        does);
      * the mean/var gradient paths are dropped (straight-through).
    Why it exists: the irreducible same-math term of exact BN is the
    stats read (2.71 GB on ResNet-50 at batch 128, counted from the
    shapes), which no conv-epilogue kernel can remove. This knob
    removes (stride-1)/stride of the stats read AND lets XLA fuse
    the whole backward into one (dy, x) read since dx no longer waits
    on the reductions. Exposed as batchnorm_param.stats_sample_stride
    (default 1 = exact op); convergence consequences are the user's
    opt-in.

    Returns (y, mean, var) like batch_norm_train.
    """
    y, mean, var, _ = _apply_sampled(x, gamma, beta, eps, stride, shift)
    return y, mean, var


def _apply_sampled(x, gamma, beta, eps, stride, shift):
    axes, shape = _axes_shape(x)
    # contiguous PREFIX rows, not a strided slice: x[::stride] lowers to
    # a gather/copy on TPU (measured: the stride-4 knob ran 9 ms SLOWER
    # than exact BN with it), while x[:n/stride] is a zero-cost view.
    # Batches are shuffled streams, so a prefix is as unbiased a sample
    # as a stride.
    nkeep = max(1, x.shape[0] // stride)
    xs = jax.lax.slice_in_dim(x, 0, nkeep, 1, axis=0)
    n = xs.size // xs.shape[1]
    mean, var = _moments(xs, axes, shape, n, shift)
    inv = jax.lax.rsqrt(var + eps)
    scale = gamma.astype(jnp.float32) * inv
    sh = beta.astype(jnp.float32) - scale * mean
    y = (
        x * scale.astype(x.dtype).reshape(shape)
        + sh.astype(x.dtype).reshape(shape)
    )
    return y, mean, var, inv


def _bns_fwd(x, gamma, beta, eps, stride, shift):
    y, mean, var, inv = _apply_sampled(x, gamma, beta, eps, stride, shift)
    return (y, mean, var), (x, gamma, beta, mean, inv, shift)


def _bns_bwd(eps, stride, res, cts):
    dy, _dmean, _dvar = cts  # stats are detached: their cotangents drop
    x, gamma, beta, mean, inv, shift = res
    axes, shape = _axes_shape(x)
    dyf = dy.astype(jnp.float32)
    xhat = (x.astype(jnp.float32) - mean.reshape(shape)) * inv.reshape(shape)
    dbeta = jnp.sum(dyf, axes)
    dgamma = jnp.sum(dyf * xhat, axes)
    # straight-through: dx independent of the reductions — one fused
    # (dy, x) read produces dx AND both param grads
    dx = (
        dyf * (gamma.astype(jnp.float32) * inv).reshape(shape)
    ).astype(x.dtype)
    dshift = None if shift is None else jnp.zeros_like(shift)
    return dx, dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype), dshift


batch_norm_train_sampled.defvjp(_bns_fwd, _bns_bwd)


def batch_norm_infer(x, gamma, beta, mean, var, eps=1e-5):
    """Normalize by running stats (eval path); plain autodiff is fine
    here — stats are constants, so it's one fused elementwise pass."""
    _, shape = _axes_shape(x)
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    scale = gamma.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - scale * mean.astype(jnp.float32)
    return (
        x * scale.astype(x.dtype).reshape(shape)
        + shift.astype(x.dtype).reshape(shape)
    )
