"""Scalar activation ops.

Formulas mirror the reference's mshadow scalar op structs
(reference: include/mshadow/cxxnet_op.h:14-113). Gradients are left to jax
autodiff; tests/test_ops.py pins grad(op) against the reference's *_grad
structs (which are written in terms of the *output* for sigmoid/tanh/stanh).
"""

from __future__ import annotations

import jax.numpy as jnp

# LeCun scaled-tanh constants, hard-coded in the reference
# (cxxnet_op.h:77-87). kTanh layers always use these.
STANH_OUTER = 1.7159047
STANH_INNER = 0.66666667


def relu(x: jnp.ndarray, negative_slope: float = 0.0) -> jnp.ndarray:
    """max(x, 0), with optional leaky slope (ReLUProto.negative_slope).

    Plain autodiff. (An output-masked custom VJP — saving y instead of
    the pre-activation for the backward mask — buys nothing: XLA
    already shares/fuses the residual.)"""
    # jnp.where (not jnp.maximum) so grad at exactly 0 is 0, matching
    # relu_grad's strict `a > 0 ? 1 : 0` (cxxnet_op.h:31-35)
    return jnp.where(x > 0, x, negative_slope * x if negative_slope else 0.0)


def sigmoid(x: jnp.ndarray) -> jnp.ndarray:
    return 1.0 / (1.0 + jnp.exp(-x))


def stanh(x: jnp.ndarray) -> jnp.ndarray:
    """Scaled tanh: 1.7159047 * tanh(0.66666667 * x)."""
    return STANH_OUTER * jnp.tanh(STANH_INNER * x)


def softplus(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.log1p(jnp.exp(x))


def bnll(x: jnp.ndarray) -> jnp.ndarray:
    """Binomial negative log-likelihood, the overflow-safe softplus
    (cxxnet_op.h:57-61): x > 0 ? x + log(1+exp(-x)) : log(1+exp(x))."""
    return jnp.where(x > 0, x + jnp.log1p(jnp.exp(-jnp.abs(x))),
                     jnp.log1p(jnp.exp(jnp.minimum(x, 0.0))))
