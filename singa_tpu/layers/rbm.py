"""Restricted Boltzmann machine layer (the contrastive-divergence path).

The reference *declares* CD training — GradCalcAlg.kContrastiveDivergence
(src/proto/model.proto:40-44) and the TrainOneBatch comment naming a
"CD worker" (include/worker/base_layer.h:96-97) — but this snapshot ships
no RBM layer or CD worker. This layer is that greenfield fill,
designed TPU-first: the whole CD-k Gibbs chain is a fixed-length
`lax.scan`-free unroll of sigmoid+matmul ops inside the jitted step, so
the MXU sees (B,V)x(V,H) matmuls and XLA fuses the sampling elementwise.

In a kBackPropagation net (or at eval time) the layer acts as a plain
feature extractor: apply() returns the mean-field hidden probabilities,
which is what lets stacked RBMs form the encoder of a deep autoencoder
(pretrain with alg: kContrastiveDivergence, then kPretrained-init the
unrolled MLP — the classic deep-autoencoder recipe).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from ..config.schema import ConfigError
from .base import Layer, Shape, feature_dim, require_one_src


class RBMLayer(Layer):
    """kRBM: binary-binary RBM with weight (V,H), vbias (V), hbias (H)."""

    TYPE = "kRBM"
    CONNECTION = "kOneToAll"

    def setup(self, src_shapes: Sequence[Shape], batchsize: int) -> Shape:
        p = self.cfg.rbm_param
        if p is None or not p.num_hidden:
            raise ConfigError(
                f"layer {self.name!r}: rbm_param.num_hidden required"
            )
        src = require_one_src(self, src_shapes)
        vdim = feature_dim(src)
        self.vdim, self.hdim = vdim, p.num_hidden
        self.cd_k = max(1, p.cd_k)
        self.sample_visible = p.sample_visible
        self.wname = self._declare_param(
            0,
            "weight",
            (vdim, self.hdim),
            fan_in=vdim * self.hdim,  # the FC convention (layer.cc:178)
            neuron_axis=1,
        )
        self.vbname = self._declare_param(1, "vbias", (vdim,))
        self.hbname = self._declare_param(
            2, "hbias", (self.hdim,), neuron_axis=0
        )
        return (src[0], self.hdim)

    # ---------------- mean-field propagation ----------------

    def _flat(self, v: jnp.ndarray) -> jnp.ndarray:
        return v.reshape(v.shape[0], -1)

    def prop_up(self, params, v: jnp.ndarray) -> jnp.ndarray:
        """P(h=1|v) = sigmoid(vW + hbias)."""
        return jax.nn.sigmoid(
            self._flat(v) @ params[self.wname] + params[self.hbname]
        )

    def prop_down(self, params, h: jnp.ndarray) -> jnp.ndarray:
        """P(v=1|h) = sigmoid(hW^T + vbias)."""
        return jax.nn.sigmoid(
            h @ params[self.wname].T + params[self.vbname]
        )

    def apply(self, params, inputs, *, training, rng=None):
        """Feature-extractor view: mean hidden probabilities."""
        return self.prop_up(params, inputs[0])

    # ---------------- contrastive divergence ----------------

    def cd_grads(self, params, v0, rng):
        """One CD-k estimate; returns (grads, metrics).

        Standard Hinton recipe: hidden states are *sampled* while driving
        the chain, the final hidden uses probabilities, the positive phase
        uses h0 probabilities, and grads are descent-oriented
        (neg - pos)/batch so the existing updaters (which subtract) ascend
        the log-likelihood.
        """
        v0 = self._flat(v0)
        batch = v0.shape[0]
        h0p = self.prop_up(params, v0)
        hk = jax.random.bernoulli(
            jax.random.fold_in(rng, 0), h0p
        ).astype(v0.dtype)
        vkp = v0
        for k in range(self.cd_k):
            vkp = self.prop_down(params, hk)
            vk = (
                jax.random.bernoulli(
                    jax.random.fold_in(rng, 2 * k + 1), vkp
                ).astype(v0.dtype)
                if self.sample_visible
                else vkp
            )
            hkp = self.prop_up(params, vk)
            hk = jax.random.bernoulli(
                jax.random.fold_in(rng, 2 * k + 2), hkp
            ).astype(v0.dtype)
        # negative-phase statistics from probabilities (lower variance, per
        # Hinton's practical guide), positive phase from the data
        grads = {
            self.wname: (vkp.T @ hkp - v0.T @ h0p) / batch,
            self.vbname: jnp.mean(vkp - v0, axis=0),
            self.hbname: jnp.mean(hkp - h0p, axis=0),
        }
        recon = jnp.mean(jnp.square(v0 - vkp))
        return grads, {"loss": recon}

    def recon_error(self, params, v):
        """Eval metric: one mean-field reconstruction pass."""
        v = self._flat(v)
        vp = self.prop_down(params, self.prop_up(params, v))
        return jnp.mean(jnp.square(v - vp))
