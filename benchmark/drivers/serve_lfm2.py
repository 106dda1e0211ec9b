"""Driver ``serve_lfm2``: a decoder of gated short-convolution layers
with a slot-resident two-row tail beside RoPE'd GQA layers over a paged
K/V pool, each layer followed by a dense SwiGLU MLP or sigmoid-scored
SwiGLU experts, behind ``Scheduler`` over ``Engine``, in
``drivers/serve.py``'s closed loop: its ticks, stamps and spans are
inherited untouched.

The model is what ``drivers/serve.py`` asks of a subclass
(``make_engine``, ``token_fwd_flops``, ``reference_specs`` and the
reference behind the check, ``reference/lfm2_moe.py``). The check's
gaps are this driver's own (``gaps_of``); ``check``, the calibration,
the planted faults and the counters are ``drivers/serve_nemotron_h.py``'s,
which read nothing of that model but its weights, its reference and
``gaps_of`` (all overridden here), so this driver subclasses that one:

- **a published layer is two one-mixer blocks**, the operator's (short
  convolution or attention) and then the MLP's or the experts'
  (``reference/lfm2_moe.py`` ``layer_kinds``), so the program's
  ``layers`` list is twice as long as ``layer_types``;
- **FLOPs** (``token_fwd_flops``): a token multiplies by each short
  convolution's two projections and its taps, by each attention layer's
  projections and 4 H d_h a visible position, by the dense MLPs, and in
  each expert layer by the router and ``num_experts_per_tok`` experts
  (a tick runs every expert on every token in the dense form, and that
  is not counted); a decoded token pays the head;
- **the check** (``gaps_of``, this driver's own): the served tokens of
  sampled finished requests against the reference's full forward,
  ``logit_gap_mean`` (the mean over every served token of the gap by
  which its logit lies under the reference's best), ``logit_gap`` (the
  widest) and ``handover_gap_mean`` (the mean over the rows whose short
  convolutions read the tail a prompt's prefill handed to the first
  decode steps: K - 1 rows a request, where a wrong tail shows whole and
  is not spread over the hundreds of rows after it); the limits file
  names what a run compares. The faults ``calibrate`` plants are N's
  two, read for a tail: ``state_kept_on_admit`` (admission leaves a
  slot's tails as its last request left them) and ``pad_advances_state``
  (a prefill chunk's padding counts, so the tail a prompt's last chunk
  hands the first decode holds padding rows).
"""

from __future__ import annotations

import json
import sys

import numpy as np

from benchmark.drivers import serve_nemotron_h
from benchmark.reference import lfm2_moe as ref


def model_config(config: dict, traffic: dict):
    """Published keys -> the program's ``TransformerConfig``."""
    from singa_tpu.models.transformer import TransformerConfig

    c = config
    if not (c["norm_topk_prob"] and c["use_expert_bias"]
            and c["tie_word_embeddings"]) or c["conv_bias"]:
        raise ValueError(
            "lfm2_moe: norm_topk_prob, use_expert_bias and a tied head; "
            "no conv bias"
        )
    kinds = ref.layer_kinds(c)
    return TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=ref.head_dim(c), n_layers=len(kinds), layers=kinds,
        d_ff=c["intermediate_size"], max_len=traffic["max_model_len"],
        norm="rmsnorm", norm_eps=c["norm_eps"], pos="rope",
        rope_theta=float(c["rope_theta"]), qk_norm=True, mlp="swiglu",
        tied_head=True, conv_kernel=c["conv_L_cache"],
        moe_experts=c["num_experts"], moe_top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"], moe_score="sigmoid",
        moe_bias=True, moe_scale=float(c["routed_scaling_factor"]),
    )


def token_fwd_flops(config: dict, visible: float, decoded: bool) -> float:
    """Forward FLOPs of ONE token that sees ``visible`` positions
    (module docstring), the head only where it is ``decoded``."""
    c = config
    d, k = c["hidden_size"], c["conv_L_cache"]
    hq, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   ref.head_dim(c))
    per_kind = {
        "shortconv": 2.0 * d * 3 * d + 2.0 * d * d + 2.0 * k * d,
        "attn": 2.0 * d * (hq + 2 * hkv) * dh + 2.0 * hq * dh * d
        + 4.0 * hq * dh * visible,
        "mlp": 2.0 * 3 * d * c["intermediate_size"],
        "moe": 2.0 * d * c["num_experts"] + c["num_experts_per_tok"]
        * 2.0 * 3 * d * c["moe_intermediate_size"],
    }
    return sum(per_kind[kind] for kind in ref.layer_kinds(c)) + (
        2.0 * d * c["vocab_size"] if decoded else 0.0
    )


class Driver(serve_nemotron_h.Driver):
    def _weights(self):
        import jax.numpy as jnp

        return ref.draw(
            self.config, self.seed, jnp.dtype(self.config["torch_dtype"])
        )

    def make_engine(self) -> None:
        from singa_tpu.serve import Engine, EngineConfig, Scheduler

        t = self.traffic
        self.mcfg = model_config(self.config, t)
        self.engine = Engine(self._weights(), self.mcfg, EngineConfig(
            slots=t["slots"], kv_block_len=t["kv_block_len"],
            kv_blocks=t["kv_blocks"], max_prefill_chunk=t["max_prefill_chunk"],
        ))
        self.sched = Scheduler(self.engine)
        # which form each program's expert layers and attention took
        print(json.dumps({
            "expert_forms": self.engine.expert_forms,
            "attend": self.engine.attend_choice,
        }), file=sys.stderr)
        if self.fault is not None:
            self._plant(self.fault)

    def _plant(self, fault: str) -> None:
        """N's faults (``serve_nemotron_h.Driver._plant``), the first
        read for a model whose only recurrent state is its tails."""
        import jax

        if fault != "state_kept_on_admit":
            return super()._plant(fault)
        engine = self.engine
        admit = engine._admit_prog

        def kept(state, slot, row):
            return {**admit(state, slot, row), "conv": state["conv"]}

        engine._admit_jit = jax.jit(kept, donate_argnums=(0,))

    def token_fwd_flops(self, position: int) -> float:
        return token_fwd_flops(self.config, position, not self._in_chunk)

    def reference_specs(self) -> dict:
        return ref.specs(self.config)

    def gaps_of(self, sample, arith: str | None = None) -> dict:
        """``logit_gap`` (the widest gap, over every served position of
        ``sample``, between the reference's best logit and the logit of
        the token served there), ``logit_gap_mean`` (their mean over
        every served token) and ``handover_gap_mean`` (their mean over
        each request's rows ``len(prompt) .. len(prompt) + K - 2``: the
        decode steps whose taps read rows the prefill left in the tail).
        With ``arith`` the token judged at each position is the one that
        arithmetic puts first (the control). The reference runs a layer
        a compiled call, outside any other."""
        import jax.numpy as jnp

        params = self._weights()
        size = self.mcfg.max_len
        handover = self.config["conv_L_cache"] - 1
        widest, gaps, first = 0.0, [], []
        for prompt, tokens in sample:
            seq = np.zeros((size,), np.int32)
            full = np.concatenate([prompt, np.asarray(tokens, np.int32)])
            n = min(len(full), size)
            seq[:n] = full[:n]
            logits = self.reference_forward(params, jnp.asarray(seq))
            # row t scores the token at t + 1: the served tokens sit at
            # rows len(prompt) - 1 .. len(prompt) + len(tokens) - 2
            lo, hi = len(prompt) - 1, n - 1
            served = jnp.asarray(full[lo + 1:hi + 1])
            if arith is not None:
                served = jnp.argmax(
                    self.reference_forward(params, jnp.asarray(seq), arith)
                    [lo:hi], axis=-1,
                )
            rows = logits[lo:hi]
            g = np.asarray(jnp.max(rows, axis=-1) - jnp.take_along_axis(
                rows, served[:, None], axis=-1
            )[:, 0])
            if not np.all(np.isfinite(g)):
                return dict.fromkeys(
                    ("logit_gap", "logit_gap_mean", "handover_gap_mean"),
                    np.inf,
                )
            widest = max(widest, float(g.max()))
            gaps.append(g)
            first.append(g[1:1 + handover])
        if not gaps:
            return dict.fromkeys(
                ("logit_gap", "logit_gap_mean", "handover_gap_mean"), np.inf
            )
        return {
            "logit_gap": widest,
            "logit_gap_mean": float(np.concatenate(gaps).mean()),
            "handover_gap_mean": float(np.concatenate(first).mean()),
        }

    def reference_forward(self, params, seq, arith: str = "float32"):
        return ref.forward(params, seq, self.config, arith)
