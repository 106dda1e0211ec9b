"""Driver ``serve_nemotron_h``: a decoder of one-mixer layers — Mamba-2
layers with slot-resident recurrent state, attention without positions
over a paged K/V pool, latent ReLU^2 experts of which this chip holds a
share — behind ``Scheduler`` over ``Engine``, in ``drivers/serve.py``'s
closed loop: its ticks, stamps and spans are inherited untouched.

The model is the four methods ``drivers/serve.py`` asks of a subclass
(``make_engine``, ``token_fwd_flops``, ``reference_specs`` and the
reference behind the check, ``reference/nemotron_h.py``):

- **the share**: the configuration's ``n_routed_experts`` is how many
  experts are HELD here, from ``experts_held_from`` on, of the
  ``n_router_outputs`` the router scores; program and reference are told
  the same share and both leave out what the other chips' experts would
  add;
- **FLOPs are those of this chip's share, in the form the schedule
  states** (``token_fwd_flops``): a token multiplies by its layer's
  projections; in an expert layer by the router, the two latent
  projections, the shared expert and ``num_experts_per_tok * held /
  router outputs`` routed experts (what its share draws on average; a
  tick runs every held expert on every token, and that is not counted);
  in a Mamba layer a decoded token pays the one-step update (the state
  decayed, the outer product added, read against C: 5 H P N), a
  prefilled token the chunked form's products (inside a block of
  ``chunk_size`` positions 2 L (G N + H P) a position, its share of the
  block's own state and the read of the carried one, 4 H P N); in the
  attention layer 4 H d_h a visible position; a decoded token pays the
  head;
- **the check** is ``drivers/serve.py``'s: the served tokens of sampled
  finished requests against the reference's full forward at the
  published widths. ``logit_gap`` is the widest gap by which a served
  token's logit lies under the reference's best at its position,
  ``logit_gap_mean`` the mean of those gaps over every served token of
  the sample; the limits file names what a run compares.
"""

from __future__ import annotations

import json
import sys

import numpy as np

from benchmark.drivers import serve
from benchmark.reference import nemotron_h as ref

#: the faults ``calibrate`` can plant under the timed path
FAULTS = ("state_kept_on_admit", "pad_advances_state")


def model_config(config: dict, traffic: dict):
    """Published keys -> the program's ``TransformerConfig``."""
    from singa_tpu.models.transformer import TransformerConfig

    c = config
    if c["n_group"] != 1 or c["topk_group"] != 1 or c["mlp_hidden_act"] != "relu2":
        raise ValueError("nemotron_h: one expert group and relu2 experts")
    return TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], n_layers=c["num_hidden_layers"],
        layers=ref.layer_kinds(c), d_ff=c["intermediate_size"],
        max_len=traffic["max_model_len"], norm="rmsnorm",
        norm_eps=c["layer_norm_epsilon"], pos="none", mlp="relu2",
        tied_head=c["tie_word_embeddings"],
        mamba_heads=c["mamba_num_heads"], mamba_head_dim=c["mamba_head_dim"],
        ssm_state=c["ssm_state_size"], ssm_groups=c["n_groups"],
        conv_kernel=c["conv_kernel"], ssm_block=c["chunk_size"],
        moe_experts=c["n_router_outputs"],
        moe_top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"], moe_score="sigmoid",
        moe_bias=True, moe_scale=float(c["routed_scaling_factor"]),
        moe_shared_d_ff=(
            c["n_shared_experts"] * c["moe_shared_expert_intermediate_size"]
        ),
        moe_held=(c["experts_held_from"], c["n_routed_experts"]),
        moe_act="relu2", moe_latent=c["moe_latent_size"],
    )


def token_fwd_flops(config: dict, visible: float, decoded: bool) -> float:
    """Forward FLOPs of ONE token that sees ``visible`` positions, of
    this chip's share (module docstring): ``decoded`` by the one-step
    update and with the head, else as one of a prefill chunk in the
    chunked form and without it."""
    c = config
    d = c["hidden_size"]
    h, p = c["mamba_num_heads"], c["mamba_head_dim"]
    g, n, block = c["n_groups"], c["ssm_state_size"], c["chunk_size"]
    d_in = h * p
    mamba = 2.0 * d * (2 * d_in + 2 * g * n + h) + 2.0 * d_in * d
    mamba += 2.0 * c["conv_kernel"] * (d_in + 2 * g * n)
    if decoded:
        mamba += 5.0 * h * p * n
    else:
        mamba += 2.0 * block * (g * n + h * p) + 4.0 * h * p * n
    hq, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    attn = 2.0 * d * (hq + 2 * hkv) * dh + 2.0 * hq * dh * d
    attn += 4.0 * hq * dh * visible
    lat = c["moe_latent_size"]
    routed = (
        c["num_experts_per_tok"] * c["n_routed_experts"]
        / c["n_router_outputs"]
    )
    moe = 2.0 * (
        d * c["n_router_outputs"] + 2 * d * lat
        + routed * 2 * lat * c["moe_intermediate_size"]
        + c["n_shared_experts"] * 2 * d
        * c["moe_shared_expert_intermediate_size"]
    )
    mlp = 2.0 * 2 * d * c["intermediate_size"]
    per_kind = {"mamba": mamba, "attn": attn, "moe": moe, "mlp": mlp}
    return sum(per_kind[k] for k in ref.layer_kinds(c)) + (
        2.0 * d * c["vocab_size"] if decoded else 0.0
    )


class Driver(serve.Driver):
    #: a planted fault (``calibrate``), None in every run of the cell
    fault: str | None = None
    #: True while a prefill chunk's tokens are being counted
    _in_chunk = False

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
        # which form each program's expert and Mamba layers compiled in
        print(json.dumps({
            "expert_forms": self.engine.expert_forms,
            "mamba_forms": self.engine.mamba_forms,
        }), file=sys.stderr)
        if self.fault is not None:
            self._plant(self.fault)

    def _plant(self, fault: str) -> None:
        """A fault under the timed path. ``state_kept_on_admit``:
        admission leaves a slot's recurrent state and convolution tail
        as its last request left them, so a request inherits its
        predecessor's (caught at the rehearsal's size; at the published
        widths an inherited state has decayed to nothing by a prompt's
        end, and served tokens do not show it: PERF.md section 6, PR
        37). ``pad_advances_state``: a prefill chunk's padding
        counts as positions, so a last chunk that is not full steps the
        state (and the convolution's tail) over its padding."""
        import jax
        import jax.numpy as jnp

        from singa_tpu.models import transformer

        engine = self.engine
        if fault == "state_kept_on_admit":
            admit = engine._admit_prog

            def kept(state, slot, row):
                out = admit(state, slot, row)
                return {**out, "ssm": state["ssm"], "conv": state["conv"]}

            engine._admit_jit = jax.jit(kept, donate_argnums=(0,))
        elif fault == "pad_advances_state":
            block_apply = transformer._block_apply

            def padded_counts(*args, valid=None, **kw):
                return block_apply(
                    *args, valid=jnp.ones_like(valid), **kw
                )

            def prefill(*args):
                from singa_tpu.serve import engine as engine_mod

                engine_mod._block_apply = padded_counts
                try:
                    return engine._prefill(*args)
                finally:
                    engine_mod._block_apply = block_apply

            engine._prefill_jit = jax.jit(prefill, donate_argnums=(1,))
        else:
            raise ValueError(f"fault {fault!r} not one of {FAULTS}")

    def _wrap_engine(self) -> None:
        """The base driver's spans, with the one thing more that this
        model's FLOP count needs: whether the token counted is one of a
        prefill chunk."""
        super()._wrap_engine()
        timed = self.engine.prefill_chunk

        def chunk(slot, tokens, pos0):
            self._in_chunk = True
            try:
                return timed(slot, tokens, pos0)
            finally:
                self._in_chunk = False

        self.engine.prefill_chunk = chunk

    def token_fwd_flops(self, position: int) -> float:
        return token_fwd_flops(self.config, position, not self._in_chunk)

    def reference_specs(self) -> dict:
        return ref.specs(self.config)

    def reference_forward(self, params, seq, arith: str = "float32"):
        return ref.forward(params, seq, self.config, arith)

    def counters(self) -> dict:
        s = self.sched
        out = super().counters()
        out.update({
            "experts_hit": s.experts_hit,
            "expert_max_load": s.expert_max_load,
            "held_pairs": s.held_pairs,
            "chunk_held_pairs": s.chunk_held_pairs,
            "cache_rows": s.cache_rows,
            "state_slots_live": s.state_slots_live,
        })
        return out

    # -- after the window -----------------------------------------------

    def gaps_of(self, sample, arith: str | None = None) -> dict:
        """``logit_gap`` (the widest, over every served position of
        ``sample``, between the reference's best logit and the logit of
        the token served there) and ``logit_gap_mean`` (their mean over
        every served token). With ``arith`` the token judged at each
        position is the one that arithmetic puts first (the control).
        The reference runs a layer a compiled call, outside any other."""
        import jax.numpy as jnp

        params = self._weights()
        size = self.mcfg.max_len
        widest, total, count = 0.0, 0.0, 0
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
                return {"logit_gap": np.inf, "logit_gap_mean": np.inf}
            widest = max(widest, float(g.max()))
            total, count = total + float(g.sum()), count + len(g)
        return {"logit_gap": widest,
                "logit_gap_mean": total / count if count else np.inf}

    def check(self) -> dict:
        got = self.gaps_of(self.sample) if self.sample else {}
        return {
            name: {"value": got.get(name), "limit": self.limits[name]}
            for name in self.limits
        }

    def calibrate(self, controls=(), faults=(), seconds=8.0) -> dict:
        """One seed's readings: a short window at the cell's own load,
        the program's gaps, each control's over the same prompts and
        tokens, and each planted fault's from a window of its own."""
        self.setup()
        self.window(seconds)
        self.release()
        out = {"program": {
            **self.gaps_of(self.sample),
            "served_tokens": sum(len(t) for _, t in self.sample),
        }}
        for arith in controls:
            out[arith] = self.gaps_of(self.sample, arith)
        for fault in faults:
            if fault not in FAULTS:
                raise ValueError(f"fault {fault!r} not one of {FAULTS}")
            faulty = type(self)(
                config=self.config, traffic=self.traffic, limits=self.limits,
                seed=self.seed, devices=self.devices, work=self.work,
                spans=self.spans,
            )
            faulty.fault = fault
            faulty.setup()
            faulty.window(seconds)
            faulty.release()
            out[fault] = faulty.gaps_of(faulty.sample)
        return out
