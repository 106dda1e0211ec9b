"""Driver ``serve_kimi_k2``: a latent-attention decoder with sigmoid-scored
experts, of which this chip holds a share, behind ``Scheduler`` over
``Engine``, in ``drivers/serve.py``'s closed loop — its ticks, stamps and
spans are inherited untouched.

The model is the four methods ``drivers/serve.py`` asks of a subclass
(``make_engine``, ``token_fwd_flops``, ``reference_specs`` and the
reference behind the check, ``reference/kimi_k2.py``):

- **the share**: the configuration's ``n_routed_experts`` is how many
  experts are HELD here, from ``experts_held_from`` on, of the
  ``n_router_outputs`` the router scores; program and reference are told
  the same share and both leave out what the other chips' experts would
  add;
- **FLOPs are those of this chip's share, in the form the schedule
  states**: a token multiplies by the attention projections, the router,
  the shared expert and ``num_experts_per_tok * held / router outputs``
  routed experts a layer (what its share draws on average; the program
  runs every held expert on every token, and that is not counted); a
  prefilled token attends MATERIALISED (keys and values of the positions
  it sees made from their latents once a chunk of ``max_prefill_chunk``
  tokens, then 2 x H x (d_nope + d_rope + d_v) a position), a decoded
  token ABSORBED (its query taken into the latent space and back, and
  2 x H x (2 r_kv + d_rope) a position) and pays the head;
- **the check** is ``drivers/serve.py``'s: the served tokens of sampled
  finished requests against the reference's full forward at the
  published widths. ``logit_gap`` is the widest gap by which a served
  token's logit lies under the reference's best at its position,
  ``logit_gap_mean`` the mean of those gaps over every served token of
  the sample; the limits file names what a run compares.
"""

from __future__ import annotations

import numpy as np

from benchmark import weights
from benchmark.drivers import serve
from benchmark.reference import kimi_k2 as ref

#: the faults ``calibrate`` can plant under the timed path
FAULTS = ("k_pe_unrotated", "bias_weighs")


def model_config(config: dict, traffic: dict):
    """Published keys -> the program's ``TransformerConfig``."""
    from singa_tpu.models.transformer import TransformerConfig

    c, y = config, config["rope_scaling"]
    if y["type"] != "yarn" or c["n_group"] != 1 or c["topk_group"] != 1:
        raise ValueError("kimi_k2: yarn rotary scaling and one expert group")
    return TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        d_ff=c["intermediate_size"], max_len=traffic["max_model_len"],
        norm="rmsnorm", norm_eps=c["rms_norm_eps"],
        pos="rope", rope_theta=float(c["rope_theta"]),
        rope_yarn=(
            float(y["factor"]), y["original_max_position_embeddings"],
            y["beta_fast"], y["beta_slow"], y["mscale"], y["mscale_all_dim"],
        ),
        head_dim=c["qk_nope_head_dim"], rope_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], kv_latent=c["kv_lora_rank"],
        q_latent=c["q_lora_rank"], tied_head=c["tie_word_embeddings"],
        mlp="swiglu", dense_layers=c["first_k_dense_replace"],
        moe_experts=c["n_router_outputs"],
        moe_top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"], moe_score=c["scoring_func"],
        moe_bias=c["topk_method"] == "noaux_tc",
        moe_scale=c["routed_scaling_factor"],
        moe_shared_d_ff=c["n_shared_experts"] * c["moe_intermediate_size"],
        moe_held=(c["experts_held_from"], c["n_routed_experts"]),
    )


def token_fwd_flops(config: dict, visible: float, decoded: bool,
                    chunk: int) -> float:
    """Forward FLOPs of ONE token that sees ``visible`` positions, of
    this chip's share (module docstring): ``decoded`` in the absorbed
    form and with the head, else as one of a prefill chunk of ``chunk``
    tokens in the materialised form and without it."""
    c = config
    d, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    expert = 3 * d * c["moe_intermediate_size"]
    attn = d * rq + rq * h * (dn + dr) + d * (rkv + dr) + h * dv * d
    if decoded:
        attn += h * rkv * (dn + dv)                  # absorb and lift
        attend = 2.0 * h * (2 * rkv + dr) * visible
    else:
        attn += rkv * h * (dn + dv) * visible / chunk   # K and V, once a chunk
        attend = 2.0 * h * (dn + dr + dv) * visible
    dense = c["first_k_dense_replace"]
    routed = (
        c["num_experts_per_tok"] * c["n_routed_experts"]
        / c["n_router_outputs"]
    )
    moe = (
        d * c["n_router_outputs"]
        + (c["n_shared_experts"] + routed) * expert
    )
    layers = c["num_hidden_layers"]
    return (
        layers * (2.0 * attn + attend)
        + dense * 2.0 * 3 * d * c["intermediate_size"]
        + (layers - dense) * 2.0 * moe
        + (2.0 * d * c["vocab_size"] if decoded else 0.0)
    )


class Driver(serve.Driver):
    #: a planted fault (``calibrate``), None in every run of the cell
    fault: str | None = None
    #: True while a prefill chunk's tokens are being counted
    _in_chunk = False

    def weights_seed(self) -> int:
        """The seed the weights are drawn from: the traffic file's
        ``weights_seed`` where it names one, else the run's. The weights
        decide how many of a chunk's token-expert pairs fall to the held
        experts, and so how long a chunk and a tick take: drawn from the
        run's seed they made the work differ from seed to seed, so a
        traffic file can fix them and leave the seed the prompts."""
        return int(self.traffic.get("weights_seed", self.seed))

    def make_engine(self) -> None:
        import jax.numpy as jnp

        from singa_tpu.serve import Engine, EngineConfig, Scheduler

        t = self.traffic
        self.mcfg = model_config(self.config, t)
        params = weights.make(
            self.reference_specs(), self.weights_seed(),
            jnp.dtype(self.config["torch_dtype"]),
        )
        self.engine = Engine(params, self.mcfg, EngineConfig(
            slots=t["slots"], kv_block_len=t["kv_block_len"],
            kv_blocks=t["kv_blocks"], max_prefill_chunk=t["max_prefill_chunk"],
        ))
        self.sched = Scheduler(self.engine)
        if self.fault is not None:
            self._plant(self.fault)

    def _plant(self, fault: str) -> None:
        """A fault under the timed path, planted while the engine's two
        programs are traced. ``k_pe_unrotated``: the rotary key all heads
        share goes to the pool as it left its projection (queries are
        still rotated). ``bias_weighs``: the router's selection bias is
        in the gates as well as in the choice."""
        import jax
        import jax.numpy as jnp

        from singa_tpu.models import transformer
        from singa_tpu.parallel import moe

        engine, rope, gates = self.engine, transformer._rope, moe.topk_gates

        def rope_but_the_key(x, *args):
            return x if x.shape[1] == 1 else rope(x, *args)

        def gates_from_biased(x2d, params, top_k, score, scale):
            _, chosen = gates(x2d, params, top_k, score, scale)
            s = jax.nn.sigmoid(jnp.matmul(
                x2d.astype(jnp.float32), params["gate"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )) + params["bias"].astype(jnp.float32)
            picked = jnp.where(chosen, s, 0.0)
            return picked / jnp.sum(picked, -1, keepdims=True) * scale, chosen

        def faulty(program):
            def run(*args):
                if fault == "k_pe_unrotated":
                    transformer._rope = rope_but_the_key
                elif fault == "bias_weighs":
                    moe.topk_gates = gates_from_biased
                else:
                    raise ValueError(f"fault {fault!r} not one of {FAULTS}")
                try:
                    return program(*args)
                finally:
                    transformer._rope, moe.topk_gates = rope, gates
            return run

        engine._decode_jit = jax.jit(
            faulty(engine._decode), donate_argnums=(1,)
        )
        engine._prefill_jit = jax.jit(
            faulty(engine._prefill), donate_argnums=(1,)
        )

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
        return token_fwd_flops(
            self.config, position, not self._in_chunk,
            self.traffic["max_prefill_chunk"],
        )

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

        params = weights.make(
            self.reference_specs(), self.weights_seed(),
            jnp.dtype(self.config["torch_dtype"]),
        )
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
