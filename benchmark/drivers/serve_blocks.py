"""Driver ``serve_blocks``: an LM generated by diffusion over blocks
behind ``Scheduler`` over ``Engine``, in ``drivers/serve.py``'s closed
loop — its ticks, stamps and spans are inherited untouched.

The model is the four methods ``drivers/serve.py`` asks of a subclass
(``make_engine``, ``token_fwd_flops``, ``reference_specs``, and the
reference behind the check, ``reference/sdar_moe.py``), and the check
restated for blocks:

- prompts never hold the mask id, and each output budget is rounded up
  so that prompt + answer ends on a block's edge (the family generates
  whole blocks);
- **FLOPs are those of the passes the schedule states**, whatever the
  program runs: a block of B tokens costs ``steps + 1`` forwards of its
  B positions (``steps`` denoising passes and the commit), each position
  2 x (attention projections + router + k experts) a layer + 2 x the
  head + attention over the positions it sees; a prompt token costs one
  forward without the head;
- **the check replays ``Request.unmask_pass``**. For each sampled
  request and each pass index s, the reference scores the sequence as it
  stood at pass s (tokens fixed before s in place, the rest masked)
  beside the clean sequence (``two_stream``). ``logit_gap_mean``: over
  every delivered token, the reference's best logit at its position in
  the pass that fixed it, less the served token's; the mean (the widest,
  ``logit_gap``, is reported and no longer compared: it swings by its
  nature and left no room under the control, PERF.md section 6, PR 33).
  ``confidence_gap``: how
  far the ORDER of unmasking lies from the reference's, as a share of
  how far it could. Over every pass and block that had a choice (more
  positions masked than the rule fixes), in log-probability by the
  reference: the regret — its n-th best confidence among the positions
  then masked, less the least confident position the program chose —
  summed, over the room — that n-th best less the least confident
  masked position — summed. 0 where every pass chose the reference's
  set, 1 where every pass chose the positions it was LEAST sure of,
  about 2/3 for sets chosen blindly; a near-tie that rounding flips
  adds next to nothing. (The widest single regret, which this replaced,
  could not tell rounding from a wrong order: at seeded weights a
  block's confidences lie closer together than bfloat16 moves them.)
  Prefill, every committed block read back through the cache, the router
  and the order of unmasking are all held to the reference's full
  forward. A block whose pass fixed another number of positions than the
  rule states reads infinity.
"""

from __future__ import annotations

import numpy as np

from benchmark import weights
from benchmark.drivers import serve
from benchmark.reference import sdar_moe as ref

#: the faults ``calibrate`` can plant under the timed path
FAULTS = ("commit_skipped", "least_confident")


def model_config(config: dict, traffic: dict):
    """Published keys -> the program's ``TransformerConfig``."""
    from singa_tpu.models.transformer import TransformerConfig

    c = config
    return TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        d_ff=c["moe_intermediate_size"], max_len=traffic["max_model_len"],
        norm="rmsnorm", norm_eps=c["rms_norm_eps"],
        pos="rope", rope_theta=float(c["rope_theta"]),
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        qk_norm=True, tied_head=c["tie_word_embeddings"],
        moe_experts=c["num_experts"], moe_top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        diffusion_block=c["block_length"], mask_id=c["mask_token_id"],
    )


def position_fwd_flops(config: dict, visible: float, head: bool) -> float:
    """Forward FLOPs of ONE position that sees ``visible`` positions:
    2 x the matrix-product parameters it meets (per layer Wq, Wk, Wv, Wo,
    the router and its k experts; the head if ``head``), plus the score
    and value products over what it sees (4 x Hq x D x visible a layer)."""
    c = config
    d, hd = c["hidden_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    layer = (
        d * (hq + 2 * hkv) * hd + hq * hd * d + d * c["num_experts"]
        + c["num_experts_per_tok"] * 3 * d * c["moe_intermediate_size"]
    )
    return (
        c["num_hidden_layers"] * (2.0 * layer + 4.0 * hq * hd * visible)
        + (2.0 * d * c["vocab_size"] if head else 0.0)
    )


def replay(request: tuple, size: int, block: int, mask_id: int,
           steps: int):
    """One served request -> what the reference is asked, a pass index
    at a time: (clean (size,), [noisy_s (size,) for s in range(steps)],
    answer (size,) bool, fixed_at (size,) int — ``steps`` where a
    position is no answer token). ``size`` pads to whole blocks past the
    request's end (later blocks, which no earlier one sees)."""
    prompt, tokens, unmask_pass = request
    p, n = len(prompt), len(tokens)
    if (p + n) % block or p + n > size or len(unmask_pass) != n:
        raise ValueError(
            f"request of {p} + {n} tokens with {len(unmask_pass)} passes "
            f"does not end on an edge of blocks of {block} within {size}"
        )
    clean = np.zeros((size,), np.int32)
    clean[:p], clean[p:p + n] = prompt, tokens
    fixed_at = np.full((size,), steps, np.int32)
    fixed_at[p:p + n] = unmask_pass
    answer = np.zeros((size,), bool)
    answer[p:p + n] = True
    noisy = [
        np.where(answer & (fixed_at >= s), mask_id, clean).astype(np.int32)
        for s in range(steps)
    ]
    return clean, noisy, answer, fixed_at


def pass_numbers(ref_logits, answer, fixed_at, s: int, block: int,
                 n_fix: int, judged_logits=None, served=None):
    """What pass ``s`` of one request adds to the two gaps, against the
    reference's ``ref_logits`` (size, vocab) of that pass.

    The program is judged by what it served: ``served`` (size,) tokens,
    and the positions it chose, those with ``fixed_at == s``. A control
    is judged by ``judged_logits`` of the same pass: its greedy tokens
    and the ``min(n_fix, masked)`` positions of each block it is most
    confident of. -> (logit_gap, regret, room, gap_sum, judged): the
    widest gap of a token, the two sums ``confidence_gap`` is the ratio
    of (module docstring), over the blocks of this pass that had a
    choice, and the gaps of the tokens this pass fixed summed, with how
    many they were (``logit_gap_mean`` is the ratio of those two over
    all passes). All but the count are infinity where a block fixed
    another number of positions than the rule states."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    logp = jax.nn.log_softmax(ref_logits.astype(f32), axis=-1)
    best = jnp.max(logp, axis=-1)                 # log of the confidence
    masked = (answer & (fixed_at >= s)).reshape(-1, block)
    n_masked = jnp.sum(masked, axis=1)
    n_rule = jnp.minimum(n_fix, n_masked)
    if judged_logits is None:
        chosen = (answer & (fixed_at == s)).reshape(-1, block)
        tokens = served
        ruled = jnp.all(jnp.sum(chosen, axis=1) == n_rule)
    else:
        theirs = jnp.max(jax.nn.log_softmax(judged_logits.astype(f32)), -1)
        order = jnp.argsort(
            -jnp.where(masked, theirs.reshape(-1, block), -jnp.inf), axis=1
        )
        rank = jnp.argsort(order, axis=1)
        chosen = masked & (rank < n_rule[:, None])
        tokens = jnp.argmax(judged_logits, axis=-1)
        ruled = True
    got = jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]
    gaps = jnp.where(chosen.reshape(-1), best - got, 0.0)
    logit_gap = jnp.max(gaps)
    conf = best.reshape(-1, block)
    ranked = -jnp.sort(-jnp.where(masked, conf, -jnp.inf), axis=1)
    nth = jnp.take_along_axis(
        ranked, jnp.maximum(n_rule - 1, 0)[:, None], axis=1
    )[:, 0]
    choice = (n_rule > 0) & (n_masked > n_rule)   # blocks that had one
    least_chosen = jnp.min(jnp.where(chosen, conf, jnp.inf), axis=1)
    least_masked = jnp.min(jnp.where(masked, conf, jnp.inf), axis=1)
    regret = jnp.sum(jnp.where(choice, nth - least_chosen, 0.0))
    room = jnp.sum(jnp.where(choice, nth - least_masked, 0.0))
    bad = jnp.where(ruled, 0.0, jnp.inf)
    return (logit_gap + bad, regret + bad, room + bad, jnp.sum(gaps) + bad,
            jnp.sum(chosen))


def pass_gaps(*args):
    """``pass_numbers``' first three, (logit_gap, regret, room), for the
    program's own test of a served request (``tests/test_block_serve.py``
    replays one through it)."""
    return pass_numbers(*args)[:3]


def confidence_gap(regret: float, room: float) -> float:
    """The share of its room the order of unmasking lies from the
    reference's (module docstring); 0 where no block had a choice."""
    if regret != regret or room != room or np.isinf(regret):
        return np.inf
    return regret / room if room > 0.0 else 0.0


class Driver(serve.Driver):
    #: a planted fault (``calibrate``), None in every run of the cell
    fault: str | None = None

    def make_engine(self) -> None:
        import jax.numpy as jnp

        from singa_tpu.serve import Engine, EngineConfig, Scheduler

        t = self.traffic
        self.mcfg = model_config(self.config, t)
        params = weights.make(
            self.reference_specs(), self.seed,
            jnp.dtype(self.config["torch_dtype"]),
        )
        self.engine = Engine(params, self.mcfg, EngineConfig(
            slots=t["slots"], kv_block_len=t["kv_block_len"],
            kv_blocks=t["kv_blocks"], max_prefill_chunk=t["max_prefill_chunk"],
            block_steps=t["block_steps"],
        ))
        self.sched = Scheduler(self.engine)
        if self.fault is not None:
            self._plant(self.fault)

    def _plant(self, fault: str) -> None:
        """A fault under the timed path, planted while the block step is
        traced. ``commit_skipped``: the scatter of a committed block's K
        and V never lands, so every block after a request's first reads
        a cache without its predecessor. ``least_confident``: a pass
        fixes the masked positions it is LEAST sure of."""
        import jax

        engine, step = self.engine, self.engine._block_step
        top_k, block = jax.lax.top_k, self.mcfg.diffusion_block

        def least(operand, k):
            if operand.shape[-1] != block:      # the router's top-k
                return top_k(operand, k)
            # masked positions hold a confidence in (0, 1], others -1
            flipped = jax.numpy.where(operand < 0, operand, 2.0 - operand)
            return top_k(flipped, k)

        def faulty(params, state):
            if fault == "commit_skipped":
                engine._kv_write = lambda pool_arr, bid, off, fresh: pool_arr
            else:
                jax.lax.top_k = least
            try:
                return step(params, state)
            finally:
                jax.lax.top_k = top_k
                engine.__dict__.pop("_kv_write", None)

        engine._block_step_jit = jax.jit(faulty, donate_argnums=(1,))

    def build(self):
        super().build()
        block, mask = self.mcfg.diffusion_block, self.mcfg.mask_id
        for r in self.requests:
            # the mask id is no word of a prompt, and the family
            # generates whole blocks
            r["prompt"] = np.where(
                r["prompt"] == mask, (mask + 1) % self.mcfg.vocab, r["prompt"]
            ).astype(np.int32)
            r["max_new_tokens"] += -(len(r["prompt"]) + r["max_new_tokens"]) % block

    def _wrap_engine(self) -> None:
        """Spans around the engine's two calls, as the base driver's: a
        block step is the tick's one decode, and a prefilled token costs
        one forward without the head. A traced run only."""
        engine, spans = self.engine, self.spans
        step, prefill = engine.block_step, engine.prefill_chunk
        block = self.mcfg.diffusion_block

        def timed_step():
            self.tick_attrs["decodes"] += 1
            with spans.span("decode"):
                return step()

        def timed_prefill(slot, tokens, pos0):
            self.tick_attrs["prefill_chunks"] += 1
            with spans.span("prefill_chunk", tokens=len(tokens)):
                out = prefill(slot, tokens, pos0)
            if self.in_window:
                self.flops_done += sum(
                    position_fwd_flops(
                        self.config, (pos0 + i) // block * block + block,
                        head=False,
                    )
                    for i in range(len(tokens))
                )
            return out

        engine.block_step, engine.prefill_chunk = timed_step, timed_prefill

    def token_fwd_flops(self, position: int) -> float:
        """What the schedule charges for ONE delivered token at absolute
        ``position``: ``steps + 1`` forwards of it (the denoising passes
        of its block and the commit), head included, each seeing the
        cache up to the end of its block."""
        block = self.mcfg.diffusion_block
        visible = position // block * block + block
        return (self.traffic["block_steps"] + 1) * position_fwd_flops(
            self.config, visible, head=True
        )

    def reference_specs(self) -> dict:
        return ref.specs(self.config)

    def reference_forward(self, params, clean, noisy,
                          arith: str = "float32"):
        """The plain reference for one pass index of one request: logits
        (size, vocab) of the sequence as it stood (``noisy``) beside the
        sequence as it ended (``clean``)."""
        return ref.two_stream(params, clean, noisy, self.config, arith)

    def counters(self) -> dict:
        s = self.sched
        out = super().counters()
        out.update({
            "block_passes": s.block_passes, "block_commits": s.block_commits,
            "tokens_delivered": s.tokens_delivered,
            "experts_hit": s.experts_hit,
            "expert_max_load": s.expert_max_load,
        })
        return out

    # -- after the window -----------------------------------------------

    def _sample(self) -> list[tuple]:
        """The longest finished request and others drawn from the seed
        (the base driver's choice), each with its ``unmask_pass``."""
        done = sorted(
            self.done, key=lambda r: -(len(r.prompt) + len(r.tokens))
        )
        if not done:
            return []
        rng = np.random.default_rng(self.seed)
        rest = list(rng.permutation(len(done) - 1) + 1)
        take = [0] + rest[: self.traffic["check_requests"] - 1]
        return [
            (np.asarray(done[i].prompt, np.int32), list(done[i].tokens),
             list(done[i].unmask_pass))
            for i in take
        ]

    def gaps_of(self, sample, arith: str | None = None) -> dict:
        """``logit_gap`` (the widest over every pass of every request of
        ``sample``), ``logit_gap_mean`` (over every token they deliver)
        and ``confidence_gap`` (over all of them together). The limits
        file says which of them a run compares. With ``arith`` what is judged at each pass is what the reference
        computed in that arithmetic serves and chooses there (the
        control)."""
        import jax.numpy as jnp

        params = weights.make(
            self.reference_specs(), self.seed,
            jnp.dtype(self.config["torch_dtype"]),
        )
        mcfg, steps = self.mcfg, self.traffic["block_steps"]
        block = mcfg.diffusion_block
        widest = regret = room = gap_sum = tokens = 0.0
        for request in sample:
            clean, noisy, answer, fixed_at = replay(
                request, mcfg.max_len, block, mcfg.mask_id, steps
            )
            for s in range(steps):
                logits = self.reference_forward(
                    params, jnp.asarray(clean), jnp.asarray(noisy[s])
                )
                judged = None if arith is None else self.reference_forward(
                    params, jnp.asarray(clean), jnp.asarray(noisy[s]), arith
                )
                g, a, b, total, n = (float(x) for x in pass_numbers(
                    logits, jnp.asarray(answer), jnp.asarray(fixed_at), s,
                    block, block // steps, judged, jnp.asarray(clean),
                ))
                widest = max(widest, g if g == g else np.inf)
                regret, room = regret + a, room + b
                gap_sum, tokens = gap_sum + total, tokens + n
        return {"logit_gap": widest,
                "logit_gap_mean": gap_sum / tokens if tokens else np.inf,
                "confidence_gap": confidence_gap(regret, room)}

    def check(self) -> dict:
        got = self.gaps_of(self.sample) if self.sample else {}
        return {
            name: {"value": got.get(name), "limit": self.limits[name]}
            for name in self.limits
        }

    def calibrate(self, controls=(), faults=(), seconds=8.0) -> dict:
        """One seed's readings: a short window at the cell's own load,
        the program's two gaps, each control's over the same requests
        and passes, and each planted fault's from a window of its own."""
        self.setup()
        self.window(seconds)
        self.release()
        out = {"program": {
            **self.gaps_of(self.sample),
            "served_tokens": sum(len(t) for _, t, _ in self.sample),
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
