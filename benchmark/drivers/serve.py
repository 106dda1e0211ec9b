"""Driver ``serve``: the code-API LM behind ``Scheduler`` over ``Engine``.

Set-up draws the weights from the seed, builds ONE engine and scheduler,
and ramps: every caller sends a request and the loop ticks until each
caller has had one finish. Only a ``--trace 1`` run wraps
``engine.decode`` and ``engine.prefill_chunk`` in the benchmark's own
spans, around the calls alone: no wait is put into them, so a program
that overlaps the pull of one tick's tokens with the next dispatch is
timed as it runs. A ``--trace 0`` run leaves the engine as it is.
That warms every program the window drives — admit, prefill chunk,
activate, decode, retire. The window keeps ticking the SAME scheduler
for the seconds asked; after every ``Scheduler.tick()`` the driver
stamps the tokens that reached the callers and lets each caller whose
request finished send its next (a closed loop).

After the window the engine is dropped, and a sample of the requests
finished in it — the longest, and others drawn from the seed — goes
through the plain reference (``benchmark/reference/lm.py``): one full
forward over each prompt with its served tokens. The number compared is
the widest gap by which a served token's logit lies under the
reference's best at its position.
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from benchmark import flops, traffic as traffic_gen, weights
from benchmark.reference import lm as ref_lm


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100), by linear interpolation between
    order statistics (numpy's default)."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


#: a tick stalled when it took more than this many median ticks (the
#: longest sound tick, two prefill chunks beside a decode, takes two)
STALL_MEDIANS = 5.0


def tick_stats(rows) -> dict:
    """The windows' ``tick`` spans -> what a far-off run is read by. A
    tick here is the whole turn of the loop, from its start to the next
    one's (the last of a window: to the window's end), so the caller's
    side counts. ``stall_s`` sums what each tick took beyond
    ``STALL_MEDIANS`` medians and ``stall_ticks`` counts those ticks;
    ``stalls`` places the ten longest of them: seconds into its window,
    the turn's milliseconds, and those of it inside
    ``Scheduler.tick()``. A few long waits read there, a slower host in
    ``tick_mean_ms``, the mean over the ticks that did not stall (the
    median sits on the edge between ticks with and without a prefill
    chunk and moves with the mix). ``caller_s`` is what the windows
    spent outside ``Scheduler.tick()``: the benchmark's own side of the
    loop. Nothing where no window closed."""
    ticks = sorted((r[1], r[2]) for r in rows if r[0] == "tick")
    turns = []  # (seconds, into the window, inside the span)
    for _, w0, w1, _ in (r for r in rows if r[0] == "window"):
        mine = [(t0, t1) for t0, t1 in ticks if w0 <= t0 < w1]
        starts = [t0 for t0, _ in mine] + [w1]
        turns += [
            (nxt - t0, t0 - w0, t1 - t0)
            for (t0, t1), nxt in zip(mine, starts[1:])
        ]
    if not turns:
        return {}
    took = [t[0] for t in turns]
    median = statistics.median(took)
    stalled = sorted(
        (t for t in turns if t[0] > STALL_MEDIANS * median), reverse=True
    )
    return {
        "tick_p50_ms": 1000.0 * median,
        # under half of the ticks can lie over the median: some did not stall
        "tick_mean_ms": 1000.0 * (
            sum(took) - sum(t[0] for t in stalled)
        ) / (len(took) - len(stalled)),
        "tick_p99_ms": 1000.0 * percentile(took, 99),
        "tick_max_ms": 1000.0 * max(took),
        "stall_s": sum(t[0] - STALL_MEDIANS * median for t in stalled),
        "stall_ticks": len(stalled),
        "stalls": [
            [at, 1000.0 * turn, 1000.0 * inside]
            for turn, at, inside in stalled[:10]
        ],
        "caller_s": sum(took) - sum(t[2] for t in turns),
    }


class Driver:
    def __init__(self, *, config, traffic, limits, seed, devices, work, spans):
        self.config, self.traffic, self.limits = config, traffic, limits
        self.seed, self.devices, self.work, self.spans = (
            seed, devices, work, spans,
        )
        self.engine = self.sched = None
        self.window_s = 0.0
        self.tokens_out = 0          # output tokens delivered in windows
        self.flops_done = 0.0        # model FLOPs of the windows' tokens
        self.gaps: list[float] = []  # inter-token gaps, seconds
        self.ttfts: list[float] = []
        self.done: list = []         # requests finished in a window
        self.bad = 0                 # finished with the wrong token count
        self.in_window = False
        #: a ``--trace 1`` run: engine spans and the model's FLOPs are
        #: taken (per-layer metrics read them), else neither
        self.traced = spans.annotate

    # -- set-up ---------------------------------------------------------

    # The served model is the three methods that follow (the reference
    # counts as one): a second served LM is a subclass in a file of its
    # own that overrides them, and the closed loop below stays as it is.

    def make_engine(self) -> None:
        """Configuration and traffic -> the program's model configuration
        (``self.mcfg``: the loop reads its ``vocab`` and ``max_len``),
        the parameters, ONE ``Engine`` (``self.engine``) and its
        ``Scheduler`` (``self.sched``)."""
        from singa_tpu.models.transformer import TransformerConfig
        from singa_tpu.serve import Engine, EngineConfig, Scheduler

        c, t = self.config, self.traffic
        self.mcfg = TransformerConfig(
            vocab=c["vocab_size"], d_model=c["n_embd"], n_heads=c["n_head"],
            n_layers=c["n_layer"], d_ff=c["n_inner"], max_len=c["n_positions"],
        )
        params = weights.make(self.reference_specs(), self.seed)
        self.engine = Engine(params, self.mcfg, EngineConfig(
            slots=t["slots"], kv_block_len=t["kv_block_len"],
            kv_blocks=t["kv_blocks"], max_prefill_chunk=t["max_prefill_chunk"],
        ))
        self.sched = Scheduler(self.engine)

    def token_fwd_flops(self, position: int) -> float:
        """Forward FLOPs of one token that reads ``position`` cached
        positions (its own among them)."""
        return flops.lm_token_fwd_flops(self.config, position)

    def reference_specs(self) -> dict:
        """The parameters' names, shapes and how they are drawn: what
        ``weights.make`` turns into the served weights, and again into
        the reference's."""
        return ref_lm.lm_specs(self.config)

    def reference_forward(self, params, seq, arith: str = "float32"):
        """The plain reference: tokens (S,) -> logits (S, vocab), row t
        scoring the token at t + 1. ``arith`` below float32 is the
        control."""
        return ref_lm.forward(params, seq, self.config, arith)

    def build(self):
        self.make_engine()
        self.tick_attrs: dict = {}
        if self.traced:
            self._wrap_engine()
        self.requests = traffic_gen.requests(
            self.traffic, self.mcfg.vocab, self.seed
        )
        self.next_request = 0
        #: rid -> [tokens seen, stamp of the last one]
        self.seen: dict[int, list] = {}

    def _wrap_engine(self) -> None:
        """Spans around the engine's two calls, from outside (the
        scheduler finds the wrapped methods on the instance), each
        counted into the attributes of the tick it ran in. A traced
        run only."""
        engine, spans = self.engine, self.spans
        decode, prefill = engine.decode, engine.prefill_chunk

        def timed_decode():
            self.tick_attrs["decodes"] += 1
            with spans.span("decode"):
                return decode()

        def timed_prefill(slot, tokens, pos0):
            self.tick_attrs["prefill_chunks"] += 1
            with spans.span("prefill_chunk", tokens=len(tokens)):
                out = prefill(slot, tokens, pos0)
            if self.in_window:
                n = len(tokens)
                # positions pos0+1 .. pos0+n are read by the chunk's rows
                self.flops_done += sum(
                    self.token_fwd_flops(pos0 + i + 1) for i in range(n)
                )
            return out

        engine.decode, engine.prefill_chunk = timed_decode, timed_prefill

    def _submit_next(self) -> None:
        from singa_tpu.serve import Request

        r = self.requests[self.next_request % len(self.requests)]
        rid = self.next_request
        self.next_request += 1
        self.sched.submit(Request(
            rid=rid, prompt=r["prompt"], max_new_tokens=r["max_new_tokens"],
            temperature=0.0 if self.traffic["greedy"] else 1.0, seed=rid,
        ))

    def _tick(self) -> None:
        """One scheduler tick, then the caller's side of it: stamp the
        tokens that arrived, and let each caller whose request finished
        send its next."""
        sched = self.sched
        n_done = len(sched.finished)
        with self.spans.span("tick", decodes=0, prefill_chunks=0) as span:
            self.tick_attrs = span.attrs  # the engine's wrappers count here
            sched.tick()
        now = time.perf_counter()
        live = sched.in_flight + sched.finished[n_done:]
        for req in live:
            seen = self.seen.setdefault(req.rid, [0, None])
            new = len(req.tokens) - seen[0]
            if new <= 0:
                continue
            if self.in_window:
                self.tokens_out += new
                if seen[1] is not None:
                    self.gaps.append(now - seen[1])
                # tokens past the first of a tick arrive together
                self.gaps.extend([0.0] * (new - 1))
                if seen[0] == 0:
                    self.ttfts.append(req.first_token_mono - req.enqueue_mono)
                if self.traced:
                    # each decoded token read the cache up to its own
                    # position
                    for i in range(max(seen[0], 1), len(req.tokens)):
                        self.flops_done += self.token_fwd_flops(
                            len(req.prompt) + i
                        )
            seen[0], seen[1] = len(req.tokens), now
        for req in sched.finished[n_done:]:
            if self.in_window:
                self.done.append(req)
                self.bad += len(req.tokens) != req.max_new_tokens
            self.seen.pop(req.rid, None)
            self._submit_next()

    def setup(self) -> None:
        self.build()
        for _ in range(self.traffic["callers"]):
            self._submit_next()
        # the ramp: until every caller has had one request finish
        while len(self.sched.finished) < self.traffic["callers"]:
            self._tick()
        self.sched.reset_counters()

    # -- the window -----------------------------------------------------

    def window(self, seconds: float) -> None:
        self.in_window = True
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self._tick()
        t1 = time.perf_counter()
        self.window_s += t1 - t0
        self.in_window = False
        # where the window's ticks end (``tick_stats``): a row, and no
        # annotation in the trace
        self.spans.rows.append(("window", t0, t1, {}))

    def end_to_end(self) -> dict:
        return {
            "serve_tokens_per_s": self.tokens_out / self.window_s,
            "serve_itl_p95_ms": 1000.0 * percentile(self.gaps, 95),
        }

    def counters(self) -> dict:
        s = self.sched
        return {
            "window_s": self.window_s, "tokens_out": self.tokens_out,
            "requests_finished": len(self.done),
            "requests_first_token": len(self.ttfts),
            "itl_samples": len(self.gaps),
            "ticks": s.ticks, "decode_ticks": s.decode_ticks,
            "prefill_chunks": s.prefill_chunks,
            "backpressure_ticks": s.backpressure_ticks,
            "mean_live_slots": s._live_ticks / max(s.decode_ticks, 1),
            "ttft_p50_ms": 1000.0 * statistics.median(self.ttfts),
            "ttft_p95_ms": 1000.0 * percentile(self.ttfts, 95),
            "ttft_max_ms": 1000.0 * max(self.ttfts),
            "itl_p50_ms": 1000.0 * statistics.median(self.gaps),
            "model_flops": self.flops_done,
            **tick_stats(self.spans.rows),
        }

    def attempted_failed(self) -> tuple[int, int]:
        return len(self.done), self.bad

    # -- after the window -----------------------------------------------

    def release(self) -> None:
        import jax

        # keep what the check reads: prompts and served tokens
        self.sample = self._sample()
        self.engine = self.sched = None
        self.seen.clear()
        gc.collect()
        jax.clear_caches()

    def _sample(self) -> list[tuple[np.ndarray, list[int]]]:
        """The longest finished request and others drawn from the seed."""
        done = sorted(
            self.done, key=lambda r: -(len(r.prompt) + len(r.tokens))
        )
        if not done:
            return []
        rng = np.random.default_rng(self.seed)
        rest = list(rng.permutation(len(done) - 1) + 1)
        take = [0] + rest[: self.traffic["check_requests"] - 1]
        return [
            (np.asarray(done[i].prompt, np.int32), list(done[i].tokens))
            for i in take
        ]

    def logit_gaps(self, sample, arith: str | None = None) -> float:
        """The widest gap, over every served position of ``sample``,
        between the reference's best logit and the logit of the token
        served there. With ``arith`` the token judged at each position
        is the one that arithmetic puts first (the control)."""
        import jax
        import jax.numpy as jnp

        params = weights.make(self.reference_specs(), self.seed)
        size = self.mcfg.max_len

        @jax.jit
        def gaps(params, seq, served):
            logits = self.reference_forward(params, seq)
            if arith is not None:
                served = jnp.argmax(
                    self.reference_forward(params, seq, arith), axis=-1
                )
            best = jnp.max(logits, axis=-1)
            got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
            return best - got

        worst = 0.0
        for prompt, tokens in sample:
            seq = np.zeros((size,), np.int32)
            full = np.concatenate([prompt, np.asarray(tokens, np.int32)])
            n = min(len(full), size)
            seq[:n] = full[:n]
            # row t scores the token at t + 1: the served tokens sit at
            # rows len(prompt) - 1 .. len(prompt) + len(tokens) - 2
            served = np.zeros((size,), np.int32)
            served[: n - 1] = full[1:n]
            g = np.asarray(gaps(params, jnp.asarray(seq), jnp.asarray(served)))
            lo, hi = len(prompt) - 1, len(prompt) + len(tokens) - 1
            if not np.all(np.isfinite(g[lo:hi])):
                return float("inf")
            worst = max(worst, float(g[lo:hi].max()))
        return worst

    def check(self) -> dict:
        value = self.logit_gaps(self.sample) if self.sample else None
        return {"logit_gap": {"value": value, "limit": self.limits["logit_gap"]}}

    def calibrate(self, controls=(), faults=(), seconds=8.0) -> dict:
        """One seed's readings: a short window at the cell's own load,
        the program's widest gap, and each control's over the same
        prompts and tokens."""
        self.setup()
        self.window(seconds)
        self.release()
        out = {"program": {
            "logit_gap": self.logit_gaps(self.sample),
            "served_tokens": sum(len(t) for _, t in self.sample),
        }}
        for arith in controls:
            out[arith] = {"logit_gap": self.logit_gaps(self.sample, arith)}
        return out
