"""Continuous batching: admit/retire request streams at each decode tick.

The control plane of the serving tier. The reference's Server answered
every worker's kGet/kPut from one process (src/server/server.cc); this
scheduler answers every client's generation request from one engine:

  - a FIFO request queue; at each tick, queued prompts are admitted
    into free slots while the block pool can cover their whole
    ``prompt + budget`` (all-or-nothing, so a live stream can never
    strand mid-generation on an exhausted pool) — an allocation that
    does not fit applies ADMISSION backpressure: the request waits for
    a retirement, it is never dropped;
  - admitted prompts prefill in fixed chunks, one chunk per request per
    tick, so a long prompt shares the host loop with live decode
    instead of stalling it; with the prefix cache on
    (``serving { prefix_cache { enabled } }``) admission first points
    the new sequence's block table at the pool's longest cached
    block-prefix of its prompt, so the chunk loop starts at the first
    UNCOVERED token — prefill work drops to the uncached tail, and the
    fully-prefilled prompt is registered for future hits once its last
    chunk lands;
  - every live slot advances one token per tick through the engine's
    single fixed-shape decode program; EOS or an exhausted budget
    retires the slot (blocks freed, available to the next admit — the
    continuous part of continuous batching). Decode runs ONE PASS AHEAD
    of the host: a tick dispatches all it has (admissions, chunks with
    their hand-overs, its decode) before it reads anything, and the
    decode it reads is the one dispatched the tick before, so delivery,
    retirement, admission and the next hand-over happen while the device
    works. The last token lives on the device and a pass needs nothing
    from the host, so the tokens are the same; a request that finished
    rides one more pass, unread (``lanes_unread``);
  - with speculation on (``serving { speculate { k } }``), each tick
    instead drafts up to k tokens per live greedy slot (model-free
    n-gram lookup over the request's own prompt+output,
    serve/speculate.py), runs the engine's fixed-shape VERIFY program
    once, and fans every accepted token out to its request — EOS or
    budget hit INSIDE an accepted run retires at exactly the token
    sequential decode would have stopped at (the tail of the run is
    discarded, never delivered). Temperature slots ride the same tick
    with zero drafts. Token streams are identical to one-token ticks
    by construction; only tick count changes. The drafter needs the
    newest token, so such a tick reads its own pass: none is in flight;
  - for a model generated by diffusion over blocks
    (``TransformerConfig.diffusion_block``) a tick dispatches the
    engine's BLOCK STEP instead: a pass over every live slot's current
    block that fixes 0..B of its tokens, out of order. A token is
    DELIVERED — joins ``Request.tokens``, is counted and stamped — when
    it joins the contiguous unmasked prefix of its request, which is
    what a caller can read; ``Request.unmask_pass`` keeps, per
    delivered token, the pass of its block that fixed it. Such a
    request's prefill covers the whole blocks of its prompt and yields
    no token; a budget that ends inside a block stops delivery there.
    Block steps run one pass ahead of the host as one-token ticks do;
  - a SIGTERM'd serving host drains via the resilience plane: the
    serve loop observes ``PreemptionHandler.requested`` at a tick
    boundary, hands every in-flight sequence back (recorded, with its
    partial output), and the host exits EXIT_RESUMABLE (75) — the same
    discipline as a training drain.

Lifecycle events (``request_admit`` / ``prefill`` / ``decode_tick`` /
``retire`` / ``evict`` / ``backpressure`` / ``drain``) and per-request
spans flow into the PR 6 flight recorder, so
``tools/trace.py --summarize`` reports serving p50/p99 and tokens/sec
with no serving-specific plumbing.

Every phase of a tick is an ``obs.span`` (``singa/sched.tick`` and,
inside it, ``admit`` / ``prefill`` / ``decode`` holding ``draft`` /
``dispatch`` / ``pull`` / ``emit``), so a profiler trace lays the
scheduler's work beside the device's operations. Each carries
``tick=``; the spans of one request carry its ``rid=``. ``sched.pull``
is the one place a tick waits for the device, and the one place it
reads from it: after the tick's ``sched.dispatch``. The engine's calls
that hand work over name themselves inside those (``engine.admit`` in
``admit``, ``engine.prefill`` and ``engine.activate`` in ``prefill``,
``engine.retire`` where a request finishes); the pass's own dispatch is
``sched.dispatch``. ``Request.chunks_ahead`` counts, for each token, the
prefill chunks its gap waited behind.
"""

from __future__ import annotations

import collections
import dataclasses
import time

import jax
import numpy as np

from ..obs import span
from .engine import Engine
from .kv_pool import PoolExhausted
from .speculate import make_drafter


@dataclasses.dataclass
class Request:
    """One generation request and its lifecycle bookkeeping."""

    rid: int
    prompt: np.ndarray            # (P,) int32
    max_new_tokens: int
    temperature: float = 0.0
    seed: int = 0
    eos: int | None = None

    # runtime (owned by the scheduler)
    status: str = "queued"        # queued|prefill|decoding|done|evicted
    slot: int | None = None
    tokens: list = dataclasses.field(default_factory=list)
    #: block steps only: per delivered token, which denoising pass of
    #: its block (0-based) fixed it
    unmask_pass: list = dataclasses.field(default_factory=list)
    #: per delivered token, the prefill chunks dispatched between the
    #: pass that delivered the token before it and the pass that
    #: delivered it, that pass's own tick's included: the chunks its gap
    #: waited behind. 0 for the first token, and for each token after
    #: the first that one pass delivers (block steps, speculation)
    chunks_ahead: list = dataclasses.field(default_factory=list)
    enqueue_mono: float = 0.0
    admit_mono: float = 0.0
    first_token_mono: float = 0.0
    finish_mono: float = 0.0
    _prefilled: int = 0
    #: the scheduler's count of chunks sent (``Scheduler._chunks_sent``)
    #: when the pass that delivered its last token went out (a first
    #: token: when its tick read it); None before this scheduler
    #: delivered it any (a sequence migrated in)
    _chunk_mark: int | None = None
    #: admission to retirement, for the recorder's ``requests`` track
    _life: object = None
    #: block steps only: the block in flight, as the host follows it —
    #: per position the pass that fixed its token (None while masked,
    #: -1 for a prompt token), the tokens, how many positions of it are
    #: spoken for (prompt tail or delivered), and passes made on it
    _blk_pass: list = dataclasses.field(default_factory=list)
    _blk_tok: list = dataclasses.field(default_factory=list)
    _blk_read: int = 0
    _blk_passes: int = 0

    @property
    def latency_s(self) -> float:
        """Admit -> finish wall seconds (0 until finished)."""
        return max(0.0, self.finish_mono - self.admit_mono)


class Scheduler:
    """Continuous-batching loop over one Engine."""

    def __init__(self, engine: Engine, *, recorder=None, preemption=None,
                 log=lambda s: None, drafter=None):
        self.engine = engine
        self.recorder = recorder
        self.preemption = preemption
        self.log = log
        #: speculative decode: k > 0 routes every decode tick through
        #: the engine's verify program; the drafter proposes (override
        #: for tests/probes — e.g. speculate.NullDrafter forces zero
        #: acceptance while keeping the whole verify path hot)
        self.spec_k = engine.serving.spec_k
        #: block length of a model generated by diffusion over blocks
        #: (0: one-token ticks): decode ticks dispatch ``block_step``
        self.block_len = engine.cfg.diffusion_block
        #: block-step accounting (all zero for one-token ticks):
        #: slot-passes (one a live slot a block step, commits included),
        #: commits, tokens delivered, experts hit summed over passes and
        #: layers, and the most tokens one expert took in any pass
        self.block_passes = 0
        self.block_commits = 0
        self.tokens_delivered = 0
        self.experts_hit = 0
        self.expert_max_load = 0
        #: one-token passes of a model with top-k expert layers count
        #: those two as well (held experts only, where the engine holds
        #: a share), and (``engine.DECODE_COUNTERS``) the token-expert
        #: pairs the passes routed to held experts, the cache rows their
        #: attention had to read a layer, and the pairs of the prefill
        #: chunks between them
        self.held_pairs = 0
        self.cache_rows = 0
        self.chunk_held_pairs = 0
        #: and of a model with recurrent layers the slots whose state
        #: the passes advanced (``engine.STATE_COUNTERS``)
        self.state_slots_live = 0
        #: decode and block steps run ONE PASS AHEAD of the host: (the
        #: device result of the pass dispatched last tick, the requests
        #: it served by slot, ``_chunks_sent`` when it went out), read
        #: this tick after the next is dispatched (None under
        #: speculation: it reads its own tick's)
        self._in_flight = None
        #: what that costs: lanes of the passes read (``_live_ticks``)
        #: whose request had finished by then, one a finished request
        self.lanes_unread = 0
        if drafter is not None:
            self.drafter = drafter
        else:
            self.drafter = (
                make_drafter(engine.serving.spec_drafter)
                if self.spec_k > 0 else None
            )
        self.spec_drafted = 0
        self.spec_accepted = 0
        #: role gate for the fleet's prefill/decode split
        #: (serve/fleet/host.py): False = ticks run admission + chunked
        #: prefill only and decoding-status requests wait for the fleet
        #: host to migrate them to a decode peer. True (default) = the
        #: unified single-host behavior.
        self.decode_enabled = True
        #: prefix-cache accounting (all zero with the cache off)
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.blocks_shared = 0
        self.cow_copies = 0
        #: hits whose shared prefix ended MID-block (a registered
        #: partial tail was COW-extended) and the tail tokens they saved
        self.partial_hits = 0
        self.tail_tokens_shared = 0
        #: full decode-written blocks indexed at retirement
        #: (``prefix_cache { decode_blocks }``)
        self.decode_blocks_registered = 0
        self.prefill_chunks = 0
        self.prefill_chunks_saved = 0
        #: prefill chunks dispatched over the scheduler's life, never
        #: reset: ``Request.chunks_ahead`` is its difference between two
        #: passes
        self._chunks_sent = 0
        # allocator lifecycle (lru_evict/lru_reclaim) rides the same
        # event path as the scheduler's own admissions
        engine.allocator.on_event = self._event
        self._queue: collections.deque[Request] = collections.deque()
        self._slot_req: dict[int, Request] = {}
        self.ticks = 0
        #: ticks that ran a decode/verify program (>= 1 slot decoding)
        self.decode_ticks = 0
        self.tokens_emitted = 0
        self.backpressure_ticks = 0
        #: sum over ticks of live (decoding) slots — occupancy reporting
        self._live_ticks = 0
        #: wall seconds / tokens over FULL-occupancy decode ticks only:
        #: the steady-state capacity number (admission work is a
        #: per-request constant; a long-running server lives here)
        self.full_tick_s = 0.0
        self.full_tick_tokens = 0
        self.finished: list[Request] = []
        # run-start provenance: which implementation the attend seam
        # runs and, where the engine chose it, why (engine.py
        # choose_attend), so an incident report can say which path this
        # run took (trace.py --summarize serving.attend_impl)
        self._event(
            "kernel_select", site="serve.paged_attention",
            impl=engine.attend_choice,
        )
        # and the form each program's expert layers compiled in
        # (parallel/moe.py choose_expert_form), where the model has any
        for program, form in engine.expert_forms.items():
            self._event(
                "kernel_select", site=f"moe.experts.{program}", impl=form
            )
        for program, form in engine.mamba_forms.items():
            self._event(
                "kernel_select", site=f"mamba.scan.{program}", impl=form
            )

    def reset_counters(self) -> None:
        """Zero every accumulated statistic (ticks, token/draft counts,
        occupancy, backpressure, finished list) — the benchmark
        harnesses call this after a compile-warm request so warmup
        never contaminates measured numbers. Live/queued requests are
        untouched."""
        self.finished.clear()
        self.ticks = self.decode_ticks = 0
        self.tokens_emitted = 0
        self.spec_drafted = self.spec_accepted = 0
        self.prefix_lookups = self.prefix_hits = 0
        self.blocks_shared = self.cow_copies = 0
        self.partial_hits = self.tail_tokens_shared = 0
        self.decode_blocks_registered = 0
        self.prefill_chunks = self.prefill_chunks_saved = 0
        self.block_passes = self.block_commits = 0
        self.tokens_delivered = 0
        self.experts_hit = self.expert_max_load = 0
        self.held_pairs = self.cache_rows = self.chunk_held_pairs = 0
        self.state_slots_live = 0
        self.engine.allocator.reset_stats()
        self._live_ticks = self.lanes_unread = 0
        self.backpressure_ticks = 0
        self.full_tick_s, self.full_tick_tokens = 0.0, 0

    # -- client side ----------------------------------------------------

    def submit(self, req: Request) -> None:
        # any temperature is admissible: the engine's per-slot
        # temperature lane means one compiled program serves every mix
        # of sampling configs (the old same-temperature rejection is
        # gone with it)
        total = len(req.prompt) + req.max_new_tokens
        if total > self.engine.cfg.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.prompt)} + budget "
                f"{req.max_new_tokens} exceeds max_len "
                f"{self.engine.cfg.max_len}"
            )
        if self.block_len and req.temperature > 0.0:
            raise ValueError(
                f"request {req.rid}: temperature {req.temperature} cannot "
                f"run for a model with diffusion_block = {self.block_len} "
                "(block steps unmask greedily by confidence)"
            )
        req.prompt = np.asarray(req.prompt, np.int32)
        # a request that crossed the fleet's front door (or a drain
        # forward) keeps its original stamp, so queue-inclusive latency
        # covers the routing hop too; fresh requests stamp here
        req.enqueue_mono = req.enqueue_mono or time.perf_counter()
        req.status = "queued"
        self._queue.append(req)

    @property
    def in_flight(self) -> list[Request]:
        return list(self._slot_req.values())

    @property
    def busy(self) -> bool:
        return bool(self._queue or self._slot_req)

    def _event(self, kind: str, **payload) -> None:
        if self.recorder is not None:
            self.recorder.event(kind, step=self.ticks, **payload)

    # -- the tick -------------------------------------------------------

    def _admit_some(self) -> None:
        free = [
            s for s in range(self.engine.serving.slots)
            if s not in self._slot_req
        ]
        stalled = False
        while self._queue and free:
            req = self._queue[0]
            with span(
                "sched.admit", tick=self.ticks, rid=req.rid, slot=free[0]
            ) as admitting:
                stalled = not self._admit(req, free[0])
                if stalled:
                    admitting.note(stalled=1)
                    break
            free.pop(0)
        if stalled:
            self.backpressure_ticks += 1
            self._event(
                "backpressure",
                queued=len(self._queue),
                free_blocks=self.engine.allocator.free_blocks,
            )

    def _admit(self, req: Request, slot: int) -> bool:
        """The queue's head into ``slot``: blocks from the allocator,
        the slot's table on the device. -> False where the pool cannot
        cover the request now (it stays queued)."""
        try:
            adm = self.engine.admit(
                slot, len(req.prompt) + req.max_new_tokens,
                prompt=req.prompt,
            )
        except PoolExhausted:
            return False
        self._queue.popleft()
        self._slot_req[slot] = req
        req.slot = slot
        req.status = "prefill"
        # prefill starts at the first token the prefix cache did
        # not cover (lane positions are seeded by pos0 each chunk,
        # so a hit just skips the covered chunks)
        req._prefilled = adm.prefill_from
        # a handed-back (drained) request restarts from scratch on
        # re-admission: its partial output was delivered at evict
        # time, regeneration must not append to it
        req.tokens, req.unmask_pass, req.chunks_ahead = [], [], []
        req._life = span("sched.request", nested=False).start()
        req.admit_mono = req._life.t0
        self._event(
            "request_admit", rid=req.rid, slot=slot,
            prompt_len=int(len(req.prompt)), blocks=len(adm.blocks),
            queued_s=round(req.admit_mono - req.enqueue_mono, 6),
        )
        if self.engine.allocator.cache is not None:
            self.prefix_lookups += 1
        if adm.cached_tokens:
            c = self.engine.serving.max_prefill_chunk
            saved = (
                -(-len(req.prompt) // c)
                - -(-(len(req.prompt) - adm.prefill_from) // c)
            )
            self.prefix_hits += 1
            # blocks this sequence reads through another owner's
            # bytes (a COW'd tail block became private)
            shared = (
                adm.cached_tokens // self.engine.pool.block_len
                - (1 if adm.cow_copied else 0)
            )
            self.blocks_shared += shared
            self.prefill_chunks_saved += saved
            self._event(
                "prefix_hit", rid=req.rid, slot=slot,
                cached_tokens=int(adm.cached_tokens),
                blocks_shared=int(shared), chunks_saved=int(saved),
            )
        if adm.tail_tokens:
            self.partial_hits += 1
            self.tail_tokens_shared += adm.tail_tokens
            self._event(
                "partial_hit", rid=req.rid, slot=slot,
                cached_tokens=int(adm.cached_tokens),
                tail_tokens=int(adm.tail_tokens),
            )
        if adm.cow_copied:
            self.cow_copies += 1
            self._event("cow_copy", rid=req.rid, slot=slot)
        return True

    def _prefill_some(self) -> list:
        """One chunk per prefilling request per tick: decode never waits
        behind more than slots * one chunk of prompt work. -> (slot,
        request, first token on the device) where it was the last."""
        firsts = []
        for slot in sorted(self._slot_req):
            req = self._slot_req[slot]
            if req.status != "prefill":
                continue
            n = min(
                self.engine.serving.max_prefill_chunk,
                self._prefill_len(req) - req._prefilled,
            )
            with span(
                "sched.prefill", tick=self.ticks, rid=req.rid, slot=slot,
                tokens=int(n),
            ):
                first = self._prefill(slot, req, n)
            if first is not None:
                firsts.append((slot, req, first))
        return firsts

    def _prefill_len(self, req: Request) -> int:
        """Prompt tokens that prefill writes: all of them, or under
        block steps the prompt's whole blocks (its tail starts the
        first block that is denoised)."""
        n = len(req.prompt)
        return n // self.block_len * self.block_len if self.block_len else n

    def _prefill(self, slot: int, req: Request, n: int):
        """One chunk of ``n`` prompt tokens; after the last the slot goes
        live. -> the request's first token, still on the device
        (``_first_tokens`` reads it once the tick's decode is out), or
        None: not the last chunk, or block steps, which yield no token."""
        last = None
        if n:
            last = self.engine.prefill_chunk(
                slot, req.prompt[req._prefilled:req._prefilled + n],
                req._prefilled,
            )
            req._prefilled += n
            self.prefill_chunks += 1
            self._chunks_sent += 1
            self._event(
                "prefill", rid=req.rid, slot=slot, tokens=int(n),
                done=int(req._prefilled), of=int(len(req.prompt)),
            )
        if req._prefilled < self._prefill_len(req):
            return None
        req.status = "decoding"
        if self.block_len:
            self.engine.activate_block(slot, req.prompt)
            self._fresh_block(req, list(req.prompt[req._prefilled:]))
            return None
        # every prompt position is now prefill-written: index
        # the fully-covered blocks for future prefix hits
        self.engine.register_prefix(slot, req.prompt)
        return self.engine.activate(
            slot, last, len(req.prompt), req.seed,
            temperature=req.temperature,
        )

    def _first_tokens(self, firsts, tick: int) -> None:
        """Read the first tokens ``_prefill_some`` left on the device and
        deliver them: each comes from a program queued before this tick's
        decode, so the wait is for work already ahead of it. A request
        its first token ends (budget of one, EOS) retires here and rides
        that decode, as any finished request rides one."""
        if not firsts:
            return
        with span("sched.pull", tick=tick):
            toks = jax.device_get([first for _, _, first in firsts])
        now = time.perf_counter()
        for (slot, req, _), tok in zip(firsts, toks):
            req.tokens.append(int(tok))
            # read after every chunk of its tick: none is behind the next
            req.chunks_ahead.append(0)
            req._chunk_mark = self._chunks_sent
            req.first_token_mono = now
            self._check_done(slot, req, int(tok))

    def _check_done(self, slot: int, req: Request, tok: int) -> bool:
        if (req.eos is not None and tok == req.eos) or (
            len(req.tokens) >= req.max_new_tokens
        ):
            self._finish(slot, req, "eos" if req.eos is not None
                         and tok == req.eos else "budget")
            return True
        return False

    def _finish(self, slot: int, req: Request, reason: str) -> None:
        if (
            self.engine.serving.prefix_decode_blocks
            and self.engine.allocator.cache is not None
            and req.tokens
        ):
            # multi-turn reuse: index the conversation's FULL blocks —
            # decode-written ones included — before the release below
            # parks them, so a follow-up prompt replaying this history
            # hits it (token-level parity: the PR 9 cross-shape caveat)
            n = self.engine.register_history(
                slot,
                np.concatenate(
                    [np.asarray(req.prompt, np.int32),
                     np.asarray(req.tokens, np.int32)]
                ),
            )
            if n:
                self.decode_blocks_registered += n
                self._event(
                    "decode_register", rid=req.rid, slot=slot,
                    blocks=int(n),
                )
        self.engine.retire(slot)
        del self._slot_req[slot]
        req.status = "done"
        req.finish_mono = time.perf_counter()
        self.finished.append(req)
        self._event(
            "retire", rid=req.rid, slot=slot, reason=reason,
            tokens=int(len(req.tokens)),
            latency_s=round(req.latency_s, 6),
        )
        if req._life is not None:
            req._life.stop()
            req._life.record(
                self.recorder, "request", track="requests",
                steps=len(req.tokens),
            )

    def _fresh_block(self, req: Request, tail=()) -> None:
        """The host's copy of a slot's block lanes: ``tail`` prompt
        tokens in place (spoken for, never delivered), the rest masked."""
        rest = self.block_len - len(tail)
        req._blk_tok = [int(t) for t in tail] + [0] * rest
        req._blk_pass = [-1] * len(tail) + [None] * rest
        req._blk_read = len(tail)
        req._blk_passes = 0

    def _deliverable(self, req: Request, fixed) -> list[int]:
        """Follow one block step on the host's copy of the slot's block
        (``fixed``: the (B,) tokens the pass fixed, -1 elsewhere) and
        return the tokens that JOINED THE CONTIGUOUS UNMASKED PREFIX of
        the request by it, in order — what its caller can now read.
        Their passes go to ``Request.unmask_pass`` as the emit loop
        appends the tokens (it stops at an EOS and trims the passes of
        what it drops; the budget is kept to here). A pass over a
        block with nothing masked was its commit: the next block starts."""
        if None not in req._blk_pass:
            self.block_commits += 1
            self._fresh_block(req)
            return []
        for j, tok in enumerate(fixed):
            if tok >= 0:
                req._blk_tok[j] = int(tok)
                req._blk_pass[j] = req._blk_passes
        req._blk_passes += 1
        room = req.max_new_tokens - len(req.tokens)
        out = []
        while (
            req._blk_read < self.block_len and len(out) < room
            and req._blk_pass[req._blk_read] is not None
        ):
            out.append(req._blk_tok[req._blk_read])
            req.unmask_pass.append(req._blk_pass[req._blk_read])
            req._blk_read += 1
        return out

    def _draft_for(self, req: Request) -> list[int]:
        """Draft tokens for one decoding request: greedy slots only
        (speculation is greedy-only per slot — a temperature slot's
        sampled continuation is not the drafter's to predict), clamped
        so the accepted run can never overshoot the budget (at most
        ``budget_remaining`` tokens emit per tick, the last being the
        bonus) nor write past the request's allocated blocks."""
        if req.temperature > 0.0:
            return []
        budget_rem = req.max_new_tokens - len(req.tokens)
        n = min(self.spec_k, budget_rem - 1)
        if n <= 0:
            return []
        ctx = list(req.prompt) + req.tokens
        return list(self.drafter.draft(ctx, n))[:n]

    def tick(self) -> int:
        """One scheduling round: retire happens inline as tokens land,
        admit fills freed slots, prefill advances one chunk each, then
        every live slot decodes — one token through the decode program,
        a pass over its block through the block step, or up to
        spec_k + 1 tokens through the verify program when speculation is
        on (none dispatched on a prefill-role fleet host,
        ``decode_enabled`` False). Every dispatch of a tick goes out
        before any read (``_decode_some``). -> tokens emitted (first
        tokens, as ever, not among them)."""
        with span("sched.tick", tick=self.ticks):
            self._admit_some()
            firsts = self._prefill_some()
            emitted_n = self._decode_some(firsts, self.decode_enabled)
            if self._in_flight is not None and not self._slot_req:
                # the last request finished with one more pass on its
                # way: nobody is left to read it, and it is not counted
                self._in_flight = None
        self.ticks += 1
        return emitted_n

    def settle(self) -> int:
        """Read the pass in flight and fan it out, dispatching no other:
        afterwards ``Request.tokens`` and the device's lanes agree, which
        whoever hands a live sequence on (``fleet/host.py``: drain to a
        peer) or swaps the weights under it (the rollout's flip) needs
        first. Requests may finish by it. -> tokens emitted."""
        return self._decode_some(dispatch=False)

    def _decode_some(self, firsts=(), dispatch: bool = True) -> int:
        """The decode phase of one tick, ONE PASS AHEAD of the host:
        this tick's pass over every decoding-status slot goes out first
        (``dispatch``), then ``firsts`` and the pass dispatched the tick
        before are read and fanned out to the requests that pass was
        dispatched FOR; EOS/budget retires inline. ``decode()`` and
        ``block_step()`` differ only in how the pulled array becomes
        tokens a slot. A request that finished since its pass went out
        rode it unread: the row it wrote lies inside the blocks it was
        admitted with, and the device runs its ``retire`` and the next
        ``admit`` behind it. Speculation alone reads its own tick's pass
        (its drafter needs the newest tokens). -> tokens emitted."""
        decoding = {
            s: r for s, r in self._slot_req.items() if r.status == "decoding"
        } if dispatch else {}
        in_flight, self._in_flight = self._in_flight, None
        if not (decoding or in_flight or firsts):
            return 0
        tick, accepted_n, emitted_n, unread = self.ticks, 0, 0, 0
        # draft, dispatch and pull: the recorder's ``decode_tick`` and
        # ``full_tick_s`` (the fan-out below is not in them)
        with span("sched.decode", tick=tick) as whole:
            if self.spec_k > 0:
                self._first_tokens(firsts, tick)
                served = {
                    s: r for s, r in decoding.items()
                    if r.status == "decoding"
                }
                if not served:
                    return 0
                slots = self.engine.serving.slots
                drafts = np.zeros((slots, self.spec_k), np.int32)
                nd = np.zeros((slots,), np.int32)
                with span("sched.draft", tick=tick) as drafting:
                    for slot, req in served.items():
                        d = self._draft_for(req)
                        drafts[slot, :len(d)] = d
                        nd[slot] = len(d)
                    drafted_n = int(nd.sum())
                    drafting.note(drafted=drafted_n)
                self.spec_drafted += drafted_n
                self._event(
                    "spec_draft", drafted=drafted_n, live=len(served),
                )
                with span("sched.dispatch", tick=tick, live=len(served)):
                    emitted_dev, accepted_dev = self.engine.verify(drafts, nd)
                sent = self._chunks_sent
                with span("sched.pull", tick=tick):
                    emitted = np.asarray(emitted_dev)
                    accepted_n = int(np.asarray(accepted_dev).sum())
                self.spec_accepted += accepted_n
            else:
                if decoding:
                    step = (
                        self.engine.block_step if self.block_len
                        else self.engine.decode
                    )
                    with span(
                        "sched.dispatch", tick=tick, live=len(decoding)
                    ):
                        self._in_flight = (
                            step(), decoding, self._chunks_sent
                        )
                self._first_tokens(firsts, tick)
                if in_flight is None:
                    return 0
                out_dev, served, sent = in_flight
                with span("sched.pull", tick=tick):
                    out = np.asarray(out_dev)
                if self.block_len:
                    # plain lists: the walk below is a few hundred scalar
                    # reads a tick, ten times dearer on numpy scalars
                    fixed = out[:-2].reshape(-1, self.block_len).tolist()
                    self.block_passes += len(served)
                    self.experts_hit += int(out[-2])
                    self.expert_max_load = max(
                        self.expert_max_load, int(out[-1])
                    )
                    # what each slot can now READ of what the pass fixed
                    emitted = {
                        s: self._deliverable(r, fixed[s])
                        for s, r in served.items() if r.status == "decoding"
                    }
                else:
                    n = self.engine.decode_counters
                    if n:
                        # each counter adds up under its own name; the
                        # most one expert took is a maximum
                        for name, value in zip(
                            self.engine.decode_counter_names,
                            out[-n:].tolist(),
                        ):
                            had = getattr(self, name)
                            setattr(self, name, (
                                max(had, value) if name == "expert_max_load"
                                else had + value
                            ))
                        out = out[:-n]
                    emitted = out[:, None].tolist()
        with span("sched.emit", tick=tick) as fan_out:
            for slot, req in sorted(served.items()):
                if req.status != "decoding":
                    # finished since its pass was dispatched
                    unread += 1
                    continue
                # fan the slot's accepted run out token by token: EOS
                # or budget INSIDE the run stops exactly where
                # sequential decode would have — the tail is discarded.
                # The run's first token waited behind the chunks sent
                # since the pass that delivered the token before it
                ahead = (
                    0 if req._chunk_mark is None
                    else sent - req._chunk_mark
                )
                for tok in emitted[slot]:
                    if tok < 0:
                        break
                    req.chunks_ahead.append(ahead if req.tokens else 0)
                    req.tokens.append(int(tok))
                    req._chunk_mark, ahead = sent, 0
                    emitted_n += 1
                    if self.block_len and len(req.tokens) == 1:
                        req.first_token_mono = time.perf_counter()
                    if self._check_done(slot, req, int(tok)):
                        break
                # an EOS inside a run drops the run's tail, passes too
                del req.unmask_pass[len(req.tokens):]
            fan_out.note(emitted=emitted_n)
        self._live_ticks += len(served)
        self.lanes_unread += unread
        self.decode_ticks += 1
        self.tokens_emitted += emitted_n
        if self.block_len:
            self.tokens_delivered += emitted_n
        if len(served) == self.engine.serving.slots:
            self.full_tick_s += whole.dur
            self.full_tick_tokens += emitted_n
        whole.record(
            self.recorder, "decode_tick", track="serving", steps=emitted_n
        )
        if self.spec_k > 0:
            self._event(
                "spec_accept", accepted=accepted_n, emitted=emitted_n,
                drafted=drafted_n,
            )
        self._event(
            "decode_tick", live=len(served), emitted=emitted_n,
            unread=unread, blocks_used=self.engine.allocator.used_blocks,
        )
        return emitted_n

    # -- loops ----------------------------------------------------------

    def serve(self, max_ticks: int = 10 ** 9):
        """Tick until idle (or ``max_ticks``). Observes the resilience
        plane at every tick boundary: a requested preemption turns into
        a drain — the accounting dict return value; None means the
        queue ran dry normally. The check runs FIRST each round, so a
        signal arriving mid-tick drains at the next boundary —
        in-flight device work always completes, exactly the training
        loop's step-boundary discipline."""
        while self.busy and self.ticks < max_ticks:
            if self.preemption is not None and self.preemption.requested:
                return self.drain(self.preemption.reason or "preempted")
            self.tick()
        return None

    def drain(self, reason: str) -> dict:
        """Preemption drain: hand every in-flight sequence back (partial
        output recorded, blocks freed, request re-queued at the front so
        a relaunch finishes it first) and report the accounting the
        launcher needs. The caller exits EXIT_RESUMABLE (75)."""
        self._event(
            "drain", reason=reason,
            in_flight=len(self._slot_req), queued=len(self._queue),
        )
        # a drained request restarts from scratch: the pass in flight
        # is dropped with the tokens it would have given
        self._in_flight = None
        handed_back = []
        for slot in sorted(self._slot_req):
            req = self._slot_req[slot]
            self.engine.retire(slot)
            req.status = "evicted"
            self._event(
                "evict", rid=req.rid, slot=slot, state="in_flight",
                tokens_done=int(len(req.tokens)),
                prefilled=int(req._prefilled),
            )
            handed_back.append(req)
        for req in reversed(handed_back):
            self._queue.appendleft(req)
        self._slot_req.clear()
        if self.recorder is not None:
            self.recorder.flush()
        return {
            "reason": reason,
            "handed_back": [
                {"rid": r.rid, "tokens_done": len(r.tokens)}
                for r in handed_back
            ],
            "queued": [r.rid for r in self._queue],
            "finished": [r.rid for r in self.finished],
        }

    # -- reporting ------------------------------------------------------

    def occupancy(self) -> dict:
        ticks = max(1, self.ticks)
        out = {
            "slot_occupancy": round(
                self._live_ticks / (ticks * self.engine.serving.slots), 4
            ),
            "kv_blocks_peak": self.engine.allocator.peak_used,
            "kv_blocks_total": self.engine.pool.n_blocks - 1,
            "backpressure_ticks": self.backpressure_ticks,
            "lanes_unread": self.lanes_unread,
            # instantaneous feedback the fleet router's least-loaded
            # placement keys on (serve/fleet/router.py): slots with no
            # live request, allocatable blocks (free + reclaimable LRU),
            # and the request queue's current depth
            "free_slots": self.engine.serving.slots - len(self._slot_req),
            "kv_blocks_free": self.engine.allocator.free_blocks,
            "queue_depth": len(self._queue),
        }
        if self.engine.expert_forms:
            out["expert_forms"] = dict(self.engine.expert_forms)
        if self.engine.mamba_forms:
            out["mamba_forms"] = dict(self.engine.mamba_forms)
        if self.spec_k > 0:
            # acceptance rate = accepted draft tokens / drafted; the
            # emitted bonus tokens ride free either way
            out["spec_drafted"] = self.spec_drafted
            out["spec_accepted"] = self.spec_accepted
            out["acceptance_rate"] = round(
                self.spec_accepted / max(1, self.spec_drafted), 4
            )
            out["tokens_per_tick"] = round(
                self.tokens_emitted / max(1, self.decode_ticks), 4
            )
        alloc = self.engine.allocator
        if alloc.cache is not None:
            out["prefix_hits"] = self.prefix_hits
            out["prefix_hit_rate"] = round(
                self.prefix_hits / max(1, self.prefix_lookups), 4
            )
            out["blocks_shared"] = self.blocks_shared
            out["cow_copies"] = self.cow_copies
            out["partial_hits"] = self.partial_hits
            out["tail_tokens_shared"] = self.tail_tokens_shared
            out["decode_blocks_registered"] = self.decode_blocks_registered
            out["prefill_chunks"] = self.prefill_chunks
            out["prefill_chunks_saved"] = self.prefill_chunks_saved
            out["lru_evictions"] = alloc.lru_evictions
            out["lru_reclaims"] = alloc.lru_reclaims
            out["kv_blocks_cached"] = alloc.cached_blocks
        return out
