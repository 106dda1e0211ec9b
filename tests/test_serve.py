"""Serving tier (singa_tpu/serve/): paged-KV block pool, slot-batched
engine, continuous-batching scheduler, conf-net decode, drain, and the
serving telemetry/lint/eval-feeder satellites.

The two parity bars the subsystem stands on:

  - the paged pool's block-table gather is BITWISE the dense cache
    (same ``cache_attend`` body; trash/garbage entries masked to exact
    softmax zero), so paged decode == dense decode bit for bit;
  - interleaved continuously-batched streams emit tokens identical to
    sequential ``models.transformer.generate`` runs — scheduling is
    never allowed to move a token.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.models.transformer import (
    TransformerConfig,
    _block_step,
    generate,
    init_lm,
)
from singa_tpu.serve import (
    BlockAllocator,
    Engine,
    EngineConfig,
    KVPool,
    Request,
    Scheduler,
)
from singa_tpu.serve.kv_pool import PoolExhausted


def tiny_cfg(**kw):
    base = dict(
        vocab=32, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=32
    )
    base.update(kw)
    return TransformerConfig(**base)


def tiny_params(cfg, seed=0):
    return init_lm(jax.random.PRNGKey(seed), cfg)


def mixed_workload(cfg, n=6, seed=0):
    """Deterministic ragged prompts/budgets (interleaved admits/retires
    by construction: every request finishes at a different tick)."""
    rs = np.random.RandomState(seed)
    prompts = [
        rs.randint(0, cfg.vocab, size=(int(rs.randint(3, 9)),)).astype(
            np.int32
        )
        for _ in range(n)
    ]
    budgets = [int(rs.randint(4, 10)) for _ in range(n)]
    return prompts, budgets


# ---------------------------------------------------------------------------
# kv_pool
# ---------------------------------------------------------------------------


class TestBlockPool:
    def test_alloc_free_reuse_and_accounting(self):
        pool = KVPool.for_model(max_len=64, block_len=16, n_blocks=9)
        alloc = BlockAllocator(pool)
        a = alloc.alloc(3)
        b = alloc.alloc(2)
        assert len(set(a) | set(b)) == 5 and 0 not in a + b
        assert alloc.used_blocks == 5 and alloc.free_blocks == 3
        alloc.free(a)
        with pytest.raises(ValueError, match="not handed out"):
            alloc.free([a[0]])  # double free
        c = alloc.alloc(3)  # freed blocks come back
        assert set(c) <= set(range(1, 9))
        assert alloc.peak_used == 5

    def test_exhaustion_is_all_or_nothing(self):
        alloc = BlockAllocator(KVPool.for_model(64, 16, n_blocks=5))
        alloc.alloc(2)
        free_before = alloc.free_blocks
        with pytest.raises(PoolExhausted):
            alloc.alloc(3)  # only 2 free
        # the failed alloc must leave the free list untouched —
        # admission backpressure retries later with the SAME budget
        assert alloc.free_blocks == free_before
        alloc.alloc(2)

    def test_uniform_blocks_cannot_fragment(self):
        """Interleaved ragged alloc/free: any request whose block count
        fits the free total must succeed (no external fragmentation —
        the uniform-block design's point)."""
        alloc = BlockAllocator(KVPool.for_model(256, 16, n_blocks=17))
        held = [alloc.alloc(k) for k in (3, 1, 4, 1, 5)]  # 14 of 16
        alloc.free(held[0])
        alloc.free(held[2])  # free 3 + 4 back: 9 free, scattered ids
        got = alloc.alloc(9)  # exactly the free total
        assert len(got) == 9 and alloc.free_blocks == 0

    def test_geometry_validation(self):
        with pytest.raises(ValueError, match="divide max_len"):
            KVPool.for_model(max_len=100, block_len=16)
        with pytest.raises(ValueError, match="cannot hold"):
            KVPool.for_model(max_len=64, block_len=16, n_blocks=3)
        pool = KVPool.for_model(max_len=64, block_len=16, slots=4)
        assert pool.n_blocks == 4 * 4 + 1  # dense-equivalent + trash
        assert pool.cache_len == 64
        assert pool.blocks_for(17) == 2 and pool.blocks_for(1) == 1


# ---------------------------------------------------------------------------
# engine: paged == dense, bitwise
# ---------------------------------------------------------------------------


def dense_reference(params, cfg, prompt, n_tokens):
    """The dense-cache oracle: the SAME ``_block_step`` body the
    pre-serving generate() ran, against plain (1, H, max_len, D)
    caches — prefill in one chunk, then greedy single-token steps.
    Returns (tokens, k_caches, v_caches)."""
    shape = (1, cfg.n_heads, cfg.max_len, cfg.head_dim)
    ks = [jnp.zeros(shape) for _ in range(cfg.n_layers)]
    vs = [jnp.zeros(shape) for _ in range(cfg.n_layers)]
    toks = jnp.asarray(prompt)[None]
    x = params["embed/tok"][toks] + params["embed/pos"][: toks.shape[1]]
    for i in range(cfg.n_layers):
        x, ks[i], vs[i] = _block_step(
            params, f"blk{i}", x, ks[i], vs[i], jnp.int32(0), cfg
        )
    from singa_tpu.models.transformer import _layernorm

    xf = _layernorm(x, params["ln_f/scale"], params["ln_f/bias"])
    tok = jnp.argmax((xf @ params["embed/tok"].T)[:, -1], -1).astype(
        jnp.int32
    )
    out = [int(tok[0])]
    pos = toks.shape[1]
    for _ in range(n_tokens - 1):
        x = (
            params["embed/tok"][tok][:, None, :]
            + params["embed/pos"][pos][None, None, :]
        )
        for i in range(cfg.n_layers):
            x, ks[i], vs[i] = _block_step(
                params, f"blk{i}", x, ks[i], vs[i], jnp.int32(pos), cfg
            )
        xf = _layernorm(x, params["ln_f/scale"], params["ln_f/bias"])
        tok = jnp.argmax((xf @ params["embed/tok"].T)[:, 0], -1).astype(
            jnp.int32
        )
        out.append(int(tok[0]))
        pos += 1
    return out, ks, vs


def test_paged_gather_is_bitwise_the_dense_cache():
    """The paging claim: against a dense-cache engine (kv_block_len =
    max_len, so every sequence is ONE block — a plain dense cache) with
    identical slots/chunking, the paged engine's tokens AND its
    gathered K/V are bit-for-bit identical at every position. Paging is
    pure data movement: the block-table gather reassembles exactly the
    dense layout, and trash-block garbage is masked to exact softmax
    zero. (Chunk-length/batch-width are separate SHAPE knobs — XLA may
    re-tile a GEMM's accumulation across different shapes, which is why
    the oracle holds every shape fixed and the cross-shape tests below
    compare at token level.)"""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompt = np.asarray([3, 1, 4, 1, 5, 9, 2], np.int32)
    n = 8

    def run(block_len):
        eng = Engine(
            params, cfg,
            EngineConfig(slots=2, kv_block_len=block_len,
                         max_prefill_chunk=4),
        )
        eng.admit(1, len(prompt) + n)  # slot 1: non-trivial table ids
        last = None
        for c0 in range(0, len(prompt), 4):
            last = eng.prefill_chunk(1, prompt[c0:c0 + 4], c0)
        got = [int(eng.activate(1, last, len(prompt), seed=0))]
        for _ in range(n - 1):
            got.append(int(np.asarray(eng.decode())[1]))
        caches = [
            (
                np.asarray(eng._gather(
                    eng.state["k"][i], eng.state["tables"][1:2]
                )[0]),
                np.asarray(eng._gather(
                    eng.state["v"][i], eng.state["tables"][1:2]
                )[0]),
            )
            for i in range(cfg.n_layers)
        ]
        return got, caches

    paged_toks, paged = run(block_len=8)       # 4 blocks per sequence
    dense_toks, dense = run(block_len=cfg.max_len)  # 1 block = dense
    assert paged_toks == dense_toks
    written = len(prompt) + n - 1  # the final sample is never cached
    for i, ((pk, pv), (dk, dv)) in enumerate(zip(paged, dense)):
        np.testing.assert_array_equal(
            pk[:, :written], dk[:, :written],
            err_msg=f"layer {i} K: paged gather != dense cache",
        )
        np.testing.assert_array_equal(
            pv[:, :written], dv[:, :written],
            err_msg=f"layer {i} V: paged gather != dense cache",
        )


def test_engine_tokens_match_block_step_oracle():
    """Cross-shape token parity: the slot-batched engine vs a hand-run
    dense ``_block_step`` oracle (single-chunk prefill, B=1 decode) —
    different GEMM shapes, same decisions."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompt = np.asarray([3, 1, 4, 1, 5, 9, 2], np.int32)
    n = 8
    want, _, _ = dense_reference(params, cfg, prompt, n)
    eng = Engine(
        params, cfg,
        EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4),
    )
    eng.admit(1, len(prompt) + n)
    last = None
    for c0 in range(0, len(prompt), 4):
        last = eng.prefill_chunk(1, prompt[c0:c0 + 4], c0)
    got = [int(eng.activate(1, last, len(prompt), seed=0))]
    for _ in range(n - 1):
        got.append(int(np.asarray(eng.decode())[1]))
    assert got == want


def test_interleaved_streams_match_sequential_generate():
    """Continuous batching with ragged prompts/budgets: admits and
    retires interleave across ticks, every stream's tokens must equal
    its own sequential generate() run."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompts, budgets = mixed_workload(cfg)
    eng = Engine(
        params, cfg,
        EngineConfig(slots=3, kv_block_len=8, max_prefill_chunk=4),
    )
    sched = Scheduler(eng)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    assert sched.serve() is None
    assert len(sched.finished) == len(prompts)
    # 3 slots, 6 ragged requests: retires MUST have freed slots mid-run
    assert sched.occupancy()["slot_occupancy"] > 0
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        want = np.asarray(generate(params, jnp.asarray(p)[None], cfg, m))[
            0, len(p):
        ]
        got = next(r for r in sched.finished if r.rid == i).tokens
        np.testing.assert_array_equal(
            want, got, err_msg=f"stream {i} diverged under batching"
        )


# ---------------------------------------------------------------------------
# one pass ahead of the host (the twins of tests/test_block_serve.py's)
# ---------------------------------------------------------------------------


def by_hand(params, cfg, prompt, n, *, seed=0, temperature=0.0, eos=None):
    """One request with the engine to itself, every token READ before
    the next pass is dispatched: what a stream is, whatever discipline
    the scheduler keeps."""
    eng = Engine(params, cfg, EngineConfig(slots=1, kv_block_len=8))
    eng.admit(0, len(prompt) + n)
    last = eng.prefill_chunk(0, prompt, 0)
    out = [int(eng.activate(0, last, len(prompt), seed,
                            temperature=temperature))]
    while len(out) < n and out[-1] != eos:
        out.append(int(np.asarray(eng.decode())[0]))
    return out


def ahead_sched(params, cfg, slots=2, **kw):
    return Scheduler(Engine(
        params, cfg,
        EngineConfig(slots=slots, kv_block_len=8, max_prefill_chunk=8),
    ), **kw)


def test_greedy_and_sampled_slots_sharing_ticks_give_their_own_streams():
    """A greedy and a temperature slot side by side, requests ending
    and their lanes riding a pass unread beside them: every stream is
    what its request gets alone and read in the tick, token for token
    (the key stream splits once a pass a slot ON THE DEVICE; a riding
    lane's split dies with the slot)."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompts, budgets = mixed_workload(cfg, n=6, seed=21)
    temps = [0.0, 0.8, 0.0, 1.1, 0.6, 0.0]
    sched = ahead_sched(params, cfg)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(
            rid=i, prompt=p, max_new_tokens=m, temperature=temps[i],
            seed=70 + i,
        ))
    sched.serve()
    assert sched.lanes_unread > 0 and sched._in_flight is None
    for r in sched.finished:
        p, m, t = prompts[r.rid], budgets[r.rid], temps[r.rid]
        assert r.tokens == by_hand(
            params, cfg, p, m, seed=70 + r.rid, temperature=t
        ), f"stream {r.rid} (temperature {t}) moved"
        if t == 0.0:
            want = np.asarray(
                generate(params, jnp.asarray(p)[None], cfg, m)
            )[0, len(p):]
            np.testing.assert_array_equal(want, r.tokens)
    assert sched.engine.allocator.used_blocks == 0


def test_a_slot_is_retired_and_taken_again_while_a_pass_is_in_flight():
    """One-token ticks run one pass ahead of the host: when a request
    ends, the pass dispatched after its last one is still on its way,
    and the next request is admitted, prefilled and decoded in the same
    slot before that pass is read. The late pass belongs to the request
    that left — its one row lies inside the blocks that request was
    admitted with — the newcomer reads none of it, and all three
    streams are what they are alone."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    rs = np.random.RandomState(11)
    first, second, third = (
        rs.randint(0, cfg.vocab, size=(n,)).astype(np.int32)
        for n in (5, 7, 4)
    )
    sched = ahead_sched(params, cfg)
    eng = sched.engine
    # a: 5 + 3 tokens fill ONE block of 8 to its last row
    a = Request(rid=0, prompt=first, max_new_tokens=3)
    c = Request(rid=2, prompt=third, max_new_tokens=20)  # keeps it live
    b = Request(rid=1, prompt=second, max_new_tokens=6)
    for req in (a, c, b):
        sched.submit(req)
    while a.status != "done":
        sched.tick()
    # a's slot is free, and the pass dispatched for it this tick rides on
    slot = next(s for s in range(2) if s not in sched._slot_req)
    late, served_by_slot, _ = sched._in_flight
    assert served_by_slot[slot] is a and late is not None
    # the riding pass wrote row pos - 1: the last of a's one block
    assert int(eng.state["pos"][slot]) == len(first) + 3 == 8
    assert int(np.asarray(late)[slot]) >= 0        # a live lane, unread
    unread = sched.lanes_unread
    sched.tick()     # b: admitted, prefilled, first token, its first pass
    assert sched.lanes_unread == unread + 1
    assert sched._slot_req[slot] is b and b.status == "decoding"
    assert len(b.tokens) == 1 and sched._in_flight[1][slot] is b
    sched.tick()     # reads b's first pass, not a's late one
    assert len(b.tokens) == 2
    sched.serve()
    for req, prompt in ((a, first), (b, second), (c, third)):
        assert req.tokens == by_hand(
            params, cfg, prompt, req.max_new_tokens
        ), f"stream {req.rid} moved"
    assert eng.allocator.used_blocks == 0


def test_no_pass_is_left_in_flight_when_the_server_runs_dry():
    """The pass dispatched after the last request's last one is dropped
    with it: a server that ran dry holds no device result, counts no
    pass nobody read, and serves the next request as a fresh one does."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompt = np.asarray([3, 1, 4, 1, 5, 9], np.int32)
    sched = ahead_sched(params, cfg)
    sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=7))
    sched.serve()
    assert not sched.busy and sched._in_flight is None
    counted = (sched.decode_ticks, sched._live_ticks, sched.lanes_unread)
    assert counted == (6, 6, 0)       # six passes read; the seventh dropped
    assert sched.tick() == 0
    assert (
        sched.decode_ticks, sched._live_ticks, sched.lanes_unread
    ) == counted
    again = Request(rid=1, prompt=prompt, max_new_tokens=7)
    sched.submit(again)
    sched.serve()
    assert sched._in_flight is None
    assert again.tokens == by_hand(params, cfg, prompt, 7)
    assert sched.engine.allocator.used_blocks == 0


@pytest.mark.parametrize("ends_by", ["budget_of_one", "eos_first"])
def test_a_first_token_that_ends_its_request(ends_by):
    """The first token is read in the tick's pull, behind the decode
    the tick dispatched: a request it ends (a budget of one, an EOS)
    has one token, frees its blocks, and rides that decode as any
    finished request rides one — beside a neighbour whose stream does
    not move."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompt = np.asarray([7, 2, 9, 4], np.int32)
    other = np.asarray([5, 5, 1, 8, 3], np.int32)
    (first,) = by_hand(params, cfg, prompt, 1)
    short = (
        Request(rid=0, prompt=prompt, max_new_tokens=1)
        if ends_by == "budget_of_one"
        else Request(rid=0, prompt=prompt, max_new_tokens=9, eos=first)
    )
    long = Request(rid=1, prompt=other, max_new_tokens=12)
    sched = ahead_sched(params, cfg)
    sched.submit(long)
    sched.tick()
    sched.submit(short)
    sched.tick()      # admitted, prefilled, activated, read: done
    assert short.status == "done" and short.tokens == [first]
    assert short.first_token_mono >= short.admit_mono > 0
    assert 1 not in sched._slot_req and sched._in_flight[1][1] is short
    # its lane rode the decode of this tick: one row at len(prompt),
    # inside the block it was admitted with
    assert int(sched.engine.state["pos"][1]) == len(prompt) + 1
    sched.serve()
    assert sched.lanes_unread == 1    # short's; long's last pass dropped
    assert long.tokens == by_hand(params, cfg, other, 12)
    assert sched.engine.allocator.used_blocks == 0


def test_lanes_unread_counts_one_a_finished_request(tmp_path):
    """One lane a request that finished while the server stayed live,
    beside ``_live_ticks``; in ``occupancy()``, on the recorder's
    ``decode_tick`` event, and zeroed by ``reset_counters``."""
    from singa_tpu.obs.recorder import FlightRecorder

    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompts, budgets = mixed_workload(cfg, n=5, seed=2)
    rec = FlightRecorder(str(tmp_path / "events"), rank=0, run_id="t")
    sched = ahead_sched(params, cfg, slots=3, recorder=rec)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    # one that outlasts them all: every other request's late pass is read
    sched.submit(Request(rid=9, prompt=prompts[0], max_new_tokens=26))
    sched.serve()
    assert [r.rid for r in sched.finished][-1] == 9
    assert sched.lanes_unread == len(prompts)
    # every lane read gave a token, but those
    assert sched.tokens_emitted == sched._live_ticks - sched.lanes_unread
    assert sched.occupancy()["lanes_unread"] == len(prompts)
    rec.flush()
    ticks = [
        json.loads(l) for l in open(tmp_path / "events" / "rank_0.jsonl")
    ]
    ticks = [e["data"] for e in ticks if e["kind"] == "decode_tick"]
    assert sum(e["unread"] for e in ticks) == len(prompts)
    assert all(e["emitted"] == e["live"] - e["unread"] for e in ticks)
    sched.reset_counters()
    assert sched.lanes_unread == 0


def test_settle_reads_the_pass_in_flight_and_dispatches_none():
    """``settle()``: afterwards no pass is in flight and every live
    slot's device lane holds the token its request holds at the
    position that follows from it — what an export, a drain to a peer
    or a flip of the weights needs — and the streams go on unmoved."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompts, budgets = mixed_workload(cfg, n=4, seed=8)
    sched = ahead_sched(params, cfg, slots=3)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=max(m, 6)))
    for _ in range(3):
        sched.tick()
    assert sched._in_flight is not None
    dispatched = sched.decode_ticks
    held = {r.rid: len(r.tokens) for r in sched.in_flight}
    assert sched.settle() == len(held)
    assert sched._in_flight is None and sched.decode_ticks == dispatched + 1
    toks = np.asarray(sched.engine.state["tokens"])
    pos = np.asarray(sched.engine.state["pos"])
    for slot, req in sched._slot_req.items():
        assert len(req.tokens) == held[req.rid] + 1
        assert toks[slot] == req.tokens[-1]
        assert pos[slot] == len(req.prompt) + len(req.tokens) - 1
    assert sched.settle() == 0        # nothing left to read
    sched.serve()
    for r in sched.finished:
        assert r.tokens == by_hand(
            params, cfg, prompts[r.rid], r.max_new_tokens
        )


class _Reads:
    """Every device->host read the scheduler and the engine can make,
    with the spans open at that moment: ``np.asarray`` / ``np.array``
    of a device array as those two modules call it, and the scalar
    conversions and ``jax.device_get`` of any code (``ArrayImpl._value``)."""

    def __init__(self, monkeypatch):
        from jax._src.array import ArrayImpl

        from singa_tpu.serve import engine as engine_mod
        from singa_tpu.serve import scheduler as sched_mod

        self.log, self.open = [], []
        reads = self

        class Numpy:
            def __getattr__(self, name):
                return getattr(np, name)

            @staticmethod
            def asarray(a, *args, **kw):
                if isinstance(a, jax.Array):
                    reads.read()
                return np.asarray(a, *args, **kw)

            array = asarray

        value = ArrayImpl._value

        def spied(arr):
            reads.read()
            return value.fget(arr)

        real_span = sched_mod.span

        class Span:
            def __init__(self, name, **attrs):
                self.name, self.inner = name, real_span(name, **attrs)

            def __enter__(self):
                reads.open.append(self.name)
                reads.log.append(("span", self.name))
                return self.inner.__enter__()

            def __exit__(self, *exc):
                reads.open.pop()
                return self.inner.__exit__(*exc)

            def start(self):
                return self.inner.start()

        monkeypatch.setattr(sched_mod, "np", Numpy())
        monkeypatch.setattr(engine_mod, "np", Numpy())
        monkeypatch.setattr(ArrayImpl, "_value", property(spied))
        monkeypatch.setattr(sched_mod, "span", Span)

    def read(self):
        self.log.append(("read", tuple(self.open)))


def test_a_tick_reads_only_in_its_pull_after_its_dispatch(monkeypatch):
    """A count, not a time: over a run with admissions, chunks, first
    tokens and finishes, every device->host read of a tick falls inside
    ``sched.pull``, after that tick's ``sched.dispatch`` — so admission,
    a chunk's hand-over, the fan-out and retirement all run with the
    tick's decode queued on the device."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompts, budgets = mixed_workload(cfg, n=7, seed=13)
    sched = Scheduler(Engine(
        params, cfg,
        EngineConfig(slots=3, kv_block_len=8, max_prefill_chunk=4,
                     prefix_cache=True, prefix_lru=True),
    ))
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    sched.tick()                      # compiles read nothing either, but
    reads = _Reads(monkeypatch)       # the count starts on warm programs
    sched.serve()
    assert len(sched.finished) == len(prompts)
    ticks, tick = [], None
    for kind, what in reads.log:
        if (kind, what) == ("span", "sched.tick"):
            tick = []
            ticks.append(tick)
        tick.append((kind, what))
    n_reads = 0
    for tick in ticks:
        names = [w for k, w in tick if k == "span"]
        where = [w for k, w in tick if k == "read"]
        n_reads += len(where)
        assert all(w[-1:] == ("sched.pull",) for w in where), where
        if where and "sched.dispatch" in names:
            assert tick.index(("span", "sched.dispatch")) < min(
                i for i, (k, _) in enumerate(tick) if k == "read"
            )
    # chunks, admissions and retirements were among those ticks
    spans = {w for k, w in reads.log if k == "span"}
    assert {"sched.admit", "sched.prefill", "sched.emit"} <= spans
    # a read a pass and one a tick that activated a request, no more
    assert sched.decode_ticks - 1 <= n_reads <= sched.decode_ticks + len(prompts)
    for r in sched.finished:
        want = np.asarray(generate(
            params, jnp.asarray(prompts[r.rid])[None], cfg, budgets[r.rid]
        ))[0, len(prompts[r.rid]):]
        np.testing.assert_array_equal(want, r.tokens)


def test_pool_exhaustion_backpressures_then_completes():
    """A pool too small for every stream at once: admission stalls
    (backpressure, never a drop), retired blocks are reused, and every
    stream still matches sequential generate."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompts, budgets = mixed_workload(cfg, seed=3)
    eng = Engine(
        params, cfg,
        # 4 usable blocks for 4 slots / 6 requests of 1-3 blocks each:
        # admission MUST stall on the pool while slots sit free
        EngineConfig(slots=4, kv_block_len=8, kv_blocks=5,
                     max_prefill_chunk=8),
    )
    sched = Scheduler(eng)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    sched.serve()
    assert len(sched.finished) == len(prompts)
    assert sched.backpressure_ticks > 0
    assert eng.allocator.peak_used <= 4
    assert eng.allocator.used_blocks == 0  # everything returned
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        want = np.asarray(generate(params, jnp.asarray(p)[None], cfg, m))[
            0, len(p):
        ]
        got = next(r for r in sched.finished if r.rid == i).tokens
        np.testing.assert_array_equal(want, got)


def test_eos_retires_early():
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompt = np.asarray([1, 2, 3], np.int32)
    free_run = np.asarray(
        generate(params, jnp.asarray(prompt)[None], cfg, 12)
    )[0, 3:]
    eos = int(free_run[4])  # the 5th generated token, forced to be EOS
    eng = Engine(params, cfg, EngineConfig(slots=2, kv_block_len=8))
    sched = Scheduler(eng)
    sched.submit(
        Request(rid=0, prompt=prompt, max_new_tokens=12, eos=eos)
    )
    sched.serve()
    (req,) = sched.finished
    assert req.tokens[-1] == eos
    assert len(req.tokens) <= 5 + 1  # stopped at (or before) the EOS hit
    np.testing.assert_array_equal(req.tokens, free_run[: len(req.tokens)])


def test_admit_retire_never_recompiles():
    """The continuous-batching contract: after the first tick, any
    pattern of admissions/retirements reuses the SAME compiled decode
    and prefill programs (fixed shapes, live-mask gating)."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompts, budgets = mixed_workload(cfg, n=8, seed=7)
    eng = Engine(
        params, cfg,
        EngineConfig(slots=3, kv_block_len=8, max_prefill_chunk=4),
    )
    sched = Scheduler(eng)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    sched.serve()
    assert eng._decode_jit._cache_size() == 1
    assert eng._prefill_jit._cache_size() == 1


def test_drain_hands_back_and_resumes(tmp_path):
    """Preemption mid-serve: the drain hands every in-flight sequence
    back (partial output accounted), records the lifecycle into the
    flight recorder, and a resumed serve() regenerates every stream to
    full sequential parity."""
    from singa_tpu.obs.recorder import FlightRecorder
    from singa_tpu.resilience.preemption import PreemptionHandler

    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompts, budgets = mixed_workload(cfg, seed=11)
    rec = FlightRecorder(str(tmp_path / "events"), rank=0, run_id="t")
    handler = PreemptionHandler()
    eng = Engine(
        params, cfg,
        EngineConfig(slots=3, kv_block_len=8, max_prefill_chunk=4),
    )
    sched = Scheduler(eng, recorder=rec, preemption=handler)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    for _ in range(4):
        sched.tick()
    handler.trigger("test preemption")
    acct = sched.serve()
    assert acct is not None and acct["reason"] == "test preemption"
    assert acct["handed_back"], "nothing was in flight at the drain?"
    assert eng.allocator.used_blocks == 0
    rec.flush()
    kinds = [
        json.loads(l)["kind"]
        for l in open(tmp_path / "events" / "rank_0.jsonl")
    ]
    assert "request_admit" in kinds and "decode_tick" in kinds
    assert "drain" in kinds and "evict" in kinds
    assert kinds.index("drain") < kinds.index("evict")
    # resumability: the handed-back queue finishes to full parity
    handler._event.clear()
    assert sched.serve() is None
    assert len(sched.finished) == len(prompts)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        want = np.asarray(generate(params, jnp.asarray(p)[None], cfg, m))[
            0, len(p):
        ]
        got = next(r for r in sched.finished if r.rid == i).tokens
        np.testing.assert_array_equal(want, got)


def test_engine_under_tensor_parallel_matches_single_device():
    """Serving composition with kLayerPartition-style TP: params sharded
    over a model=2 mesh, KV pools laid out by serving_kv_shardings —
    every emitted token equals the unsharded engine's."""
    from jax.sharding import Mesh

    from singa_tpu.models.transformer import lm_param_shardings
    from singa_tpu.parallel.shardings import serving_kv_shardings

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompts, budgets = mixed_workload(cfg, n=4, seed=5)

    def run(eng):
        sched = Scheduler(eng)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
        sched.serve()
        return {r.rid: r.tokens for r in sched.finished}

    serving = EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4)
    plain = run(Engine(params, cfg, serving))
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    sh = lm_param_shardings(mesh, params)
    sharded = {k: jax.device_put(v, sh[k]) for k, v in params.items()}
    pool_sh, _ = serving_kv_shardings(mesh, cfg.n_heads)
    assert "model" in [str(a) for a in pool_sh.spec if a is not None]
    tp = run(Engine(sharded, cfg, serving, mesh=mesh))
    assert tp == plain


@pytest.mark.parametrize("boundary", ["export_slot", "export_blocks"])
def test_boundary_formats_keep_the_wire_shape(boundary):
    """The pools are stored (n_blocks, block_len, H * D); what LEAVES
    an engine keeps the fleet's wire format, (L, n, H, BL, D), holding
    the values the gathered view holds — and a slot that left one
    engine in that format decodes the same tokens in another."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompt = np.asarray([3, 1, 4, 1, 5, 9, 2, 6, 5], np.int32)
    ec = EngineConfig(slots=3, kv_block_len=8, max_prefill_chunk=4)
    ea = Engine(params, cfg, ec)
    h, d, bl = cfg.n_heads, cfg.head_dim, ec.kv_block_len
    assert (
        ea.state["k"][0].shape
        == ea.pool.array_shape(h, d)
        == (ea.pool.n_blocks, bl, h * d)
    )
    ea.admit(1, len(prompt) + 10)
    for c0 in range(0, len(prompt), 4):
        last = ea.prefill_chunk(1, prompt[c0:c0 + 4], c0)
    ea.activate(1, last, len(prompt), seed=0)
    for _ in range(3):
        ea.decode()
    blocks = ea._slot_blocks[1]
    payload = ea.export_slot(1)
    if boundary == "export_blocks":
        k, v = ea.export_blocks(blocks)
        payload = {**payload, "k": k, "v": v}
    n = len(blocks)
    assert n == 3
    assert payload["k"].shape == (cfg.n_layers, n, h, bl, d)
    assert payload["v"].shape == (cfg.n_layers, n, h, bl, d)
    for i in range(cfg.n_layers):
        for name in ("k", "v"):
            view = np.asarray(ea._gather(
                ea.state[name][i], ea.state["tables"][1:2]
            )[0])                                   # (H, CL, D)
            want = np.moveaxis(view.reshape(h, -1, bl, d), 1, 0)[:n]
            np.testing.assert_array_equal(payload[name][i], want)
    ref = [int(np.asarray(ea.decode())[1]) for _ in range(5)]
    eb = Engine(params, cfg, ec)
    eb.admit(0, 16)  # occupy: the import's block ids must differ
    eb.import_slot(2, payload)
    got = [int(np.asarray(eb.decode())[2]) for _ in range(5)]
    assert got == ref


def test_serving_kv_shardings_fallback():
    from jax.sharding import Mesh

    from singa_tpu.parallel.shardings import serving_kv_shardings

    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    with pytest.warns(UserWarning, match="falls? back to replication"):
        pool_sh, _ = serving_kv_shardings(mesh, 3, warn=True)
    assert not any(pool_sh.spec)


# ---------------------------------------------------------------------------
# conf-surface decode (tools/generate.py satellite)
# ---------------------------------------------------------------------------


LM_CONF = """
name: "serve-conf-test"
train_steps: 2
updater {{ base_learning_rate: 0.05 param_type: "Param" }}
neuralnet {{
  layer {{ name: "data" type: "kSequenceData"
    data_param {{ path: "{shard}" batchsize: 8 }} }}
  layer {{ name: "embed" type: "kEmbedding" srclayers: "data"
    embedding_param {{ vocab_size: 64 embedding_dim: 32 }}
    param {{ name: "tok" init_method: "kGaussain" std: 0.02 }}
    param {{ name: "pos" init_method: "kGaussain" std: 0.02 }} }}
  layer {{ name: "ln" type: "kLayerNorm" srclayers: "embed"
    param {{ name: "scale" init_method: "kConstant" value: 1 }}
    param {{ name: "bias" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "attn" type: "kAttention" srclayers: "ln"
    attention_param {{ num_heads: 2 }}
    param {{ name: "qkv" init_method: "kUniformSqrtFanIn" }}
    param {{ name: "out" init_method: "kUniformSqrtFanIn" }} }}
  layer {{ name: "res" type: "kAdd" srclayers: "embed" srclayers: "attn" }}
  layer {{ name: "head" type: "kDense" srclayers: "res"
    dense_param {{ num_output: 64 bias_term: false }}
    param {{ name: "weight" init_method: "kGaussain" std: 0.02 }} }}
  layer {{ name: "loss" type: "kLMLoss" srclayers: "head" srclayers: "data" }}
}}
"""


@pytest.fixture()
def conf_net(tmp_path):
    from singa_tpu.config import parse_model_config
    from singa_tpu.data.loader import synthetic_token_arrays, write_records
    from singa_tpu.graph.builder import build_net
    from singa_tpu.trainer import Trainer

    shard = str(tmp_path / "tokens")
    write_records(shard, *synthetic_token_arrays(64, seq_len=16, vocab=64))
    cfg = parse_model_config(LM_CONF.format(shard=shard))
    tr = Trainer(cfg, seed=0, log=lambda s: None, prefetch=False)
    tr.run()
    net = build_net(cfg, "kTest")
    params = {k: jnp.asarray(v) for k, v in jax.device_get(tr.params).items()}
    return net, params


def test_conf_decode_matches_rolling_oracle(conf_net):
    """The conf-net KV-cache decode vs the rolling-buffer recompute
    oracle (the pre-serving tools/generate.py path, kept for exactly
    this): identical greedy continuations, chunked prefill included."""
    from singa_tpu.serve.conf_decode import NetDecoder
    from singa_tpu.tools.generate import rolling_generate_from_net

    net, params = conf_net
    dec = NetDecoder(net, max_prefill_chunk=4)
    for prompt in ([5], [3, 1, 4, 1, 5], list(range(9))):
        want = rolling_generate_from_net(net, params, prompt, 6, 0.0, 0)
        got = dec.generate(params, prompt, 6, 0.0, 0)
        assert got == want, (prompt, got, want)
    # temperature: deterministic under a seed, in-vocab
    a = dec.generate(params, [3, 1], 8, 0.8, 7)
    b = dec.generate(params, [3, 1], 8, 0.8, 7)
    assert a == b and all(0 <= t < 64 for t in a)


def test_conf_decode_falls_back_beyond_window(conf_net):
    """A generation that exceeds the positional table must fall back to
    the rolling-buffer decode (which slides), not truncate or crash."""
    from singa_tpu.serve.conf_decode import NetDecoder, UnsupportedNet
    from singa_tpu.tools.generate import generate_from_net

    net, params = conf_net
    with pytest.raises(UnsupportedNet, match="positional table"):
        NetDecoder(net).generate(params, [1, 2, 3], 60, 0.0, 0)
    msgs = []
    toks = generate_from_net(
        net, params, [1, 2, 3], 60, 0.0, 0, log=msgs.append
    )
    assert len(toks) == 63
    assert any("falling back" in m for m in msgs)


def test_conf_decode_rejects_unsupported_graphs():
    """A conv net has no incremental path: NetDecoder refuses (the CLI
    then falls back), it never silently mis-serves."""
    from singa_tpu.config import parse_model_config
    from singa_tpu.graph.builder import build_net
    from singa_tpu.serve.conf_decode import NetDecoder, UnsupportedNet

    import tempfile

    from singa_tpu.data.loader import synthetic_arrays, write_records

    tmp = tempfile.mkdtemp(prefix="serve_conv_")
    shard = os.path.join(tmp, "shard")
    write_records(shard, *synthetic_arrays(16, seed=0))
    cfg = parse_model_config(f"""
name: "conv"
train_steps: 1
updater {{ base_learning_rate: 0.01 }}
neuralnet {{
  layer {{ name: "data" type: "kShardData"
    data_param {{ path: "{shard}" batchsize: 4 }} }}
  layer {{ name: "mnist" type: "kMnistImage" srclayers: "data" }}
  layer {{ name: "label" type: "kLabel" srclayers: "data" }}
  layer {{ name: "fc" type: "kInnerProduct" srclayers: "mnist"
    inner_product_param {{ num_output: 10 }}
    param {{ name: "weight" init_method: "kUniform" }}
    param {{ name: "bias" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "loss" type: "kSoftmaxLoss" srclayers: "fc"
    srclayers: "label" }}
}}
""")
    net = build_net(cfg, "kTest")
    with pytest.raises(UnsupportedNet):
        NetDecoder(net)


# ---------------------------------------------------------------------------
# satellites: lint, eval feeder, trace summarize
# ---------------------------------------------------------------------------


def test_serving_conf_lint_did_you_mean(tmp_path):
    """netlint's schema walk covers the serving block: every knob typo'd
    gets CFG001 with a did-you-mean, and a typo'd block name points at
    serving."""
    from singa_tpu.data.loader import synthetic_token_arrays, write_records
    from singa_tpu.lint import Collector, lint_model_text

    shard = str(tmp_path / "tokens")
    write_records(shard, *synthetic_token_arrays(16, seq_len=16, vocab=64))
    base = LM_CONF.format(shard=shard) + (
        "serving { slots: 8 kv_block_len: 16 kv_blocks: 64 "
        "max_prefill_chunk: 32 }\n"
    )
    col = Collector()
    lint_model_text(base, "job.conf", col)
    assert not any(d.code == "CFG001" for d in col.sorted()), [
        str(d) for d in col.sorted()
    ]
    for typo, want in [
        ("slots:", "slots"),
        ("kv_block_len:", "kv_block_len"),
        ("kv_blocks:", "kv_blocks"),
        ("max_prefill_chunk:", "max_prefill_chunk"),
        ("serving {", "serving"),
    ]:
        text = base.replace(typo, typo[:-2] + "x" + typo[-2:], 1)
        col = Collector()
        lint_model_text(text, "job.conf", col)
        assert any(
            d.code == "CFG001" and want in (d.fix_hint or "")
            for d in col.sorted()
        ), (typo, [str(d) for d in col.sorted()])


def test_eval_burst_feeder_matches_sync(tmp_path):
    """The eval-stream feeder gap: uncached test batches now ride the
    bounded burst feeder when prefetch is on. Metrics AND stream
    positions must be identical to the synchronous path — the feeder is
    overlap, never different data."""
    from singa_tpu.config import parse_model_config
    from singa_tpu.data.loader import synthetic_arrays, write_records
    from singa_tpu.trainer import Trainer

    train = str(tmp_path / "train")
    test = str(tmp_path / "test")
    write_records(train, *synthetic_arrays(64, seed=0))
    write_records(test, *synthetic_arrays(48, seed=1))
    conf = f"""
name: "eval-feeder"
train_steps: 6
test_steps: 3
test_frequency: 3
updater {{ base_learning_rate: 0.05 type: kSGD }}
neuralnet {{
  layer {{ name: "data" type: "kShardData" exclude: kTest
    data_param {{ path: "{train}" batchsize: 16 }} }}
  layer {{ name: "data" type: "kShardData" exclude: kTrain
    data_param {{ path: "{test}" batchsize: 16 }} }}
  layer {{ name: "mnist" type: "kMnistImage" srclayers: "data" }}
  layer {{ name: "label" type: "kLabel" srclayers: "data" }}
  layer {{ name: "fc" type: "kInnerProduct" srclayers: "mnist"
    inner_product_param {{ num_output: 10 }}
    param {{ name: "weight" init_method: "kUniform" low: -0.05 high: 0.05 }}
    param {{ name: "bias" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "loss" type: "kSoftmaxLoss" srclayers: "fc"
    srclayers: "label" }}
}}
"""

    def run(prefetch):
        logs = []
        tr = Trainer(
            parse_model_config(conf), seed=0, log=logs.append,
            prefetch=prefetch, device_cache=False,
        )
        assert tr.feeder_mode != "cached"
        tr.run()
        pos = {
            name: pipe.position
            for net_id in tr._pipelines
            for name, pipe in tr._pipelines[net_id].items()
        }
        return [l for l in logs if "test" in l], pos

    sync_logs, sync_pos = run(False)
    burst_logs, burst_pos = run(True)
    assert sync_logs == burst_logs
    assert sync_pos == burst_pos
    assert any("test" in l for l in sync_logs)


def test_trace_summarize_serving_section(tmp_path):
    """Synthetic serving events + spans -> trace.summarize grows the
    serving block (request p50/p99, tick throughput, lifecycle counts);
    a training-only log keeps serving == None."""
    from singa_tpu.tools.trace import load_events, summarize

    events = tmp_path / "events"
    os.makedirs(events)
    recs = [
        {"ts": 1.0, "mono": 1.0, "rank": 0, "run": "r", "step": 0,
         "kind": "request_admit", "data": {"rid": 0, "slot": 0}},
        {"ts": 1.1, "mono": 1.1, "rank": 0, "run": "r", "step": 1,
         "kind": "span", "name": "decode_tick", "track": "serving",
         "dur": 0.004, "steps": 2},
        {"ts": 1.2, "mono": 1.2, "rank": 0, "run": "r", "step": 2,
         "kind": "span", "name": "decode_tick", "track": "serving",
         "dur": 0.006, "steps": 2},
        {"ts": 1.3, "mono": 1.3, "rank": 0, "run": "r", "step": 3,
         "kind": "retire", "data": {"rid": 0, "tokens": 5}},
        {"ts": 1.0, "mono": 1.0, "rank": 0, "run": "r", "step": 3,
         "kind": "span", "name": "request", "track": "requests",
         "dur": 0.3, "steps": 5},
        {"ts": 1.4, "mono": 1.4, "rank": 0, "run": "r", "step": 4,
         "kind": "backpressure", "data": {"queued": 3}},
    ]
    with open(events / "rank_0.jsonl", "w") as f:
        f.write("\n".join(json.dumps(r) for r in recs) + "\n")
    records, skipped = load_events(str(tmp_path))
    assert skipped == 0
    s = summarize(records)["serving"]
    assert s["request_latency_ms"] == {"p50": 300.0, "p99": 300.0, "n": 1}
    assert s["decode_ticks"] == 2 and s["tokens"] == 5
    assert s["tokens_per_s"] == 400.0  # 4 tick tokens / 0.010 s
    assert s["admitted"] == 1 and s["retired"] == 1
    assert s["backpressure"] == 1

    plain = [
        {"ts": 2.0, "mono": 2.0, "rank": 0, "run": "r", "step": 0,
         "kind": "run_start"},
    ]
    with open(events / "rank_0.jsonl", "w") as f:
        f.write("\n".join(json.dumps(r) for r in plain) + "\n")
    records, _ = load_events(str(tmp_path))
    assert summarize(records)["serving"] is None


def test_serve_bench_cli_drill_smoke(tmp_path, capsys):
    """serve_bench end to end at toy size: the sigterm drill exits 75
    with hand-back accounting and a mergeable event log."""
    from singa_tpu.tools.serve_bench import main as sb_main
    from singa_tpu.tools.trace import load_events, summarize

    ws = str(tmp_path / "ws")
    rc = sb_main([
        "--d_model", "32", "--n_heads", "2", "--n_layers", "1",
        "--d_ff", "64", "--vocab", "32", "--max_len", "32",
        "--prompt_len", "4", "--max_new", "8", "--block_len", "8",
        "--prefill_chunk", "4", "--requests", "6", "--concurrency", "2",
        "--sigterm_at_tick", "3", "--workspace", ws,
    ])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 75
    assert out["drained"] and out["drain"]["handed_back"]
    records, _ = load_events(ws)
    s = summarize(records)
    assert s["serving"]["admitted"] >= 1
    assert s["serving"]["evicted"] == len(out["drain"]["handed_back"])
    assert s["counts"]["drains"] == 1
