"""``Request.chunks_ahead``: for each delivered token, the prefill chunks
dispatched between the pass that delivered the token before it and the
pass that delivered it (that pass's own tick's included).

The scheduler is driven tick by tick and watched from outside: the chunks
each tick dispatched (``prefill_chunks`` before and after) and the tick
in which each request's tokens arrived. A pass that runs one ahead of the
host is dispatched the tick before the one that reads it, after that
tick's chunks; a speculative tick reads its own, and a first token is
read after every chunk of its tick. So a delivery in tick ``t`` whose
previous one was in tick ``p`` waited behind the chunks of ticks
``p .. t - 1`` (a pass one ahead after a pass one ahead) or
``p + 1 .. t`` (speculation; and after a first token, ``p + 1 .. t - 1``
one ahead), on its first token; the tokens after the first that one pass
delivers carry 0, and so does a request's first.
"""

import jax
import numpy as np
import pytest

from singa_tpu.models.transformer import TransformerConfig, init_lm
from singa_tpu.serve import Engine, EngineConfig, Request, Scheduler

LM = TransformerConfig(
    vocab=32, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=48
)
BLOCKS = TransformerConfig(
    vocab=40, d_model=32, n_heads=4, n_layers=2, max_len=48,
    norm="rmsnorm", pos="rope", n_kv_heads=2, head_dim=8, qk_norm=True,
    tied_head=False, moe_experts=4, moe_top_k=2, moe_d_ff=16,
    diffusion_block=4, mask_id=39,
)


def scheduler(cfg, slots=3, drafter=None, **serving):
    params = init_lm(jax.random.PRNGKey(0), cfg)
    return Scheduler(Engine(params, cfg, EngineConfig(
        slots=slots, kv_block_len=8, max_prefill_chunk=4, **serving,
    )), drafter=drafter)


def requests(cfg, shapes, seed=0):
    rs = np.random.RandomState(seed)
    return [
        Request(rid=rid, prompt=rs.randint(0, cfg.vocab - 1, size=(p,)),
                max_new_tokens=n)
        for rid, (p, n) in enumerate(shapes)
    ]


def serve_watched(sched, reqs, every=2, reset_at=None):
    """Submit one request every ``every`` ticks (so that chunks fall
    among live passes) and tick until all are done. -> (chunks each tick
    dispatched, {rid: [(tick, tokens that arrived in it)]})."""
    pending, chunks, arrivals = list(reqs), [], {r.rid: [] for r in reqs}
    tick = 0
    while pending or sched.busy:
        if pending and tick % every == 0:
            sched.submit(pending.pop(0))
        if tick == reset_at:
            sched.reset_counters()  # zeroes prefill_chunks, not the marks
        seen = {r.rid: len(r.tokens) for r in reqs}
        before = sched.prefill_chunks
        sched.tick()
        chunks.append(sched.prefill_chunks - before)
        for r in reqs:
            if len(r.tokens) > seen[r.rid]:
                arrivals[r.rid].append((tick, len(r.tokens) - seen[r.rid]))
        tick += 1
        assert tick < 2000
    return chunks, arrivals


def expected(chunks, arrivals, own_tick, first_token):
    """What ``chunks_ahead`` should read, from the outside watch: each
    delivery counts the chunks from the tick after what the previous one
    waited for up to its pass's own tick. ``own_tick``: a tick reads the
    pass it dispatched; ``first_token``: the first delivery is a first
    token, read after its tick's chunks."""
    out, start = [], None
    for i, (tick, n) in enumerate(arrivals):
        end = tick + 1 if own_tick else tick
        out += [0 if start is None else sum(chunks[start:end])]
        out += [0] * (n - 1)
        start = tick + 1 if own_tick or (first_token and i == 0) else tick
    return out


class Oracle:
    """A drafter that proposes the stream's own next tokens, so that a
    speculative tick delivers several tokens at once."""

    def __init__(self, streams):
        self.streams = streams

    def draft(self, ctx, n):
        ctx = [int(t) for t in ctx]
        for prompt, out in self.streams:
            if ctx[:len(prompt)] == prompt:
                done = len(ctx) - len(prompt)
                return out[done:done + n]
        return []


SHAPES = [(6, 9), (13, 7), (3, 12), (17, 10), (9, 8), (22, 6), (5, 11)]


def greedy_streams(reqs):
    sched = scheduler(LM)
    for r in reqs:
        sched.submit(r)
    sched.serve()
    return [([int(t) for t in r.prompt], list(r.tokens)) for r in reqs]


@pytest.mark.parametrize("kind", ["one_token", "blocks", "speculate"])
def test_each_token_counts_the_chunks_since_the_pass_before(kind):
    cfg = BLOCKS if kind == "blocks" else LM
    if kind == "speculate":
        streams = greedy_streams(requests(cfg, SHAPES))
        sched = scheduler(cfg, spec_k=3, drafter=Oracle(streams))
    else:
        sched = scheduler(
            cfg, **({"block_steps": 2} if kind == "blocks" else {})
        )
    reqs = requests(cfg, SHAPES)
    chunks, arrivals = serve_watched(sched, reqs, reset_at=9)
    assert sum(chunks) > len(SHAPES)   # chunks did fall among passes
    for r in reqs:
        assert r.status == "done"
        assert len(r.chunks_ahead) == len(r.tokens) == r.max_new_tokens
        assert r.chunks_ahead == expected(
            chunks, arrivals[r.rid], own_tick=kind == "speculate",
            first_token=kind != "blocks",
        )
        assert r.chunks_ahead[0] == 0
    # some token waited behind a chunk, and under blocks or speculation
    # some pass delivered more than one token at once
    assert any(max(r.chunks_ahead) > 0 for r in reqs)
    if kind != "one_token":
        assert any(n > 1 for a in arrivals.values() for _, n in a[1:])


def test_two_chunks_in_a_tick_fall_on_the_next_token_of_every_request():
    """Two requests decoding, then two that prefill three chunks each,
    one a tick: each pass dispatched in a tick with two chunks puts 2 on
    the next token of both decoding requests; the newcomers' first
    tokens were read after their chunks, and their next carry 0."""
    sched = scheduler(LM, slots=4)
    a, b, c, d = requests(LM, [(4, 20), (4, 20), (12, 6), (12, 6)], seed=3)
    for r in (a, b):
        sched.submit(r)
    while len(a.tokens) < 3:
        sched.tick()
    for r in (c, d):
        sched.submit(r)
    sched.tick()   # C, D: first chunks; the pass goes out behind them
    assert a.chunks_ahead[-1] == b.chunks_ahead[-1] == 0
    for _ in range(3):  # second chunks, last chunks, no chunk
        sched.tick()    # each reads the pass of the tick before
        assert a.chunks_ahead[-1] == b.chunks_ahead[-1] == 2
    sched.tick()   # the pass that went out with no chunk ahead of it
    assert a.chunks_ahead[-1] == b.chunks_ahead[-1] == 0
    assert c.chunks_ahead == d.chunks_ahead == [0, 0, 0]


def test_requests_admitted_together_with_no_later_chunk_read_zeros():
    sched = scheduler(LM, slots=3)
    reqs = requests(LM, [(4, 9), (3, 7), (4, 12)], seed=5)
    for r in reqs:
        sched.submit(r)
    sched.serve()
    for r in reqs:
        assert r.chunks_ahead == [0] * len(r.tokens) == [0] * r.max_new_tokens


def test_block_steps_count_the_chunks_ahead_of_a_commit_pass():
    """A commit pass delivers nothing: the next token's gap spans it and
    counts the chunks dispatched before it too, one a tick while a long
    prompt prefills beside the decoding request."""
    sched = scheduler(BLOCKS, slots=2, block_steps=2)
    a, long = requests(BLOCKS, [(4, 12), (32, 4)], seed=7)
    sched.submit(a)
    while not a.tokens:
        sched.tick()
    sched.submit(long)
    seen, gaps = len(a.tokens), []
    quiet = 0    # ticks since A's last delivery
    while long.status == "prefill" or not long.tokens:
        sched.tick()
        quiet += 1
        if len(a.tokens) > seen:
            gaps.append((quiet, a.chunks_ahead[seen]))
            seen, quiet = len(a.tokens), 0
        if a.status == "done":
            break
    # one chunk a tick: a delivery after a commit counts two of them
    assert (2, 2) in gaps
    assert all(n == q for q, n in gaps[1:])


def test_a_drained_request_counts_anew_when_it_is_served_again():
    sched = scheduler(LM, slots=2)
    reqs = requests(LM, [(9, 10), (6, 10)], seed=9)
    for r in reqs:
        sched.submit(r)
    for _ in range(5):
        sched.tick()
    sched.drain("test")
    sched.serve()
    for r in reqs:
        assert len(r.chunks_ahead) == len(r.tokens) == r.max_new_tokens
        assert r.chunks_ahead[0] == 0
