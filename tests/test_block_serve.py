"""Generation by diffusion over blocks through the paged engine and the
scheduler, at a small size on the CPU with seeded random weights,
against the plain reference's replay (``benchmark/drivers/serve_blocks``
``replay`` / ``pass_gaps`` over ``reference/sdar_moe.two_stream``): the
comparison that decides the cell's ``correct`` on the chip.

Tolerance 2e-4 (float32 both sides, another order of accumulation; a
wrong mask, a block read from a cache without its predecessor, or a
router's choice gone the other way reads 1e-2 or more).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.drivers import serve_blocks
from benchmark.reference import sdar_moe as ref
from singa_tpu.models.transformer import TransformerConfig, init_lm
from singa_tpu.serve import Engine, EngineConfig, Request, Scheduler
from tests.test_sdar_model import CFG, MCFG

TOL = 2e-4
B, MASK, STEPS = CFG["block_length"], CFG["mask_token_id"], 2


@pytest.fixture(scope="module")
def params():
    return weights.make(ref.specs(CFG), 2**31 + 7)


def engine(params, slots=3, chunk=8, **kw):
    return Engine(params, MCFG, EngineConfig(
        slots=slots, kv_block_len=8, max_prefill_chunk=chunk,
        block_steps=STEPS, **kw,
    ))


def gaps(params, req) -> tuple[float, float]:
    """Both gaps of one finished request, as the driver's check reads
    them: every pass index replayed through the reference."""
    request = (np.asarray(req.prompt), list(req.tokens), list(req.unmask_pass))
    clean, noisy, answer, fixed_at = serve_blocks.replay(
        request, MCFG.max_len, B, MASK, STEPS
    )
    widest = regret = room = 0.0
    for s in range(STEPS):
        logits = ref.two_stream(
            params, jnp.asarray(clean), jnp.asarray(noisy[s]), CFG
        )
        g, a, b = (float(x) for x in serve_blocks.pass_gaps(
            logits, jnp.asarray(answer), jnp.asarray(fixed_at), s, B,
            B // STEPS, None, jnp.asarray(clean),
        ))
        widest, regret, room = max(widest, g), regret + a, room + b
    return widest, serve_blocks.confidence_gap(regret, room)


#: prompt lengths that are and are not a multiple of B, shorter than a
#: block, across a kv_block_len edge (8) and a chunk's (8, 16); answers
#: that end the request on a block's edge
SHAPES = [(9, 7), (8, 8), (3, 9), (14, 10), (21, 15), (16, 4), (5, 3)]


@pytest.fixture(scope="module")
def served(params):
    sched = Scheduler(engine(params))
    rng = np.random.default_rng(1)
    for rid, (p, n) in enumerate(SHAPES):
        sched.submit(Request(
            rid=rid, prompt=rng.integers(0, 199, (p,)).astype(np.int32),
            max_new_tokens=n,
        ))
    sched.serve(max_ticks=400)
    return sched


@pytest.mark.parametrize("rid", range(len(SHAPES)))
def test_served_request_against_the_reference_replay(params, served, rid):
    """Chunked block-causal prefill, then block steps through the paged
    pool with slots in different phases in one pass (three slots, seven
    requests of other lengths): every delivered token is the reference's
    best at its position in the pass that fixed it, and every pass chose
    the positions the reference is most confident of."""
    (req,) = [r for r in served.finished if r.rid == rid]
    p, n = SHAPES[rid]
    assert len(req.tokens) == n == len(req.unmask_pass)
    assert set(req.unmask_pass) <= set(range(STEPS))
    logit_gap, confidence_gap = gaps(params, req)
    assert logit_gap <= TOL and confidence_gap <= TOL


def test_the_check_catches_a_token_and_an_order_that_were_not_served(
    params, served
):
    (req,) = [r for r in served.finished if r.rid == 4]
    sound = gaps(params, req)
    wrong_token = Request(rid=0, prompt=req.prompt, max_new_tokens=15)
    wrong_token.tokens = list(req.tokens)
    wrong_token.tokens[5] = (wrong_token.tokens[5] + 1) % 199
    wrong_token.unmask_pass = list(req.unmask_pass)
    assert gaps(params, wrong_token)[0] > 100 * max(sound[0], TOL)
    # another order of unmasking inside one block: the passes swapped
    wrong_order = Request(rid=0, prompt=req.prompt, max_new_tokens=15)
    wrong_order.tokens = list(req.tokens)
    wrong_order.unmask_pass = list(req.unmask_pass)
    blk = slice(3, 7)          # prompt 21: the answer's first whole block
    wrong_order.unmask_pass[blk] = [1 - u for u in req.unmask_pass[blk]]
    assert gaps(params, wrong_order)[1] > 0.1     # one block of three, all of its room
    # a pass that fixed three positions where the rule says two
    greedy = Request(rid=0, prompt=req.prompt, max_new_tokens=15)
    greedy.tokens = list(req.tokens)
    greedy.unmask_pass = list(req.unmask_pass)
    greedy.unmask_pass[blk] = [0, 0, 0, 1]
    assert gaps(params, greedy) == (np.inf, np.inf)


def test_counters_of_a_block_step_scheduler(served):
    n = sum(n for _, n in SHAPES)
    assert served.tokens_delivered == n == served.tokens_emitted
    # a whole block: STEPS denoising passes and a commit
    assert served.block_passes >= n * (STEPS + 1) // B - len(SHAPES)
    assert 0 < served.block_commits < served.block_passes
    layers, experts = MCFG.n_layers, MCFG.moe_experts
    assert 0 < served.experts_hit <= served.decode_ticks * layers * experts
    assert 0 < served.expert_max_load <= 3 * B
    assert served.engine.allocator.used_blocks == 0


def pools(eng):
    return [np.asarray(a) for a in eng.state["k"] + eng.state["v"]]


def test_pool_untouched_by_denoising_and_exact_after_commit(params):
    """Nothing is written before commit; the commit writes exactly the
    unmasked block's K and V (what a prefill of the same tokens writes,
    to float32's rounding) and nothing else."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 199, (13,)).astype(np.int32)
    eng = engine(params, slots=2)
    eng.admit(1, 13 + 11)
    eng.prefill_chunk(1, prompt[:8], 0)
    eng.prefill_chunk(1, prompt[8:12], 8)
    eng.activate_block(1, prompt)
    before = pools(eng)
    fixed = []
    for _ in range(STEPS):                     # the tail block: 3 masked
        out = np.asarray(eng.block_step())
        fixed.append(out[:-2].reshape(2, B)[1])
        for was, now in zip(before, pools(eng)):
            np.testing.assert_array_equal(was[1:], now[1:])  # 0 is trash
    assert [int((f >= 0).sum()) for f in fixed] == [2, 1]
    assert all((f[0] < 0) for f in fixed)      # the prompt's token stays
    block = np.where(fixed[0] >= 0, fixed[0], fixed[1])
    block[0] = prompt[12]
    out = np.asarray(eng.block_step())         # nothing masked: commit
    assert (out[:-2] < 0).all()
    assert int(eng.state["pos"][1]) == 16
    assert bool(eng.state["blk_masked"][1].all())
    after = pools(eng)
    # the same 16 tokens prefilled whole into another slot's blocks
    other = engine(params, slots=2)
    other.admit(0, 16)
    other.prefill_chunk(0, np.concatenate([prompt[:12], block])[:8], 0)
    other.prefill_chunk(0, np.concatenate([prompt[:12], block])[8:], 8)
    mine = eng._slot_blocks[1][1]              # positions 8..15
    theirs = other._slot_blocks[0][1]
    for was, now, ref_pool in zip(before, after, pools(other)):
        np.testing.assert_allclose(
            now[mine, 4:8], ref_pool[theirs, 4:8], atol=2e-5, rtol=0
        )
        np.testing.assert_array_equal(now[mine, :4], was[mine, :4])
        rest = [b for b in range(1, now.shape[0]) if b != mine]
        np.testing.assert_array_equal(now[rest], was[rest])


def test_prefill_is_chunk_split_invariant(params):
    """Whole blocks a chunk: however a prompt is cut into chunks, its
    K and V in the pool are the same, bit for bit."""
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 199, (24,)).astype(np.int32)
    written = []
    for cuts in ([16, 8], [8, 8, 8], [4, 12, 8]):
        eng = engine(params, slots=1, chunk=16)
        eng.admit(0, 24)
        at = 0
        for n in cuts:
            eng.prefill_chunk(0, prompt[at:at + n], at)
            at += n
        blocks = eng._slot_blocks[0]
        written.append([p[blocks] for p in pools(eng)])
    for other in written[1:]:
        for a, b in zip(written[0], other):
            np.testing.assert_array_equal(a, b)


# -- the scheduler ------------------------------------------------------


def test_delivery_is_by_contiguous_prefix(params):
    """Positions unmask out of order; a caller reads a token when every
    position before it is unmasked."""
    sched = Scheduler(engine(params))
    req = Request(rid=0, prompt=np.zeros((8,), np.int32), max_new_tokens=8)
    sched._fresh_block(req)
    assert sched._deliverable(req, np.array([-1, 7, -1, 9])) == []
    assert sched._deliverable(req, np.array([5, -1, 6, -1])) == [5, 7, 6, 9]
    assert req.unmask_pass == [1, 0, 1, 0]
    assert sched._deliverable(req, np.array([-1, -1, -1, -1])) == []  # commit
    assert sched.block_commits == 1 and req._blk_read == 0
    # a prompt's tail is spoken for and never delivered
    sched._fresh_block(req, tail=[3, 4])
    assert sched._deliverable(req, np.array([-1, -1, -1, 8])) == []
    assert sched._deliverable(req, np.array([-1, -1, 2, -1])) == [2, 8]
    assert req.unmask_pass == [1, 0, 1, 0, 1, 0]


def test_budget_inside_a_block_stops_delivery_and_frees_the_blocks(params):
    sched = Scheduler(engine(params))
    rng = np.random.default_rng(3)
    for rid, (p, n) in enumerate([(6, 5), (8, 2), (9, 1)]):
        sched.submit(Request(
            rid=rid, prompt=rng.integers(0, 199, (p,)).astype(np.int32),
            max_new_tokens=n,
        ))
    sched.serve(max_ticks=100)
    assert sorted(len(r.tokens) for r in sched.finished) == [1, 2, 5]
    for r in sched.finished:
        assert len(r.unmask_pass) == len(r.tokens) and r.first_token_mono > 0
    assert sched.engine.allocator.used_blocks == 0
    assert not sched.busy


def alone(params, prompt, n):
    sched = Scheduler(engine(params, slots=1))
    sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=n))
    sched.serve(max_ticks=200)
    (req,) = sched.finished
    return list(req.tokens), list(req.unmask_pass)


def test_a_slot_is_retired_and_taken_again_while_a_pass_is_in_flight(params):
    """Block steps run one pass ahead of the host: when a request ends,
    the pass dispatched after its last one is still on its way, and the
    next request is admitted, prefilled and stepped in the same slot
    before that pass is read. The late pass belongs to the request that
    left: the newcomer reads none of it, and its tokens are those it
    gets with the engine to itself."""
    rng = np.random.default_rng(11)
    first = rng.integers(0, 199, (6,)).astype(np.int32)
    second = rng.integers(0, 199, (9,)).astype(np.int32)
    third = rng.integers(0, 199, (4,)).astype(np.int32)
    sched = Scheduler(engine(params, slots=2))
    a = Request(rid=0, prompt=first, max_new_tokens=6)
    c = Request(rid=2, prompt=third, max_new_tokens=24)   # keeps the server live
    b = Request(rid=1, prompt=second, max_new_tokens=7)
    for req in (a, c, b):
        sched.submit(req)
    while a.status != "done":
        sched.tick()
    # a's slot is free, and the pass dispatched for it this tick rides on
    slot = next(s for s in range(2) if s not in sched._slot_req)
    late, served_by_slot, _ = sched._in_flight
    assert served_by_slot[slot] is a and late is not None
    unread = sched.lanes_unread
    sched.tick()               # b: admitted, prefilled, its first pass sent
    assert sched.lanes_unread == unread + 1     # a's lane of the late pass
    assert sched._slot_req[slot] is b and b.status == "decoding"
    assert b.tokens == [] and sched._in_flight[1][slot] is b
    sched.tick()               # reads b's first pass, not a's late one
    assert len(b.tokens) <= STEPS and b._blk_passes == 1
    sched.serve(max_ticks=200)
    assert (list(a.tokens), list(a.unmask_pass)) == alone(params, first, 6)
    assert (list(b.tokens), list(b.unmask_pass)) == alone(params, second, 7)
    assert (list(c.tokens), list(c.unmask_pass)) == alone(params, third, 24)
    assert max(gaps(params, b)) <= TOL
    assert sched.engine.allocator.used_blocks == 0


def test_no_pass_is_left_in_flight_when_the_server_runs_dry(params):
    """The pass dispatched after the last request's last one is dropped
    with it: a server that ran dry holds no device result, counts no
    pass nobody read, and serves the next request as a fresh one does."""
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, 199, (8,)).astype(np.int32)
    sched = Scheduler(engine(params, slots=2))
    sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=8))
    sched.serve(max_ticks=200)
    assert not sched.busy and sched._in_flight is None
    assert sched.lanes_unread == 0      # the dropped pass is not counted
    passes, ticks = sched.block_passes, sched.decode_ticks
    assert sched.tick() == 0
    assert (sched.block_passes, sched.decode_ticks) == (passes, ticks)
    again = Request(rid=1, prompt=prompt, max_new_tokens=8)
    sched.submit(again)
    sched.serve(max_ticks=200)
    assert sched._in_flight is None
    assert (list(again.tokens), list(again.unmask_pass)) == alone(
        params, prompt, 8)
    assert sched.engine.allocator.used_blocks == 0


# -- the paged kernel: a block's queries as query rows --------------------


#: prompts on and across the 16-row pool blocks' edges, shorter than a
#: block too; answers of three blocks or more past the prompt's tail
KERNEL_SHAPES = [(21, 14), (9, 16), (30, 12), (3, 17), (16, 13)]


def _live_rows(eng, slot):
    """Every pool's rows of ``slot`` below its committed length, in
    position order: (pools, positions, width)."""
    pos = int(eng.state["pos"][slot])
    bl = eng.pool.block_len
    blocks = np.asarray(eng.state["tables"][slot])[:-(-pos // bl)]
    return np.stack([
        p[blocks].reshape(-1, p.shape[-1])[:pos] for p in pools(eng)
    ])


def test_the_kernel_serves_block_steps_as_the_gather_path_does(params):
    """The block step on the paged kernel (interpreted): every live
    slot's block written to its own rows first, the block's 4 queries
    of each head read as 4 more query rows over the head's K/V head.
    Beside the gather path, ticked in turn on the same requests (three
    slots for five, so a slot is admitted and prefilled while the others
    denoise, and a lane stands dead): every pass fixes the same tokens
    at the same places, every slot's committed pool rows agree to
    float32's rounding after every tick, and each request gets the
    tokens it gets alone."""
    rng = np.random.default_rng(41)
    prompts = [rng.integers(0, 199, (p,)).astype(np.int32)
               for p, _ in KERNEL_SHAPES]
    scheds, passes = [], []
    for impl in ("fused", "reference"):
        eng = Engine(params, MCFG, EngineConfig(
            slots=3, kv_block_len=16, max_prefill_chunk=16,
            block_steps=STEPS, attend_impl=impl, interpret=True,
        ))
        assert eng._fused == (impl == "fused")
        seen, step = [], eng.block_step

        def recorded(step=step, seen=seen):
            out = step()
            seen.append(out)
            return out

        eng.block_step = recorded
        sched = Scheduler(eng)
        for rid, (prompt, (_, n)) in enumerate(zip(prompts, KERNEL_SHAPES)):
            sched.submit(Request(rid=rid, prompt=prompt, max_new_tokens=n))
        scheds.append(sched)
        passes.append(seen)
    fused, reference = scheds
    gap, dead_lane, admitted_among_live = 0.0, False, False
    while fused.busy or reference.busy:
        for sched in scheds:
            sched.tick()
        a, b = fused.engine.state, reference.engine.state
        for lane in ("pos", "live", "tables", "blk_tok", "blk_masked"):
            np.testing.assert_array_equal(a[lane], b[lane])
        live = np.asarray(a["live"])
        for slot in np.flatnonzero(live):
            if int(a["pos"][slot]):
                gap = max(gap, float(np.max(np.abs(
                    _live_rows(fused.engine, slot)
                    - _live_rows(reference.engine, slot)
                ))))
        dead_lane |= bool(live.any() and not live.all())
        admitted_among_live |= live.any() and any(
            not live[slot] for slot in fused._slot_req
        )
    assert dead_lane and admitted_among_live
    assert len(passes[0]) == len(passes[1]) > 0
    for out_f, out_r in zip(*passes):
        np.testing.assert_array_equal(
            np.asarray(out_f)[:-2], np.asarray(out_r)[:-2]
        )
    assert 0.0 < gap <= 2e-5
    assert fused.block_commits >= 3 * len(KERNEL_SHAPES)
    for f, r in zip(
        sorted(fused.finished, key=lambda q: q.rid),
        sorted(reference.finished, key=lambda q: q.rid),
    ):
        assert (f.tokens, f.unmask_pass) == (r.tokens, r.unmask_pass)
        assert len(f.tokens) == KERNEL_SHAPES[f.rid][1]
    assert fused.engine.allocator.used_blocks == 0


# -- refused loudly, not run wrongly --------------------------------------


GQA = TransformerConfig(
    vocab=50, d_model=32, n_heads=4, n_kv_heads=2, n_layers=1, max_len=32
)


@pytest.mark.parametrize("mcfg,field", [
    (MCFG, "diffusion_block"), (GQA, "n_kv_heads"),
])
@pytest.mark.parametrize("what,kw", [
    ("spec_k", {"spec_k": 2}),
    ("prefix_cache", {"prefix_cache": True}),
    ("attend_impl", {"attend_impl": "fused"}),
    ("mesh", {}),
    ("slot export", {}), ("slot import", {}),
    ("export_blocks", {}), ("install_prefix", {}),
])
def test_what_cannot_run_is_refused_by_the_fields_name(mcfg, field, what, kw):
    p = init_lm(jax.random.PRNGKey(0), mcfg)
    base = dict(slots=2, kv_block_len=8, max_prefill_chunk=8)

    def build(**more):
        return Engine(p, mcfg, EngineConfig(**base, **kw), **more)

    if what == "attend_impl":
        # the paged kernel reads query heads over fewer K/V heads, and a
        # block step's queries as query rows of one query
        assert build().attend_choice == "fused"
        return
    with pytest.raises(ValueError, match=field) as e:
        if what == "mesh":
            from singa_tpu.parallel.mesh import axis_pair_mesh

            build(mesh=axis_pair_mesh(1, 1, "model", None, "tp mesh"))
        elif what == "slot export":
            build().export_slot(0)
        elif what == "slot import":
            build().import_slot(0, {})
        elif what == "export_blocks":
            build().export_blocks([1])
        elif what == "install_prefix":
            build().install_prefix([], None, None)
        else:
            build()
    assert what.split()[-1] in str(e.value)


@pytest.mark.parametrize("what", [
    "spec_k", "prefix_cache", "mesh", "slot export", "slot import",
])
def test_block_steps_on_the_kernel_keep_their_refusals(params, what):
    """The kernel serves the block step, nothing more: speculation, the
    prefix cache, a mesh and slot export / import are refused by
    ``diffusion_block``'s name with the kernel pinned, as on the gather
    path."""
    conf = dict(slots=2, kv_block_len=8, max_prefill_chunk=8,
                attend_impl="fused")
    more = {"spec_k": {"spec_k": 2}, "prefix_cache": {"prefix_cache": True}}
    with pytest.raises(ValueError, match="diffusion_block"):
        if what == "mesh":
            from singa_tpu.parallel.mesh import axis_pair_mesh

            Engine(params, MCFG, EngineConfig(**conf), mesh=axis_pair_mesh(
                1, 1, "model", None, "tp mesh"))
            return
        eng = Engine(params, MCFG, EngineConfig(**conf, **more.get(what, {})))
        assert eng._fused
        if what == "slot export":
            eng.export_slot(0)
        elif what == "slot import":
            eng.import_slot(0, {})


@pytest.mark.parametrize("kw,name", [
    ({"kv_block_len": 6}, "kv_block_len"),
    ({"max_prefill_chunk": 6}, "max_prefill_chunk"),
    ({"block_steps": 3}, "block_steps"),
])
def test_block_lengths_that_do_not_divide_are_refused(params, kw, name):
    conf = dict(slots=2, kv_block_len=8, max_prefill_chunk=8)
    with pytest.raises(ValueError, match=name):
        Engine(params, MCFG, EngineConfig(**{**conf, **kw}))


def test_a_sampling_request_is_refused_for_block_steps(params):
    sched = Scheduler(engine(params))
    with pytest.raises(ValueError, match="diffusion_block"):
        sched.submit(Request(
            rid=0, prompt=np.zeros((4,), np.int32), max_new_tokens=4,
            temperature=0.7,
        ))


def test_a_causal_model_with_fewer_kv_heads_decodes_through_the_pool():
    """Fewer K/V heads alone (no blocks): one-token ticks through pools
    whose rows are ``n_kv_heads * head_dim`` wide give the tokens of the
    cache-free ``lm_apply``."""
    from singa_tpu.models.transformer import lm_apply

    cfg = TransformerConfig(
        vocab=50, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
        max_len=32, norm="rmsnorm", pos="rope", qk_norm=True,
        tied_head=False,
    )
    p = init_lm(jax.random.PRNGKey(3), cfg)
    eng = Engine(p, cfg, EngineConfig(slots=2, kv_block_len=8,
                                      max_prefill_chunk=8))
    assert eng.state["k"][0].shape[-1] == 2 * 8
    sched = Scheduler(eng)
    prompt = np.arange(5, 16, dtype=np.int32)
    sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=6))
    sched.serve(max_ticks=50)
    (req,) = sched.finished
    seq = np.concatenate([prompt, req.tokens]).astype(np.int32)
    logits = np.asarray(lm_apply(p, jnp.asarray(seq)[None], cfg)[0])
    for i, tok in enumerate(req.tokens):
        row = logits[len(prompt) + i - 1]
        assert row.max() - row[tok] <= 1e-4


def test_a_rotary_model_speculates_to_the_stream_it_decodes():
    """Rotary positions with as many K/V heads as query heads pass the
    refusals, so the verify program has to hand the block body its
    positions: drafted runs give the tokens of one-token ticks."""
    cfg = TransformerConfig(
        vocab=50, d_model=32, n_heads=4, n_layers=2, max_len=48,
        norm="rmsnorm", pos="rope", qk_norm=True, tied_head=False,
    )
    p = init_lm(jax.random.PRNGKey(4), cfg)
    prompt = np.tile(np.arange(7, 12, dtype=np.int32), 3)
    streams = []
    for spec_k in (0, 3):
        sched = Scheduler(Engine(p, cfg, EngineConfig(
            slots=2, kv_block_len=8, max_prefill_chunk=8, spec_k=spec_k,
        )))
        sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=12))
        sched.serve(max_ticks=100)
        streams.append(list(sched.finished[0].tokens))
    assert streams[0] == streams[1] and len(streams[0]) == 12

