"""One-mixer layers — Mamba-2 with slot-resident recurrent state,
attention without positions over a paged pool, latent ReLU^2 experts
with a share held — at a small size on the CPU with seeded float32
weights: the program (``TransformerConfig`` -> ``Engine`` ->
``Scheduler``, and ``lm_apply``) against the plain reference
(``benchmark/reference/nemotron_h.py``), at the level of logits.

Tolerances are float32's: program and reference compute the same
equations in another order (the program's recurrence in blocks or a step
at a time from a carried state, the reference's a position at a time
from zero), so logits of size 1-10 agree to some 1e-5; a router's
near-tie that went the other way, a state inherited or stepped over
padding show as 1e-2 or more, so 2e-4 pins them.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.drivers import serve_nemotron_h as drv
from benchmark.reference import nemotron_h as ref
from benchmark.reference.confnet import rounder
from singa_tpu.models.transformer import (
    TransformerConfig, generate, init_lm, lm_apply,
)
from singa_tpu.ops import ssm
from singa_tpu.parallel import moe
from singa_tpu.serve import Engine, EngineConfig, Request, Scheduler

TOL = 2e-4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


#: the shipped configuration with the rehearsal's tiny widths laid over
#: it (blocks of 8 positions in the chunked scan), in the shipped order
#: of kinds, "*EMEM", with a state of 8 and the published time steps (the
#: rehearsal's own pattern and steps are chosen so that its planted
#: faults show through ITS traffic; these tests plant theirs by hand)
CFG = load("benchmark", "configs", "nemotron_3_super_120b_a12b.json") | load(
    "tests", "benchmark", "tiny", "configs",
    "nemotron_3_super_120b_a12b.json",
) | {
    "hybrid_override_pattern": "*EMEM", "ssm_state_size": 8,
    "time_step_min": 0.001, "time_step_max": 0.1,
}
MCFG = drv.model_config(CFG, {"max_model_len": 64})
SEED = 2**31 + 37


@pytest.fixture(scope="module")
def params():
    return ref.draw(CFG, SEED)


def gaps(params, cfg, prompt, tokens):
    """How far each served token's logit lies under the reference's best
    at its position, and the reference's logits at those positions."""
    full = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    logits = np.asarray(ref.forward(params, jnp.asarray(full), cfg))
    lo, hi = len(prompt) - 1, len(full) - 1
    rows = logits[lo:hi]
    return rows.max(-1) - rows[np.arange(hi - lo), full[lo + 1:hi + 1]], rows


def serve(params, mcfg, shapes, *, slots=3, chunk=16, seed=0, **kw):
    """Requests of ``shapes`` (prompt length, tokens) through a
    scheduler: chunked prefill from a slot's state, then one step a
    tick."""
    engine = Engine(params, mcfg, EngineConfig(
        slots=slots, kv_block_len=8, max_prefill_chunk=chunk, **kw
    ))
    sched = Scheduler(engine)
    rng = np.random.default_rng(seed)
    for i, (n, m) in enumerate(shapes):
        sched.submit(Request(
            rid=i, prompt=rng.integers(0, mcfg.vocab, (n,)).astype(np.int32),
            max_new_tokens=m, temperature=0.0, seed=i,
        ))
    sched.serve()
    return sched, engine


def test_reference_specs_are_the_programs_parameters(params):
    mine = init_lm(jax.random.PRNGKey(0), MCFG)
    assert {k: v.shape for k, v in mine.items()} == {
        k: v.shape for k, v in params.items()
    }
    # one mixer a layer: attention, experts, Mamba, experts, Mamba
    assert MCFG.layers == ("attn", "moe", "mamba", "moe", "mamba")
    assert "blk0/attn/qkv" in mine and "blk0/ln2/scale" not in mine
    assert "blk1/moe/w_gate" not in mine and "blk1/moe/s_gate" not in mine
    assert mine["blk1/moe/w_up"].shape == (4, 16, 24)        # held, latent, F
    assert mine["blk1/moe/lat_down"].shape == (32, 16)
    assert mine["blk1/moe/s_up"].shape == (32, 40)           # the full width
    assert mine["blk2/mamba/in_proj"].shape == (32, 32 + 64 + 8)
    assert "embed/pos" not in mine


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "import singa_tpu" not in text and "from singa_tpu" not in text


def test_the_lineages_initialisation_of_a_mamba_layer(params):
    """``A_log = log(1..H)``, ``dt_bias`` the inverse softplus of a step
    within [time_step_min, time_step_max], convolution weights within
    +-1/sqrt(K), norms and D one."""
    np.testing.assert_allclose(
        np.exp(params["blk2/mamba/A_log"]), np.arange(1, 9), rtol=1e-6
    )
    dt = np.asarray(jax.nn.softplus(params["blk2/mamba/dt_bias"]))
    assert np.all(dt >= 1e-3 * (1 - 1e-5)) and np.all(dt <= 0.1 * (1 + 1e-5))
    assert len(set(dt.round(6))) > 4
    w = np.asarray(params["blk2/mamba/conv_w"])
    assert np.abs(w).max() <= 0.5 and np.abs(w).max() > 0.4
    assert abs(w.mean()) < 0.05
    assert np.all(np.asarray(params["blk2/mamba/D"]) == 1.0)
    assert not np.array_equal(
        params["blk2/mamba/dt_bias"], params["blk4/mamba/dt_bias"]
    )


@pytest.mark.parametrize("letter", ["M", "*", "E", "-"])
def test_each_layer_kind_alone_against_the_reference(letter):
    """A model of ONE layer of the kind: every kind's equations stand
    alone against the reference's."""
    cfg = CFG | {"hybrid_override_pattern": letter, "num_hidden_layers": 1}
    mcfg = drv.model_config(cfg, {"max_model_len": 64})
    assert mcfg.layers == (ref.KINDS[letter],)
    params = ref.draw(cfg, SEED + 1)
    toks = np.random.default_rng(7).integers(0, 200, (29,)).astype(np.int32)
    got = lm_apply(params, jnp.asarray(toks)[None], mcfg)[0]
    want = ref.forward(params, jnp.asarray(toks), cfg)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert np.ptp(np.asarray(want), axis=-1).min() > 0.5


@pytest.mark.parametrize("length", [9, 24, 41])
def test_lm_apply_against_the_reference_forward(params, length):
    toks = np.random.default_rng(length).integers(0, 200, (length,))
    toks = toks.astype(np.int32)
    got = lm_apply(params, jnp.asarray(toks)[None], MCFG)[0]
    want = ref.forward(params, jnp.asarray(toks), CFG)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_attention_reads_no_position(params):
    """``pos = "none"``: neither a table nor a rotation. An attention
    layer alone is then blind to order among the positions it sees: the
    last row's logits do not move when the earlier tokens swap places."""
    cfg = CFG | {"hybrid_override_pattern": "*", "num_hidden_layers": 1}
    mcfg = drv.model_config(cfg, {"max_model_len": 64})
    p1 = ref.draw(cfg, SEED + 2)
    toks = np.asarray([5, 17, 42, 99, 3], np.int32)
    swapped = toks[[2, 0, 3, 1, 4]]
    a = lm_apply(p1, jnp.asarray(toks)[None], mcfg)[0, -1]
    b = lm_apply(p1, jnp.asarray(swapped)[None], mcfg)[0, -1]
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
    with pytest.raises(ValueError, match="served by serve/engine.py"):
        generate(params, jnp.zeros((1, 4), jnp.int32), MCFG, 2)


# -- the recurrence's three forms ---------------------------------------


def _mixer_inputs(seed, bsz=2, s=21):
    rng = np.random.default_rng(seed)
    w = {k[len("blk2/mamba/"):]: v for k, v in ref.draw(CFG, SEED).items()
         if k.startswith("blk2/mamba/")}
    u = jnp.asarray(rng.normal(size=(bsz, s, 32)), jnp.float32)
    return w, u


MIXER = dict(heads=8, head_dim=4, state_dim=8, groups=2, eps=1e-5)


@pytest.mark.parametrize("block", [4, 8, 32])
def test_the_three_forms_of_the_recurrence_agree(block):
    """The whole sequence from a zero state in blocks; the same in
    pieces that do and do not end on a block, each from the state the
    last left; a step at a time; and the reference's sequential scan."""
    w, u = _mixer_inputs(1)
    whole, (st_whole, tail_whole) = ssm.mamba2_mixer(
        w, u, block=block, **MIXER
    )
    lp = {f"mamba/{k}": v for k, v in w.items()}
    dims = ref.Dims.of(CFG)
    want = jnp.stack([
        ref.mamba(lp, u[i], dims, rounder("float32")) for i in range(2)
    ])
    np.testing.assert_allclose(whole, want, atol=2e-5, rtol=0)
    # pieces of 8, 5 and 8 positions, the state carried
    carried, outs = None, []
    for lo, hi in ((0, 8), (8, 13), (13, 21)):
        y, carried = ssm.mamba2_mixer(
            w, u[:, lo:hi], block=block, carried=carried, **MIXER
        )
        outs.append(y)
    np.testing.assert_allclose(
        jnp.concatenate(outs, axis=1), whole, atol=2e-5, rtol=0
    )
    np.testing.assert_allclose(carried[0], st_whole, atol=2e-5, rtol=0)
    np.testing.assert_array_equal(carried[1], tail_whole)
    # one step at a time from a carried state
    carried = (jnp.zeros_like(st_whole), jnp.zeros_like(tail_whole))
    steps = []
    for t in range(u.shape[1]):
        y, carried = ssm.mamba2_mixer(
            w, u[:, t:t + 1], block=block, carried=carried, **MIXER
        )
        steps.append(y)
    np.testing.assert_allclose(
        jnp.concatenate(steps, axis=1), whole, atol=2e-5, rtol=0
    )
    np.testing.assert_allclose(carried[0], st_whole, atol=2e-5, rtol=0)
    np.testing.assert_array_equal(carried[1], tail_whole)


@pytest.mark.parametrize("n_valid", [0, 1, 5, 8, 11])
def test_padding_leaves_state_and_tail_as_the_valid_positions_made_them(
    n_valid
):
    """A chunk of 12 positions of which ``n_valid`` count: the state and
    the tail that come back are those of the valid positions alone,
    whatever the padding holds, and with none valid they are bit for bit
    what went in."""
    w, u = _mixer_inputs(2, bsz=1, s=12)
    rng = np.random.default_rng(3)
    start = (
        jnp.asarray(rng.normal(size=(1, 8, 4, 8)), jnp.float32),
        jnp.asarray(rng.normal(size=(1, 3, 64)), jnp.float32),
    )
    valid = (jnp.arange(12) < n_valid)[None]
    y, (st, tail) = ssm.mamba2_mixer(
        w, u, block=8, carried=start, valid=valid, **MIXER
    )
    junk = u.at[:, n_valid:].set(1e3)
    y2, (st2, tail2) = ssm.mamba2_mixer(
        w, junk, block=8, carried=start, valid=valid, **MIXER
    )
    np.testing.assert_array_equal(st, st2)
    np.testing.assert_array_equal(tail, tail2)
    np.testing.assert_array_equal(y[:, :n_valid], y2[:, :n_valid])
    if n_valid == 0:
        np.testing.assert_array_equal(st, start[0])
        np.testing.assert_array_equal(tail, start[1])
    else:
        _, (st3, tail3) = ssm.mamba2_mixer(
            w, u[:, :n_valid], block=8, carried=start, **MIXER
        )
        np.testing.assert_allclose(st, st3, atol=1e-5, rtol=0)
        np.testing.assert_array_equal(tail, tail3)


def test_the_convolution_carries_its_tail():
    """Four taps over [tail | sequence]; the new tail is the last three
    inputs up to where the valid positions end."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 6, 3)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(2, 3, 3)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 3)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(3,)), jnp.float32)
    out, new = ssm.causal_conv(x, tail, w, b, jnp.asarray([6, 2]))
    full = np.concatenate([tail, x], axis=1)
    want = np.stack([
        sum(np.asarray(w)[k] * full[:, t + k] for k in range(4)) + np.asarray(b)
        for t in range(6)
    ], axis=1)
    np.testing.assert_allclose(out, jax.nn.silu(want), atol=1e-6, rtol=0)
    np.testing.assert_array_equal(new[0], x[0, 3:6])
    np.testing.assert_array_equal(new[1], full[1, 2:5])


@pytest.mark.parametrize("s,carried,form", [
    (1, True, "step"), (512, True, "chunked: 512 positions in 4 blocks"),
    (1, False, "chunked: 1 positions in 1 block of 128"),
    (4864, False, "chunked: 4864 positions in 38 blocks"),
])
def test_the_form_follows_the_pass(s, carried, form):
    assert ssm.choose_mamba_form(s, 128, carried).startswith(form)


# -- the served path: chunked prefill from a slot's state, then ticks ----

#: prompts that end inside a block of the scan (8), on its edge and on a
#: chunk's; with their answers every sequence decodes across a K/V
#: block's edge, and six requests on three slots use every slot twice
SHAPES = [(37, 12), (5, 20), (16, 9), (44, 15), (24, 6), (12, 5)]


@pytest.fixture(scope="module", params=[16, 12])
def served(params, request):
    """Chunks of 16 fall on the scan's blocks of 8; chunks of 12 do not
    (a chunk is a block and a half, padded inside the scan)."""
    return serve(params, MCFG, SHAPES, chunk=request.param)


@pytest.mark.parametrize("rid", range(len(SHAPES)))
def test_chunked_prefill_then_ticks_against_the_reference(
    params, served, rid
):
    sched, engine = served
    (req,) = [r for r in sched.finished if r.rid == rid]
    assert len(req.tokens) == SHAPES[rid][1]
    gap, rows = gaps(params, CFG, req.prompt, req.tokens)
    # every served token is the reference's best to rounding: the
    # chunks' carried states, the pool's rows and the ticks' steps all
    # stand behind the later ones
    assert gap.max() < TOL, gap
    assert np.ptp(rows, axis=-1).min() > 0.5    # logits that could differ


def test_the_paged_kernel_serves_the_same_streams(params):
    """The decode tick's attention layer read in place by the paged
    kernel (4 query heads over 2 K/V heads, through the interpreter)
    serves every request token for token as the gather path does, over
    ticks in which slots are admitted and retired: six requests on three
    slots."""
    def streams(impl):
        sched, engine = serve(
            params, MCFG, SHAPES, attend_impl=impl, interpret=True
        )
        assert engine.attend_choice == impl
        return {r.rid: r.tokens for r in sched.finished}

    fused = streams("fused")
    assert fused == streams("reference")
    assert [len(fused[i]) for i in range(len(SHAPES))] == [
        m for _, m in SHAPES
    ]


def test_pools_for_the_attention_layer_alone_and_state_beside_them(served):
    _, engine = served
    assert engine.attend_choice.startswith("reference")
    # one attention layer of five: one K and one V pool, 2 heads of 8
    assert len(engine.state["k"]) == len(engine.state["v"]) == 1
    assert engine.state["k"][0].shape == (3 * 8 + 1, 8, 16)
    # two Mamba layers: a float32 state and a convolution tail a slot
    assert [a.shape for a in engine.state["ssm"]] == [(3, 8, 4, 8)] * 2
    assert [a.shape for a in engine.state["conv"]] == [(3, 3, 64)] * 2
    assert engine.state["ssm"][0].dtype == jnp.float32
    assert engine.mamba_forms["jit__decode"].startswith("step")
    assert engine.mamba_forms["jit__prefill"].startswith("chunked")
    assert set(engine.expert_forms) == {"jit__decode", "jit__prefill"}


def test_counters_ride_the_pass(served):
    sched, engine = served
    assert engine.decode_counter_names[-1] == "state_slots_live"
    assert engine.decode_counters == 6
    # a live lane a token after a request's first
    tokens = sum(m for _, m in SHAPES) - len(SHAPES)
    assert tokens <= sched.state_slots_live <= tokens + sched.lanes_unread
    assert sched.state_slots_live == sched._live_ticks
    # 2 expert layers x 4 held experts bound a pass's hits
    assert 0 < sched.experts_hit <= sched.decode_ticks * 2 * 4
    assert 0 < sched.held_pairs <= tokens * 2 * 4 + sched.lanes_unread * 8
    assert sched.chunk_held_pairs > 0 and sched.cache_rows > 0
    assert sched.occupancy()["mamba_forms"] == engine.mamba_forms


def test_a_slot_used_twice_gives_what_a_fresh_engine_gives(params):
    """Admission zeroes a slot's state: the second request of a slot
    reads nothing of the first."""

    def second_of(first_len):
        engine = Engine(params, MCFG, EngineConfig(
            slots=1, kv_block_len=8, max_prefill_chunk=16
        ))
        sched = Scheduler(engine)
        rng = np.random.default_rng(4)
        second = rng.integers(0, 200, (19,)).astype(np.int32)
        for rid, prompt in enumerate(
            [rng.integers(0, 200, (first_len,)).astype(np.int32)]
            * bool(first_len) + [second]
        ):
            sched.submit(Request(
                rid=rid, prompt=prompt, max_new_tokens=14, temperature=0.0,
                seed=7,
            ))
        sched.serve()
        return sched.finished[-1]

    used, fresh = second_of(30), second_of(0)
    np.testing.assert_array_equal(used.prompt, fresh.prompt)
    assert used.tokens == fresh.tokens and len(used.tokens) == 14
    assert gaps(params, CFG, used.prompt, used.tokens)[0].max() < TOL


def test_dead_lanes_and_padding_leave_state_bit_identical(params):
    """A tick advances live lanes alone, and a chunk its own slot alone:
    the state and the tail of every other slot are bit for bit what
    they were."""
    engine = Engine(params, MCFG, EngineConfig(
        slots=3, kv_block_len=8, max_prefill_chunk=16
    ))
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 200, (21,)).astype(np.int32)
    # slot 1 holds a prefilled prompt and is NOT live; slot 0 is live
    engine.admit(1, 40)
    engine.prefill_chunk(1, prompt[:16], 0)
    last = engine.prefill_chunk(1, prompt[16:], 16)
    engine.admit(0, 40)
    first = engine.prefill_chunk(0, prompt[:7], 0)
    engine.activate(0, first, 7, seed=0)
    def held():
        # slots first: the tail is kept (K - 1, slots, C)
        return [np.asarray(a) for a in engine.state["ssm"]] + [
            np.moveaxis(np.asarray(a), 1, 0) for a in engine.state["conv"]
        ]

    before = held()
    engine.decode()
    engine.decode()
    after = held()
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b[1:], a[1:])     # dead lanes
        assert not np.array_equal(b[0], a[0])           # the live one moved
    # a chunk with nothing valid moves nothing at all
    engine.prefill_chunk(2, prompt[:0], 0)
    for a, g in zip(after, held()):
        np.testing.assert_array_equal(a, g)
    # and slot 1, activated now, still answers as the reference does
    engine.activate(1, last, 21, seed=1)
    tok = int(np.asarray(engine.decode())[1])
    logits = np.asarray(ref.forward(params, jnp.asarray(prompt), CFG))[-1]
    first_tok = int(np.argmax(logits))
    full = np.concatenate([prompt, [first_tok]]).astype(np.int32)
    nxt = np.asarray(ref.forward(params, jnp.asarray(full), CFG))[-1]
    assert nxt.max() - nxt[tok] < TOL


@pytest.mark.parametrize("fault", drv.FAULTS)
def test_a_wrong_state_fails_the_same_comparison(params, fault):
    """The planted faults of the cell's calibration, at the tiny size: a
    state kept at admission (a later request of the slot inherits its
    predecessor's) and a last chunk's padding stepping the state. Served
    tokens leave the reference's best where the wrong state tips a
    near-tie, so the requests are long enough to meet some (without a
    fault the same requests read 0.0)."""
    driver = drv.Driver(
        config=CFG, traffic={}, limits={}, seed=SEED, devices=None,
        work=None, spans=harness.Spans(False),
    )
    engine = Engine(params, MCFG, EngineConfig(
        slots=1, kv_block_len=8, max_prefill_chunk=16
    ))
    driver.engine = engine
    driver._plant(fault)
    sched = Scheduler(engine)
    rng = np.random.default_rng(0)
    for i, (n, m) in enumerate([(40, 20), (6, 40), (3, 40)]):
        sched.submit(Request(
            rid=i, prompt=rng.integers(0, 200, (n,)).astype(np.int32),
            max_new_tokens=m, temperature=0.0, seed=i,
        ))
    sched.serve()
    worst = max(
        gaps(params, CFG, r.prompt, r.tokens)[0].max()
        for r in sched.finished if r.rid > 0
    )
    assert worst > 100 * TOL, worst


# -- the expert layer: its share, its latent, its form -------------------


def _expert_layer(params):
    names = moe.topk_param_names("relu2", True, True, True)
    return {k: params[f"blk1/moe/{k}"] for k in names}


def test_four_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST. 16 experts over 4 chips, 4 held each: the four
    shares' routed parts — each through its own ``W_2`` product, which
    is linear — with the shared expert counted once, add up to what the
    uncut reference gives for the whole layer; program and reference
    agree share by share."""
    whole_cfg = CFG | {"n_routed_experts": 16, "experts_held_from": 0}
    whole = ref.draw(whole_cfg, SEED)
    lp = {k[len("blk1/"):]: v for k, v in whole.items()
          if k.startswith("blk1/")}
    h = jnp.asarray(
        np.random.default_rng(11).normal(size=(23, 32)), jnp.float32
    )
    r = rounder("float32")
    routed_all, shared = ref.expert_parts(lp, h, ref.Dims.of(whole_cfg), r)
    total = jnp.zeros_like(routed_all)
    for first in (0, 4, 8, 12):
        share_cfg = CFG | {"experts_held_from": first}
        share = dict(lp)
        for k in ("moe/w_up", "moe/w_down"):
            share[k] = lp[k][first:first + 4]
        routed, shared_again = ref.expert_parts(
            share, h, ref.Dims.of(share_cfg), r
        )
        np.testing.assert_array_equal(shared_again, shared)
        got, _ = moe.moe_topk_ffn(
            h[None], {k[len("moe/"):]: v for k, v in share.items()
                      if k.startswith("moe/")},
            5, score="sigmoid", scale=5.0, held_from=first,
        )
        np.testing.assert_allclose(
            got[0], routed + shared, atol=2e-5, rtol=0
        )
        total = total + routed
    np.testing.assert_allclose(total, routed_all, atol=2e-5, rtol=0)
    assert float(jnp.abs(routed_all).max()) > 0.1


@pytest.mark.parametrize("form", ["dense", "grouped"])
def test_both_forms_compute_the_latent_relu2_experts(
    params, form, monkeypatch
):
    lp = _expert_layer(params)
    h = jnp.asarray(
        np.random.default_rng(12).normal(size=(2, 9, 32)), jnp.float32
    )
    monkeypatch.setattr(moe, "choose_expert_form", lambda *a: form + ": test")
    got, stats = moe.moe_topk_ffn(
        h, lp, 5, score="sigmoid", scale=5.0, held_from=4
    )
    routed, shared = ref.expert_parts(
        {f"moe/{k}": v for k, v in lp.items()}, h.reshape(18, 32),
        ref.Dims.of(CFG), rounder("float32"),
    )
    np.testing.assert_allclose(
        got.reshape(18, 32), routed + shared, atol=2e-5, rtol=0
    )
    assert 0 < int(stats[0]) <= 4 and int(stats[2]) <= 18 * 4


@pytest.mark.parametrize("n,form", [(128, "dense: 128 tokens a pass ride"),
                                    (512, "grouped: 512 tokens a pass")])
def test_the_chooser_on_the_cells_two_passes(n, form):
    """(tokens, 128 held, 512, 22) on a TPU: a tick's 128 tokens ride on
    the weight reads (a flat router leaves no held expert idle), a
    chunk's 512 are bound by arithmetic and 22 routed rows an expert
    with a tile's rounding are under half of them."""
    assert moe.choose_expert_form(n, 128, 512, 22, "tpu").startswith(form)


def test_what_cannot_run_beside_recurrent_state_is_refused_by_name(params):
    for kw, what in (
        ({"spec_k": 2}, "speculate"), ({"prefix_cache": True}, "prefix_cache"),
    ):
        with pytest.raises(ValueError, match="layers = 5 one-mixer blocks"):
            Engine(params, MCFG, EngineConfig(kv_block_len=8, **kw))
    # the paged kernel reads the attention layer's pools (PR 38)
    assert Engine(params, MCFG, EngineConfig(
        kv_block_len=8, attend_impl="fused",
    )).attend_choice == "fused"
    engine = Engine(params, MCFG, EngineConfig(kv_block_len=8))
    for call in (
        lambda: engine.export_slot(0),
        lambda: engine.import_slot(0, {}),
        lambda: engine.export_blocks([1]),
        lambda: engine.install_prefix([], None, None),
    ):
        with pytest.raises(ValueError, match="layers = 5 one-mixer blocks"):
            call()
    with pytest.raises(ValueError, match="layers"):
        TransformerConfig(vocab=8, n_layers=2, layers=("attn",))
    with pytest.raises(ValueError, match="mamba_heads"):
        TransformerConfig(vocab=8, n_layers=1, layers=("mamba",))


def test_the_published_pattern_maps_onto_the_layer_list():
    big = load("benchmark", "configs", "nemotron_3_super_120b_a12b.json")
    mcfg = drv.model_config(big, {"max_model_len": 4864})
    assert mcfg.layers == ("attn",) + ("moe", "mamba") * 5
    assert mcfg.layers_of("mamba") == (2, 4, 6, 8, 10)
    assert mcfg.layers_of("attn") == (0,)
    assert mcfg.conv_dim == 8192 + 2 * 8 * 128 == 10240
    assert (mcfg.moe_held, mcfg.moe_latent, mcfg.moe_top_k) == (
        (128, 128), 1024, 22
    )
    # a model without the list holds attention in every block
    plain = TransformerConfig(vocab=8, n_layers=3)
    assert plain.layers_of("attn") == (0, 1, 2)
    assert plain.layers_of("mamba") == ()
