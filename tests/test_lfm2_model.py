"""Gated short-convolution layers with a slot-resident tail beside RoPE'd
GQA layers over a paged pool, each followed by a dense SwiGLU MLP or
sigmoid-scored SwiGLU experts, at a small size on the CPU with seeded
float32 weights: the program (``TransformerConfig`` -> ``Engine`` ->
``Scheduler``, and ``lm_apply``) against the plain reference
(``benchmark/reference/lfm2_moe.py``), at the level of logits.

Tolerances are float32's: program and reference compute the same
equations in another order (the program's convolution from a carried
tail, a chunk or a step at a time; the reference's over the whole
sequence from zeros), so logits of size 1-10 agree to some 1e-5; the
two gate epsilons (1e-20 and transformers' 1e-6) differ by under 1e-6
relative. A tail dropped at a chunk's edge, kept from a slot's last
request or stepped over padding shows as 1e-2 or more, so 2e-4 pins
them.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.drivers import serve_lfm2 as drv
from benchmark.reference import lfm2_moe as ref
from benchmark.reference.confnet import rounder
from singa_tpu.models import transformer
from singa_tpu.models.transformer import TransformerConfig, init_lm
from singa_tpu.ops import ssm
from singa_tpu.serve import Engine, EngineConfig, Request, Scheduler

TOL = 2e-4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


#: the shipped configuration with the rehearsal's tiny widths laid over
#: it: d 32, 4 query heads over 2 K/V heads of 8, 4 experts top-2, and
#: published layers conv, attention, conv, conv of which the first is
#: dense: every kind of block is here
CFG = load("benchmark", "configs", "lfm2_8b_a1b.json") | load(
    "tests", "benchmark", "tiny", "configs", "lfm2_8b_a1b.json"
)
MCFG = drv.model_config(CFG, {"max_model_len": 64})
SEED = 2**31 + 43
#: compiled whole: a program traced op by op compiles each op on its own
lm_apply = jax.jit(transformer.lm_apply, static_argnums=2)
short_conv = jax.jit(ssm.short_conv)


def draw(cfg, seed):
    return ref.draw(cfg, seed)


#: the longest sequence a test hands the reference: every call pads to
#: it, so that each kind of block compiles once
REF_LEN = 64


def ref_logits(params, seq):
    """The reference's logits of ``seq``, computed at ``REF_LEN``
    (causal: the padding's rows come after and move nothing)."""
    padded = np.zeros((REF_LEN,), np.int32)
    padded[:len(seq)] = seq
    return np.asarray(ref.forward(params, jnp.asarray(padded), CFG))[
        :len(seq)
    ]


@pytest.fixture(scope="module")
def params():
    return draw(CFG, SEED)


def gaps(params, prompt, tokens):
    """How far each served token's logit lies under the reference's best
    at its position, and the reference's logits at those positions."""
    full = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    logits = ref_logits(params, full)
    lo, hi = len(prompt) - 1, len(full) - 1
    rows = logits[lo:hi]
    return rows.max(-1) - rows[np.arange(hi - lo), full[lo + 1:hi + 1]], rows


def serve(params, shapes, *, slots=3, chunk=12, seed=0, engine=None, **kw):
    """Requests of ``shapes`` (prompt length, tokens) through a
    scheduler: chunked prefill from a slot's tail, then one token a
    tick."""
    engine = engine or Engine(params, MCFG, EngineConfig(
        slots=slots, kv_block_len=8, max_prefill_chunk=chunk, **kw
    ))
    sched = Scheduler(engine)
    rng = np.random.default_rng(seed)
    for i, (n, m) in enumerate(shapes):
        sched.submit(Request(
            rid=i, prompt=rng.integers(0, MCFG.vocab, (n,)).astype(np.int32),
            max_new_tokens=m, temperature=0.0, seed=i,
        ))
    sched.serve()
    return sched, engine


def test_reference_specs_are_the_programs_parameters(params):
    mine = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), MCFG))
    assert {k: v.shape for k, v in mine.items()} == {
        k: v.shape for k, v in params.items()
    }
    # two one-mixer blocks a published layer: the operator, then the
    # dense MLP (published layer 0) or the experts
    assert MCFG.layers == (
        "shortconv", "mlp", "attn", "moe", "shortconv", "moe",
        "shortconv", "moe",
    )
    assert mine["blk0/shortconv/in_proj"].shape == (32, 96)
    assert mine["blk0/shortconv/conv_w"].shape == (3, 32)
    assert mine["blk2/attn/qkv"].shape == (32, (4 + 2 * 2) * 8)
    assert mine["blk2/attn/q_norm"].shape == (8,)
    assert mine["blk3/moe/w_gate"].shape == (4, 32, 24)
    assert mine["blk1/mlp/gate"].shape == (32, 48)
    assert "head/out" not in mine and "embed/pos" not in mine


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "import singa_tpu" not in text and "from singa_tpu" not in text


@pytest.mark.parametrize("length", [9, 41])
def test_lm_apply_against_the_reference_forward(params, length):
    toks = np.random.default_rng(length).integers(0, 200, (length,))
    toks = toks.astype(np.int32)
    got = lm_apply(params, jnp.asarray(toks)[None], MCFG)[0]
    want = ref_logits(params, toks)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert np.ptp(want, axis=-1).min() > 0.5


@pytest.mark.parametrize("types", [["conv"], ["full_attention"]])
def test_each_operator_alone_against_the_reference(types):
    """One published layer of the kind, dense: the short convolution's
    and the rotated attention's equations stand alone."""
    cfg = CFG | {"layer_types": types, "num_hidden_layers": 1}
    mcfg = drv.model_config(cfg, {"max_model_len": 64})
    p1 = draw(cfg, SEED + 1)
    toks = np.random.default_rng(7).integers(0, 200, (29,)).astype(np.int32)
    got = lm_apply(p1, jnp.asarray(toks)[None], mcfg)[0]
    want = ref.forward(p1, jnp.asarray(toks), cfg)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # order matters to both operators: rotary positions, the taps
    swapped = toks[[1, 0] + list(range(2, 29))]
    moved = lm_apply(p1, jnp.asarray(swapped)[None], mcfg)[0, 2]
    assert np.abs(np.asarray(moved) - np.asarray(got[2])).max() > 1e-3


# -- the short convolution's two forms and its tail ----------------------


@functools.cache
def _conv_weights():
    return {k[len("blk0/shortconv/"):]: v for k, v in draw(CFG, SEED).items()
            if k.startswith("blk0/shortconv/")}


def _conv_inputs(seed, bsz=2, s=21):
    w = _conv_weights()
    u = jnp.asarray(
        np.random.default_rng(seed).normal(size=(bsz, s, 32)), jnp.float32
    )
    return w, u


def test_the_step_form_against_the_sequence_form():
    """The whole sequence from zeros; the same in pieces of 8, 5 and 8,
    each from the tail the last left; a step at a time; and the
    reference's sum over the taps: one result, and the same tail."""
    w, u = _conv_inputs(1)
    whole, tail_whole = short_conv(w, u)
    lp = {f"shortconv/{k}": v for k, v in w.items()}
    want = jax.vmap(jax.jit(
        lambda x: ref.short_conv(lp, x, rounder("float32"))
    ))(u)
    np.testing.assert_allclose(whole, want, atol=1e-5, rtol=0)
    carried, outs = None, []
    for lo, hi in ((0, 8), (8, 13), (13, 21)):
        y, carried = short_conv(w, u[:, lo:hi], carried=carried)
        outs.append(y)
    np.testing.assert_allclose(
        jnp.concatenate(outs, axis=1), whole, atol=1e-5, rtol=0
    )
    np.testing.assert_array_equal(carried, tail_whole)
    carried, steps = jnp.zeros_like(tail_whole), []
    for t in range(u.shape[1]):
        y, carried = short_conv(w, u[:, t:t + 1], carried=carried)
        steps.append(y)
    np.testing.assert_allclose(
        jnp.concatenate(steps, axis=1), whole, atol=1e-5, rtol=0
    )
    np.testing.assert_array_equal(carried, tail_whole)
    assert tail_whole.shape == (2, 2, 32)
    # the tail is what the taps read: the last two gated inputs B * x
    bcx = u @ w["in_proj"]
    np.testing.assert_allclose(
        tail_whole, (bcx[..., :32] * bcx[..., 64:])[:, -2:], atol=1e-6,
        rtol=0,
    )


@pytest.mark.parametrize("n_valid", [0, 1, 7, 12])
def test_padding_leaves_the_tail_as_the_valid_positions_made_it(n_valid):
    """A chunk of 12 positions of which ``n_valid`` count: the tail that
    comes back is the valid positions' alone, whatever the padding
    holds, and with none valid it is bit for bit what went in."""
    w, u = _conv_inputs(2, bsz=1, s=12)
    start = jnp.asarray(
        np.random.default_rng(3).normal(size=(1, 2, 32)), jnp.float32
    )
    valid = (jnp.arange(12) < n_valid)[None]
    y, tail = short_conv(w, u, carried=start, valid=valid)
    junk = u.at[:, n_valid:].set(1e3)
    y2, tail2 = short_conv(w, junk, carried=start, valid=valid)
    np.testing.assert_array_equal(tail, tail2)
    np.testing.assert_array_equal(y[:, :n_valid], y2[:, :n_valid])
    if n_valid == 0:
        np.testing.assert_array_equal(tail, start)
    else:
        _, tail3 = short_conv(w, u[:, :n_valid], carried=start)
        np.testing.assert_array_equal(tail, tail3)


def test_the_convolution_without_bias_or_silu_is_its_tap_sum():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 6, 3)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(2, 2, 3)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3)), jnp.float32)
    out, new = ssm.causal_conv(
        x, tail, w, None, jnp.asarray([6, 1]), silu=False
    )
    full = np.concatenate([tail, x], axis=1)
    want = np.stack([
        sum(np.asarray(w)[k] * full[:, t + k] for k in range(3))
        for t in range(6)
    ], axis=1)
    np.testing.assert_allclose(out, want, atol=1e-6, rtol=0)
    np.testing.assert_array_equal(new[0], x[0, 4:6])
    np.testing.assert_array_equal(new[1], full[1, 1:3])


# -- the served path: chunked prefill from a slot's tail, then ticks -----

#: prompts that cross two, three and four chunk edges of 12, one that
#: ends on an edge and short ones; with their answers every sequence
#: decodes across a K/V block's edge, and six requests on three slots
#: use every slot twice
SHAPES = [(37, 12), (5, 20), (24, 9), (44, 15), (30, 6), (12, 5)]


@pytest.fixture(scope="module")
def served(params):
    return serve(params, SHAPES)


@pytest.mark.parametrize("rid", range(len(SHAPES)))
def test_chunked_prefill_then_ticks_against_the_reference(
    params, served, rid
):
    sched, _ = served
    (req,) = [r for r in sched.finished if r.rid == rid]
    assert len(req.tokens) == SHAPES[rid][1]
    gap, rows = gaps(params, req.prompt, req.tokens)
    # every served token is the reference's best to rounding: the tails
    # carried across chunk edges, the pools' rotated rows and the ticks'
    # steps all stand behind the later ones
    assert gap.max() < TOL, gap
    assert np.ptp(rows, axis=-1).min() > 0.5    # logits that could differ


def test_tails_for_the_conv_layers_and_pools_for_attention(served):
    sched, engine = served
    # one attention layer: one K and one V pool of 2 heads of 8
    assert len(engine.state["k"]) == len(engine.state["v"]) == 1
    assert engine.state["k"][0].shape == (3 * 8 + 1, 8, 16)
    # three short convolutions: a tail of K - 1 = 2 rows a slot, no
    # recurrent state beside it
    assert [a.shape for a in engine.state["conv"]] == [(2, 3, 32)] * 3
    assert "ssm" not in engine.state and engine.mamba_forms == {}
    assert engine.decode_counter_names[-1] == "state_slots_live"
    tokens = sum(m for _, m in SHAPES) - len(SHAPES)
    assert tokens <= sched.state_slots_live <= tokens + sched.lanes_unread
    assert sched.state_slots_live == sched._live_ticks


def _drop_tail_at_chunk_edges(engine):
    """A chunk that starts from zeros instead of its slot's tail."""
    prefill = engine._prefill

    def dropped(params, state, slot, chunk, pos0, n_valid):
        state = {**state, "conv": tuple(
            a.at[:, slot].set(0) for a in state["conv"]
        )}
        return prefill(params, state, slot, chunk, pos0, n_valid)

    engine._prefill_jit = jax.jit(dropped, donate_argnums=(1,))


def test_a_tail_dropped_at_a_chunk_edge_fails_the_comparison(params):
    """The same comparison catches a chunk that does not carry its
    slot's tail in: the first positions of every chunk after the first
    read zeros where the last chunk's inputs were."""
    engine = Engine(params, MCFG, EngineConfig(
        slots=2, kv_block_len=8, max_prefill_chunk=12
    ))
    _drop_tail_at_chunk_edges(engine)
    sched, _ = serve(params, [(37, 12), (44, 10)], engine=engine)
    worst = max(gaps(params, r.prompt, r.tokens)[0].max()
                for r in sched.finished)
    assert worst > 100 * TOL, worst


def _second_request(params, first_len, plant=None):
    """Two requests on ONE slot; the second's served tokens."""
    engine = Engine(params, MCFG, EngineConfig(
        slots=1, kv_block_len=8, max_prefill_chunk=16
    ))
    if plant:
        driver = drv.Driver(
            config=CFG, traffic={}, limits={}, seed=SEED, devices=None,
            work=None, spans=harness.Spans(False),
        )
        driver.engine = engine
        driver._plant(plant)
    rng = np.random.default_rng(4)
    second = rng.integers(0, 200, (19,)).astype(np.int32)
    prompts = [rng.integers(0, 200, (first_len,)).astype(np.int32)] * bool(
        first_len
    ) + [second]
    sched = Scheduler(engine)
    for rid, prompt in enumerate(prompts):
        sched.submit(Request(
            rid=rid, prompt=prompt, max_new_tokens=14, temperature=0.0,
            seed=7,
        ))
    sched.serve()
    return sched.finished[-1]


def test_a_slot_used_twice_starts_its_second_request_from_zeros(params):
    """Admission zeroes a slot's tails: the second request of a slot
    reads nothing of the first, and is served as the reference's full
    pass from zeros gives it (the planted fault below is the same
    request from the first one's tails)."""
    used = _second_request(params, 30)
    assert len(used.tokens) == 14
    assert gaps(params, used.prompt, used.tokens)[0].max() < TOL


@pytest.mark.parametrize("fault", ["state_kept_on_admit", "pad_advances_state"])
def test_a_wrong_tail_fails_the_same_comparison(params, fault):
    """The cell's planted faults at the tiny size: a tail kept at
    admission (the slot's second request starts from its predecessor's
    last two rows) and a last chunk's padding counted into the tail."""
    stale = _second_request(params, 30, plant=fault)
    assert gaps(params, stale.prompt, stale.tokens)[0].max() > 100 * TOL


def test_dead_lanes_keep_their_tails_bit_for_bit(params):
    """A tick steps live lanes alone, and a chunk its own slot alone:
    every other slot's tails are what they were."""
    engine = Engine(params, MCFG, EngineConfig(
        slots=3, kv_block_len=8, max_prefill_chunk=16
    ))
    prompt = np.random.default_rng(9).integers(0, 200, (21,)).astype(np.int32)
    # slot 1 holds a prefilled prompt and is NOT live; slot 0 is live
    engine.admit(1, 40)
    engine.prefill_chunk(1, prompt[:16], 0)
    last = engine.prefill_chunk(1, prompt[16:], 16)
    engine.admit(0, 40)
    first = engine.prefill_chunk(0, prompt[:7], 0)
    engine.activate(0, first, 7, seed=0)

    def tails():
        return [np.moveaxis(np.asarray(a), 1, 0) for a in engine.state["conv"]]

    before = tails()
    engine.decode()
    engine.decode()
    after = tails()
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b[1:], a[1:])     # dead lanes
        assert not np.array_equal(b[0], a[0])           # the live one moved
    engine.prefill_chunk(2, prompt[:0], 0)
    for a, g in zip(after, tails()):
        np.testing.assert_array_equal(a, g)
    # slot 1, activated now, still answers as the reference does
    engine.activate(1, last, 21, seed=1)
    tok = int(np.asarray(engine.decode())[1])
    logits = ref_logits(params, prompt)[-1]
    full = np.concatenate([prompt, [int(np.argmax(logits))]]).astype(np.int32)
    nxt = ref_logits(params, full)[-1]
    assert nxt.max() - nxt[tok] < TOL


def test_the_paged_kernel_serves_the_same_streams(params, served):
    """The decode tick's attention read in place by the paged kernel (4
    query heads over 2 K/V heads, through the interpreter) serves every
    request token for token as the gather path did (the same prompts:
    a greedy stream is its request's alone, whatever shares the
    slots)."""
    sched, engine = serve(
        params, SHAPES[:4], slots=2, attend_impl="fused", interpret=True
    )
    assert engine.attend_choice == "fused"
    assert served[1].attend_choice.startswith("reference")
    gathered = {r.rid: r.tokens for r in served[0].finished if r.rid < 4}
    assert {r.rid: r.tokens for r in sched.finished} == gathered


def test_what_cannot_run_beside_a_tail_is_refused_by_name(params):
    for kw in ({"spec_k": 2}, {"prefix_cache": True}):
        with pytest.raises(ValueError, match="layers = 8 one-mixer blocks"):
            Engine(params, MCFG, EngineConfig(kv_block_len=8, **kw))
    engine = Engine(params, MCFG, EngineConfig(kv_block_len=8))
    with pytest.raises(ValueError, match="layers = 8 one-mixer blocks"):
        engine.export_slot(0)
    with pytest.raises(ValueError, match="conv_kernel >= 2"):
        TransformerConfig(
            vocab=8, n_layers=1, layers=("shortconv",), conv_kernel=1
        )


def test_the_published_layers_map_onto_the_layer_list():
    big = load("benchmark", "configs", "lfm2_8b_a1b.json")
    mcfg = drv.model_config(big, {"max_model_len": 4096})
    assert mcfg.n_layers == 28
    assert mcfg.layers_of("attn") == (4, 12, 20)
    assert mcfg.layers_of("shortconv") == tuple(
        2 * i for i in range(14) if i not in (2, 6, 10)
    )
    assert mcfg.layers_of("mlp") == (1, 3)
    assert len(mcfg.layers_of("moe")) == 12
    assert (mcfg.head_dim, mcfg.n_kv_heads, mcfg.conv_kernel) == (64, 8, 3)
    assert (mcfg.pos, mcfg.qk_norm, mcfg.rope_theta) == ("rope", True, 1e6)
    assert (mcfg.moe_experts, mcfg.moe_top_k, mcfg.moe_d_ff) == (32, 4, 1792)
