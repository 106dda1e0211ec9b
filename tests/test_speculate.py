"""Speculative multi-token decode (serve/speculate.py + the engine's
verify program + the scheduler's accepted-token fan-out).

The two bars the subsystem stands on:

  - IDENTITY: speculative token streams equal non-speculative greedy
    streams for every request, across any interleaved ragged workload —
    speculation may change *when* tokens appear, never *which*;
  - KV REWIND: after any accept/reject pattern the paged cache is
    bitwise what sequential one-token ticks (the verify program at
    zero drafts — "zero acceptance degrades to exactly the one-token
    tick") would have written, and paged == dense stays bitwise under
    speculation. Cross-PROGRAM parity (verify (S, K+1) vs the
    non-speculative decode program's (S, 1)) is token-level, exactly
    the cross-shape caveat PR 9 documented: XLA may re-tile a GEMM's
    accumulation across shapes, so bitwise bars hold shapes fixed.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.models.transformer import (
    TransformerConfig,
    generate,
    init_lm,
)
from singa_tpu.serve import (
    Engine,
    EngineConfig,
    NGramDrafter,
    NullDrafter,
    Request,
    Scheduler,
    make_drafter,
)


def tiny_cfg(**kw):
    base = dict(
        vocab=32, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=32
    )
    base.update(kw)
    return TransformerConfig(**base)


def tiny_params(cfg, seed=0):
    return init_lm(jax.random.PRNGKey(seed), cfg)


def mixed_workload(cfg, n=6, seed=0):
    rs = np.random.RandomState(seed)
    prompts = [
        rs.randint(0, cfg.vocab, size=(int(rs.randint(3, 9)),)).astype(
            np.int32
        )
        for _ in range(n)
    ]
    budgets = [int(rs.randint(4, 10)) for _ in range(n)]
    return prompts, budgets


class ScriptedDrafter:
    """Returns scripted drafts in submission order (then nothing) — the
    accept/reject-pattern injector for the rewind parity tests."""

    name = "scripted"

    def __init__(self, scripts):
        self.scripts = list(scripts)

    def draft(self, ctx, k):
        if not self.scripts:
            return []
        return list(self.scripts.pop(0))[:k]


# ---------------------------------------------------------------------------
# drafters
# ---------------------------------------------------------------------------


class TestNGramDrafter:
    def test_longest_suffix_wins(self):
        d = NGramDrafter(ngram_max=3)
        # suffix [7, 8] occurred earlier followed by [9, 1]; the 1-gram
        # [8] also occurred (followed by 9) — the longer match decides,
        # and both agree here
        assert d.draft([7, 8, 9, 1, 7, 8], k=2) == [9, 1]

    def test_most_recent_occurrence_wins(self):
        d = NGramDrafter(ngram_max=1)
        # token 5 occurs followed by 1 (early) and by 2 (late): the
        # most recent occurrence's continuation is proposed
        assert d.draft([5, 1, 5, 2, 5], k=1) == [2]

    def test_clamps_to_k_and_available_tail(self):
        d = NGramDrafter()
        ctx = [1, 2, 3, 1, 2]
        # match at [1, 2] (start), continuation [3, 1, 2] clipped to k
        assert d.draft(ctx, k=2) == [3, 1]
        # continuation shorter than k: returns what exists
        assert d.draft([4, 9, 4], k=5) == [9, 4]

    def test_no_match_proposes_nothing(self):
        assert NGramDrafter().draft([1, 2, 3, 4], k=3) == []
        assert NGramDrafter().draft([7], k=3) == []
        assert NGramDrafter().draft([1, 2], k=0) == []

    def test_null_drafter_and_registry(self):
        assert NullDrafter().draft([1, 1, 1, 1], 4) == []
        assert isinstance(make_drafter("ngram"), NGramDrafter)
        assert isinstance(make_drafter("null"), NullDrafter)
        with pytest.raises(ValueError, match="unknown drafter"):
            make_drafter("oracle")
        with pytest.raises(ValueError, match="ngram_min"):
            NGramDrafter(ngram_max=0)


# ---------------------------------------------------------------------------
# identity: speculative == sequential greedy
# ---------------------------------------------------------------------------


def test_speculative_streams_match_sequential_generate():
    """The identity bar across interleaved ragged streams: admits and
    retires interleave, acceptance varies per tick, every stream's
    tokens must equal its own sequential generate() run — and
    speculation must actually engage (some drafts accepted)."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompts, budgets = mixed_workload(cfg)
    eng = Engine(
        params, cfg,
        EngineConfig(slots=3, kv_block_len=8, max_prefill_chunk=4,
                     spec_k=3),
    )
    sched = Scheduler(eng)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    assert sched.serve() is None
    assert len(sched.finished) == len(prompts)
    occ = sched.occupancy()
    assert occ["spec_accepted"] > 0, "speculation never engaged"
    # the amortization claim: accepted tokens mean fewer ticks than
    # tokens (one-token ticks would need >= tokens_emitted ticks)
    assert sched.decode_ticks < sched.tokens_emitted
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        want = np.asarray(generate(params, jnp.asarray(p)[None], cfg, m))[
            0, len(p):
        ]
        got = next(r for r in sched.finished if r.rid == i).tokens
        np.testing.assert_array_equal(
            want, got, err_msg=f"stream {i} diverged under speculation"
        )


def test_zero_acceptance_degrades_to_one_token_tick():
    """A drafter that proposes nothing: every verify tick emits exactly
    one token per live slot (the one-token tick) and streams stay
    identical. The non-speculative run reads each pass one tick after it
    dispatched it, so it frees a slot a tick later and takes no fewer
    ticks; its lanes give one token each but those that rode unread."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompts, budgets = mixed_workload(cfg, seed=4)

    def run(spec_k, drafter=None):
        eng = Engine(
            params, cfg,
            EngineConfig(slots=3, kv_block_len=8, max_prefill_chunk=4,
                         spec_k=spec_k),
        )
        sched = Scheduler(eng, drafter=drafter)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
        sched.serve()
        return sched

    base = run(0)
    null = run(3, drafter=NullDrafter())
    assert null.spec_accepted == 0 and null.spec_drafted == 0
    assert null.tokens_emitted == null._live_ticks and null.lanes_unread == 0
    assert base.tokens_emitted == base._live_ticks - base.lanes_unread
    assert null.ticks <= base.ticks
    assert null.tokens_emitted == base.tokens_emitted
    for r in base.finished:
        got = next(s for s in null.finished if s.rid == r.rid).tokens
        assert got == r.tokens

    # garbage drafts: acceptance may be zero or not, identity holds
    # regardless (a drafter can cost acceptance, never correctness)
    rs = np.random.RandomState(9)
    garbage = run(3, drafter=ScriptedDrafter(
        [rs.randint(0, cfg.vocab, size=(3,)).tolist() for _ in range(200)]
    ))
    for r in base.finished:
        got = next(s for s in garbage.finished if s.rid == r.rid).tokens
        assert got == r.tokens


def test_eos_mid_accepted_run_retires_at_the_right_token():
    """EOS landing INSIDE an accepted multi-token run: the request must
    end exactly at the EOS token — accepted tokens past it are
    discarded, never delivered (sequential decode would have stopped
    there)."""
    cfg = tiny_cfg()
    # seed 5: a free run whose first five tokens are all distinct (the
    # seed-0 model's run is one constant token under this XLA, which
    # would put the EOS at token 0 and void the scenario)
    params = tiny_params(cfg, seed=5)
    prompt = np.asarray([1, 2, 3], np.int32)
    free_run = np.asarray(
        generate(params, jnp.asarray(prompt)[None], cfg, 12)
    )[0, 3:]
    eos = int(free_run[4])
    # the scenario itself: the EOS first appears at index 4, INSIDE the
    # first accepted run (4 scripted drafts + the bonus token)
    assert list(free_run).index(eos) == 4, free_run
    want = list(free_run[:5])  # sequential stops at the EOS hit
    # script the TRUE continuation as the draft: the run containing the
    # EOS is accepted whole, the scheduler must still cut at EOS
    eng = Engine(
        params, cfg,
        EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4,
                     spec_k=4),
    )
    sched = Scheduler(eng, drafter=ScriptedDrafter(
        [list(free_run[1:5]), list(free_run[5:9]), list(free_run[9:12])]
    ))
    sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=12, eos=eos))
    sched.serve()
    (req,) = sched.finished
    assert req.tokens == want, (req.tokens, want)
    assert req.tokens[-1] == eos
    assert eng.allocator.used_blocks == 0  # retired, blocks freed


def test_budget_hit_inside_accepted_run_never_overshoots():
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompts, budgets = mixed_workload(cfg, seed=2)
    eng = Engine(
        params, cfg,
        EngineConfig(slots=3, kv_block_len=8, max_prefill_chunk=4,
                     spec_k=4),
    )
    sched = Scheduler(eng)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    sched.serve()
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        req = next(r for r in sched.finished if r.rid == i)
        assert len(req.tokens) == m, f"stream {i} overshot its budget"


# ---------------------------------------------------------------------------
# KV rewind: the cache after any accept/reject pattern
# ---------------------------------------------------------------------------


def _drive_engine(params, cfg, prompt, n, spec_k, drafter, block_len=8):
    """One stream through slot 1 (non-trivial table ids) with drafts
    from ``drafter`` each tick; returns (tokens, gathered per-layer
    K/V)."""
    eng = Engine(
        params, cfg,
        EngineConfig(slots=2, kv_block_len=block_len, max_prefill_chunk=4,
                     spec_k=spec_k),
    )
    eng.admit(1, len(prompt) + n)
    last = None
    for c0 in range(0, len(prompt), 4):
        last = eng.prefill_chunk(1, prompt[c0:c0 + 4], c0)
    got = [int(eng.activate(1, last, len(prompt), seed=0))]
    while len(got) < n:
        nd_i = min(spec_k, n - len(got) - 1)
        d = drafter.draft(list(prompt) + got, nd_i) if nd_i > 0 else []
        d = list(d)[:max(nd_i, 0)]
        drafts = np.zeros((2, spec_k), np.int32)
        ndv = np.zeros((2,), np.int32)
        drafts[1, :len(d)] = d
        ndv[1] = len(d)
        em, _ = eng.verify(drafts, ndv)
        for t in np.asarray(em)[1]:
            if t < 0:
                break
            got.append(int(t))
            if len(got) >= n:
                break
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


def test_kv_after_rewind_is_bitwise_the_sequential_paged_cache():
    """The rewind bar: run the verify program with real accept/reject
    patterns (n-gram drafts — this model/prompt mixes full accepts,
    partial accepts, and full rejections) and with zero drafts (the
    one-token tick). Tokens AND every written cache position must be
    bit-for-bit identical: rejected positions were never written, so
    un-advancing them is exact, and accepted positions carry exactly
    the values sequential ticks would have computed. A dense-equivalent
    engine (kv_block_len = max_len: one block per sequence) must match
    bitwise too — paging stays pure data movement under speculation.
    (Same-program shapes throughout; verify-vs-decode-PROGRAM parity
    is token-level, the PR 9 cross-shape discipline.)"""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompt = np.asarray([3, 1, 4, 1, 5, 9, 2], np.int32)
    n = 10

    spec_toks, spec_c = _drive_engine(
        params, cfg, prompt, n, spec_k=3, drafter=NGramDrafter()
    )
    seq_toks, seq_c = _drive_engine(
        params, cfg, prompt, n, spec_k=3, drafter=NullDrafter()
    )
    assert spec_toks == seq_toks
    written = len(prompt) + n - 1  # the final sample is never cached
    for i, ((pk, pv), (dk, dv)) in enumerate(zip(spec_c, seq_c)):
        np.testing.assert_array_equal(
            pk[:, :written], dk[:, :written],
            err_msg=f"layer {i} K: speculative cache != one-token cache",
        )
        np.testing.assert_array_equal(
            pv[:, :written], dv[:, :written],
            err_msg=f"layer {i} V: speculative cache != one-token cache",
        )
    dense_toks, dense_c = _drive_engine(
        params, cfg, prompt, n, spec_k=3, drafter=NGramDrafter(),
        block_len=cfg.max_len,
    )
    assert dense_toks == spec_toks
    for i, ((pk, pv), (dk, dv)) in enumerate(zip(spec_c, dense_c)):
        np.testing.assert_array_equal(
            pk[:, :written], dk[:, :written],
            err_msg=f"layer {i} K: paged != dense under speculation",
        )
        np.testing.assert_array_equal(
            pv[:, :written], dv[:, :written],
            err_msg=f"layer {i} V: paged != dense under speculation",
        )


def test_kv_rewind_forced_patterns():
    """Scripted accept/reject extremes: a fully-correct draft (accept
    all), a first-token-wrong draft (reject all), and alternating —
    cache bitwise vs the zero-draft run for each."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompt = np.asarray([2, 7, 1, 8], np.int32)
    n = 8
    seq_toks, seq_c = _drive_engine(
        params, cfg, prompt, n, spec_k=3, drafter=NullDrafter()
    )
    free = seq_toks  # the true greedy continuation, for scripting
    patterns = {
        "accept_all": [free[1:4], free[4:7], free[7:]],
        "reject_all": [[(t + 1) % cfg.vocab for t in free[1:4]]] * 8,
        "partial": [
            [free[1], (free[2] + 1) % cfg.vocab, free[3]],
            [(free[i] + 1) % cfg.vocab for i in range(3)],
        ] + [free[3:6], free[6:]],
    }
    written = len(prompt) + n - 1
    for name, script in patterns.items():
        toks, caches = _drive_engine(
            params, cfg, prompt, n, spec_k=3,
            drafter=ScriptedDrafter([list(s) for s in script]),
        )
        assert toks == seq_toks, (name, toks, seq_toks)
        for i, ((pk, pv), (dk, dv)) in enumerate(zip(caches, seq_c)):
            np.testing.assert_array_equal(
                pk[:, :written], dk[:, :written],
                err_msg=f"{name}: layer {i} K diverged",
            )
            np.testing.assert_array_equal(
                pv[:, :written], dv[:, :written],
                err_msg=f"{name}: layer {i} V diverged",
            )


def test_pool_block_offset_mirrors_device_index_math():
    """KVPool.block_offset is the host-side mirror of the verify
    program's (position // block_len, position % block_len) write
    targeting — pinned so the geometry cannot drift."""
    from singa_tpu.serve import KVPool

    pool = KVPool.for_model(max_len=64, block_len=16, slots=2)
    for pos in (0, 1, 15, 16, 17, 63):
        row, off = pool.block_offset(pos)
        assert row == pos // 16 and off == pos % 16
        assert 0 <= row < pool.max_blocks_per_seq
        assert 0 <= off < pool.block_len


def test_jit_cache_pinned_with_speculation_on():
    """The continuous-batching contract survives speculation: any
    admit/retire pattern over a ragged workload reuses ONE compiled
    verify program (and one prefill)."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompts, budgets = mixed_workload(cfg, n=8, seed=7)
    eng = Engine(
        params, cfg,
        EngineConfig(slots=3, kv_block_len=8, max_prefill_chunk=4,
                     spec_k=3),
    )
    sched = Scheduler(eng)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    sched.serve()
    assert len(sched.finished) == len(prompts)
    assert eng._verify_jit._cache_size() == 1
    assert eng._prefill_jit._cache_size() == 1


# ---------------------------------------------------------------------------
# per-slot temperature lanes
# ---------------------------------------------------------------------------


def test_mixed_temperatures_share_one_program():
    """The temperature-lane satellite: greedy and sampled requests ride
    the SAME engine concurrently (the old same-temperature rejection is
    gone) through one compiled decode program; greedy streams still
    match sequential generate(), sampled streams are deterministic
    under their seed and in-vocab."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    rs = np.random.RandomState(3)
    prompts = [
        rs.randint(0, cfg.vocab, size=(5,)).astype(np.int32)
        for _ in range(4)
    ]

    def run():
        eng = Engine(
            params, cfg,
            EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4),
        )
        sched = Scheduler(eng)
        for i, p in enumerate(prompts):
            sched.submit(Request(
                rid=i, prompt=p, max_new_tokens=7,
                temperature=0.0 if i % 2 == 0 else 0.9, seed=100 + i,
            ))
        sched.serve()
        assert eng._decode_jit._cache_size() == 1
        return {r.rid: r.tokens for r in sched.finished}

    a = run()
    b = run()
    assert a == b  # sampled slots deterministic under their seeds
    for i, p in enumerate(prompts):
        assert all(0 <= t < cfg.vocab for t in a[i])
        if i % 2 == 0:
            want = np.asarray(
                generate(params, jnp.asarray(p)[None], cfg, 7)
            )[0, len(p):]
            np.testing.assert_array_equal(want, a[i])


def test_temperature_slots_ride_speculative_ticks_undrafted():
    """Speculation stays greedy-only per slot: with spec on, sampled
    slots verify with zero drafts (one token per tick) while greedy
    neighbors speculate — streams on both sides unchanged vs a
    non-speculative engine."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    rs = np.random.RandomState(5)
    prompts = [
        rs.randint(0, cfg.vocab, size=(4,)).astype(np.int32)
        for _ in range(4)
    ]

    def run(spec_k):
        eng = Engine(
            params, cfg,
            EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4,
                         spec_k=spec_k),
        )
        sched = Scheduler(eng)
        for i, p in enumerate(prompts):
            sched.submit(Request(
                rid=i, prompt=p, max_new_tokens=8,
                temperature=0.0 if i % 2 == 0 else 0.7, seed=50 + i,
            ))
        sched.serve()
        return sched

    base = run(0)
    spec = run(3)
    for r in base.finished:
        got = next(s for s in spec.finished if s.rid == r.rid).tokens
        assert got == r.tokens, f"stream {r.rid} moved under speculation"


# ---------------------------------------------------------------------------
# satellites: conf knobs, lint, trace, serve_bench CLI
# ---------------------------------------------------------------------------


def test_engine_config_from_conf_speculate():
    from singa_tpu.config.schema import ServingConfig

    serving = ServingConfig.from_fields({
        "slots": [4], "speculate": [{"k": [5], "drafter": ["null"]}],
    })
    ec = EngineConfig.from_conf(serving)
    assert ec.spec_k == 5 and ec.spec_drafter == "null"
    assert EngineConfig.from_conf(None).spec_k == 0
    assert EngineConfig.from_conf(
        ServingConfig.from_fields({"slots": [4]})
    ).spec_k == 0


LINT_CONF = """
name: "spec-lint"
train_steps: 1
updater {{ base_learning_rate: 0.05 param_type: "Param" }}
neuralnet {{
  layer {{ name: "data" type: "kSequenceData"
    data_param {{ path: "{shard}" batchsize: 8 }} }}
  layer {{ name: "embed" type: "kEmbedding" srclayers: "data"
    embedding_param {{ vocab_size: 64 embedding_dim: 32 }}
    param {{ name: "tok" init_method: "kGaussain" std: 0.02 }}
    param {{ name: "pos" init_method: "kGaussain" std: 0.02 }} }}
  layer {{ name: "head" type: "kDense" srclayers: "embed"
    dense_param {{ num_output: 64 bias_term: false }}
    param {{ name: "weight" init_method: "kGaussain" std: 0.02 }} }}
  layer {{ name: "loss" type: "kLMLoss" srclayers: "head" srclayers: "data" }}
}}
serving {{ slots: 4 speculate {{ k: 4 drafter: "ngram" }} }}
"""


def test_speculate_conf_lint_did_you_mean(tmp_path):
    """netlint's schema walk covers the nested speculate block: typo'd
    knobs get CFG001 with a did-you-mean, a typo'd block name points at
    speculate, and a bad drafter enum gets CFG002."""
    from singa_tpu.data.loader import synthetic_token_arrays, write_records
    from singa_tpu.lint import Collector, lint_model_text

    shard = str(tmp_path / "tokens")
    write_records(shard, *synthetic_token_arrays(16, seq_len=16, vocab=64))
    base = LINT_CONF.format(shard=shard)
    col = Collector()
    lint_model_text(base, "job.conf", col)
    assert not any(d.code in ("CFG001", "CFG002") for d in col.sorted()), [
        str(d) for d in col.sorted()
    ]
    for typo, want in [
        ("k:", "k"),
        ("drafter:", "drafter"),
        ("speculate {", "speculate"),
    ]:
        text = base.replace(typo, typo[:-2] + "x" + typo[-2:], 1)
        col = Collector()
        lint_model_text(text, "job.conf", col)
        assert any(
            d.code == "CFG001" and want in (d.fix_hint or "")
            for d in col.sorted()
        ), (typo, [str(d) for d in col.sorted()])
    col = Collector()
    lint_model_text(
        base.replace('drafter: "ngram"', 'drafter: "ngrm"'), "job.conf", col
    )
    assert any(
        d.code == "CFG002" and "ngram" in (d.fix_hint or "")
        for d in col.sorted()
    ), [str(d) for d in col.sorted()]


def test_trace_summarize_acceptance_columns(tmp_path):
    """spec_draft/spec_accept events -> the serving section grows
    acceptance_rate and tokens_per_tick; a speculation-free serving log
    keeps acceptance_rate None."""
    from singa_tpu.tools.trace import load_events, summarize

    events = tmp_path / "events"
    os.makedirs(events)
    recs = [
        {"ts": 1.0, "mono": 1.0, "rank": 0, "run": "r", "step": 0,
         "kind": "spec_draft", "data": {"drafted": 6, "live": 2}},
        {"ts": 1.1, "mono": 1.1, "rank": 0, "run": "r", "step": 0,
         "kind": "spec_accept", "data": {"accepted": 3, "emitted": 5,
                                         "drafted": 6}},
        {"ts": 1.2, "mono": 1.2, "rank": 0, "run": "r", "step": 0,
         "kind": "span", "name": "decode_tick", "track": "serving",
         "dur": 0.004, "steps": 5},
        {"ts": 1.3, "mono": 1.3, "rank": 0, "run": "r", "step": 1,
         "kind": "spec_draft", "data": {"drafted": 2, "live": 2}},
        {"ts": 1.4, "mono": 1.4, "rank": 0, "run": "r", "step": 1,
         "kind": "spec_accept", "data": {"accepted": 1, "emitted": 3,
                                         "drafted": 2}},
        {"ts": 1.5, "mono": 1.5, "rank": 0, "run": "r", "step": 1,
         "kind": "span", "name": "decode_tick", "track": "serving",
         "dur": 0.004, "steps": 3},
    ]
    with open(events / "rank_0.jsonl", "w") as f:
        f.write("\n".join(json.dumps(r) for r in recs) + "\n")
    records, skipped = load_events(str(tmp_path))
    assert skipped == 0
    s = summarize(records)["serving"]
    assert s["spec_drafted"] == 8 and s["spec_accepted"] == 4
    assert s["acceptance_rate"] == 0.5
    assert s["tokens_per_tick"] == 4.0  # 8 tokens / 2 ticks
    # speculation-free serving log: columns present, acceptance None
    plain = [
        {"ts": 2.0, "mono": 2.0, "rank": 0, "run": "r", "step": 0,
         "kind": "span", "name": "decode_tick", "track": "serving",
         "dur": 0.004, "steps": 2},
        {"ts": 2.1, "mono": 2.1, "rank": 0, "run": "r", "step": 0,
         "kind": "request_admit", "data": {"rid": 0, "slot": 0}},
    ]
    with open(events / "rank_0.jsonl", "w") as f:
        f.write("\n".join(json.dumps(r) for r in plain) + "\n")
    records, _ = load_events(str(tmp_path))
    s = summarize(records)["serving"]
    assert s["acceptance_rate"] is None and s["tokens_per_tick"] == 2.0


def test_scheduler_records_spec_events(tmp_path):
    """The lifecycle events ride the flight recorder: per-tick
    spec_draft/spec_accept with counts that reconcile with the
    scheduler's own accounting."""
    from singa_tpu.obs.recorder import FlightRecorder

    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompts, budgets = mixed_workload(cfg, n=4, seed=6)
    rec = FlightRecorder(str(tmp_path / "events"), rank=0, run_id="t")
    eng = Engine(
        params, cfg,
        EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4,
                     spec_k=3),
    )
    sched = Scheduler(eng, recorder=rec)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    sched.serve()
    rec.flush()
    recs = [
        json.loads(line)
        for line in open(tmp_path / "events" / "rank_0.jsonl")
    ]
    drafted = sum(
        r["data"]["drafted"] for r in recs if r["kind"] == "spec_draft"
    )
    accepted = sum(
        r["data"]["accepted"] for r in recs if r["kind"] == "spec_accept"
    )
    assert drafted == sched.spec_drafted > 0
    assert accepted == sched.spec_accepted
    ticks = [r for r in recs if r["kind"] == "decode_tick"]
    assert len(ticks) == sched.decode_ticks


def test_serve_bench_speculation_gate_smoke(capsys):
    """serve_bench end to end at toy size in speculation mode: the
    or-gate passes (end-to-end or machinery arm), token streams match
    the one-token run, and the speculation columns ride the JSON."""
    from singa_tpu.tools.serve_bench import main as sb_main

    rc = sb_main([
        "--d_model", "32", "--n_heads", "2", "--n_layers", "1",
        "--d_ff", "64", "--vocab", "32", "--max_len", "64",
        "--prompt_len", "8", "--max_new", "12", "--block_len", "8",
        "--prefill_chunk", "4", "--requests", "4", "--concurrency", "2",
        "--speculate_k", "2", "--workload", "repeat",
    ])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0, out
    assert out["pass"] and out["pass_mode"] in ("end_to_end", "machinery")
    assert out["token_mismatches"] == 0
    assert out["spec_k"] == 2
    for key in ("acceptance_rate", "tokens_per_tick", "base_tokens_per_s",
                "spec_speedup", "spec_machinery_ratio"):
        assert key in out, key


def test_serve_bench_poisson_arrival_smoke(capsys):
    """The open-loop satellite: a seeded Poisson arrival schedule runs
    to completion and reports queue-inclusive latency percentiles
    alongside the batch numbers."""
    from singa_tpu.tools.serve_bench import main as sb_main

    rc = sb_main([
        "--d_model", "32", "--n_heads", "2", "--n_layers", "1",
        "--d_ff", "64", "--vocab", "32", "--max_len", "32",
        "--prompt_len", "4", "--max_new", "8", "--block_len", "8",
        "--prefill_chunk", "4", "--requests", "5", "--concurrency", "2",
        "--arrival", "poisson", "--rate", "200", "--no_gate",
    ])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0, out
    p = out["poisson"]
    assert p["finished"] == 5
    assert p["tokens_per_s"] > 0
    assert p["p99_ms"] >= p["p50_ms"] > 0
