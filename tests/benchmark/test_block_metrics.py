"""What the cell ``sdar_30b_a3b_serve_blocks`` adds to the benchmark,
on the CPU: its configuration against the published numbers, its traffic,
the FLOPs and bytes worked out by hand, the replay and the two gaps on
hand-made logits, the six new readers on a hand-made run, on the cut
recorded on the chip (``data/scopes_sdar_30b_a3b_serve_blocks.json``)
and on an empty run (None, never 0), and the controls: the check FAILS
for the reference computed in a lower precision and for each planted
fault (commit skipped; the least confident positions unmasked first).
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "sdar_30b_a3b_serve_blocks"

from benchmark import run as harness  # noqa: E402
from benchmark.drivers import serve_blocks  # noqa: E402

NEW = ("block_step_device_ms", "moe_ms_per_block_step",
       "attend_ms_per_block_step", "moe_hbm_roofline",
       "tokens_per_block_step", "kv_gather_ms_per_block_step")

#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load(BENCH, "configs", "sdar_30b_a3b.json")


@pytest.fixture(scope="module")
def traffic():
    return load(BENCH, "traffic", "closed_blocks_c64.json")


def test_configuration_is_the_published_one_but_for_depth(config):
    (entry,) = [
        c for c in load(ROOT, "BENCHMARK.json")["configs"]
        if c["name"] == "sdar_30b_a3b"
    ]
    assert entry["reduced"] == ["num_hidden_layers"]
    differs = [k for k, v in PUBLISHED.items() if config.get(k, "absent") != v]
    assert differs == entry["reduced"]
    assert config["reduced_from"] == {"num_hidden_layers": 48}
    assert config["num_hidden_layers"] == 6       # floor: four, period 1
    for key in ("deployment", "precision", "assumed", "departures"):
        assert config[key]
    assert {"block_length", "remasking", "mask_token_id", "qk_norm",
            "no_shift", "serving_limit", "initializer_range"} <= set(
        config["assumed"])


def test_parameters_and_memory_by_hand(config, traffic):
    from benchmark.reference import sdar_moe as ref

    specs = ref.specs(config)
    n = sum(int(np.prod(s["shape"])) for s in specs.values())
    layer = (
        2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048   # q, k, v, o
        + 2048 * 128 + 128 * 3 * 2048 * 768          # router, experts
        + 2 * 2048 + 2 * 128                         # four norms
    )
    assert layer == 623_120_640
    assert n == 6 * layer + 2 * 151936 * 2048 + 2048 == 4_361_055_744
    assert 2 * n / 1e9 == pytest.approx(8.72, abs=0.01)   # bfloat16, GB
    pools = 2 * 6 * traffic["slots"] * traffic["max_model_len"] * 4 * 128 * 2
    assert pools / 1e9 == pytest.approx(1.21, abs=0.01)


def test_flops_are_those_of_the_passes_the_schedule_states(config, traffic):
    per_layer = 2 * (18_874_368 + 262_144 + 8 * 4_718_592)
    head = 2 * 2048 * 151936
    assert serve_blocks.position_fwd_flops(config, 100, head=True) == (
        6 * (per_layer + 4 * 32 * 128 * 100) + head
    )
    assert serve_blocks.position_fwd_flops(config, 100, head=False) == (
        6 * (per_layer + 4 * 32 * 128 * 100)
    )
    d = serve_blocks.Driver(
        config=config, traffic=traffic, limits={}, seed=1, devices=[],
        work="", spans=harness.Spans(False),
    )
    d.mcfg = serve_blocks.model_config(config, traffic)
    # a token at position 301 sees its block's end, 304 positions, in
    # each of the 2 + 1 passes its block takes
    assert d.token_fwd_flops(301) == 3 * serve_blocks.position_fwd_flops(
        config, 304, head=True
    )
    assert d.mcfg.n_kv_heads == 4 and d.mcfg.head_dim == 128
    assert d.mcfg.max_len == 1536 and d.mcfg.diffusion_block == 4


def test_traffic_is_the_issues_and_ends_on_block_edges(config, traffic):
    from benchmark import traffic as gen

    assert (traffic["callers"], traffic["slots"]) == (64, 64)
    assert traffic["prompt_len"] == {
        "median": 256, "sigma": 0.7, "min": 32, "max": 1024}
    assert traffic["output_len"] == {
        "median": 192, "sigma": 0.7, "min": 64, "max": 512}
    assert (traffic["kv_block_len"], traffic["kv_blocks"]) == (16, 0)
    assert (traffic["max_model_len"], traffic["max_prefill_chunk"]) == (1536, 256)
    assert (traffic["block_steps"], traffic["pool"]) == (2, 64)
    assert (traffic["check_requests"], traffic["trace_seconds"]) == (4, 3)
    shapes = gen.request_shapes(traffic)
    assert all(p + o + 3 <= 1536 + 3 and p + o <= 1536 for p, o in shapes)
    # what a slot's pass delivers, by the schedule: the tail block has
    # fewer positions to fill, the last block is never committed
    tokens = passes = 0
    for p, o in shapes:
        o += -(p + o) % 4
        tail = -p % 4
        tokens += o
        passes += (-(-tail // 2) + 1 if tail else 0) + 3 * ((o - tail) // 4) - 1
    assert 1.25 <= tokens / passes <= 1.34


def test_replay_builds_each_pass_of_a_request():
    prompt, tokens = np.array([5, 6, 7, 8, 9, 10]), [11, 12, 13, 14, 15, 16]
    clean, noisy, answer, fixed_at = serve_blocks.replay(
        (prompt, tokens, [1, 0, 0, 1, 1, 0]), 16, 4, 99, 2
    )
    assert list(clean[:12]) == list(range(5, 17)) and not clean[12:].any()
    assert list(noisy[0][:12]) == [5, 6, 7, 8, 9, 10] + [99] * 6
    assert list(noisy[1][:12]) == [5, 6, 7, 8, 9, 10, 99, 12, 13, 99, 99, 16]
    assert list(np.flatnonzero(answer)) == list(range(6, 12))
    assert list(fixed_at[:6]) == [2] * 6 and list(fixed_at[12:]) == [2] * 4
    with pytest.raises(ValueError, match="edge of blocks"):
        serve_blocks.replay((prompt, tokens[:5], [0] * 5), 16, 4, 99, 2)


def test_pass_gaps_on_hand_made_logits():
    import jax.numpy as jnp

    # one block of 4 answer positions, vocabulary of 3
    answer = jnp.ones((4,), bool)
    fixed_at = jnp.asarray([0, 1, 0, 1])
    logits = jnp.log(jnp.asarray([
        [0.7, 0.2, 0.1], [0.4, 0.35, 0.25], [0.6, 0.3, 0.1], [0.5, 0.3, 0.2],
    ]))
    served = jnp.asarray([0, 0, 0, 0])
    room = np.log(0.6 / 0.4)      # its second best less its least sure
    lg, regret, had, total, n = serve_blocks.pass_numbers(
        logits, answer, fixed_at, 0, 4, 2, None, served)
    assert float(lg) == pytest.approx(0.0, abs=1e-6)
    assert float(regret) == pytest.approx(0.0, abs=1e-6)   # 0.7 and 0.6: its set
    assert float(had) == pytest.approx(room, abs=1e-5)
    # the program chose positions 0 and 3 (0.5) where the reference's
    # second best is 0.6, and served token 1 at position 3
    fixed_at = jnp.asarray([0, 1, 1, 0])
    served = jnp.asarray([0, 0, 0, 1])
    lg, regret, had, total, n = serve_blocks.pass_numbers(
        logits, answer, fixed_at, 0, 4, 2, None, served)
    assert float(lg) == pytest.approx(np.log(0.5 / 0.3), abs=1e-5)
    # of the two tokens the pass fixed one is the reference's: the sum
    # that logit_gap_mean divides by the count is the other's gap
    assert float(total) == pytest.approx(np.log(0.5 / 0.3), abs=1e-5)
    assert int(n) == 2
    assert float(regret) == pytest.approx(np.log(0.6 / 0.5), abs=1e-5)
    assert float(had) == pytest.approx(room, abs=1e-5)
    assert serve_blocks.confidence_gap(float(regret), float(had)) == (
        pytest.approx(np.log(0.6 / 0.5) / room, abs=1e-5))
    # the least sure first: the whole of the room
    lg, regret, had, total, n = serve_blocks.pass_numbers(
        logits, answer, jnp.asarray([1, 0, 1, 0]), 0, 4, 2, None, served)
    assert float(regret) == pytest.approx(float(had)) == pytest.approx(room, abs=1e-5)
    # a pass with no choice (two masked, two to fix) adds to neither sum
    lg, regret, had, total, n = serve_blocks.pass_numbers(
        logits, answer, jnp.asarray([0, 1, 0, 1]), 1, 4, 2, None, served)
    assert float(regret) == float(had) == 0.0
    assert serve_blocks.confidence_gap(0.0, 0.0) == 0.0
    # a control is judged by its own logits' choices
    theirs = jnp.log(jnp.asarray([
        [0.1, 0.8, 0.1], [0.3, 0.3, 0.4], [0.2, 0.2, 0.6], [0.1, 0.1, 0.8],
    ]))
    lg, regret, had, total, n = serve_blocks.pass_numbers(
        logits, answer, jnp.asarray([0, 0, 1, 1]), 0, 4, 2, theirs)
    # it chooses positions 0 and 3 (0.8 each) and serves tokens 1 and 2
    assert float(lg) == pytest.approx(np.log(0.7 / 0.2), abs=1e-5)
    assert float(total) == pytest.approx(
        np.log(0.7 / 0.2) + np.log(0.5 / 0.2), abs=1e-5)
    assert float(regret) == pytest.approx(np.log(0.6 / 0.5), abs=1e-5)
    # three fixed where the rule says two
    bad = serve_blocks.pass_numbers(
        logits, answer, jnp.asarray([0, 0, 0, 1]), 0, 4, 2, None, served)
    assert all(float(b) == np.inf for b in bad[:4]) and int(bad[4]) == 3
    assert serve_blocks.confidence_gap(float(bad[1]), float(bad[2])) == np.inf


# -- the readers --------------------------------------------------------

B = "jit(_block_step)"
#: two block steps and a prefill chunk of a server. Times in ns.
TRACE = {
    "host": [],
    "devices": [{
        "name": "/device:TPU:0",
        "modules": [
            ["jit__block_step", 0, 1000], ["jit__prefill", 1000, 500],
            ["jit__block_step", 1500, 1400],
        ],
        "ops": [
            ["fusion.1", 0, 200, f"{B}/blk0/attend/gather_kv/gather"],
            ["fusion.2", 200, 100, f"{B}/blk0/attend/cache_attend/dot_general"],
            ["fusion.3", 300, 50, f"{B}/blk0/moe/route/dot_general"],
            ["fusion.4", 350, 400, f"{B}/blk0/moe/experts/nd,edf->enf/dot_general"],
            ["fusion.5", 750, 150, f"{B}/blk0/moe/combine/dot_general"],
            ["fusion.6", 900, 100, f"{B}/lm_head/dot_general"],
            ["fusion.7", 1000, 500, "jit(_prefill)/blk0/moe/experts/dot_general"],
            ["fusion.1", 1500, 300, f"{B}/blk0/attend/gather_kv/gather"],
            ["fusion.4", 1800, 800, f"{B}/blk0/moe/experts/nd,edf->enf/dot_general"],
            ["fusion.8", 2600, 300, f"{B}/blk0/kv_write/scatter"],
        ],
    }],
}


def view(trace, counters, config):
    from benchmark import program_trace

    class FakeDriver:
        work = "/nowhere"

    key = os.path.join("/nowhere", "trace")
    program_trace._cache[key] = trace
    return {
        "spans": harness.Spans(False), "chips": 1,
        "device_kind": "TPU v5 lite", "end_to_end": {}, "counters": counters,
        # the traced window read the whole run's counters here (PR 39:
        # the rooflines read ``traced_counters``; test_traced_counters.py
        # holds runs in which the two differ)
        "traced_counters": counters if trace else None,
        "trace": {"busy_s": 1.0, "window_s": 1.0} if trace else None,
        "driver": FakeDriver(), "config": config, "traffic": {},
    }


def test_new_readers_on_a_hand_made_run(config):
    counters = {"decode_ticks": 10, "experts_hit": 10 * 6 * 100,
                "tokens_delivered": 400, "block_passes": 320}
    read = {n: harness.load_reader(n)(view(TRACE, counters, config)) for n in NEW}
    assert read["block_step_device_ms"] == pytest.approx(1200 / 1e6)
    # route + experts + combine of both runs, over two runs; the
    # prefill chunk's experts are not a block step's
    assert read["moe_ms_per_block_step"] == pytest.approx(1400 / 2 / 1e6)
    assert read["attend_ms_per_block_step"] == pytest.approx(600 / 2 / 1e6)
    # the two gathers; ``cache_attend`` beside them is attention's alone
    assert read["kv_gather_ms_per_block_step"] == pytest.approx(500 / 2 / 1e6)
    assert read["tokens_per_block_step"] == pytest.approx(1.25)
    need = 6 * (100 * 3 * 2048 * 768 * 2 + 2048 * 128 * 2)
    assert read["moe_hbm_roofline"] == pytest.approx(
        100 * need / (700e-9 * 819e9)
    )


def test_roofline_bytes_are_a_lower_bound_by_construction(config):
    reader = harness.load_reader("moe_hbm_roofline").__globals__
    every = reader["bytes_a_pass"](config, 128)
    assert every == 6 * (128 * 3 * 2048 * 768 * 2 + 2048 * 128 * 2)
    assert every / 1e9 == pytest.approx(7.25, abs=0.01)
    assert reader["bytes_a_pass"](config, 64) < every
    assert reader["peak_bytes_per_s"]("TPU v5 lite") == 819e9
    with pytest.raises(ValueError):
        reader["peak_bytes_per_s"]("TPU v9 imaginary")


def test_new_readers_return_nothing_where_there_is_nothing(config):
    """The parent commit has no ``jit__block_step``, no ``moe`` scope in
    it and no block counters: every new reader returns None, never 0,
    and does not raise."""
    no_block_steps = {
        "host": [], "devices": [{
            "name": "/device:TPU:0",
            "modules": [["jit__decode", 0, 1000]],
            "ops": [["fusion.1", 0, 200, "jit(_decode)/blk0/mlp/dot_general"]],
        }],
    }
    for trace in (None, no_block_steps):
        for name in NEW:
            got = harness.load_reader(name)(
                view(trace, {"decode_ticks": 5}, config)
            )
            assert got is None, name


def test_new_readers_on_the_cut_recorded_on_the_chip(config):
    """The cut of a ``--trace 1`` run of the cell on a v5e (PERF.md, PR
    28): runs of ``jit__block_step`` with ``moe`` and ``attend`` inside."""
    from benchmark import program_trace

    cut = load(HERE, "data", f"scopes_{CELL}.json")
    runs = program_trace.module_runs(cut, "jit__block_step")
    assert runs
    counters = {"decode_ticks": 10, "experts_hit": 10 * 6 * 128,
                "tokens_delivered": 4, "block_passes": 3}
    read = {n: harness.load_reader(n)(view(cut, counters, config)) for n in NEW}
    assert all(v is not None and v > 0 for v in read.values()), read
    assert read["moe_ms_per_block_step"] < read["block_step_device_ms"]
    assert read["attend_ms_per_block_step"] < read["block_step_device_ms"]
    assert (read["kv_gather_ms_per_block_step"]
            < read["attend_ms_per_block_step"])
    assert 0 < read["moe_hbm_roofline"] <= 100.0
    scopes = program_trace.scope_seconds(cut, "jit__block_step")
    assert {"moe", "cache_attend", "gather_kv", "lm_head"} <= set(scopes)


# -- the controls ---------------------------------------------------------


@pytest.fixture(scope="module")
def calibrated(tmp_path_factory):
    """One seed's calibration at the rehearsal's tiny sizes: program,
    the ``float8`` control and the planted fault."""
    import jax

    from conftest import TinyFiles

    files = TinyFiles()
    d = serve_blocks.Driver(
        config=files.config("sdar_30b_a3b"),
        traffic=files.traffic("closed_blocks_c64"),
        limits=files.limits(CELL), seed=2**31 + 3,
        devices=jax.devices()[:1], work=str(tmp_path_factory.mktemp("w")),
        spans=harness.Spans(False),
    )
    return d.limits, d.calibrate(
        controls=["float8"], faults=["commit_skipped", "least_confident"],
        seconds=0.5,
    )


def test_program_passes_its_limits(calibrated):
    limits, sides = calibrated
    assert sides["program"]["served_tokens"] > 0
    for name, limit in limits.items():
        assert sides["program"][name] <= limit, name


@pytest.mark.parametrize("side,fails", [
    ("float8", "logit_gap_mean"), ("commit_skipped", "logit_gap_mean"),
    ("least_confident", "confidence_gap"),
])
def test_control_and_faults_fail_a_limit(calibrated, side, fails):
    limits, sides = calibrated
    assert sides[side][fails] > 10 * limits[fails], sides[side]
