"""What the cell ``kimi_k2_serve_long`` adds to the benchmark, on the
CPU: its configuration against the catalog's published numbers, the cut
and its arithmetic, its traffic, the bytes of its two roofline shares by
hand, the four new readers on a hand-made run, on the cut recorded on
the chip (``data/scopes_kimi_k2_serve_long.json``) and on an empty run
(None, never 0), and the controls at the rehearsal's tiny size: the
check FAILS for the reference computed in a lower precision and for each
planted fault (the rotary key cached unrotated; the selection bias
weighing).
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
CELL = "kimi_k2_serve_long"

from benchmark import run as harness  # noqa: E402
from benchmark.drivers import serve_kimi_k2  # noqa: E402

NEW = ("moe_ms_per_tick", "moe_share_hbm_roofline",
       "latent_attend_hbm_roofline", "tokens_per_held_expert")

#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 7168, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "kimi_k2",
    "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 384, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 0, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 50000, "routed_scaling_factor": 2.827,
    "rope_scaling": {
        "beta_fast": 1, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn",
    },
    "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 163840,
}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load(BENCH, "configs", "kimi_k2_instruct.json")


@pytest.fixture(scope="module")
def traffic():
    return load(BENCH, "traffic", "closed_long_c48.json")


def test_configuration_is_the_published_one_but_for_the_cut(config):
    (entry,) = [
        c for c in load(ROOT, "BENCHMARK.json")["configs"]
        if c["name"] == "kimi_k2_instruct"
    ]
    assert entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
    ]
    differs = [k for k, v in PUBLISHED.items() if config.get(k, "absent") != v]
    assert sorted(differs) == sorted(entry["reduced"])
    assert config["reduced_from"] == {
        "num_hidden_layers": 61, "n_routed_experts": 384, "vocab_size": 163840,
    }
    # the floors: the dense layer and at least four that follow it, at
    # least 8 routed experts, at least an eighth of the vocabulary
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] == 12 == 384 // 32
    assert config["vocab_size"] == 20480 == 163840 // 8
    # the router keeps its width, and the share lies inside it
    assert config["n_router_outputs"] == 384
    assert 0 <= config["experts_held_from"] <= 384 - 12
    assert config["experts_held_from"] % 12 == 0
    for key in ("deployment", "precision", "assumed", "departures"):
        assert config[key]
    assert {"initializer_range", "router_bias_std", "serving_limit",
            "greedy"} <= set(config["assumed"])
    assert {"float8_checkpoint", "rope_layout", "group_limit"} <= set(
        config["departures"])


def test_the_cut_and_its_arithmetic_by_hand(config, traffic):
    from benchmark.reference import kimi_k2 as ref
    from singa_tpu.serve.kv_pool import KVPool

    specs = ref.specs(config)

    def count(prefix):
        return sum(
            int(np.prod(s["shape"])) for k, s in specs.items()
            if k.startswith(prefix)
        )

    mla = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 + 8192 * 7168
    assert mla == 101_122_048
    expert = 3 * 7168 * 2048
    assert expert == 44_040_192
    norms = 2 * 7168 + 1536 + 512
    layer = mla + norms + 7168 * 384 + 384 + 13 * expert
    assert count("blk1/") == layer
    assert count("blk0/") == mla + norms + 3 * 7168 * 18432
    assert count("embed/") + count("head/") == 2 * 20480 * 7168
    n = sum(int(np.prod(s["shape"])) for s in specs.values())
    assert n == 5 * layer + count("blk0/") + 2 * 20480 * 7168 + 7168
    assert 2 * n / 1e9 == pytest.approx(8.35, abs=0.01)   # bfloat16, GB
    # the latent pool: one row a token a layer, 576 values in 640
    rows = traffic["slots"] * traffic["max_model_len"] * 6
    assert rows * 576 * 2 / 1e9 == pytest.approx(4.25, abs=0.01)
    assert rows * KVPool.latent_row(576) * 2 / 1e9 == pytest.approx(
        4.72, abs=0.01)
    mcfg = serve_kimi_k2.model_config(config, traffic)
    assert mcfg.latent_width == 576 and mcfg.moe_held == (96, 12)
    assert mcfg.moe_experts == 384 and mcfg.dense_layers == 1
    assert mcfg.max_len == 12800 and mcfg.d_ff == 18432


def test_traffic_is_the_issues(config, traffic):
    from benchmark import traffic as gen

    assert traffic["driver"] == "serve_kimi_k2"
    assert (traffic["callers"], traffic["slots"]) == (48, 48)
    assert traffic["prompt_len"] == {
        "median": 4096, "sigma": 0.8, "min": 512, "max": 12288}
    assert traffic["output_len"] == {
        "median": 160, "sigma": 0.7, "min": 32, "max": 512}
    assert (traffic["max_model_len"], traffic["max_prefill_chunk"]) == (12800, 512)
    assert (traffic["kv_blocks"], traffic["pool"]) == (0, 64)
    assert (traffic["check_requests"], traffic["trace_seconds"]) == (4, 3)
    assert traffic["greedy"] is True
    assert traffic["prefix_cache"] is False and traffic["speculate"] == 0
    assert traffic["max_model_len"] % traffic["kv_block_len"] == 0
    assert traffic["kv_block_len_why"]
    shapes = gen.request_shapes(traffic)
    assert all(p + o <= 12800 for p, o in shapes)
    prompts = sorted(p for p, _ in shapes)
    assert 512 <= prompts[0] < 700 and 12000 < prompts[-1] <= 12288
    assert 3800 <= prompts[32] <= 4400
    # every id the traffic draws lies in the slice of the vocabulary
    reqs = gen.requests(traffic | {"pool": 4}, config["vocab_size"], 2**31 + 1)
    assert all(r["prompt"].max() < 20480 for r in reqs)


def test_every_seed_serves_one_draw_of_weights(traffic):
    """The traffic file fixes the weights (they set the held experts'
    share of the work, PERF.md section 6, PR 39) and ``--seed`` draws the
    prompts; a traffic file without ``weights_seed`` leaves both to it."""
    from types import SimpleNamespace

    from benchmark import traffic as gen

    fixed = traffic["weights_seed"]
    assert isinstance(fixed, int) and traffic["weights_seed_why"]
    pick = serve_kimi_k2.Driver.weights_seed
    for seed in (1, 2**31 + 5, 3900000111):
        assert pick(SimpleNamespace(traffic=traffic, seed=seed)) == fixed
        assert pick(SimpleNamespace(traffic={}, seed=seed)) == seed
    a, b = (
        gen.requests(traffic | {"pool": 2}, 20480, s)[0]["prompt"]
        for s in (3900000111, 3900000112)
    )
    assert len(a) == len(b) and (a != b).any()


# -- the readers --------------------------------------------------------

D = "jit(_decode)"
#: two decode runs and a prefill chunk of a server. Times in ns.
TRACE = {
    "host": [],
    "devices": [{
        "name": "/device:TPU:0",
        "modules": [
            ["jit__decode", 0, 1000], ["jit__prefill", 1000, 500],
            ["jit__decode", 1500, 1400],
        ],
        "ops": [
            ["fusion.1", 0, 200, f"{D}/blk1/attend/gather_kv/gather"],
            ["fusion.2", 200, 100, f"{D}/blk1/attend/cache_attend/dot_general"],
            ["fusion.3", 300, 50, f"{D}/blk1/moe/route/dot_general"],
            ["fusion.4", 350, 400, f"{D}/blk1/moe/experts/nd,edf->enf/dot_general"],
            ["fusion.5", 750, 150, f"{D}/blk1/moe/shared/dot_general"],
            ["fusion.6", 900, 100, f"{D}/blk0/mlp/dot_general"],
            ["fusion.7", 1000, 500, "jit(_prefill)/blk1/moe/experts/dot_general"],
            ["fusion.1", 1500, 300, f"{D}/blk1/attend/gather_kv/gather"],
            ["fusion.4", 1800, 800, f"{D}/blk1/moe/experts/nd,edf->enf/dot_general"],
            ["fusion.8", 2600, 300, f"{D}/blk1/attend/kv_write/scatter"],
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
    counters = {"decode_ticks": 10, "experts_hit": 10 * 5 * 8,
                "held_pairs": 10 * 5 * 12, "cache_rows": 10 * 250_000}
    read = {n: harness.load_reader(n)(view(TRACE, counters, config)) for n in NEW}
    # route + experts + shared of both runs, over two runs; layer 0's
    # dense MLP and the chunk's experts are not a tick's expert layers
    assert read["moe_ms_per_tick"] == pytest.approx(1400 / 2 / 1e6)
    expert = 3 * 7168 * 2048 * 2
    need = 5 * 8 * expert + 5 * (expert + 7168 * 384 * 2)
    assert read["moe_share_hbm_roofline"] == pytest.approx(
        100 * need / (700e-9 * 819e9)
    )
    # gather, the products and the write of both runs
    assert read["latent_attend_hbm_roofline"] == pytest.approx(
        100 * 250_000 * 1152 * 6 / (450e-9 * 819e9)
    )
    assert read["tokens_per_held_expert"] == pytest.approx(1.0)


def test_roofline_bytes_are_lower_bounds_by_construction(config):
    moe = harness.load_reader("moe_share_hbm_roofline").__globals__
    expert = 3 * 7168 * 2048 * 2
    assert expert / 1e6 == pytest.approx(88.1, abs=0.05)
    every = moe["bytes_a_tick"](config, 5 * 12)
    assert every == 5 * (13 * expert + 7168 * 384 * 2)
    assert every / 1e9 == pytest.approx(5.75, abs=0.01)
    # an expert that drew no token is not counted
    assert moe["bytes_a_tick"](config, 5 * 7) == every - 25 * expert
    from benchmark import hbm

    assert hbm.peak_bytes_per_s("TPU v5 lite") == 819e9
    with pytest.raises(ValueError):
        hbm.peak_bytes_per_s("TPU v9 imaginary")
    lat = harness.load_reader("latent_attend_hbm_roofline").__globals__
    # a row is the latent as the equations have it, not the pool's 640
    assert lat["bytes_a_tick"](config, 1) == 576 * 2 * 6
    assert lat["bytes_a_tick"](config, 250_000) / 1e9 == pytest.approx(
        1.73, abs=0.01)


def test_new_readers_return_nothing_where_there_is_nothing(config):
    """The parent commit has no ``moe`` scope in a run of ``jit__decode``
    and none of the counters: every new reader returns None, never 0,
    and does not raise."""
    no_experts = {
        "host": [], "devices": [{
            "name": "/device:TPU:0",
            "modules": [["jit__decode", 0, 1000]],
            "ops": [["fusion.1", 0, 200, "jit(_decode)/blk0/mlp/dot_general"]],
        }],
    }
    for trace in (None, no_experts):
        for name in NEW:
            got = harness.load_reader(name)(
                view(trace, {"decode_ticks": 5}, config)
            )
            assert got is None, name


def test_new_readers_on_the_cut_recorded_on_the_chip(config):
    """The cut of a ``--trace 1`` run of the cell on a v5e (PERF.md, PR
    34): runs of ``jit__decode`` with ``moe`` and ``attend`` inside."""
    from benchmark import program_trace

    cut = load(HERE, "data", f"scopes_{CELL}.json")
    assert program_trace.module_runs(cut, "jit__decode")
    assert program_trace.module_runs(cut, "jit__prefill")
    counters = {"decode_ticks": 10, "experts_hit": 10 * 5 * 8,
                "held_pairs": 10 * 5 * 12, "cache_rows": 10 * 250_000}
    read = {n: harness.load_reader(n)(view(cut, counters, config)) for n in NEW}
    assert all(v is not None and v > 0 for v in read.values()), read
    assert 0 < read["moe_share_hbm_roofline"] <= 100.0
    assert 0 < read["latent_attend_hbm_roofline"] <= 100.0
    decode = harness.load_reader("decode_device_ms")(view(cut, counters, config))
    attend = harness.load_reader("attend_ms_per_tick")(view(cut, counters, config))
    assert read["moe_ms_per_tick"] + attend < decode
    scopes = program_trace.scope_seconds(cut, "jit__decode")
    assert {"moe", "mlp", "paged_attention", "kv_write", "lm_head"} <= set(scopes)
    assert "cache_attend" in program_trace.scope_seconds(cut, "jit__prefill")


# -- the controls ---------------------------------------------------------


@pytest.fixture(scope="module")
def calibrated(tmp_path_factory):
    """One seed's calibration at the rehearsal's tiny sizes: program,
    the ``float8`` control and the planted faults."""
    import jax

    from conftest import TinyFiles

    files = TinyFiles()
    d = serve_kimi_k2.Driver(
        config=files.config("kimi_k2_instruct"),
        traffic=files.traffic("closed_long_c48"),
        limits=files.limits(CELL), seed=2**31 + 3,
        devices=jax.devices()[:1], work=str(tmp_path_factory.mktemp("w")),
        spans=harness.Spans(False),
    )
    return d.limits, d.calibrate(
        controls=["float8"], faults=list(serve_kimi_k2.FAULTS), seconds=0.5,
    )


def test_program_passes_its_limits(calibrated):
    limits, sides = calibrated
    assert sides["program"]["served_tokens"] > 0
    for name, limit in limits.items():
        assert sides["program"][name] <= limit, name


@pytest.mark.parametrize("side", ["float8", "k_pe_unrotated", "bias_weighs"])
def test_control_and_faults_fail_a_limit(calibrated, side):
    limits, sides = calibrated
    assert any(
        sides[side][name] > 10 * limit for name, limit in limits.items()
    ), sides[side]
