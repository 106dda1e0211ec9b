"""What the cell ``nemotron_3_super_serve_chat`` adds to the benchmark,
on the CPU: its configuration against the catalog's published numbers,
the cut and its arithmetic, its traffic, the bytes of its two roofline
shares by hand, the five new readers on a hand-made run, on the cut
recorded on the chip (``data/scopes_nemotron_3_super_serve_chat.json``)
and on an empty run (None, never 0), the FLOP count by hand, and the
controls at the rehearsal's tiny size: the check FAILS for the reference
computed in a lower precision and for each planted fault (a slot's state
kept at admission; a chunk's padding stepping the state).
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
CELL = "nemotron_3_super_serve_chat"
NAME = "nemotron_3_super_120b_a12b"

from benchmark import hbm_nemotron_h  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark.drivers import serve_nemotron_h  # noqa: E402

#: the readers PR 37 brought under ``benchmark/metrics/``, with the
#: entries PR 39 appended for them: (unit, better, source, layer)
NEW = {
    "mamba_ms_per_tick": ("ms", "lower", "device_trace", "state-space layer"),
    "mamba_ms_per_chunk": ("ms", "lower", "device_trace", "state-space layer"),
    "ssm_state_hbm_roofline": ("%", "higher", "device_trace", "state-space layer"),
    "latent_moe_hbm_roofline": ("%", "higher", "device_trace", "expert layer"),
    "state_slots_live": ("slots", "higher", "program_counter", "scheduler"),
}
#: the accepted metrics whose ``workloads`` PR 39 appended the cell to
JOINED_39 = ("moe_ms_per_chunk", "paged_attention_ms_per_tick")
#: the accepted metrics whose ``workloads`` gained the cell
JOINED = ("serve_tokens_per_s", "serve_itl_p95_ms", "step_mfu.serve",
          "device_idle_share.serve", "decode_tick_ms", "prefill_chunk_ms",
          "serve_ttft_p95_ms", "decode_device_ms", "prefill_chunk_device_ms",
          "attend_ms_per_tick", "moe_ms_per_tick", "sched_host_ms_per_tick")

#: the catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: ``NVIDIA-Nemotron-3-Super-120B-A12B-BF16``)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
    ),
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load(BENCH, "configs", f"{NAME}.json")


@pytest.fixture(scope="module")
def traffic():
    return load(BENCH, "traffic", "closed_chat_c128.json")


def test_configuration_is_the_published_one_but_for_the_cut(config):
    bench = load(ROOT, "BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
    ]
    assert entry["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
        "/blob/main/config.json"
    )
    differs = [k for k, v in PUBLISHED.items() if config.get(k, "absent") != v]
    # every number as published but for the cut; of the strings, the
    # pattern is the cut in depth spelt out
    assert sorted(differs) == sorted(
        entry["reduced"] + ["hybrid_override_pattern"]
    )
    assert config["reduced_from"] == {
        "hybrid_override_pattern": PUBLISHED["hybrid_override_pattern"],
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072,
    }
    # layers 25..35 of the published list: one whole period, every kind
    # in its published ratio
    published = PUBLISHED["hybrid_override_pattern"]
    assert len(published) == 88
    assert published[25:36] == config["hybrid_override_pattern"] == "*EMEMEMEMEM"
    assert [published.count(c) for c in "ME*"] == [40, 40, 8]
    assert config["num_hidden_layers"] == 11
    # the floors: a whole period, at least 8 routed experts, at least an
    # eighth of the vocabulary
    assert config["n_routed_experts"] == 128 == 512 // 4
    assert config["vocab_size"] == 32768 == 131072 // 4
    assert config["n_router_outputs"] == 512
    assert config["experts_held_from"] % 128 == 0
    assert 0 <= config["experts_held_from"] <= 512 - 128
    for key in ("deployment", "precision", "assumed", "departures"):
        assert config[key]
    assert {"attention_positions", "latent_experts", "initializer_range",
            "mamba_init", "router_bias_std", "serving_limit", "greedy"} <= set(
        config["assumed"])
    assert {"multi_token_prediction", "packing",
            "max_position_embeddings"} <= set(config["departures"])
    # one cell runs it, on one chip, and the lists named in the issue
    # hold the cell
    (cell,) = [w for w in bench["workloads"] if w["config"] == NAME]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "closed_chat_c128", 1)
    assert "4x their share" in cell["why"]
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in JOINED:
        assert CELL in by_name[name]["workloads"], name
    # that share's bytes are three matrices an expert and a leading dense
    # layer: another model's (``latent_moe_hbm_roofline`` counts this
    # one's)
    assert CELL not in by_name["moe_share_hbm_roofline"]["workloads"]
    # PR 39: the tick's paged kernel (PR 38) and the chunk's expert
    # layers, and the five entries of PR 37's readers, appended
    for name in JOINED_39:
        assert CELL in by_name[name]["workloads"], name
    for name, (unit, better, source, layer) in NEW.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "serve_tokens_per_s",
            "workloads": [CELL],
        }, name


def test_the_cut_and_its_arithmetic_by_hand(config, traffic):
    from benchmark.reference import nemotron_h as ref

    specs = ref.specs(config)

    def count(prefix):
        return sum(
            int(np.prod(s["shape"])) for k, s in specs.items()
            if k.startswith(prefix)
        )

    mamba = (4096 * (2 * 8192 + 2 * 8 * 128 + 128) + 8192 * 4096
             + 5 * 10240 + 3 * 128 + 8192 + 4096)
    assert mamba == 109_640_064 == count("blk2/")
    attn = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    assert attn == 35_655_680 == count("blk0/")
    expert = 2 * 1024 * 2688
    assert expert == 5_505_024
    beside = 4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096
    assert beside == 54_530_560
    assert count("blk1/") == 128 * expert + beside
    layers = attn + 5 * (128 * expert + beside) + 5 * mamba
    assert 2 * layers / 1e9 == pytest.approx(8.76, abs=0.01)    # bfloat16
    ends = 2 * 32768 * 4096
    assert 2 * ends / 1e9 == pytest.approx(0.54, abs=0.01)
    n = sum(int(np.prod(s["shape"])) for s in specs.values())
    assert n == layers + ends + 4096
    assert 2 * n / 1e9 == pytest.approx(9.30, abs=0.01)
    # recurrent state: 5 layers of a float32 state and a bfloat16 tail
    state = 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert state / 1e6 == pytest.approx(21.3, abs=0.05)
    assert traffic["slots"] * state / 1e9 == pytest.approx(2.72, abs=0.01)
    # the ONE attention layer's pools: K and V rows of 2 heads of 128
    kv = traffic["slots"] * traffic["max_model_len"] * 2 * 256 * 2
    assert kv / 1e9 == pytest.approx(0.64, abs=0.01)
    assert (2 * n + traffic["slots"] * state + kv) / 1e9 == pytest.approx(
        12.65, abs=0.05)
    mcfg = serve_nemotron_h.model_config(config, traffic)
    assert mcfg.layers == ("attn",) + ("moe", "mamba") * 5
    assert mcfg.moe_held == (128, 128) and mcfg.moe_experts == 512
    assert mcfg.max_len == 4864 and mcfg.pos == "none"
    assert (mcfg.mamba_heads, mcfg.mamba_head_dim, mcfg.ssm_state,
            mcfg.ssm_groups, mcfg.conv_kernel, mcfg.ssm_block) == (
        128, 64, 128, 8, 4, 128)


def test_traffic_is_the_issues(config, traffic):
    from benchmark import traffic as gen

    assert traffic["driver"] == "serve_nemotron_h"
    assert (traffic["callers"], traffic["slots"]) == (128, 128)
    assert traffic["prompt_len"] == {
        "median": 384, "sigma": 0.9, "min": 32, "max": 4096}
    assert traffic["output_len"] == {
        "median": 224, "sigma": 0.7, "min": 32, "max": 768}
    assert (traffic["max_model_len"], traffic["max_prefill_chunk"]) == (4864, 512)
    assert (traffic["kv_block_len"], traffic["kv_blocks"]) == (128, 0)
    assert (traffic["pool"], traffic["check_requests"]) == (64, 4)
    assert traffic["trace_seconds"] == 3 and traffic["greedy"] is True
    assert traffic["prefix_cache"] is False and traffic["speculate"] == 0
    assert traffic["max_model_len"] % traffic["kv_block_len"] == 0
    assert traffic["max_prefill_chunk"] % config["chunk_size"] == 0
    shapes = gen.request_shapes(traffic)
    assert len(shapes) == 64
    assert all(p + o <= 4864 for p, o in shapes)
    prompts = sorted(p for p, _ in shapes)
    assert 32 <= prompts[0] < 64 and 3000 < prompts[-1] <= 4096
    assert 350 <= prompts[32] <= 420
    # every id the traffic draws lies in the slice of the vocabulary
    reqs = gen.requests(traffic | {"pool": 4}, config["vocab_size"], 2**31 + 1)
    assert all(r["prompt"].max() < 32768 for r in reqs)


def test_flops_of_a_token_by_hand(config):
    """This chip's share: 22 x 128 / 512 = 5.5 routed experts a token."""
    got = serve_nemotron_h.token_fwd_flops(config, 1000, decoded=True)
    mamba = (2 * 4096 * 18560 + 2 * 8192 * 4096 + 2 * 4 * 10240
             + 5 * 128 * 64 * 128)
    attn = 2 * 4096 * 4608 + 2 * 4096 * 4096 + 4 * 32 * 128 * 1000
    moe = 2 * (4096 * 512 + 2 * 4096 * 1024 + 5.5 * 2 * 1024 * 2688
               + 2 * 4096 * 5376)
    assert got == pytest.approx(
        5 * mamba + attn + 5 * moe + 2 * 4096 * 32768, rel=1e-12)
    chunked = serve_nemotron_h.token_fwd_flops(config, 1000, decoded=False)
    step, block = 5 * 128 * 64 * 128, 2 * 128 * (1024 + 8192) + 4 * 128 * 64 * 128
    assert got - chunked == pytest.approx(
        5 * (step - block) + 2 * 4096 * 32768, rel=1e-12)
    # the projections dominate a token: some 2 GFLOPs of this chip's share
    assert 1.9e9 < got < 2.4e9


# -- the readers --------------------------------------------------------

D, P = "jit(_decode)", "jit(_prefill)"
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
            ["fusion.1", 0, 100, f"{D}/blk0/attend/gather_kv/gather"],
            ["fusion.2", 100, 50, f"{D}/blk1/moe/route/dot_general"],
            ["fusion.3", 150, 300, f"{D}/blk1/moe/experts/nd,edf->enf/dot_general"],
            ["fusion.4", 450, 50, f"{D}/blk1/moe/latent_up/dot_general"],
            ["fusion.5", 500, 100, f"{D}/blk2/mamba/in_proj/dot_general"],
            ["fusion.6", 600, 300, f"{D}/blk2/mamba/step/mul"],
            ["fusion.7", 900, 100, f"{D}/lm_head/dot_general"],
            ["fusion.8", 1000, 200, f"{P}/blk2/mamba/scan/dot_general"],
            ["fusion.9", 1200, 300, f"{P}/blk1/moe/experts/dot_general"],
            ["fusion.5", 1500, 200, f"{D}/blk2/mamba/in_proj/dot_general"],
            ["fusion.6", 1700, 600, f"{D}/blk2/mamba/step/mul"],
            ["fusion.3", 2300, 600, f"{D}/blk1/moe/experts/nd,edf->enf/dot_general"],
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


COUNTERS = {"decode_ticks": 10, "experts_hit": 10 * 5 * 120,
            "state_slots_live": 10 * 100}


def test_new_readers_on_a_hand_made_run(config):
    read = {n: harness.load_reader(n)(view(TRACE, COUNTERS, config))
            for n in NEW}
    # in_proj and step of both runs, over two runs; the chunk's scan is
    # not a tick's
    assert read["mamba_ms_per_tick"] == pytest.approx(1200 / 2 / 1e6)
    assert read["mamba_ms_per_chunk"] == pytest.approx(200 / 1e6)
    assert read["state_slots_live"] == pytest.approx(100.0)
    moved = hbm_nemotron_h.state_bytes_a_tick(config, 100)
    assert read["ssm_state_hbm_roofline"] == pytest.approx(
        100 * moved / (600e-9 * 819e9))
    need = hbm_nemotron_h.latent_moe_bytes_a_tick(config, 5 * 120)
    moe_ms = harness.load_reader("moe_ms_per_tick")(
        view(TRACE, COUNTERS, config))
    assert moe_ms == pytest.approx(1000 / 2 / 1e6)
    assert read["latent_moe_hbm_roofline"] == pytest.approx(
        100 * need / (500e-9 * 819e9))


def test_roofline_bytes_are_lower_bounds_by_hand(config):
    # a live slot: 5 layers of a float32 state in and out, and a tail of
    # three rows in and one out
    slot = 5 * (2 * 128 * 64 * 128 * 4 + 4 * 10240 * 2)
    weights = 5 * 2 * (4096 * 18560 + 8192 * 4096 + 5 * 10240 + 3 * 128
                       + 8192 + 4096)
    assert hbm_nemotron_h.state_bytes_a_tick(config, 0) == weights
    assert weights / 1e9 == pytest.approx(1.10, abs=0.01)
    full = hbm_nemotron_h.state_bytes_a_tick(config, 128)
    assert full == weights + 128 * slot
    assert 128 * slot / 1e9 == pytest.approx(5.42, abs=0.01)
    # a dead slot's state is not counted
    assert hbm_nemotron_h.state_bytes_a_tick(config, 100) == full - 28 * slot
    expert = 2 * 1024 * 2688 * 2
    assert expert / 1e6 == pytest.approx(11.01, abs=0.005)
    beside = 2 * (2 * 4096 * 5376 + 4096 * 512 + 2 * 4096 * 1024)
    every = hbm_nemotron_h.latent_moe_bytes_a_tick(config, 5 * 128)
    assert every == 5 * (128 * expert + beside)
    assert every / 1e9 == pytest.approx(7.59, abs=0.01)
    # an expert that drew no token is not counted
    assert hbm_nemotron_h.latent_moe_bytes_a_tick(config, 5 * 100) == (
        every - 5 * 28 * expert)
    # the bytes of the issue's tick: experts, state, Mamba weights, the
    # head and the attention layer's weights: 17.6 ms at the memory's peak
    head = 2 * (4096 * 32768 + 4096 * 4608 + 4096 * 4096)
    assert (every + full + head) / 819e9 * 1e3 == pytest.approx(17.6, abs=0.2)


def test_new_readers_return_nothing_where_there_is_nothing(config):
    """The parent commit has no ``mamba`` scope and none of the
    counters: every new reader returns None, never 0, and does not
    raise."""
    no_mamba = {
        "host": [], "devices": [{
            "name": "/device:TPU:0",
            "modules": [["jit__decode", 0, 1000]],
            "ops": [["fusion.1", 0, 200, "jit(_decode)/blk0/mlp/dot_general"]],
        }],
    }
    for trace in (None, no_mamba):
        for name in NEW:
            got = harness.load_reader(name)(
                view(trace, {"decode_ticks": 5}, config)
            )
            assert got is None, name


def test_new_readers_on_the_cut_recorded_on_the_chip(config):
    """The cut of a ``--trace 1`` run of the cell on a v5e (PERF.md, PR
    39, from PR 38's program): runs of ``jit__decode`` and
    ``jit__prefill`` with ``mamba``, ``moe`` and ``attend`` inside."""
    from benchmark import program_trace

    cut = load(HERE, "data", f"scopes_{CELL}.json")
    assert program_trace.module_runs(cut, "jit__decode")
    assert program_trace.module_runs(cut, "jit__prefill")
    read = {n: harness.load_reader(n)(view(cut, COUNTERS, config))
            for n in NEW}
    assert all(v is not None and v > 0 for v in read.values()), read
    assert 0 < read["ssm_state_hbm_roofline"] <= 100.0
    assert 0 < read["latent_moe_hbm_roofline"] <= 100.0
    v = view(cut, COUNTERS, config)
    decode = harness.load_reader("decode_device_ms")(v)
    attend = harness.load_reader("attend_ms_per_tick")(v)
    moe = harness.load_reader("moe_ms_per_tick")(v)
    assert read["mamba_ms_per_tick"] + moe + attend < decode
    # the scopes inside a Mamba layer, by the program's names
    inside = {
        seg for dev in cut["devices"] for op in dev["ops"]
        for seg in op[3].split("/") if "/mamba/" in op[3]
    }
    assert {"in_proj", "conv", "step", "scan", "gate_norm",
            "out_proj"} <= inside
    paths = {op[3] for dev in cut["devices"] for op in dev["ops"]}
    assert any("/moe/latent_down" in p for p in paths)
    assert any("/moe/latent_up" in p for p in paths)


@pytest.mark.parametrize("name", list(NEW) + list(JOINED_39))
def test_each_metric_listed_by_pr39_reads_the_cut(config, name):
    """Each metric whose ``workloads`` gained the cell in PR 39 reads a
    number from the cut: ``paged_attention_ms_per_tick`` the tick's one
    Mosaic call (PR 38), ``moe_ms_per_chunk`` the chunk's grouped
    experts."""
    cut = load(HERE, "data", f"scopes_{CELL}.json")
    got = harness.load_reader(name)(view(cut, COUNTERS, config))
    assert got is not None and got > 0, name


# -- the controls ---------------------------------------------------------


@pytest.fixture(scope="module")
def calibrated(tmp_path_factory):
    """One seed's calibration at the rehearsal's tiny sizes: program,
    the ``float8`` control and the planted faults."""
    import jax

    from conftest import TinyFiles

    files = TinyFiles()
    d = serve_nemotron_h.Driver(
        config=files.config(NAME), traffic=files.traffic("closed_chat_c128"),
        limits=files.limits(CELL), seed=2**31 + 5,
        devices=jax.devices()[:1], work=str(tmp_path_factory.mktemp("w")),
        spans=harness.Spans(False),
    )
    return d.limits, d.calibrate(
        controls=["float8"], faults=list(serve_nemotron_h.FAULTS),
        seconds=0.5,
    )


def test_program_passes_its_limits(calibrated):
    limits, sides = calibrated
    assert sides["program"]["served_tokens"] > 0
    for name, limit in limits.items():
        assert sides["program"][name] <= limit, name


@pytest.mark.parametrize(
    "side", ["float8", "state_kept_on_admit", "pad_advances_state"]
)
def test_control_and_faults_fail_a_limit(calibrated, side):
    limits, sides = calibrated
    assert any(
        sides[side][name] > 10 * limit for name, limit in limits.items()
    ), sides[side]
