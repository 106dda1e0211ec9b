"""What the cell ``lfm2_8b_a1b_serve_rag`` adds to the benchmark, on the
CPU: its configuration against the catalog's published numbers, the cut
and its arithmetic, its traffic, the FLOP count by hand, the two new
readers on a hand-made run, on the cut recorded on the chip
(``data/scopes_lfm2_8b_a1b_serve_rag.json``: also why the short
convolutions have no roofline share) and on an empty run (None, never
0), and the controls at the
rehearsal's tiny size: the check FAILS for the reference computed in a
lower precision and for each planted fault (a slot's tails kept at
admission; a chunk's padding counted into the tail).
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
CELL = "lfm2_8b_a1b_serve_rag"
NAME = "lfm2_8b_a1b"

from benchmark import hbm, program_trace  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark.drivers import serve_lfm2  # noqa: E402

#: the readers this cell brought, with their entries: (unit, better,
#: source, layer)
NEW = {
    "shortconv_ms_per_tick": ("ms", "lower", "device_trace", "short-conv layer"),
    "shortconv_ms_per_chunk": ("ms", "lower", "device_trace", "short-conv layer"),
}
#: the accepted metrics whose ``workloads`` gained the cell
JOINED = (
    "serve_tokens_per_s", "step_mfu.serve", "device_idle_share.serve",
    "serve_ttft_p95_ms", "sched_host_ms_per_tick",
    "paged_attention_ms_per_tick", "attend_ms_per_tick", "moe_ms_per_tick",
    "moe_ms_per_chunk", "sched_idle_share.serve",
)
#: the ITL p95 of this closed loop is the length of one of two groups of
#: ticks 1.5 % apart, and six seeds split between them (PERF.md section
#: 6): over half its bound. The cell does not report it, nor the
#: per-layer metrics that move it
NOT_ITL = (
    "serve_itl_p95_ms", "decode_tick_ms", "prefill_chunk_ms",
    "decode_device_ms", "prefill_chunk_device_ms", "itl_tail_chunks_ahead",
)

#: the catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: ``LFM2-8B-A1B``)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv", "full_attention", "conv",
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv", "conv",
    ],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536,
}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load(BENCH, "configs", f"{NAME}.json")


@pytest.fixture(scope="module")
def traffic():
    return load(BENCH, "traffic", "closed_rag_c128.json")


def test_configuration_is_the_published_one_but_for_the_cut(config):
    bench = load(ROOT, "BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert entry["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    )
    differs = [k for k, v in PUBLISHED.items() if config.get(k, "absent") != v]
    assert sorted(differs) == sorted(entry["reduced"])
    assert config["reduced_from"] == {
        "num_hidden_layers": 24, "layer_types": PUBLISHED["layer_types"],
    }
    # published layers 0..13: the two dense layers, then three whole
    # periods of [attention, conv, conv, conv]
    published = PUBLISHED["layer_types"]
    assert config["layer_types"] == published[:14]
    assert published[2:14] == ["full_attention", "conv", "conv", "conv"] * 3
    assert config["layer_types"].count("conv") == 11
    assert config["layer_types"].count("full_attention") == 3
    assert config["num_hidden_layers"] == 14
    for key in ("deployment", "precision", "assumed", "departures"):
        assert config[key]
    assert {"tie_word_embeddings", "initializer_range", "conv_w",
            "expert_bias_std", "dense_mlp_width", "max_position_embeddings",
            "greedy"} <= set(config["assumed"])
    assert {"gate_epsilon", "packing"} <= set(config["departures"])
    assert config["tie_word_embeddings"] is True
    (cell,) = [w for w in bench["workloads"] if w["config"] == NAME]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "closed_rag_c128", 1)
    assert "attention 3 layers by design" in cell["why"]
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in JOINED:
        assert CELL in by_name[name]["workloads"], name
    # that share's bytes are another model's (a latent or a share of
    # experts): no MoE roofline reads this cell. ``state_slots_live``
    # reads it (the run's counters), but N's test pins that entry whole
    for name in ("moe_share_hbm_roofline", "latent_moe_hbm_roofline",
                 "moe_hbm_roofline", "state_slots_live") + NOT_ITL:
        assert CELL not in by_name[name]["workloads"], name
    for name in NOT_ITL[1:]:
        assert by_name[name]["moves"] == "serve_itl_p95_ms", name
    for name, (unit, better, source, layer) in NEW.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "serve_tokens_per_s",
            "workloads": [CELL],
        }, name


def test_the_cut_and_its_arithmetic_by_hand(config, traffic):
    from benchmark.reference import lfm2_moe as ref

    specs = ref.specs(config)

    def count(*prefixes):
        return sum(
            int(np.prod(s["shape"])) for k, s in specs.items()
            if k.startswith(prefixes)
        )

    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048 + 2048
    mlp = 3 * 2048 * 7168 + 2048
    attn = 2048 * 3072 + 2048 * 2048 + 2048 + 2 * 64
    experts = 32 * 3 * 2048 * 1792 + 2048 * 32 + 32 + 2048
    assert count("blk0/", "blk1/") == conv + mlp
    assert (conv + mlp) / 1e6 == pytest.approx(60.8, abs=0.05)
    assert count("blk4/", "blk5/") == attn + experts
    assert (attn + experts) / 1e6 == pytest.approx(362.9, abs=0.05)
    assert count("blk6/", "blk7/") == conv + experts
    assert (conv + experts) / 1e6 == pytest.approx(369.2, abs=0.05)
    n = sum(int(np.prod(s["shape"])) for s in specs.values())
    assert n == 65536 * 2048 + 2 * (conv + mlp) + 3 * (attn + experts) + 9 * (
        conv + experts) + 2048
    assert 2 * n / 1e9 == pytest.approx(9.33, abs=0.01)          # bfloat16
    # three attention layers' pools: 6,144 B a token
    kv = traffic["slots"] * traffic["max_model_len"] * 3 * 8 * 64 * 2 * 2
    assert kv / 1e9 == pytest.approx(3.22, abs=0.01)
    tails = traffic["slots"] * 11 * 2 * 2048 * 2
    assert tails / 1e6 == pytest.approx(11.5, abs=0.05)
    assert (2 * n + kv + tails) / 16e9 == pytest.approx(0.79, abs=0.005)
    mcfg = serve_lfm2.model_config(config, traffic)
    assert mcfg.n_layers == 28 and mcfg.max_len == 4096
    assert mcfg.tied_head and mcfg.moe_held == ()


def test_traffic_is_the_issues(config, traffic):
    from benchmark import traffic as gen

    assert traffic["driver"] == "serve_lfm2"
    assert (traffic["callers"], traffic["slots"]) == (128, 128)
    assert traffic["prompt_len"] == {
        "median": 1024, "sigma": 0.8, "min": 64, "max": 3584}
    assert traffic["output_len"] == {
        "median": 256, "sigma": 0.7, "min": 32, "max": 512}
    assert (traffic["max_model_len"], traffic["max_prefill_chunk"]) == (4096, 512)
    assert (traffic["kv_block_len"], traffic["kv_blocks"]) == (128, 0)
    assert (traffic["pool"], traffic["check_requests"]) == (64, 4)
    assert traffic["trace_seconds"] == 3 and traffic["greedy"] is True
    assert traffic["prefix_cache"] is False and traffic["speculate"] == 0
    shapes = gen.request_shapes(traffic)
    assert len(shapes) == 64
    assert all(p + o <= 4096 for p, o in shapes)
    prompts = sorted(p for p, _ in shapes)
    assert prompts[-1] == 3584 and 64 <= prompts[0] < 200
    assert 950 <= prompts[32] <= 1100
    # most prompts cross a chunk's edge, where a carried tail goes wrong
    assert sum(p > 512 for p in prompts) >= 48


def test_the_replay_finds_the_class_of_the_p95():
    """``replay_schedule.classes``: the largest class whose ticks of it
    or more hold 5 % of the gaps, read on the tick's own chunks or on
    the tick before's."""
    from replay_schedule import classes

    # 100 ticks of 10 gaps: 6 with 4 chunks, 2 with 5, the rest none
    rows = [([512] * 4, 10)] * 6 + [([512] * 5, 10)] * 2 + [([], 10)] * 92
    assert classes(rows, 0) == {"p95_class": 4, "share_at_least_class": 8.0,
                                "share_at_least_next": 2.0}
    # one tick later the first tick's gaps wait behind no chunk
    assert classes(rows, 1)["p95_class"] == 4
    # over 30 ticks the two of 5 chunks hold 6.7 %
    assert classes(rows[:30], 0)["p95_class"] == 5


def test_the_shape_seed_puts_the_p95_inside_a_class(traffic):
    """The traffic file's ``shape_seed`` is one the replay tried, and at
    every window end the replay read, on either reading, the p95 lies
    inside its class: 1.5 points or more from 5 % on both sides."""
    with open(os.path.join(HERE, "data", f"replay_{CELL}.jsonl")) as f:
        tried = {r["shape_seed"]: r for r in map(json.loads, f)}
    assert len(tried) > 1
    mine = tried[traffic["shape_seed"]]
    for window in mine["windows"].values():
        for reading in window.values():
            assert reading["share_at_least_class"] >= 6.5, reading
            assert reading["share_at_least_next"] <= 3.5, reading


def test_flops_of_a_token_by_hand(config):
    got = serve_lfm2.token_fwd_flops(config, 1000, decoded=True)
    conv = 2 * 2048 * 6144 + 2 * 2048 * 2048 + 2 * 3 * 2048
    attn = 2 * 2048 * 3072 + 2 * 2048 * 2048 + 4 * 32 * 64 * 1000
    mlp = 2 * 3 * 2048 * 7168
    moe = 2 * 2048 * 32 + 4 * 2 * 3 * 2048 * 1792
    assert got == pytest.approx(
        11 * conv + 3 * attn + 2 * mlp + 12 * moe + 2 * 2048 * 65536,
        rel=1e-12,
    )
    chunked = serve_lfm2.token_fwd_flops(config, 1000, decoded=False)
    assert got - chunked == 2 * 2048 * 65536
    # about 1.9 GFLOPs a decoded token: the "A1B" of the name, cut
    assert 1.8e9 < got < 2.0e9


def test_a_ticks_in_proj_outruns_its_weights_on_the_recorded_cut():
    """Why the short convolutions have no roofline share of the memory's
    peak (PERF.md section 7): in a tick, the (128 x 2048) x (2048 x 6144)
    ``in_proj`` product of a short convolution that follows an expert
    layer takes less time than its 25.2 MB of bfloat16 weights need at
    819 GB/s, because the compiler copies them into the core's memory
    while the expert layer runs; it is bound by its arithmetic. The two
    that follow the embedding and the first dense MLP (blocks 0 and 2)
    read their weights in their own time. So time under ``shortconv``
    leaves most of its weight reads out."""
    cut = load(HERE, "data", f"scopes_{CELL}.json")
    floor_ns = 2048 * 6144 * 2 / hbm.peak_bytes_per_s("TPU v5 lite") * 1e9
    flops_ns = 2 * 128 * 2048 * 6144 / 197e12 * 1e9
    by_block: dict[int, list] = {}
    for dev in cut["devices"]:
        for _, _, dur, name in program_trace.device_ops(dev, "jit__decode"):
            if "/shortconv/in_proj/" in name and "dot_general" in name:
                block = int(name.split("/")[1].removeprefix("blk"))
                by_block.setdefault(block, []).append(dur)
    assert sorted(by_block) == [0, 2, 6, 8, 10, 14, 16, 18, 22, 24, 26]
    for block, durs in by_block.items():
        if block in (0, 2):
            assert min(durs) > floor_ns, block
        else:
            assert flops_ns < max(durs) < floor_ns, block


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
            ["fusion.1", 0, 100, f"{D}/blk0/shortconv/in_proj/dot_general"],
            ["fusion.2", 100, 40, f"{D}/blk0/shortconv/conv/add"],
            ["fusion.3", 140, 60, f"{D}/blk0/shortconv/out_proj/dot_general"],
            ["fusion.4", 200, 500, f"{D}/blk3/moe/combine/dot_general"],
            ["fusion.5", 700, 300, f"{D}/lm_head/dot_general"],
            ["fusion.6", 1000, 200, f"{P}/blk0/shortconv/in_proj/dot_general"],
            ["fusion.7", 1200, 300, f"{P}/blk3/moe/experts/dot_general"],
            ["fusion.1", 1500, 300, f"{D}/blk0/shortconv/in_proj/dot_general"],
            ["fusion.4", 1800, 1100, f"{D}/blk3/moe/combine/dot_general"],
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
        "traced_counters": counters if trace else None,
        "trace": {"busy_s": 1.0, "window_s": 1.0} if trace else None,
        "driver": FakeDriver(), "config": config, "traffic": {},
    }


COUNTERS = {"decode_ticks": 10}


def test_new_readers_on_a_hand_made_run(config):
    read = {n: harness.load_reader(n)(view(TRACE, COUNTERS, config))
            for n in NEW}
    # the three scopes of the first run and in_proj of the second, over
    # two runs; the chunk's is not a tick's
    assert read["shortconv_ms_per_tick"] == pytest.approx(500 / 2 / 1e6)
    assert read["shortconv_ms_per_chunk"] == pytest.approx(200 / 1e6)


def test_new_readers_return_nothing_where_there_is_nothing(config):
    """The parent commit has no ``shortconv`` scope: every new reader
    returns None, never 0, and does not raise."""
    bare = {
        "host": [], "devices": [{
            "name": "/device:TPU:0",
            "modules": [["jit__decode", 0, 1000]],
            "ops": [["fusion.1", 0, 200, "jit(_decode)/blk0/mlp/dot_general"]],
        }],
    }
    for trace in (None, bare):
        for name in NEW:
            got = harness.load_reader(name)(
                view(trace, {"decode_ticks": 5}, config)
            )
            assert got is None, name


#: the metrics listing the cell that read the device plane of a trace
TRACE_READERS = tuple(NEW) + (
    "decode_device_ms", "prefill_chunk_device_ms",
    "paged_attention_ms_per_tick", "attend_ms_per_tick", "moe_ms_per_tick",
    "moe_ms_per_chunk",
)


@pytest.mark.parametrize("name", TRACE_READERS)
def test_each_trace_reader_of_the_cell_reads_the_cut(config, name):
    """Each per-layer metric that lists the cell and reads the device
    plane finds a number in the cut of a ``--trace 1`` run on a v5e."""
    from benchmark import program_trace

    cut = load(HERE, "data", f"scopes_{CELL}.json")
    assert program_trace.module_runs(cut, "jit__decode")
    assert program_trace.module_runs(cut, "jit__prefill")
    got = harness.load_reader(name)(view(cut, COUNTERS, config))
    assert got is not None and got > 0, name


def test_the_cut_holds_the_short_convolutions_scopes():
    cut = load(HERE, "data", f"scopes_{CELL}.json")
    inside = {
        seg for dev in cut["devices"] for op in dev["ops"]
        for seg in op[3].split("/") if "/shortconv/" in op[3]
    }
    assert {"in_proj", "conv", "out_proj"} <= inside


# -- the check -------------------------------------------------------------


def test_the_taps_are_drawn_uniform_in_their_fan_in(config):
    """``draw`` is ``weights.make`` but for the short convolutions' taps,
    uniform in +-1/sqrt(K) as the program's ``init_lm`` draws them (at
    ``initializer_range`` a tail would move no served token)."""
    import jax

    from benchmark import weights
    from benchmark.reference import lfm2_moe as ref
    from conftest import TinyFiles

    tiny = TinyFiles().config(NAME)
    drawn = ref.draw(tiny, 2**31 + 43)
    plain = weights.make(ref.specs(tiny), 2**31 + 43)
    taps = [k for k in drawn if k.endswith("/shortconv/conv_w")]
    assert len(taps) == tiny["layer_types"].count("conv")
    bound = 1 / np.sqrt(config["conv_L_cache"])
    every = np.concatenate([np.asarray(drawn[k]).ravel() for k in taps])
    assert np.abs(every).max() <= bound
    # uniform in +-b: a standard deviation of b / sqrt(3)
    assert every.std() == pytest.approx(bound / np.sqrt(3), rel=0.15)
    for k in drawn.keys() - set(taps):
        np.testing.assert_array_equal(drawn[k], plain[k])
    assert jax.tree.structure(drawn) == jax.tree.structure(plain)


def test_the_handover_rows_are_the_first_decode_steps(config):
    """``handover_gap_mean`` reads each request's rows ``len(prompt)``
    and ``len(prompt) + 1`` (K - 1 = 2: the decode steps whose taps read
    the tail the prefill left), ``logit_gap_mean`` every served row.
    Here row r scores token 0 at r and every other token at 0, so a
    served token other than 0 at row r lies r under the best."""
    import types

    import jax.numpy as jnp

    d = serve_lfm2.Driver(
        config=config, traffic={}, limits={}, seed=0, devices=None,
        work=None, spans=harness.Spans(False),
    )
    d.mcfg = types.SimpleNamespace(max_len=16)
    d._weights = lambda: None
    d.reference_forward = lambda params, seq, arith="float32": (
        jnp.zeros((len(seq), 3)).at[:, 0].set(jnp.arange(len(seq)))
    )
    prompt = np.array([1, 2, 1, 2], np.int32)
    # served rows 3..8; tokens off the best at rows 4, 5 and 6
    got = d.gaps_of([(prompt, [0, 1, 2, 1, 0, 0])])
    assert got == {"logit_gap": 6.0, "logit_gap_mean": 15 / 6,
                   "handover_gap_mean": 4.5}


# -- the controls ---------------------------------------------------------


@pytest.fixture(scope="module")
def calibrated(tmp_path_factory):
    """One seed's calibration at the rehearsal's tiny sizes: program,
    the ``float8`` control and the planted faults."""
    import jax

    from conftest import TinyFiles

    files = TinyFiles()
    d = serve_lfm2.Driver(
        config=files.config(NAME), traffic=files.traffic("closed_rag_c128"),
        limits=files.limits(CELL), seed=2**31 + 5,
        devices=jax.devices()[:1], work=str(tmp_path_factory.mktemp("w")),
        spans=harness.Spans(False),
    )
    return d.limits, d.calibrate(
        controls=["float8"], faults=list(serve_lfm2.serve_nemotron_h.FAULTS),
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


def test_a_padded_tail_shows_in_the_handover_rows(calibrated):
    """The rows that read the prefill's tail carry the fault whole: their
    mean lies several times over the mean of every served row."""
    pad = calibrated[1]["pad_advances_state"]
    assert pad["handover_gap_mean"] > 4 * pad["logit_gap_mean"], pad
