"""The benchmark's own tests, on the CPU at tiny sizes.

- BENCHMARK.json keeps to the contract's shapes and characters, and every
  name in it finds its file;
- the FLOP counts and parameter counts of the shipped cells against
  numbers worked out by hand, and the generated nets against the
  program's own (shipped conf, ``param_specs``);
- percentile, step-time and MFU arithmetic on hand-made inputs;
- the trace reduction on a hand-made trace and on a cut of a trace
  recorded on the chip (``data/trace_resnet_v5e.json``);
- ``run.py`` end to end for every cell of BENCHMARK.json with the device
  gate steered from ``conftest.py``, both ``--trace`` modes, at the tiny
  sizes kept as data under ``tiny/`` (one cell built a test);
- the controls: the output check FAILS for the reference computed in a
  lower precision, and for each fault planted under the timed path (a
  step that returns its state unchanged, half of the batch left out, a
  token altered where it is produced).

Nothing here describes a TPU topology; nothing runs at import.
"""

from __future__ import annotations

import json
import math
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(ROOT, "benchmark")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return load(ROOT, "BENCHMARK.json")


# ---------------------------------------------------------------------
# BENCHMARK.json against the contract
# ---------------------------------------------------------------------


def test_top_level_keys(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    # a full check of 24 cells must fit 43200 s
    cells = 24
    runs = 2 + 14 * cells
    assert (
        runs * (bench["run_seconds"] + 60) + cells * 2 * 90 + 1200 <= 43200
    )


def test_names_and_units(bench):
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[kind]:
            assert NAME.match(e["name"]), e["name"]
            names.append((kind, e["name"]))
    assert len(names) == len(set(names))
    for c in bench["workloads"]:
        assert NAME.match(c["config"]) and NAME.match(c["traffic"])
        assert c["chips"] in (1, 4)
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for m in bench["per_layer"]:
        assert set(m) <= {
            "name", "unit", "better", "source", "layer", "moves", "workloads"
        }
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16


def test_every_name_finds_its_file(bench):
    for c in bench["configs"]:
        assert c["file"].startswith("benchmark/")
        cfg = load(ROOT, c["file"])
        assert cfg["name"] == c["name"]
        # a configuration that trains through the conf trainer names the
        # generator of its layer list; one that is only served has none
        if "generator" in cfg:
            assert os.path.exists(
                os.path.join(BENCH, "models", cfg["generator"] + ".py")
            )
    configs = {c["name"] for c in bench["configs"]}
    used = set()
    for cell in bench["workloads"]:
        assert cell["config"] in configs
        used.add(cell["config"])
        traffic = load(BENCH, "traffic", cell["traffic"] + ".json")
        assert os.path.exists(
            os.path.join(BENCH, "drivers", traffic["driver"] + ".py")
        )
        assert os.path.exists(
            os.path.join(BENCH, "limits", cell["name"] + ".json")
        )
    assert used == configs
    pairs = [(c["config"], c["traffic"]) for c in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics", m["name"] + ".py"))


def test_each_cell_reports_what_it_must(bench):
    from benchmark import run as harness

    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {c["name"] for c in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", ())) <= cells
    for cell in cells:
        mine = {
            m["name"] for m in harness.metrics_of(bench, "end_to_end", cell)
        }
        assert "setup_s" in mine and len(mine) >= 2
        layer = harness.metrics_of(bench, "per_layer", cell)
        assert layer
        for m in layer:
            # a per-layer metric moves an end-to-end metric of this cell
            assert m["moves"] in e2e and m["moves"] in mine
        assert any("mfu" in m["name"].split(".")[0].split("_") for m in layer)
    four = sum(c["chips"] == 4 for c in bench["workloads"])
    assert four <= max(1, len(cells) // 4)


def test_every_per_layer_metric_names_its_cells(bench):
    """No per-layer metric is handed to a cell by what it ``moves``: a
    cell's driver feeds the readers it is listed for and no others, so
    the rehearsal's set of printed names stays an equality when a cell
    is added."""
    from benchmark import run as harness

    for m in bench["per_layer"]:
        assert m.get("workloads"), m["name"]
    unlisted = json.loads(json.dumps(bench))
    del unlisted["per_layer"][0]["workloads"]
    with pytest.raises(SystemExit, match="lists no workloads"):
        harness.metrics_of(
            unlisted, "per_layer", bench["workloads"][0]["name"]
        )


# ---------------------------------------------------------------------
# FLOPs, parameters, the generated nets
# ---------------------------------------------------------------------


def cell_layers(bench, cell_name):
    import importlib

    (cell,) = [c for c in bench["workloads"] if c["name"] == cell_name]
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    cfg = load(ROOT, entry["file"])
    traffic = load(BENCH, "traffic", cell["traffic"] + ".json")
    gen = importlib.import_module(f"benchmark.models.{cfg['generator']}")
    return cfg, traffic, gen.build(cfg, traffic, "SHARD")


def n_params(specs):
    return sum(math.prod(s["shape"]) for s in specs.values())


def test_resnet50_flops_and_params(bench):
    from benchmark import flops
    from benchmark.models import confnet

    cfg, traffic, layers = cell_layers(bench, "resnet50_train")
    fwd, per = flops.net_fwd_flops(
        layers, traffic, flops.record_shape(layers, traffic)
    )
    macs_per_image = fwd / 2 / traffic["batch"]
    # He et al., table 1: 3.8 x 10^9 multiply-adds for the 50-layer net
    # at 224x224; the walk over this list gives 3.858 G
    assert macs_per_image == pytest.approx(3.858e9, rel=1e-3)
    assert len(per) == 54  # 53 convolutions and the classifier
    assert flops.train_step_flops(layers, traffic) == pytest.approx(3 * fwd)
    # torchvision's resnet50 has 25,557,032 parameters
    assert n_params(confnet.param_specs(layers)) == 25_557_032


def test_gpt2_medium_flops_and_params(bench):
    from benchmark import flops
    from benchmark.models import confnet
    from benchmark.reference import lm

    cfg = load(BENCH, "configs", "gpt2_medium.json")
    d, f, n, v, p = 1024, 4096, 24, 50257, 1024
    published = v * d + p * d + n * (12 * d * d + 13 * d) + 2 * d
    assert published == 354_823_168
    # serving path: tied head, no biases outside LayerNorm
    assert n_params(lm.lm_specs(cfg)) == published - n * 9 * d
    assert flops.lm_matmul_params(cfg) == n * 12 * d * d + d * v
    assert flops.lm_token_fwd_flops(cfg, 100) == pytest.approx(
        2 * (n * 12 * d * d + d * v) + 4 * n * d * 100
    )
    names = {c["name"] for c in bench["workloads"]}
    if "gpt2_medium_train" not in names:
        return
    cfg, traffic, layers = cell_layers(bench, "gpt2_medium_train")
    # conf path: no qkv/out bias, UNTIED head
    assert n_params(confnet.param_specs(layers)) == (
        published - n * 4 * d + v * d
    ) == 406_188_032
    b, s = traffic["batch"], traffic["seq_len"]
    by_hand = 3 * (
        n * (8 * b * s * d * d + 4 * b * s * d * f + 2 * b * s * s * d)
        + 2 * b * s * d * v
    )
    assert flops.train_step_flops(layers, traffic) == pytest.approx(by_hand)


def test_resnet_layer_list_is_the_shipped_net(tmp_path):
    """The yardstick's own generator against the conf the repo ships:
    same layers, types, sources and sizes (the benchmark differs only
    in what its configuration file lists under ``assumed``)."""
    from singa_tpu.config import load_model_config

    from benchmark.models import confnet, resnet_conf

    cfg = load(BENCH, "configs", "resnet50.json")
    layers = resnet_conf.build(cfg, {"batch": 256}, "examples/imagenet/train_shard")
    text = confnet.render("resnet50", layers, cfg["updater"], "bfloat16")
    path = tmp_path / "gen.conf"
    path.write_text(text)
    mine = load_model_config(str(path))
    shipped = load_model_config(
        os.path.join(ROOT, "examples", "imagenet", "resnet50.conf")
    )
    theirs = [l for l in shipped.neuralnet.layer if "kTrain" not in l.exclude]
    ours = list(mine.neuralnet.layer)
    assert [l.name for l in ours] == [l.name for l in theirs]
    for a, b in zip(ours, theirs):
        assert (a.type, list(a.srclayers)) == (b.type, list(b.srclayers)), a.name
        if a.type == "kConvolution":
            pa, pb = a.convolution_param, b.convolution_param
            assert (pa.num_filters, pa.kernel, pa.stride, pa.pad, pa.bias_term) == (
                pb.num_filters, pb.kernel, pb.stride, pb.pad, pb.bias_term
            )
        if a.type == "kPooling":
            assert (a.pooling_param.pool, a.pooling_param.kernel,
                    a.pooling_param.stride) == (
                b.pooling_param.pool, b.pooling_param.kernel,
                b.pooling_param.stride)


# ---------------------------------------------------------------------
# arithmetic on hand-made inputs
# ---------------------------------------------------------------------


def test_percentile():
    from benchmark.drivers.serve import percentile

    v = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert percentile(v, 50) == 3.0
    assert percentile(v, 0) == 1.0 and percentile(v, 100) == 5.0
    assert percentile(v, 95) == pytest.approx(np.percentile(v, 95))
    many = list(np.random.default_rng(0).random(1000))
    assert percentile(many, 95) == pytest.approx(np.percentile(many, 95))


def test_mfu_and_peaks():
    from benchmark import flops

    assert flops.peak_flops("TPU v5 lite") == 197e12
    assert flops.mfu_percent(98.5e12, 1, "TPU v5 lite") == pytest.approx(50.0)
    assert flops.mfu_percent(98.5e12, 4, "TPU v5 lite") == pytest.approx(12.5)
    with pytest.raises(ValueError):
        flops.peak_flops("TPU v9 imaginary")
    with pytest.raises(ValueError):
        flops.peak_flops("cpu")


def test_metric_readers_on_hand_made_runs():
    from benchmark import run as harness

    spans = harness.Spans(False)
    spans.rows += [
        ("train_chunk", 0.0, 0.004, {"steps": 8}),
        ("train_chunk", 1.0, 1.012, {"steps": 8}),
        ("tick", 0.0, 0.030, {"decodes": 1, "prefill_chunks": 1}),
        ("tick", 0.030, 0.050, {"decodes": 1, "prefill_chunks": 0}),
        ("tick", 0.050, 0.072, {"decodes": 1, "prefill_chunks": 0}),
        ("tick", 0.072, 0.073, {"decodes": 0, "prefill_chunks": 0}),
        ("decode", 0.002, 0.003, {}), ("decode", 0.031, 0.032, {}),
        ("prefill_chunk", 0.000, 0.002, {"tokens": 128}),
    ]

    class FakeDriver:
        def step_flops(self):
            return 6.0e12

    view = {
        "spans": spans, "chips": 1, "device_kind": "TPU v5 lite",
        "end_to_end": {"train_step_ms": 100.0},
        "counters": {"model_flops": 1.97e12, "window_s": 10.0,
                     "ttft_p95_ms": 321.0},
        "trace": {"busy_s": 2.7, "window_s": 3.0}, "driver": FakeDriver(),
    }
    read = {
        n: harness.load_reader(n)(view)
        for n in (
            "step_mfu.train", "step_mfu.serve", "device_idle_share.train",
            "device_idle_share.serve", "trainer_host_ms_per_step",
            "decode_tick_ms", "prefill_chunk_ms", "serve_ttft_p95_ms",
        )
    }
    assert read["step_mfu.train"] == pytest.approx(100 * 6.0e13 / 197e12)
    assert read["step_mfu.serve"] == pytest.approx(100 * 1.97e11 / 197e12)
    assert read["device_idle_share.train"] == pytest.approx(10.0)
    assert read["trainer_host_ms_per_step"] == pytest.approx(1.0)
    # pure decode ticks only: the tick with a chunk and the idle one are out
    assert read["decode_tick_ms"] == pytest.approx(21.0)
    assert read["prefill_chunk_ms"] == pytest.approx(2.0)
    assert read["serve_ttft_p95_ms"] == pytest.approx(321.0)
    # a reader with nothing to read returns nothing, never 0
    empty = dict(view, spans=harness.Spans(False), trace=None, counters={},
                 end_to_end={})
    for n in read:
        assert harness.load_reader(n)(empty) is None, n


def test_traffic_same_work_for_every_seed():
    from benchmark import traffic as gen

    t = load(BENCH, "traffic", "closed_c32.json")
    shapes = gen.request_shapes(t)
    assert len(shapes) == t["pool"]
    assert all(p + o <= 1024 for p, o in shapes)
    assert min(p for p, _ in shapes) >= 32 and max(p for p, _ in shapes) <= 768
    a = gen.requests(t, 50257, 1)
    b = gen.requests(t, 50257, 2**31 + 5)
    key = lambda rs: [(len(r["prompt"]), r["max_new_tokens"]) for r in rs]  # noqa: E731
    assert key(a) == key(b) == shapes  # same work, same order
    assert not np.array_equal(a[0]["prompt"], b[0]["prompt"])
    assert len({p for p, _ in shapes[:8]}) > 4  # the order is shuffled
    again = gen.requests(t, 50257, 1)
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, again))
    med = float(np.median([p for p, _ in shapes]))
    assert 180 <= med <= 205


# ---------------------------------------------------------------------
# the trace reduction
# ---------------------------------------------------------------------


def test_trace_reduction_hand_made():
    from benchmark import trace_reduce as tr

    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_step", 0, 1000]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 0, 100], ["conv.2", 100, 300],
                ["fusion.1", 350, 100],  # overlaps conv.2's tail
                ["copy.3", 700, 100],
            ]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench/train_chunk", 0, 420], ["bench/chunk_wait", 420, 600],
        ]}]},
    ]}
    s = tr.summarize(trace, 1)
    assert s["busy_s"] == pytest.approx(550e-9)
    assert s["span_s"] == pytest.approx(800e-9)
    assert s["device_ops"][0] == ["conv", pytest.approx(300e-9)]
    assert s["device_ops"][1] == ["fusion", pytest.approx(200e-9)]
    assert s["idle_gaps"] == [["chunk_wait", pytest.approx(250e-9)]]
    with pytest.raises(RuntimeError):
        tr.summarize({"planes": trace["planes"][1:]}, 1)
    # a second chip that ran nothing halves the mean busy time
    assert tr.summarize(trace, 2)["busy_s"] == pytest.approx(275e-9)


def test_trace_reduction_recorded():
    from benchmark import trace_reduce as tr

    trace = load(HERE, "data", "trace_resnet_v5e.json")
    s = tr.summarize(trace, 1)
    assert 0 < s["busy_s"] <= s["span_s"]
    assert 1 <= len(s["device_ops"]) <= 10
    assert all(sec > 0 for _, sec in s["device_ops"])
    total = sum(
        d for p in trace["planes"] if p["name"].startswith("/device:TPU")
        for _, _, d in tr.device_op_events(p)
    )
    assert s["busy_s"] <= total / 1e9 + 1e-12


# ---------------------------------------------------------------------
# run.py end to end, tiny, gate steered from here
# ---------------------------------------------------------------------

def cell_names():
    return [c["name"] for c in load(ROOT, "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", cell_names())
def test_run_end_to_end(rehearse, cell, trace):
    rehearse(cell, trace)


def test_no_tpu_no_result(capsys):
    from benchmark import run as harness

    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", cell_names()[0], "--seed", "1",
                      "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out


# ---------------------------------------------------------------------
# the controls: lower precision and planted faults must FAIL the check
# ---------------------------------------------------------------------


def train_driver(tiny_files, tmp_path, config="gpt2_medium",
                 traffic="tokens_b4_s1024"):
    """The train driver at the rehearsal's tiny sizes, comparing the
    numbers that the shipped training cell compares
    (``limits/resnet50_train.json``)."""
    import jax

    from benchmark import run as harness
    from benchmark.drivers import train

    files = tiny_files()
    return train.Driver(
        config=files.config(config), traffic=files.traffic(traffic),
        limits=files.limits("resnet50_train"), seed=7,
        devices=jax.devices()[:1], work=str(tmp_path),
        spans=harness.Spans(False),
    )


@pytest.mark.parametrize("config,traffic,ariths", [
    pytest.param("gpt2_medium", "tokens_b4_s1024", ("bfloat16", "float8"),
                 id="tokens-ariths0"),
    pytest.param("resnet50", "imagenet_b256", ("float8", "bfloat16_all"),
                 id="image-ariths1"),
])
def test_training_check_passes_then_fails_lower_precision(
    tiny_files, tmp_path, config, traffic, ariths
):
    """The program passes the check as a run makes it; the reference in
    a lower precision, put in its place, fails it on the shipped keys,
    and so does half of the batch left out."""
    from benchmark import run as harness
    from benchmark.drivers import train

    d = train_driver(tiny_files, tmp_path, config, traffic)
    d.program = d.first_steps(d.build())
    d.release()
    assert harness.passes(d.check())
    reference = d.reference_readings()

    def judged(**kw):
        got = train.compare(
            d.reference_readings(**kw), reference, d.matrices()
        )
        return train.judged(got, d.limits)

    for arith in ariths:
        assert not harness.passes(judged(arith=arith)), arith
    half = judged(fault="half_batch")
    assert any(c["value"] > 10 * c["limit"] for c in half.values()), half


@pytest.mark.parametrize(
    "fault", ["state_unchanged", "later_steps_unchanged", "half_batch"]
)
def test_training_fault_under_the_timed_path(
    tiny_files, tmp_path, monkeypatch, fault
):
    """The rest of a run with the timed path broken underneath.
    ``later_steps_unchanged`` exists only in the window's chunk program:
    step 0 (the one-step program) and the chunk's first step are sound,
    every later step of the scan returns its state unchanged."""
    import jax
    import jax.numpy as jnp

    from benchmark import run as harness
    from singa_tpu.trainer import Trainer

    if fault == "half_batch":
        name, real = "_resolve_batch", Trainer._resolve_batch

        def broken(self, net, batch, constrain=True):
            out = real(self, net, batch, constrain)
            return {
                name: {k: v[: v.shape[0] // 2] for k, v in feed.items()}
                for name, feed in out.items()
            }
    else:
        name, real = "_train_step_fn", Trainer._train_step_fn
        sound_until = 0 if fault == "state_unchanged" else 2

        def broken(self, params, state, buffers, step, batch, rng):
            new = real(self, params, state, buffers, step, batch, rng)
            kept = jax.tree.map(
                lambda n, o: jnp.where(step < sound_until, n, o),
                new[:3], (params, state, buffers),
            )
            return *kept, new[3]
    monkeypatch.setattr(Trainer, name, broken)
    d = train_driver(tiny_files, tmp_path)
    d.setup()
    d.window(0.3)
    assert d.attempted_failed()[1] == 0  # the window itself sees nothing
    d.release()
    compared = d.check()
    assert not harness.passes(compared), compared
    assert all(math.isfinite(c["value"]) for c in compared.values())


def serve_driver(tiny_files, tmp_path):
    import jax

    from benchmark import run as harness
    from benchmark.drivers import serve

    files = tiny_files()
    return serve.Driver(
        config=files.config("gpt2_medium"),
        traffic=files.traffic("closed_c32"),
        limits=files.limits("gpt2_medium_serve_closed"), seed=9,
        devices=jax.devices()[:1], work=str(tmp_path),
        spans=harness.Spans(False),
    )


def test_serving_check_passes_then_fails_lower_precision(tiny_files, tmp_path):
    from benchmark import run as harness

    d = serve_driver(tiny_files, tmp_path)
    d.setup()
    d.window(0.6)
    # a loaded CPU finishes few requests in 0.6 s: more windows until
    # the sample holds enough served tokens to judge
    for _ in range(50):
        if sum(len(t) for _, t in d._sample()) >= 10:
            break
        d.window(0.2)
    d.release()
    assert sum(len(t) for _, t in d.sample) >= 10
    assert harness.passes(d.check())
    assert d.logit_gaps(d.sample, "float8") > d.limits["logit_gap"]


def test_serving_fault_token_altered(tiny_files, tmp_path, monkeypatch):
    """A token altered where it is produced: the decode program's
    output shifted by one id on every live slot."""
    import jax.numpy as jnp

    from benchmark import run as harness
    from singa_tpu.serve import Engine

    real = Engine.decode

    def altered(self):
        out = real(self)
        return jnp.where(out >= 0, (out + 1) % self.cfg.vocab, out)

    monkeypatch.setattr(Engine, "decode", altered)
    d = serve_driver(tiny_files, tmp_path)
    d.setup()
    d.window(0.6)
    d.release()
    assert not harness.passes(d.check())
