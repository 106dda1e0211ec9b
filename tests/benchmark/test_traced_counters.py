"""What PR 39 corrected in the yardstick, on the CPU:

- a roofline's bytes and time cover the same ticks: ``run.py`` reads the
  scheduler's counters when the traced window closes
  (``run["traced_counters"]``), and each of the five rooflines divides
  those, not the whole window's, by the traced device time; without them
  it returns None;
- a ``lax.cond`` (``cond.N.clone`` on the device) is a container: its
  children count once, the idle gaps between them count as idle, and no
  ``cond`` row reaches the ``breakdown``; no scope metric moves on the
  cuts recorded on the chip.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.dirname(os.path.abspath(__file__))

from benchmark import program_trace, trace_reduce  # noqa: E402
from benchmark import run as harness  # noqa: E402

#: before PR 39
OLD_CONTAINERS = ("while", "conditional", "call")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# -- the snapshot ---------------------------------------------------------


class FakeSched:
    def __init__(self):
        self.decode_ticks = 0
        self.experts_hit = 0
        self.state_slots_live = 0
        self._live_ticks = 7          # private: not a counter to hand on
        self.full_tick_s = 0.0        # a float: a sum of seconds
        self.decode_enabled = True    # a flag
        self.block_len = None


def test_counters_now_takes_the_schedulers_whole_numbers():
    d = types.SimpleNamespace(sched=FakeSched())
    d.sched.decode_ticks, d.sched.experts_hit = 10, 4600
    assert harness.counters_now(d) == {
        "decode_ticks": 10, "experts_hit": 4600, "state_slots_live": 0,
    }
    # the train driver has no scheduler
    assert harness.counters_now(types.SimpleNamespace()) is None
    assert harness.counters_now(types.SimpleNamespace(sched=None)) is None


class CountingDriver:
    """A driver whose windows only count: the first adds 10 decode
    ticks, the second 20."""

    def __init__(self, *, config, traffic, limits, seed, devices, work, spans):
        self.sched = FakeSched()
        self.work = work
        self.windows: list[float] = []

    def setup(self):
        pass

    def window(self, seconds):
        self.windows.append(seconds)
        self.sched.decode_ticks += 10 * len(self.windows)

    def end_to_end(self):
        return {"toy_per_s": 1.0}

    def counters(self):
        return {"decode_ticks": self.sched.decode_ticks,
                "windows": len(self.windows)}

    def attempted_failed(self):
        return 1, 0

    def release(self):
        self.sched = None

    def check(self):
        return {"toy_gap": {"value": 0.0, "limit": 1.0}}


TOY_READER = '''def read(run):
    c = run.get("traced_counters")
    return None if c is None else c["decode_ticks"]
'''


def test_the_snapshot_lies_between_the_two_windows(tmp_path, monkeypatch,
                                                   capsys):
    """A ``--trace 1`` run of a driver that only counts: the readers get
    the counters as the traced window left them (10 ticks), the result's
    line the whole run's (30)."""
    import jax

    bench = {
        "configs": [{"name": "toy", "source": "none", "file": "toy.json",
                     "reduced": [], "why": "a test"}],
        "workloads": [{"name": "toy_cell", "config": "toy",
                       "traffic": "toy_mix", "chips": 1, "why": "a test"}],
        "end_to_end": [
            {"name": "toy_per_s", "unit": "1/s", "better": "higher",
             "bound": 0.01, "source": "host_clock", "workloads": ["toy_cell"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock"},
        ],
        "per_layer": [{"name": "toy_traced_ticks", "unit": "ticks",
                       "better": "higher", "source": "program_counter",
                       "layer": "scheduler", "moves": "toy_per_s",
                       "workloads": ["toy_cell"]}],
    }
    for rel, content in {
        "BENCHMARK.json": bench, "toy.json": {},
        "traffic/toy_mix.json": {"driver": "toy_counting",
                                 "trace_seconds": 0.1},
        "limits/toy_cell.json": {"toy_gap": 1.0},
    }.items():
        (tmp_path / rel).parent.mkdir(exist_ok=True)
        (tmp_path / rel).write_text(json.dumps(content))
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "toy_traced_ticks.py").write_text(TOY_READER)
    monkeypatch.setitem(
        sys.modules, "benchmark.drivers.toy_counting",
        types.SimpleNamespace(Driver=CountingDriver),
    )
    monkeypatch.setattr(harness, "BENCH_FILE", str(tmp_path / "BENCHMARK.json"))
    monkeypatch.setattr(harness, "TRAFFIC_DIR", str(tmp_path / "traffic"))
    monkeypatch.setattr(harness, "LIMITS_DIR", str(tmp_path / "limits"))
    monkeypatch.setattr(harness, "METRICS_DIR", str(tmp_path / "metrics"))
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(
        harness, "require_devices", lambda chips: jax.devices()[:chips]
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    # the CPU has no device plane: the reduction is fed a recorded one
    recorded = load(HERE, "data", "trace_resnet_v5e.json")
    monkeypatch.setattr(trace_reduce, "load_xplane", lambda path: recorded)
    assert harness.main([
        "--workload", "toy_cell", "--seed", str(2**31 + 3),
        "--seconds", "1.0", "--trace", "1",
    ]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["counters"]["windows"] == 2
    assert last["counters"]["decode_ticks"] == 30
    assert last["metrics"]["toy_traced_ticks"]["value"] == 10


# -- the five rooflines ----------------------------------------------------

D, B = "jit(_decode)", "jit(_block_step)"
#: a decode run and a block step, each with the scopes the five read
TRACE = {
    "host": [],
    "devices": [{
        "name": "/device:TPU:0",
        "modules": [["jit__decode", 0, 1000], ["jit__block_step", 1000, 1000]],
        "ops": [
            ["fusion.1", 0, 200, f"{D}/blk1/moe/experts/dot_general"],
            ["fusion.2", 200, 150, f"{D}/blk0/attend/paged_attention/x"],
            ["fusion.3", 350, 300, f"{D}/blk2/mamba/step/mul"],
            ["fusion.4", 1000, 700, f"{B}/blk0/moe/experts/dot_general"],
        ],
    }],
}
#: what the traced window read, and the whole window: other passes,
#: another mean a pass
TRACED = {"decode_ticks": 10, "experts_hit": 10 * 5 * 7,
          "cache_rows": 10 * 200_000, "state_slots_live": 10 * 100}
WINDOW = {"decode_ticks": 100, "experts_hit": 100 * 5 * 9,
          "cache_rows": 100 * 250_000, "state_slots_live": 100 * 120}
ROOFLINES = {
    "moe_hbm_roofline": "sdar_30b_a3b",
    "moe_share_hbm_roofline": "kimi_k2_instruct",
    "latent_attend_hbm_roofline": "kimi_k2_instruct",
    "ssm_state_hbm_roofline": "nemotron_3_super_120b_a12b",
    "latent_moe_hbm_roofline": "nemotron_3_super_120b_a12b",
}


def view(trace, counters, traced, config):
    class FakeDriver:
        work = "/nowhere"

    program_trace._cache[os.path.join("/nowhere", "trace")] = trace
    return {
        "spans": harness.Spans(False), "chips": 1,
        "device_kind": "TPU v5 lite", "end_to_end": {}, "counters": counters,
        "traced_counters": traced,
        "trace": {"busy_s": 1.0, "window_s": 1.0} if trace else None,
        "driver": FakeDriver(), "config": config, "traffic": {},
    }


@pytest.mark.parametrize("name", sorted(ROOFLINES))
def test_a_roofline_reads_the_traced_counters(name):
    config = load(ROOT, "benchmark", "configs", f"{ROOFLINES[name]}.json")
    read = harness.load_reader(name)
    got = read(view(TRACE, WINDOW, TRACED, config))
    assert got is not None and got > 0
    # the traced counters alone decide it, the window's are not read
    assert got == read(view(TRACE, TRACED, TRACED, config))
    assert got != pytest.approx(read(view(TRACE, WINDOW, WINDOW, config)))
    # no snapshot (an untraced run): nothing, never a mix of windows
    assert read(view(TRACE, WINDOW, None, config)) is None
    assert read(view(TRACE, WINDOW, {}, config)) is None


# -- a cond is a container -------------------------------------------------


def test_a_cond_counts_its_children_once():
    """A recorded-style trace: ``cond.3.clone`` wraps two operations
    with 150 ns between them."""
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit__prefill", 0, 1000]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 0, 100],
                ["cond.3.clone", 200, 500],
                ["gmm.1", 200, 150], ["gmm.2", 500, 200],
                ["fusion.2", 800, 100],
            ]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench/tick", 0, 1000],
        ]}]},
    ]}
    s = trace_reduce.summarize(trace, 1)
    assert s["busy_s"] == pytest.approx((100 + 150 + 200 + 100) * 1e-9)
    assert dict(s["device_ops"]) == {
        "gmm": pytest.approx(350e-9), "fusion": pytest.approx(200e-9),
    }
    assert not [k for k, _ in s["device_ops"] if k.startswith("cond")]
    # the gap inside the cond is idle: 100 + 150 + 100 ns
    assert s["idle_gaps"] == [["tick", pytest.approx(350e-9)]]


def test_program_trace_leaves_a_cond_out():
    P = "jit(_prefill)/blk1/moe"
    trace = {"host": [], "devices": [{
        "name": "/device:TPU:0", "modules": [["jit__prefill", 0, 1000]],
        "ops": [
            ["cond.3.clone", 200, 500, ""],
            ["gmm.1", 200, 150, f"{P}/while/body/cond/branch_1_fun/experts/x"],
            ["gmm.2", 500, 200, f"{P}/while/body/cond/branch_1_fun/combine/x"],
        ],
    }]}
    (run,) = program_trace.module_runs(trace, "jit__prefill")
    assert run["busy_ns"] == 350
    assert program_trace.scope_seconds(trace)["moe"]["fwd"] == pytest.approx(
        350e-9)
    assert "unscoped" not in program_trace.scope_seconds(trace)


SCOPE_READERS = ("moe_ms_per_chunk", "moe_ms_per_tick", "attend_ms_per_tick",
                 "paged_attention_ms_per_tick", "mamba_ms_per_tick",
                 "mamba_ms_per_chunk", "moe_ms_per_block_step")
CUTS = sorted(glob.glob(os.path.join(HERE, "data", "scopes_*.json")))


def test_some_recorded_cut_holds_a_cond():
    assert any(
        trace_reduce.is_container(op[0]) and op[0].startswith("cond.")
        for path in CUTS for dev in load(path)["devices"] for op in dev["ops"]
    )


@pytest.mark.parametrize("reader", SCOPE_READERS)
@pytest.mark.parametrize(
    "cut", CUTS, ids=[os.path.basename(p)[7:-5] for p in CUTS]
)
def test_no_scope_metric_moves_on_the_recorded_cuts(cut, reader, monkeypatch):
    """The containers carry no scope that ``program_trace.KNOWN`` or the
    ``mamba`` lookup matches, so leaving ``cond`` out moves no scope's
    time."""
    trace = load(cut)
    read = harness.load_reader(reader)
    new = read(view(trace, {}, None, {}))
    monkeypatch.setattr(trace_reduce, "CONTAINERS", OLD_CONTAINERS)
    assert read(view(trace, {}, None, {})) == new
