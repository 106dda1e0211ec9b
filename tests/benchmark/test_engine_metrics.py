"""The readers of the engine's hand-overs and of the chunks behind a
token: ``engine_prefill_ms``, ``engine_idle_share.serve`` and
``sched_idle_share.serve`` on a hand-made trace whose engine spans nest
in the scheduler's, and ``itl_tail_chunks_ahead`` on a driver with
planted requests. Each reads nothing (None, never 0) where its names are
absent: a program before the engine named its hand-overs, a training
run, no trace.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import idle_by_layer  # noqa: E402
from benchmark import program_trace as pt  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark import trace_reduce  # noqa: E402

D = "jit(_decode)"

#: two ticks of a server, times in ns. Tick 0 admits a request and hands
#: its one chunk over (``engine.admit`` in ``sched.admit``,
#: ``engine.prefill`` and ``engine.activate`` in ``sched.prefill``), tick
#: 1 retires one (``engine.retire`` in ``sched.emit``). The device idles
#: 90 ns under ``engine.prefill``, 300 under tick 0's ``sched.emit``,
#: 30 under ``engine.retire`` and 130 after the last tick
SERVE = {
    "host": [
        ["singa/sched.tick", 0, 1000, {"tick": 0}, "main"],
        ["singa/sched.admit", 10, 50, {"tick": 0, "rid": 7, "slot": 1}, "main"],
        ["singa/engine.admit", 15, 40, {"slot": 1, "blocks": 3}, "main"],
        ["singa/sched.prefill", 70, 230,
         {"tick": 0, "rid": 7, "slot": 1, "tokens": 4}, "main"],
        ["singa/engine.prefill", 75, 185,
         {"slot": 1, "tokens": 4, "pos0": 0}, "main"],
        ["singa/engine.activate", 265, 30, {"slot": 1}, "main"],
        ["singa/sched.decode", 300, 600, {"tick": 0}, "main"],
        ["singa/sched.dispatch", 300, 50, {"tick": 0, "live": 2}, "main"],
        ["singa/sched.pull", 350, 530, {"tick": 0}, "main"],
        ["singa/sched.emit", 900, 80, {"tick": 0, "emitted": 2}, "main"],
        ["singa/sched.tick", 1000, 600, {"tick": 1}, "main"],
        ["singa/sched.decode", 1010, 490, {"tick": 1}, "main"],
        ["singa/sched.dispatch", 1010, 40, {"tick": 1, "live": 2}, "main"],
        ["singa/sched.pull", 1050, 450, {"tick": 1}, "main"],
        ["singa/sched.emit", 1510, 80, {"tick": 1, "emitted": 2}, "main"],
        ["singa/engine.retire", 1520, 60, {"slot": 0}, "main"],
    ],
    "devices": [{
        "name": "/device:TPU:0",
        "modules": [
            ["jit__admit_prog", 20, 10], ["jit__prefill", 120, 280],
            ["jit__activate_prog", 400, 10], ["jit__decode", 410, 390],
            ["jit__decode", 1100, 430], ["jit__retire_prog", 1560, 10],
            ["jit__decode", 1700, 100],
        ],
        "ops": [
            ["fusion.1", 20, 10, "jit(_admit_prog)/scatter"],
            ["fusion.2", 120, 280, "jit(_prefill)/blk0/mlp/dot_general"],
            ["fusion.3", 400, 10, "jit(_activate_prog)/sample/argmax"],
            ["fusion.4", 410, 390, f"{D}/blk0/mlp/dot_general"],
            ["fusion.4", 1100, 430, f"{D}/blk0/mlp/dot_general"],
            ["fusion.5", 1560, 10, "jit(_retire_prog)/scatter"],
            ["fusion.4", 1700, 100, f"{D}/blk0/mlp/dot_general"],
        ],
    }],
}
WINDOW_S = 2000e-9

#: the same run as a program that names no hand-over of the engine's
PARENT = dict(SERVE, host=[
    h for h in SERVE["host"] if not h[0].startswith("singa/engine.")
])
TRAIN = {
    "host": [["singa/trainer.train", 0, 100, {"steps": 2}, "main"]],
    "devices": [{
        "name": "/device:TPU:0", "modules": [["jit_chunk_fn", 0, 50]],
        "ops": [["fusion.1", 0, 50, "jit(chunk_fn)/update/mul"]],
    }],
}
EMPTY = {"host": [], "devices": []}

READERS = {
    "engine_prefill_ms": 185 / 1e6,
    "engine_idle_share.serve": 100 * (90 + 30) * 1e-9 / WINDOW_S,
    "sched_idle_share.serve": 100 * 300e-9 / WINDOW_S,
}


class FakeDriver:
    work = "/nowhere/at/all"


def view(driver=None, trace=True) -> dict:
    return {
        "trace": {"busy_s": 1.0, "window_s": WINDOW_S} if trace else None,
        "driver": driver or FakeDriver(),
    }


def test_the_engine_spans_nest_in_the_schedulers():
    nested = pt.spans(SERVE)
    parent = {
        sp["name"]: nested[sp["parent"]]["name"] for sp in nested
        if sp["name"].startswith("engine.")
    }
    assert parent == {
        "engine.admit": "sched.admit", "engine.prefill": "sched.prefill",
        "engine.activate": "sched.prefill", "engine.retire": "sched.emit",
    }
    assert pt.gaps_by_span(SERVE) == {
        "engine.prefill": pytest.approx(90e-9),
        "sched.emit": pytest.approx(300e-9),
        "engine.retire": pytest.approx(30e-9),
        "host_unannotated": pytest.approx(130e-9),
    }


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_hand_made_run_and_where_its_names_are_absent(
    monkeypatch, name
):
    read = harness.load_reader(name)
    monkeypatch.setattr(pt, "load", lambda trace_dir: SERVE)
    assert read(view()) == pytest.approx(READERS[name])
    # a program whose engine names nothing: the engine's readers read
    # nothing, and the scheduler's takes the gaps the engine's held
    monkeypatch.setattr(pt, "load", lambda trace_dir: PARENT)
    if name.startswith("engine"):
        assert read(view()) is None
    else:
        assert read(view()) == pytest.approx(100 * 420e-9 / WINDOW_S)
    for other in (TRAIN, EMPTY, None):
        monkeypatch.setattr(pt, "load", lambda trace_dir: other)
        assert read(view()) is None, other
    monkeypatch.setattr(pt, "load", lambda trace_dir: SERVE)
    assert read(view(trace=False)) is None


def test_the_two_idle_shares_are_part_of_the_devices(monkeypatch):
    """``engine_idle_share.serve`` + ``sched_idle_share.serve`` <=
    ``device_idle_share.serve``, on the same trace as ``run.py`` reduces
    it (``trace_reduce.summarize`` of the device's operations): the rest
    is idle outside any span of the program, and before the first and
    after the last operation of the window."""
    (dev,) = SERVE["devices"]
    planes = {"planes": [{"name": dev["name"], "lines": [{
        "name": "XLA Ops", "events": [op[:3] for op in dev["ops"]],
    }]}]}
    summary = trace_reduce.summarize(planes, 1)
    run = {"trace": {"busy_s": summary["busy_s"], "window_s": WINDOW_S},
           "driver": FakeDriver()}
    monkeypatch.setattr(pt, "load", lambda trace_dir: SERVE)
    shares = {
        n: harness.load_reader(n)(run) for n in (
            "engine_idle_share.serve", "sched_idle_share.serve",
            "device_idle_share.serve",
        )
    }
    device = shares.pop("device_idle_share.serve")
    assert device == pytest.approx(100 * (1 - 1230e-9 / WINDOW_S))
    assert 0 < sum(shares.values()) <= device
    assert device - sum(shares.values()) == pytest.approx(
        100 * (20 + 130 + 200) * 1e-9 / WINDOW_S
    )


def random_trace(seed: int) -> dict:
    """Spans on one to three threads, nested and overlapping, some of
    equal start or none long; zero to three devices whose operations
    overlap, touch, and include containers."""
    rs = np.random.RandomState(seed)
    host = []
    for thread in range(rs.randint(1, 4)):
        t = 0
        for _ in range(rs.randint(0, 40)):
            t += int(rs.randint(0, 50))
            d = int(rs.randint(0, 200))
            name = rs.choice(["sched.tick", "engine.prefill", "other"])
            host.append([f"singa/{name}", t, d, {}, f"t{thread}"])
            for _ in range(rs.randint(0, 3)):
                a = t + int(rs.randint(0, d + 1))
                host.append([f"singa/{rs.choice(['sched.emit', 'engine.admit'])}",
                             a, int(rs.randint(0, t + d - a + 1)), {},
                             f"t{thread}"])
            t += d
    devices = []
    for k in range(rs.randint(0, 4)):
        ops, t = [], 0
        for _ in range(rs.randint(0, 60)):
            t += int(rs.randint(0, 40))
            ops.append([rs.choice(["fusion.1", "while.2", "copy"]), t,
                        int(rs.randint(0, 30)), "jit(_decode)/x"])
        devices.append({"name": f"/device:TPU:{k}", "modules": [], "ops": ops})
    return {"host": host, "devices": devices}


CUTS = sorted(glob.glob(os.path.join(HERE, "data", "scopes_*.json")))


def traces_named(which: str) -> list[dict]:
    if which == "random":
        return [random_trace(seed) for seed in range(300)]
    if which in ("serve", "parent"):
        return [SERVE if which == "serve" else PARENT]
    with open(which) as f:
        return [json.load(f)]


@pytest.mark.parametrize(
    "which", CUTS + ["serve", "parent", "random"],
    ids=lambda w: os.path.basename(w),
)
def test_the_sweep_names_each_gap_as_program_trace_does(which):
    """``idle_by_layer.gaps_by_span`` is ``program_trace``'s table, made
    in one pass over the gaps instead of one pass over the spans a gap."""
    for t in traces_named(which):
        want = pt.gaps_by_span(t)
        got = idle_by_layer.gaps_by_span(t)
        assert got.keys() == want.keys()
        for name, seconds in want.items():
            assert got[name] == pytest.approx(seconds, rel=1e-12, abs=0)


def request(chunks_ahead):
    return types.SimpleNamespace(chunks_ahead=list(chunks_ahead))


def test_itl_tail_chunks_ahead_on_planted_requests():
    read = harness.load_reader("itl_tail_chunks_ahead")
    done = [
        request([0, 0, 2, 0, 1, 0]),
        request([0] + [0] * 30 + [8, 7]),
        request([0, 3, 0, 0, 0]),
        # a request's first token is never read, whatever it holds
        request([9]),
    ]
    tokens = [n for r in done for n in r.chunks_ahead[1:]]
    driver = types.SimpleNamespace(done=done)
    assert read(view(driver)) == pytest.approx(np.percentile(tokens, 95))
    # 41 tokens, 36 of them behind no chunk: the 39th of them in order
    assert read(view(driver)) == pytest.approx(3.0)
    # the tail is the window's, traced or not: no trace is needed
    assert read(view(driver, trace=False)) == pytest.approx(3.0)
    # the tokens that arrive in a tick with no chunk ahead of them
    few = types.SimpleNamespace(done=[request([0, 0, 0]), request([0, 1])])
    assert read(view(few)) == pytest.approx(np.percentile([0, 0, 1], 95))


@pytest.mark.parametrize("driver", [
    # a scheduler before the count: its requests have no such list
    types.SimpleNamespace(done=[types.SimpleNamespace(tokens=[1, 2, 3])]),
    types.SimpleNamespace(done=[request([0])]),  # first tokens only
    types.SimpleNamespace(done=[]),
    FakeDriver(),                                # a training driver
], ids=["parent", "first_tokens", "none_done", "training"])
def test_itl_tail_chunks_ahead_reads_nothing_where_there_is_no_count(driver):
    assert harness.load_reader("itl_tail_chunks_ahead")(view(driver)) is None
