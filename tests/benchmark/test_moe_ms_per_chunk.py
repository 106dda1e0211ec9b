"""``moe_ms_per_chunk`` (PR 35): the device time of the expert layers
inside a run of ``jit__prefill``, on a hand-made run (the dense form's
operations and the grouped form's, whose kernels sit under a
``while/body/.../cond`` inside ``moe``), on the cut recorded on the chip
for ``kimi_k2_serve_long``, where there is nothing to read (None,
never 0: the parent of a PR that names the scope otherwise, a run with
no trace), and its entry in BENCHMARK.json, found by name (PR 39: a
later PR appends its own entries after it)."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "kimi_k2_serve_long"
NAME = "moe_ms_per_chunk"

from benchmark import program_trace  # noqa: E402
from benchmark import run as harness  # noqa: E402

P = "jit(_prefill)"
INSIDE = "while/body/closed_call/cond/branch_1_fun"
#: two chunks and a tick between them. Times in ns. The first chunk in
#: the dense form, the second in the grouped one
TRACE = {
    "host": [],
    "devices": [{
        "name": "/device:TPU:0",
        "modules": [
            ["jit__prefill", 0, 2000], ["jit__decode", 2000, 1000],
            ["jit__prefill", 3000, 1500],
        ],
        "ops": [
            ["fusion.1", 0, 50, f"{P}/blk1/moe/route/dot_general"],
            ["fusion.2", 50, 800, f"{P}/blk1/moe/experts/nd,edf->enf/dot_general"],
            ["fusion.3", 850, 400, f"{P}/blk1/moe/combine/enf,efd->nd/dot_general"],
            ["fusion.4", 1250, 100, f"{P}/blk1/moe/shared/dot_general"],
            ["fusion.5", 1350, 600, f"{P}/blk1/attend/cache_attend/dot_general"],
            ["fusion.6", 1950, 50, f"{P}/blk0/mlp/dot_general"],
            # a tick's expert layer is not a chunk's
            ["fusion.7", 2000, 700, "jit(_decode)/blk1/moe/experts/dot_general"],
            ["sort.1", 3000, 10, f"{P}/blk1/moe/route/jit(argsort)/sort"],
            ["while.1", 3010, 700, ""],
            ["gmm.1", 3010, 200, f"{P}/blk1/moe/{INSIDE}/experts/jit(gmm)/pallas_call"],
            ["gmm.2", 3210, 200, f"{P}/blk1/moe/{INSIDE}/experts/jit(gmm)/pallas_call"],
            ["gmm.3", 3410, 200, f"{P}/blk1/moe/{INSIDE}/combine/jit(gmm)/pallas_call"],
            ["fusion.8", 3610, 60, f"{P}/blk1/moe/{INSIDE}/combine/dot_general"],
            ["fusion.9", 3700, 100, f"{P}/blk1/moe/shared/dot_general"],
            ["fusion.10", 3800, 600, f"{P}/blk1/attend/cache_attend/dot_general"],
        ],
    }],
}


def view(trace):
    class FakeDriver:
        work = "/nowhere"

    program_trace._cache[os.path.join("/nowhere", "trace")] = trace
    return {
        "chips": 1, "device_kind": "TPU v5 lite", "end_to_end": {},
        "counters": {}, "driver": FakeDriver(), "config": {}, "traffic": {},
        "trace": {"busy_s": 1.0, "window_s": 1.0} if trace else None,
    }


def test_on_a_hand_made_run_in_both_forms():
    read = harness.load_reader(NAME)
    dense = 50 + 800 + 400 + 100
    grouped = 10 + 200 + 200 + 200 + 60 + 100
    assert read(view(TRACE)) == pytest.approx((dense + grouped) / 2 / 1e6)


def assert_declared(bench: dict) -> None:
    """``moe_ms_per_chunk``'s entry, found by its name wherever it
    stands in ``per_layer``: its fields, the two cells whose chunks run
    expert layers (K since PR 35, N since PR 39), and not SDAR's, whose
    block steps are not chunks of this kind."""
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "expert layer",
        "moves": "serve_tokens_per_s",
    }
    assert {CELL, "nemotron_3_super_serve_chat"} <= set(entry["workloads"])
    assert "sdar_30b_a3b_serve_blocks" not in entry["workloads"]
    names = [m["name"] for m in harness.metrics_of(bench, "per_layer", CELL)]
    assert NAME in names and "moe_ms_per_tick" in names
    other = harness.metrics_of(bench, "per_layer", "sdar_30b_a3b_serve_blocks")
    assert NAME not in [m["name"] for m in other]


#: what a later PR appends: one more per-layer entry at the list's end
TOY = {"name": "toy_ms_per_tick", "unit": "ms", "better": "lower",
       "source": "device_trace", "layer": "expert layer",
       "moves": "serve_tokens_per_s", "workloads": [CELL]}


@pytest.mark.parametrize("appended", [False, True], ids=["as_shipped", "one_more_entry"])
def test_it_is_declared_for_the_cell_it_reads(appended):
    """The declaration holds on BENCHMARK.json as it is and on a copy
    with one more per-layer entry appended: no place in the list is
    pinned, so a later PR can append."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if appended:
        bench["per_layer"].append(TOY)
        assert bench["per_layer"][-1]["name"] != NAME
    assert_declared(bench)


@pytest.mark.parametrize("trace", [
    None,
    {"host": [], "devices": []},
    # a parent whose chunk names no expert layer
    {"host": [], "devices": [{
        "name": "/device:TPU:0", "modules": [["jit__prefill", 0, 1000]],
        "ops": [["fusion.1", 0, 200, f"{P}/blk0/mlp/dot_general"]],
    }]},
    # expert layers, but no chunk in the traced seconds
    {"host": [], "devices": [{
        "name": "/device:TPU:0", "modules": [["jit__decode", 0, 1000]],
        "ops": [["fusion.1", 0, 200, "jit(_decode)/blk1/moe/experts/dot_general"]],
    }]},
], ids=["no_trace", "no_device", "no_expert_layer", "no_chunk"])
def test_nothing_to_read_is_none_and_never_raises(trace):
    assert harness.load_reader(NAME)(view(trace)) is None


def test_on_the_cut_recorded_on_the_chip():
    """The cut of a ``--trace 1`` run of the cell on a v5e (PR 34's
    program: the dense form): two runs of ``jit__prefill`` with their
    ``moe`` operations, 16.8 of a chunk's 35.8 ms."""
    with open(os.path.join(HERE, "data", f"scopes_{CELL}.json")) as f:
        cut = json.load(f)
    runs = program_trace.module_runs(cut, "jit__prefill")
    assert len(runs) >= 2
    got = harness.load_reader(NAME)(view(cut))
    chunk = harness.load_reader("prefill_chunk_device_ms")(view(cut))
    tick = harness.load_reader("moe_ms_per_tick")(view(cut))
    assert 16.5 < got < 17.5        # 16.82 over the 76 runs of the whole trace
    assert 0.4 * chunk < got < 0.5 * chunk
    assert got > 2 * tick
