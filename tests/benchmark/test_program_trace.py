"""``benchmark/program_trace.py`` and the seven metrics that read it.

- the four reductions on a hand-made trace: a fusion under two nested
  scopes counts once, under the inner; a ``while`` container is not
  counted; a gap is booked to the innermost span; a span's self time
  leaves out what its children cover;
- the same reductions on two cuts recorded on the chip by PR 24
  (``data/scopes_<cell>.json``, found by the cell's name);
- each reader on a hand-made run and on an empty one (None, never 0).
"""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.dirname(os.path.abspath(__file__))

from benchmark import program_trace as pt  # noqa: E402
from benchmark import run as harness  # noqa: E402

D = "jit(_decode)"
C = "jit(chunk_fn)/while/body"

#: two ticks of a server: the first prefills a chunk and decodes, the
#: second only decodes. Times in ns.
SERVE = {
    "host": [
        ["singa/sched.tick", 0, 1000, {"tick": 0}, "main"],
        ["singa/sched.admit", 10, 40, {"tick": 0, "rid": 7, "slot": 1}, "main"],
        ["singa/sched.prefill", 60, 100,
         {"tick": 0, "rid": 7, "slot": 1, "tokens": 4}, "main"],
        ["singa/sched.decode", 200, 700, {"tick": 0}, "main"],
        ["singa/sched.dispatch", 200, 50, {"tick": 0, "live": 2}, "main"],
        ["singa/sched.pull", 250, 650, {"tick": 0}, "main"],
        ["singa/sched.emit", 900, 60, {"tick": 0, "emitted": 2}, "main"],
        ["singa/sched.tick", 1000, 600, {"tick": 1}, "main"],
        ["singa/sched.decode", 1010, 500, {"tick": 1}, "main"],
        ["singa/sched.dispatch", 1010, 40, {"tick": 1, "live": 2}, "main"],
        ["singa/sched.pull", 1050, 460, {"tick": 1}, "main"],
        ["singa/sched.emit", 1520, 30, {"tick": 1, "emitted": 2}, "main"],
        # another thread's span does not nest in the tick
        ["singa/feeder.assemble_batch", 100, 300, {}, "feeder"],
    ],
    "devices": [{
        "name": "/device:TPU:0",
        "modules": [
            ["jit__prefill", 100, 300], ["jit__decode", 420, 400],
            ["jit__decode", 1100, 400],
        ],
        "ops": [
            ["fusion.9", 100, 300, "jit(_prefill)/blk0/attend/gather_kv/gather"],
            # tick 0's decode
            ["fusion.1", 420, 100, f"{D}/blk0/attend/gather_kv/gather"],
            ["copy.2", 520, 50, f"{D}/blk0/attend/kv_write/scatter"],
            ["fusion.3", 570, 100,
             f"{D}/blk0/attend/cache_attend/bhqd,bhkd->bhqk/dot_general"],
            ["fusion.4", 700, 80, f"{D}/blk0/mlp/dot_general"],
            ["fusion.5", 780, 40, f"{D}/add"],
            # tick 1's decode, with a container around two of its ops
            ["while.6", 1100, 400, f"{D}/while"],
            ["fusion.1", 1100, 120, f"{D}/blk0/attend/gather_kv/gather"],
            ["copy.2", 1220, 30, f"{D}/blk0/attend/kv_write/scatter"],
            ["fusion.3", 1250, 100,
             f"{D}/blk0/attend/cache_attend/bhqk,bhkd->bhqd/dot_general"],
            ["fusion.4", 1350, 100, f"{D}/lm_head/dot_general"],
            ["fusion.7", 1460, 40, f"{D}/sample/argmax"],
        ],
    }],
}


def under_the_kernel(trace: dict) -> dict:
    """``trace`` as the paged kernel runs it: inside a run of
    ``jit__decode`` one Mosaic call stands where the gather stood and
    ``cache_attend`` is gone; the prefill chunk keeps its gather."""
    (dev,) = trace["devices"]
    ops = []
    for name, start, dur, op_name in dev["ops"]:
        if op_name.startswith(D) and "/gather_kv/" in op_name:
            name = "paged_attention.1"
            op_name = f"{D}/blk0/attend/paged_attention/pallas_call"
        if not (op_name.startswith(D) and "/cache_attend/" in op_name):
            ops.append([name, start, dur, op_name])
    return {"host": trace["host"], "devices": [dict(dev, ops=ops)]}


SERVE_KERNEL = under_the_kernel(SERVE)

#: one chunk of two training steps
TRAIN = {
    "host": [
        ["singa/trainer.data", 0, 10, {"steps": 2}, "main"],
        ["singa/trainer.train", 10, 90, {"steps": 2}, "main"],
    ],
    "devices": [{
        "name": "/device:TPU:0",
        "modules": [["jit_chunk_fn", 50, 1000]],
        "ops": [
            ["while.1", 50, 1000, f"{C}"],
            ["fusion.2", 50, 200, f"{C}/jvp(kConvolution.c1)/conv_general_dilated"],
            ["fusion.3", 250, 100, f"{C}/jvp(kBatchNorm.bn1)/reduce_sum"],
            ["fusion.4", 350, 60, f"{C}/jvp(kReLU.r1)/max"],
            ["fusion.5", 410, 140,
             f"{C}/transpose(jvp(kBatchNorm.bn1))/reduce_sum"],
            ["fusion.6", 550, 300,
             f"{C}/transpose(jvp(kConvolution.c1))/conv_general_dilated"],
            ["fusion.7", 850, 100, f"{C}/update/mul"],
            ["copy.8", 950, 50, ""],
        ],
    }],
}

EMPTY = {"host": [], "devices": []}


@pytest.mark.parametrize("op_name,scopes,direction", [
    ("jit(f)/transpose(jvp(blk0))/gather_kv/transpose",
     ["blk0", "gather_kv"], "bwd"),
    (f"{D}/blk3/attend/cache_attend/bhqd,bhkd->bhqk/dot_general",
     ["blk3", "attend", "cache_attend"], "fwd"),
    (f"{C}/transpose(jvp(kBatchNorm.s1b2_c_bn))/reduce_sum",
     ["kBatchNorm.s1b2_c_bn"], "bwd"),
    (f"{C}/jvp(kConvolution.stem_conv)/conv_general_dilated",
     ["kConvolution.stem_conv"], "fwd"),
    (f"{D}/blk0/ln1/jit(_var)/reduce_sum", ["blk0", "ln1"], "fwd"),
    # the primitive ``transpose`` at a path's end is no direction
    (f"{D}/blk0/attend/gather_kv/transpose",
     ["blk0", "attend", "gather_kv"], "fwd"),
    ("", [], "fwd"),
])
def test_scope_path(op_name, scopes, direction):
    assert pt.scope_path(op_name) == (scopes, direction)


def test_scope_seconds_counts_each_operation_once_under_the_inner_scope():
    s = pt.scope_seconds(SERVE)
    assert s["gather_kv"]["fwd"] == pytest.approx(520e-9)  # prefill's too
    assert s["kv_write"]["fwd"] == pytest.approx(80e-9)
    assert s["cache_attend"]["fwd"] == pytest.approx(200e-9)
    assert "attend" not in s and "blk0" not in s  # only what they hold
    assert s[pt.UNSCOPED]["fwd"] == pytest.approx(40e-9)
    # the ``while`` (400 ns) is a container: everything adds up without it
    total = sum(r["fwd"] + r["bwd"] for r in s.values())
    assert total == pytest.approx(1060e-9)
    inside = pt.scope_seconds(SERVE, "jit__decode")
    assert inside["gather_kv"]["fwd"] == pytest.approx(220e-9)
    t = pt.scope_seconds(TRAIN)
    assert t["kBatchNorm.bn1"] == {
        "fwd": pytest.approx(100e-9), "bwd": pytest.approx(140e-9),
    }
    assert t["kConvolution.c1"]["bwd"] == pytest.approx(300e-9)
    assert t["update"]["fwd"] == pytest.approx(100e-9)
    assert t[pt.UNSCOPED]["fwd"] == pytest.approx(50e-9)


def test_seconds_under_an_outer_scope_and_a_prefix():
    assert pt.seconds_under(SERVE, "attend", "jit__decode") == pytest.approx(
        500e-9
    )
    assert pt.seconds_under(SERVE, "attend") == pytest.approx(800e-9)
    assert pt.seconds_under(TRAIN, "kBatchNorm.") == pytest.approx(240e-9)
    assert pt.seconds_under(TRAIN, "kBatch") == 0.0  # a name, not a prefix


def test_module_runs():
    runs = pt.module_runs(SERVE, "jit__decode")
    assert [r["start_ns"] for r in runs] == [420, 1100]
    assert [r["dur_ns"] for r in runs] == [400, 400]
    # busy: the union of the operations inside, the container left out
    assert [r["busy_ns"] for r in runs] == [370, 390]
    assert pt.module_runs(SERVE, "jit__verify") == []
    assert pt.median_run_ms(SERVE, "jit__prefill") == pytest.approx(300e-6)


def test_host_self_and_nesting():
    nested = pt.spans(SERVE)
    tick0 = next(s for s in nested if s["attrs"] == {"tick": 0}
                 and s["name"] == "sched.tick")
    held = {nested[i]["name"] for i in pt.inside(nested, nested.index(tick0))}
    assert held == {
        "sched.admit", "sched.prefill", "sched.decode", "sched.dispatch",
        "sched.pull", "sched.emit",
    }
    # the tick's own children are admit, prefill, decode and emit
    assert tick0["self_ns"] == 1000 - 40 - 100 - 700 - 60
    table = pt.host_self(SERVE)
    assert table["sched.tick"]["n"] == 2
    assert table["sched.decode"]["self_s"] == pytest.approx(0.0)
    assert table["sched.pull"]["self_s"] == pytest.approx(1110e-9)
    assert table["feeder.assemble_batch"]["self_s"] == pytest.approx(300e-9)


def test_gaps_are_booked_to_the_innermost_span():
    gaps = pt.gaps_by_span(SERVE)
    # 400-420 lies in tick 0's pull (inside decode, inside the tick);
    # 670-700 too; 820-1100 has its middle (960) in tick 0's emit... no:
    # emit ends at 960, so the tick itself; 1450-1460 in tick 1's pull
    assert gaps["sched.pull"] == pytest.approx((20 + 30 + 10) * 1e-9)
    assert gaps["sched.tick"] == pytest.approx(280e-9)
    assert set(gaps) == {"sched.pull", "sched.tick"}
    assert pt.gaps_by_span(EMPTY) == {}


# ---------------------------------------------------------------------
# cuts recorded on the chip
# ---------------------------------------------------------------------


def recorded(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("name,program,scopes,unscoped_share", [
    # serving, re-recorded by PR 33 (the decode tick on the paged kernel,
    # a prefill chunk on the gather path beside it): unscoped are the
    # waits for the weights streamed in beside the kernel and a copy of
    # each layer's qkv weight (PERF.md section 5). The cuts carry their
    # cells' names (PR 26); the ids are the ones the tests have had since
    # PR 24
    pytest.param(
        "scopes_gpt2_medium_serve_closed.json", "jit__decode",
        {"paged_attention", "kv_write", "gather_kv", "cache_attend", "qkv",
         "mlp"}, 0.75,
        id="scopes_serve_v5e.json-jit__decode-scopes0-0.75",
    ),
    pytest.param(
        "scopes_resnet50_train.json", "jit_chunk_fn", set(), 0.05,
        id="scopes_resnet_v5e.json-jit_chunk_fn-scopes1-0.05",
    ),
])
def test_reductions_on_recorded_cuts(name, program, scopes, unscoped_share):
    trace = recorded(name)
    table = pt.scope_seconds(trace)
    assert set(table) >= scopes
    total = sum(r["fwd"] + r["bwd"] for r in table.values())
    ops = [
        e for d in trace["devices"] for e in pt.device_ops(d)
    ]
    assert total == pytest.approx(sum(e[2] for e in ops) / 1e9)
    unscoped = table.get(pt.UNSCOPED, {"fwd": 0.0, "bwd": 0.0})
    assert (unscoped["fwd"] + unscoped["bwd"]) < unscoped_share * total
    assert pt.unscoped_rows(trace, None)[0][0] in (
        "copy", "copy-done", "slice-done",
    )
    runs = pt.module_runs(trace, program)
    assert runs and all(0 < r["busy_ns"] <= r["dur_ns"] for r in runs)
    assert all(v >= 0 for v in pt.gaps_by_span(trace).values())
    for row in pt.host_self(trace).values():
        assert 0 <= row["self_s"] <= row["total_s"]
    if program == "jit_chunk_fn":
        assert any(k.startswith("kBatchNorm.") for k in table)
        assert any(table[k]["bwd"] > 0 for k in table
                   if k.startswith("kConvolution."))


# ---------------------------------------------------------------------
# the seven readers
# ---------------------------------------------------------------------

READERS = {
    "sched_host_ms_per_tick": (SERVE, (600 - 460) / 1e6),
    "decode_device_ms": (SERVE, 400 / 1e6),
    "prefill_chunk_device_ms": (SERVE, 300 / 1e6),
    "paged_attention_ms_per_tick": (SERVE_KERNEL, (100 + 120) / 2 / 1e6),
    "attend_ms_per_tick": (SERVE, (250 + 250) / 2 / 1e6),
    "bn_ms_per_step": (TRAIN, 240 / 2 / 1e6),
    "conv_ms_per_step": (TRAIN, 500 / 2 / 1e6),
}


class FakeDriver:
    work = "/nowhere/at/all"


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_hand_made_run_and_on_an_empty_one(monkeypatch, name):
    trace, want = READERS[name]
    read = harness.load_reader(name)
    run = {"trace": {"busy_s": 1.0, "window_s": 1.0}, "driver": FakeDriver()}
    monkeypatch.setattr(pt, "load", lambda trace_dir: trace)
    assert read(run) == pytest.approx(want)
    # the other cell's trace holds nothing of this metric's (nor does
    # the gather path of the paged kernel's)
    other = TRAIN if trace is SERVE else SERVE
    monkeypatch.setattr(pt, "load", lambda trace_dir: other)
    assert read(run) is None
    # a parent that names nothing, a run with no trace at all
    monkeypatch.setattr(pt, "load", lambda trace_dir: EMPTY)
    assert read(run) is None
    monkeypatch.setattr(pt, "load", lambda trace_dir: None)
    assert read(run) is None
    assert read(dict(run, trace=None)) is None


def test_a_parent_without_names_still_reads_its_programs(monkeypatch):
    """The parent commit names no scope and no span, but its programs
    are called what they are: the two module metrics read there, the
    other five return nothing."""
    bare = {"host": [], "devices": [{
        "name": "/device:TPU:0", "modules": SERVE["devices"][0]["modules"],
        "ops": [[e[0], e[1], e[2], ""] for e in SERVE["devices"][0]["ops"]],
    }]}
    monkeypatch.setattr(pt, "load", lambda trace_dir: bare)
    run = {"trace": {"busy_s": 1.0}, "driver": FakeDriver()}
    got = {n: harness.load_reader(n)(run) for n in READERS}
    assert {n for n, v in got.items() if v is not None} == {
        "decode_device_ms", "prefill_chunk_device_ms",
    }


def test_load_finds_nothing_where_there_is_no_trace(tmp_path):
    assert pt.load(str(tmp_path)) is None
