"""The counters a far-off serving run is read by (``tick_stats`` in
``benchmark/drivers/serve.py``): by hand, and after a rehearsal window
of every cell that a serving driver runs. No cell is named here: the
serving cells are those whose traffic file names a driver that inherits
``drivers/serve.py``'s loop."""

import importlib
import json
import os

import pytest

from benchmark.drivers import serve

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

TICK_KEYS = (
    "tick_p50_ms", "tick_mean_ms", "tick_p99_ms", "tick_max_ms", "stall_s",
    "stall_ticks", "stalls", "caller_s",
)


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def serving_cells():
    out = []
    for cell in load(ROOT, "BENCHMARK.json")["workloads"]:
        traffic = load(
            ROOT, "benchmark", "traffic", f"{cell['traffic']}.json"
        )
        driver = importlib.import_module(
            f"benchmark.drivers.{traffic['driver']}"
        ).Driver
        if issubclass(driver, serve.Driver):
            out.append(cell["name"])
    return out


def window_of(turns, t0=100.0, inside=0.75):
    """Rows of one window whose ticks take ``turns`` seconds from start
    to start, ``inside`` of each within the ``tick`` span, then the row
    that closes the window."""
    rows, t = [], t0
    for turn in turns:
        rows.append(("tick", t, t + inside * turn, {"decodes": 1}))
        t += turn
    return rows + [("window", t0, t, {})]


def test_uniform_ticks_have_no_stall():
    m = 0.007
    got = serve.tick_stats(window_of([m] * 400))
    assert got["stall_s"] == 0 and got["stall_ticks"] == 0
    for key in ("tick_p50_ms", "tick_mean_ms", "tick_p99_ms", "tick_max_ms"):
        assert got[key] == pytest.approx(1000 * m)
    assert got["caller_s"] == pytest.approx(0.25 * 400 * m)


def test_a_planted_tick_of_forty_medians_is_the_stall():
    m = 0.007
    turns = [m] * 400
    turns[123] = 40 * m
    got = serve.tick_stats(window_of(turns))
    assert got["stall_ticks"] == 1
    assert got["stall_s"] == pytest.approx((40 - serve.STALL_MEDIANS) * m)
    assert got["tick_p50_ms"] == pytest.approx(1000 * m)
    assert got["tick_p50_ms"] <= got["tick_p99_ms"] <= got["tick_max_ms"]
    assert got["tick_max_ms"] == pytest.approx(1000 * 40 * m)
    # the mean is over the ticks that did not stall: the host's pace
    assert got["tick_mean_ms"] == pytest.approx(1000 * m)
    ((at, turn, inside),) = got["stalls"]
    assert at == pytest.approx(123 * m) and turn == pytest.approx(40000 * m)
    assert inside == pytest.approx(0.75 * turn)


def test_a_sound_long_tick_is_no_stall():
    """Two prefill chunks beside a decode take two medians: under the
    threshold, in the tail."""
    m = 0.007
    got = serve.tick_stats(window_of([m] * 90 + [2 * m] * 10))
    assert got["stall_ticks"] == 0 and got["stall_s"] == 0
    assert got["tick_p99_ms"] > got["tick_p50_ms"]


def test_the_gap_between_two_windows_is_no_tick():
    """A traced run's two windows lie seconds apart (the profiler
    stops between them); other spans in the rows count nothing."""
    m = 0.005
    rows = (
        window_of([m] * 50, t0=10.0)
        + [("decode", 11.0, 11.001, {})]
        + window_of([m] * 50, t0=20.0)
    )
    got = serve.tick_stats(rows)
    assert got["stall_ticks"] == 0
    assert got["tick_max_ms"] == pytest.approx(1000 * m)
    assert got["caller_s"] == pytest.approx(0.25 * 100 * m)


def test_no_window_no_numbers():
    assert serve.tick_stats([]) == {}
    assert serve.tick_stats([("tick", 0.0, 1.0, {})]) == {}


@pytest.mark.parametrize("cell", serving_cells())
def test_a_serving_run_carries_the_tick_counters(rehearse, cell):
    c = rehearse(cell, 0)["counters"]
    assert set(TICK_KEYS) <= set(c)
    assert 0 < c["tick_p50_ms"] <= c["tick_p99_ms"] <= c["tick_max_ms"]
    assert 0 < c["tick_mean_ms"] <= c["tick_max_ms"]
    assert c["stall_s"] >= 0 and c["stall_ticks"] >= len(c["stalls"])
    assert 0 <= c["caller_s"] < c["window_s"]
    assert c["tick_max_ms"] <= 1000 * c["window_s"]
