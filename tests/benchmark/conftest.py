"""The CPU rehearsal of ``run.py`` (``test_run_end_to_end``) asserts that
every per-layer metric of a cell prints a value, and the CPU has no
device plane to read one from. ``test_benchmark.py`` feeds
``trace_reduce.load_xplane`` a cut recorded on the chip for that reason;
this does the same for ``program_trace``'s loader: where the path of a
run's trace holds a cell's name (``.bench_work/<cell>/trace``), the
cell's recorded cut stands in for the file."""

import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

#: cell -> its cut, recorded by PR 24 with ``program_trace.py --json``
CUTS = {
    "resnet50_train": "scopes_resnet_v5e.json",
    "gpt2_medium_serve_closed": "scopes_serve_v5e.json",
}


@pytest.fixture(autouse=True)
def recorded_program_trace(monkeypatch):
    from benchmark import program_trace

    real = program_trace.load_xplane

    def load_xplane(path):
        for cell, cut in CUTS.items():
            if f"{os.sep}{cell}{os.sep}" in path:
                with open(os.path.join(HERE, "data", cut)) as f:
                    return json.load(f)
        return real(path)

    monkeypatch.setattr(program_trace, "load_xplane", load_xplane)
    monkeypatch.setattr(program_trace, "_cache", {})
