"""What the CPU rehearsal of ``run.py`` needs besides the shipped files,
found by the same names ``BENCHMARK.json`` uses. No cell, configuration
or traffic mix is named in this file or in the tests that use it: a PR
that adds one adds the files below and edits nothing.

    tiny/configs/<config>.json    keys laid over the shipped configuration
    tiny/traffic/<traffic>.json   the traffic mix at a size the CPU holds
    tiny/limits.json              the rehearsal's limit for each number compared
    tiny/limits/<cell>.json       (only for a cell that compares a new number)
    data/scopes_<cell>.json       the cell's cut of a trace recorded on the chip

The CPU has no device plane, and ``test_run_end_to_end`` asserts that
every per-layer metric of a cell prints a value: ``trace_reduce`` is fed
one recorded cut whatever the cell (``data/trace_resnet_v5e.json``: busy
time and operations are plumbing here), and ``program_trace``'s loader is
fed the cell's own cut, found from the path of the run's trace
(``.bench_work/<cell>/trace``).
"""

import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class TinyFiles:
    """The rehearsal's sizes as data, under ``tree`` (a checkout, or the
    temporary copy of one that a test has added a cell to). A file that
    is missing fails the test that asked for it, with the path to add."""

    def __init__(self, tree: str = ROOT):
        self.tree = tree
        self.bench = load(tree, "BENCHMARK.json")

    def _tiny(self, what: str, *parts: str):
        path = os.path.join(self.tree, "tests", "benchmark", "tiny", *parts)
        if not os.path.exists(path):
            pytest.fail(
                f"the rehearsal has no tiny {what}: add "
                f"{os.path.relpath(path, self.tree)} (benchmark/README.md, \"How a "
                f"run finds its files\")"
            )
        return load(path)

    def config(self, name: str) -> dict:
        """The shipped configuration with its tiny keys laid over it."""
        (entry,) = [c for c in self.bench["configs"] if c["name"] == name]
        return load(self.tree, entry["file"]) | self._tiny(
            f"configuration {name!r}", "configs", f"{name}.json"
        )

    def traffic(self, name: str) -> dict:
        return self._tiny(f"traffic mix {name!r}", "traffic", f"{name}.json")

    def limits(self, cell: str) -> dict:
        """The numbers that the cell's shipped limits file compares,
        each at the rehearsal's limit: float32 on the CPU follows the
        reference to rounding, and these stand well above that and well
        below any fault. A cell that compares a number under a new name
        brings ``tiny/limits/<cell>.json``; the others share
        ``tiny/limits.json``."""
        keys = load(self.tree, "benchmark", "limits", f"{cell}.json")
        own = os.path.join(
            self.tree, "tests", "benchmark", "tiny", "limits", f"{cell}.json"
        )
        tiny = load(own) if os.path.exists(own) else self._tiny(
            "limits", "limits.json"
        )
        missing = [k for k in keys if k not in tiny]
        if missing:
            pytest.fail(
                f"the rehearsal has no tiny limit for {missing}, which "
                f"benchmark/limits/{cell}.json compares: add "
                f"{os.path.relpath(own, self.tree)}"
            )
        return {k: tiny[k] for k in keys}


@pytest.fixture()
def tiny_files():
    """``tiny_files()`` -> the tiny files of this checkout;
    ``tiny_files(tree)`` -> those of a copy."""
    return TinyFiles


def cut_loader(data_dir: str, real):
    """``program_trace.load_xplane`` for the rehearsal: the trace of a
    run of ``<cell>`` (``.bench_work/<cell>/trace/...``) is that cell's
    recorded cut, ``<data_dir>/scopes_<cell>.json``; any other path is
    read as it is."""

    def load_xplane(path):
        parts = path.split(os.sep)
        if ".bench_work" not in parts[:-1]:
            return real(path)
        cell = parts[parts.index(".bench_work") + 1]
        cut = os.path.join(data_dir, f"scopes_{cell}.json")
        if not os.path.exists(cut):
            pytest.fail(
                f"cell {cell!r} has a per-layer metric that reads "
                f"program_trace and no recorded cut: run the cell on the "
                f"chip with --trace 1, then `python3 "
                f"benchmark/program_trace.py <trace dir> --json "
                f"tests/benchmark/data/scopes_{cell}.json`"
            )
        return load(cut)

    return load_xplane


@pytest.fixture(autouse=True)
def recorded_program_trace(monkeypatch):
    """Every test reads recorded cuts from ``data/`` here; the value is
    a function that points the loader at another directory of cuts."""
    from benchmark import program_trace

    real = program_trace.load_xplane

    def point_at(data_dir: str) -> None:
        monkeypatch.setattr(
            program_trace, "load_xplane", cut_loader(data_dir, real)
        )

    point_at(os.path.join(HERE, "data"))
    monkeypatch.setattr(program_trace, "_cache", {})
    return point_at


@pytest.fixture(scope="session")
def compile_cache(tmp_path_factory):
    """One compile cache for the session: the tiny programs compile once."""
    return str(tmp_path_factory.mktemp("cc"))


@pytest.fixture()
def tiny(tmp_path, monkeypatch, compile_cache, recorded_program_trace):
    """``tiny(cell)`` -> (the harness, the tiny BENCHMARK.json): ONE
    cell's configuration, traffic and limits written at their tiny sizes
    (``run.py`` loads no other), the harness pointed at them, and the
    look for a chip steered to the CPU. ``tree`` is where the shipped
    files and the tiny ones are looked up."""

    def build(cell_name: str, tree: str = ROOT):
        import jax

        from benchmark import flops, trace_reduce
        from benchmark import run as harness

        files = TinyFiles(tree)
        tiny_bench = json.loads(json.dumps(files.bench))
        cell = harness.find_cell(tiny_bench, cell_name)
        (entry,) = [
            c for c in tiny_bench["configs"] if c["name"] == cell["config"]
        ]
        written = {
            f"{entry['name']}.json": files.config(entry["name"]),
            f"traffic/{cell['traffic']}.json": files.traffic(cell["traffic"]),
            f"limits/{cell['name']}.json": files.limits(cell["name"]),
        }
        entry["file"] = str(tmp_path / f"{entry['name']}.json")
        written["BENCHMARK.json"] = tiny_bench
        for rel, content in written.items():
            path = tmp_path / rel
            path.parent.mkdir(exist_ok=True)
            path.write_text(json.dumps(content))
        monkeypatch.setattr(harness, "BENCH_FILE", str(tmp_path / "BENCHMARK.json"))
        monkeypatch.setattr(harness, "TRAFFIC_DIR", str(tmp_path / "traffic"))
        monkeypatch.setattr(harness, "LIMITS_DIR", str(tmp_path / "limits"))
        monkeypatch.setattr(
            harness, "METRICS_DIR", os.path.join(tree, "benchmark", "metrics")
        )
        monkeypatch.setattr(harness, "ROOT", str(tmp_path))
        monkeypatch.setattr(
            harness, "require_devices", lambda chips: jax.devices()[:chips]
        )
        # the rehearsal's device is a CPU, which has no peak on record
        # (and must have none): the share it prints here is plumbing,
        # not a number
        monkeypatch.setattr(flops, "peak_flops", lambda kind: 197e12)
        # the CPU has no device plane: the reduction is fed the recorded cut
        recorded = load(HERE, "data", "trace_resnet_v5e.json")
        monkeypatch.setattr(trace_reduce, "load_xplane", lambda path: recorded)
        recorded_program_trace(os.path.join(tree, "tests", "benchmark", "data"))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", compile_cache)
        return harness, tiny_bench

    return build


@pytest.fixture()
def rehearse(tiny, capsys):
    """``rehearse(cell, trace)`` -> the result line of one run of
    ``run.main`` for the cell at its tiny sizes, held to what the driver
    holds a run to: the keys and their order, ``correct``, no
    compilation inside the window, and EXACTLY the metrics that
    BENCHMARK.json lists for the cell in that ``--trace`` mode."""

    def run(cell: str, trace: int, tree: str = ROOT) -> dict:
        harness, tiny_bench = tiny(cell, tree)
        rc = harness.main([
            "--workload", cell, "--seed", str(2**31 + 11), "--seconds", "0.6",
            "--trace", str(trace),
        ])
        assert rc == 0
        out = capsys.readouterr()
        last = json.loads(out.out.strip().splitlines()[-1])
        assert list(last)[:5] == [
            "correct", "attempted", "failed", "metrics", "device",
        ]
        assert list(last)[-1] == "compared"
        assert last["correct"] is True, last["compared"]
        assert last["attempted"] > 0 and last["failed"] == 0
        assert last["counters"]["window_compiles"] == 0
        assert set(last["device"]) >= {
            "platform", "kind", "count", "memory_peak_bytes",
        }
        kind = "per_layer" if trace else "end_to_end"
        want = {m["name"] for m in harness.metrics_of(tiny_bench, kind, cell)}
        if trace:
            assert set(last["device"]) >= {"busy_s", "window_s"}
            assert last["device"]["busy_s"] > 0
            assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
        assert set(last["metrics"]) == want
        units = {
            m["name"]: m["unit"]
            for m in tiny_bench["end_to_end"] + tiny_bench["per_layer"]
        }
        for name, m in last["metrics"].items():
            assert m["unit"] == units[name] and m["value"] > 0, name
        for name, c in last["compared"].items():
            assert f"compared {name}: value" in out.err
        return last

    return run
