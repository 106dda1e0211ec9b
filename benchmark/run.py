#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one import of JAX. The cell is found by name: BENCHMARK.json
gives its configuration and its traffic mix, ``configs/`` and
``traffic/`` hold their files, the traffic file names the driver
(``drivers/<driver>.py``), ``limits/<workload>.json`` holds the limits of
the output check, and each per-layer metric is read by
``metrics/<name>.py``. No list of names lives in this file.

A run sets up (records and weights from ``--seed``, every shape the
window uses warmed), measures for ``--seconds``, reads the device's peak
memory, frees the program's state, compares what the timed path produced
with the plain reference (``reference/``), and prints ONE JSON object as
the last line of standard output. It fails, printing no result, unless
JAX reports a TPU with as many chips as the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: where the cell's files are looked up (the CPU rehearsal points these
#: at tiny copies)
BENCH_FILE = os.path.join(ROOT, "BENCHMARK.json")
TRAFFIC_DIR = os.path.join(HERE, "traffic")
LIMITS_DIR = os.path.join(HERE, "limits")
METRICS_DIR = os.path.join(HERE, "metrics")

#: seconds of the window that a ``--trace 1`` run keeps under the
#: profiler unless the traffic file says otherwise
TRACE_SECONDS = 3.0


def require_devices(chips: int):
    """The device gate: -> the chips to use, or exit non-zero with one
    line saying why. The CPU rehearsal steers this from the test."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: needs a TPU, JAX found platform "
            f"{devices[0].platform!r} ({len(devices)} device(s))"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} chips, JAX found "
            f"{len(devices)}"
        )
    return devices[:chips]


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(
        f"benchmark: no workload {name!r} in BENCHMARK.json (has "
        f"{[c['name'] for c in bench['workloads']]})"
    )


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """-> (BENCHMARK.json, the cell's entry, its configuration, its
    traffic mix), each from the file its name points at."""
    bench = load_json(BENCH_FILE)
    cell = find_cell(bench, name)
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    config = load_json(ROOT, entry["file"])
    traffic = load_json(TRAFFIC_DIR, f"{cell['traffic']}.json")
    return bench, cell, config, traffic


def metrics_of(bench: dict, kind: str, cell: str) -> list:
    """The ``kind`` ("end_to_end" / "per_layer") entries this cell
    reports: those that list it. An end-to-end metric that lists no
    cells (``setup_s``) is every cell's; a per-layer metric always names
    its cells."""
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end":
            out.append(m)
        else:
            raise SystemExit(
                f"benchmark: per-layer metric {m['name']!r} lists no "
                f"workloads in BENCHMARK.json"
            )
    return out


def load_reader(name: str):
    """``metrics/<name>.py`` -> its ``read(run)``. Names hold dots, so
    the file is loaded by path."""
    path = os.path.join(METRICS_DIR, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Spans:
    """The benchmark's own spans, kept in memory: (name, start, end,
    attributes) on ``time.perf_counter``. In a traced run each also
    writes a ``bench/<name>`` annotation into the profiler's trace, so
    that idle gaps on the device can be named by what the host did."""

    def __init__(self, annotate: bool):
        self.rows: list[tuple] = []
        self.annotate = annotate

    def span(self, name: str, **attrs):
        return _Span(self, name, attrs)

    def named(self, name: str) -> list[tuple]:
        return [r for r in self.rows if r[0] == name]


class _Span:
    def __init__(self, spans: Spans, name: str, attrs: dict):
        self.spans, self.name, self.attrs = spans, name, attrs
        self.ann = None

    def __enter__(self):
        if self.spans.annotate:
            import jax

            self.ann = jax.profiler.TraceAnnotation(f"bench/{self.name}")
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.spans.rows.append((self.name, self.t0, t1, self.attrs))


def counters_now(driver) -> dict | None:
    """The scheduler's own counters as they stand: every whole number it
    keeps (``decode_ticks``, ``block_passes``, ``experts_hit``,
    ``cache_rows``, ``state_slots_live``, ``held_pairs``, ...), and no
    statistic derived from them. None for a driver with no scheduler
    (training). Read when the traced window closes, so that a reader
    divides what the traced ticks did by the time they took on the
    device (``run["traced_counters"]``)."""
    sched = getattr(driver, "sched", None)
    if sched is None:
        return None
    return {
        k: v for k, v in vars(sched).items()
        if type(v) is int and not k.startswith("_")
    }


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip: the runtime's peak of live buffers
    plus its peak of memory reserved for running programs' temporaries
    (on the TPU the two are counted apart: ``bytes_reservable_limit`` is
    ``bytes_limit - bytes_in_use``). 0 where the backend keeps no count:
    the CPU of the rehearsal."""
    def peak(d):
        stats = d.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0)) + int(
            stats.get("peak_bytes_reserved", 0)
        )

    return max(peak(d) for d in devices)


def passes(compared: dict) -> bool:
    """Whether every number compared is there and within its limit."""
    return all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values()
    )


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, config, traffic = load_cell(args.workload)
    limits = load_json(LIMITS_DIR, f"{cell['name']}.json")

    devices = require_devices(cell["chips"])
    import jax

    from singa_tpu.utils.compile_cache import CacheCounter, setup_compile_cache

    setup_compile_cache(log=lambda s: print(s, file=sys.stderr))

    work = os.path.join(ROOT, ".bench_work", cell["name"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    spans = Spans(annotate=bool(args.trace))
    driver_mod = importlib.import_module(
        f"benchmark.drivers.{traffic['driver']}"
    )
    driver = driver_mod.Driver(
        config=config, traffic=traffic, limits=limits, seed=args.seed,
        devices=devices, work=work, spans=spans,
    )
    with CacheCounter() as setup_cache:
        driver.setup()
    setup_s = time.perf_counter() - T_START
    spans.rows.clear()  # per-layer metrics read the window's spans only

    trace_summary = traced_counters = None
    with CacheCounter() as window_cache:
        if args.trace:
            from benchmark import trace_reduce

            trace_dir = os.path.join(work, "trace")
            traced = min(
                args.seconds, float(traffic.get("trace_seconds", TRACE_SECONDS))
            )
            jax.profiler.start_trace(trace_dir)
            t0 = time.perf_counter()
            driver.window(traced)
            window_s = time.perf_counter() - t0
            traced_counters = counters_now(driver)
            jax.profiler.stop_trace()
            if args.seconds - traced > 0.5:
                driver.window(args.seconds - traced)
        else:
            driver.window(args.seconds)
    end_to_end = driver.end_to_end()
    end_to_end["setup_s"] = setup_s
    peak = memory_peak_bytes(devices)
    if args.trace:
        trace_summary = trace_reduce.summarize(
            trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir)),
            len(devices),
        )
        trace_summary["window_s"] = window_s
    counters = driver.counters()
    counters["window_compiles"] = window_cache.misses + window_cache.hits
    counters["setup_cache_hits"] = setup_cache.hits
    counters["setup_cache_misses"] = setup_cache.misses
    attempted, failed = driver.attempted_failed()

    driver.release()
    compared = driver.check()
    correct = (
        failed == 0 and counters["window_compiles"] == 0 and passes(compared)
    )

    kind = "per_layer" if args.trace else "end_to_end"
    run_view = {
        "spans": spans, "counters": counters, "trace": trace_summary,
        "traced_counters": traced_counters,
        "end_to_end": end_to_end, "config": config, "traffic": traffic,
        "device_kind": devices[0].device_kind, "chips": len(devices),
        "driver": driver,
    }
    out_metrics = {}
    for m in metrics_of(bench, kind, cell["name"]):
        value = (
            load_reader(m["name"])(run_view) if args.trace
            else end_to_end[m["name"]]
        )
        if value is not None:
            out_metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": peak,
    }
    result = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": out_metrics, "device": device,
    }
    if trace_summary is not None:
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = trace_summary["window_s"]
        result["breakdown"] = {
            "device_ops": trace_summary["device_ops"],
            "idle_gaps": trace_summary["idle_gaps"],
        }
    result["counters"] = counters
    result["compared"] = compared
    for name, c in compared.items():
        print(
            f"compared {name}: value {c['value']} limit {c['limit']}",
            file=sys.stderr,
        )
    shutil.rmtree(work, ignore_errors=True)
    return result


def main(argv=None) -> int:
    result = run(argv)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
