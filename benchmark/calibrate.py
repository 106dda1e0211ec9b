#!/usr/bin/env python3
"""Readings for a cell's limits: the program, the controls and the
planted faults over several seeds, in one process (set-up is long, the
readings need no measured window or only a short one).

    python3 benchmark/calibrate.py --workload <name> --seeds 1,2,3 \\
        [--controls float8] [--faults half_batch] [--seconds 8]

Every side (the program, each control, each fault) is judged as a run
judges it: its numbers go through the cell's ``limits/<workload>.json``
and ``run.passes``, and the line says ``"passes": true|false`` beside
them. A control or a fault that passes sets no upper reading. Prints one
JSON line a seed and appends it to
``chiprun_out/calibrate_<workload>.jsonl``. The benchmark's own runs
never call this; PERF.md quotes what it printed.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument(
        "--set", action="append", default=[], metavar="KEY=JSON",
        help="override a key of the configuration for a look by hand "
        "(a second witness, e.g. compute_dtype=\"float32\")",
    )
    args = ap.parse_args(argv)

    _, cell, config, traffic = harness.load_cell(args.workload)
    for item in args.set:
        key, value = item.split("=", 1)
        config[key] = json.loads(value)
    limits = harness.load_json(harness.LIMITS_DIR, f"{cell['name']}.json")
    devices = harness.require_devices(cell["chips"])
    from singa_tpu.utils.compile_cache import setup_compile_cache

    setup_compile_cache(log=lambda s: print(s, file=sys.stderr))
    driver_mod = importlib.import_module(
        f"benchmark.drivers.{traffic['driver']}"
    )
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        work = os.path.join(ROOT, ".bench_work", f"calibrate_{cell['name']}")
        shutil.rmtree(work, ignore_errors=True)
        os.makedirs(work)
        driver = driver_mod.Driver(
            config=config, traffic=traffic, limits=limits, seed=seed,
            devices=devices, work=work, spans=harness.Spans(False),
        )
        t0 = time.perf_counter()
        sides = driver.calibrate(
            controls=[c for c in args.controls.split(",") if c],
            faults=[f for f in args.faults.split(",") if f],
            seconds=args.seconds,
        )
        for numbers in sides.values():
            numbers["passes"] = harness.passes({
                k: {"value": numbers.get(k), "limit": limits[k]}
                for k in limits
            })
        row = {"workload": cell["name"], "seed": seed, "set": args.set,
               "limits": limits, "seconds": time.perf_counter() - t0,
               **sides}
        line = json.dumps(row)
        print(line, flush=True)
        with open(
            os.path.join(out_dir, f"calibrate_{cell['name']}.jsonl"), "a"
        ) as f:
            f.write(line + "\n")
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
