#!/usr/bin/env python3
"""Replay a closed-loop serving cell's schedule on the CPU at the
rehearsal's tiny widths, and say in which class of ticks its ITL p95
falls.

The schedule of such a cell (which tick carries how many prefill chunks,
how many slots are live) is fixed by the traffic file alone: the shapes
and their order (``shape_seed``), the callers, the slots, the chunk
length. No decision of ``Scheduler`` reads a clock and every answer runs
to its budget, so the tiny model (``tests/benchmark/tiny/configs``, laid
over the shipped configuration) runs the chip's ticks in the chip's
order with the cell's own traffic; only their lengths differ. A tick's
length grows with the chunks dispatched before its pull, so the 95th
percentile of the gaps lies in the class of ticks with ``c`` chunks for
the largest ``c`` whose ticks of ``c`` or more hold 5 % of the gaps. A
share near 5 % on either side of that class means the percentile stands
at an edge, and a change that moves the packing of chunks moves it by a
chunk's time (some 13 % in ``lfm2_8b_a1b_serve_rag``).

    JAX_PLATFORMS=cpu python3 tests/benchmark/replay_schedule.py \\
        --workload lfm2_8b_a1b_serve_rag --shape-seeds 6,20261018

Prints one JSON line a shape seed: the histogram of chunks a tick over
the window's ticks, and for each window end (``--ticks``, the chip's
30 s hold some 590-620) and each reading of which chunks a gap waits
behind (``own``: the tick's own; ``before``: the tick before's, which
the pass one tick ahead waits behind) the class that holds the
percentile and the shares of the gaps in ticks of that class or more
and of the next or more. ``data/replay_lfm2_8b_a1b_serve_rag.jsonl``
holds what it printed for the shape seeds tried.
"""

from __future__ import annotations

import argparse
import collections
import importlib
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)


def schedule(cell: dict, config: dict, traffic: dict, ticks: int,
             seed: int = 0) -> list[tuple[list[int], int]]:
    """(chunks, gaps) of each of the first ``ticks`` ticks after the
    driver's ramp: the lengths of the prefill chunks the tick dispatched
    and the gaps its delivered tokens closed."""
    import jax

    from benchmark import run as harness

    driver_mod = importlib.import_module(
        f"benchmark.drivers.{traffic['driver']}"
    )
    d = driver_mod.Driver(
        config=config, traffic=traffic, limits={}, seed=seed,
        devices=jax.devices()[:1], work=tempfile.mkdtemp(),
        spans=harness.Spans(False),
    )
    d.setup()
    engine, sizes = d.engine, []
    prefill = engine.prefill_chunk

    def counted(slot, tokens, pos0):
        sizes.append(len(tokens))
        return prefill(slot, tokens, pos0)

    engine.prefill_chunk = counted     # the scheduler finds it here
    d.in_window = True
    rows = []
    for _ in range(ticks):
        sizes, n = [], len(d.gaps)
        d._tick()
        rows.append((sizes, len(d.gaps) - n))
    return rows


def classes(rows: list[tuple[list[int], int]], shift: int) -> dict:
    """Where the 95th percentile falls when a tick's gaps wait behind
    the chunks of the tick ``shift`` before."""
    pairs = [
        (len(rows[k - shift][0]) if k >= shift else 0, rows[k][1])
        for k in range(len(rows))
    ]
    total = sum(g for _, g in pairs)
    top = max(c for c, _ in pairs)
    share = {
        c: 100.0 * sum(g for ch, g in pairs if ch >= c) / total
        for c in range(top + 2)
    }
    c = max(k for k, v in share.items() if v >= 5.0)
    return {"p95_class": c, "share_at_least_class": round(share[c], 2),
            "share_at_least_next": round(share[c + 1], 2)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--shape-seeds", required=True)
    ap.add_argument("--ticks", default="590,605,620")
    ap.add_argument("--rows", help="also write each tick's (chunk "
                    "lengths, gaps) here, one JSON line a shape seed")
    args = ap.parse_args(argv)

    from benchmark import run as harness
    from conftest import TinyFiles

    _, cell, _, traffic = harness.load_cell(args.workload)
    config = TinyFiles().config(cell["config"])
    ends = [int(t) for t in args.ticks.split(",")]
    for shape_seed in (int(s) for s in args.shape_seeds.split(",")):
        rows = schedule(
            cell, config, {**traffic, "shape_seed": shape_seed}, max(ends)
        )
        if args.rows:
            with open(args.rows, "a") as f:
                f.write(json.dumps({"shape_seed": shape_seed,
                                    "rows": rows}) + "\n")
        print(json.dumps({
            "workload": cell["name"], "shape_seed": shape_seed,
            "chunks_a_tick": dict(sorted(collections.Counter(
                len(c) for c, _ in rows[:min(ends)]).items())),
            "windows": {
                str(n): {
                    reading: classes(rows[:n], shift)
                    for reading, shift in (("own", 0), ("before", 1))
                } for n in ends
            },
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
