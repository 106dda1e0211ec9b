"""From a profiler trace to numbers, by the PROGRAM's own names.

``trace_reduce.py`` names device time by the compiler's operation kinds
and idle gaps by the benchmark's ``bench/`` spans. This file reads the
same ``.xplane.pb`` by what the program calls things:

- host spans ``singa/<name>`` (``singa_tpu/obs/span.py``), with their
  attributes (``tick``, ``rid``, ...);
- each device's ``XLA Modules`` events: one per run of a compiled
  program, named after the jitted function (``jit__decode``);
- each device's ``XLA Ops`` events with the scope path that
  ``jax.named_scope`` put into the operation's ``op_name``
  (``jit(_decode)/blk3/attend/gather_kv/gather``).

Two steps, as in ``trace_reduce``: ``load`` turns the file into plain
data (below), the reductions are plain functions over that data, checked
on hand-made traces and on cuts recorded on the chip
(tests/benchmark/data/scopes_*.json).

A trace, as plain data:
    {"host": [[name, start_ns, dur_ns, attrs, thread], ...],
     "devices": [{"name": "/device:TPU:0",
                  "modules": [[name, start_ns, dur_ns], ...],
                  "ops": [[name, start_ns, dur_ns, op_name], ...]}]}

``python3 benchmark/program_trace.py <trace dir>`` prints the tables;
``--json OUT --start-ms A --end-ms B`` keeps a cut.
"""

from __future__ import annotations

import os
import re
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark import trace_reduce  # noqa: E402

PREFIX = "singa/"
UNSCOPED = "unscoped"

#: the scopes the program names (docs: PERF.md section 3). A path's
#: other segments are JAX's own (``jit(_where)``, an einsum's spec, the
#: primitive at the end) and name nothing of ours.
KNOWN = re.compile(
    r"^(k[A-Z]\w*\..+|blk\d+|ln1|qkv|attend|attn_out|ln2|mlp|moe|embed|"
    r"lm_head|kv_write|gather_kv|cache_attend|paged_attention|sample|"
    r"update|flash_fwd|flash_bwd_dq|flash_bwd_dkv)$"
)
#: ``transpose(jvp(`` and the like in front of a segment's own name
_WRAPPED = re.compile(r"^(?:[A-Za-z_]\w*\()+")

_cache: dict[str, dict | None] = {}


# -- loading ------------------------------------------------------------


def trace_dir_of(run: dict) -> str:
    """Where ``run.py`` put this run's trace (it deletes the directory
    only after the readers ran)."""
    return os.path.join(run["driver"].work, "trace")


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) over one protobuf message's bytes: an int
    for a varint, the bytes of a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def op_names(path: str) -> dict:
    """{device plane: {(program id, instruction text): op_name}}.

    On this runtime (libtpu of JAX 0.9) a device operation's ``op_name``
    — the ``jax.named_scope`` path — is no stat of its event: it is the
    ``tf_op`` stat of the event's METADATA (one entry an instruction of
    a compiled program, shared by all its executions), and
    ``jax.profiler.ProfileData`` shows an event's own stats only. So
    this one table is read from the file's protobuf wire format
    (``XSpace.planes[].event_metadata``; tsl/profiler/protobuf/
    xplane.proto): the planes' lines, where the bytes are, are skipped
    by length. An event joins its metadata by the program it ran in and
    its name (the instruction's text)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, stat_names, metadata = "", {}, []
        for num, value in _fields(plane):
            if num == 2:
                name = bytes(value).decode()
            elif num == 5:      # map<int64, XStatMetadata>
                entry = dict(_fields(value))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
            elif num == 4:      # map<int64, XEventMetadata>
                metadata.append(dict(_fields(value)).get(2, b""))
        if not name.startswith("/device:"):
            continue
        table = out.setdefault(name, {})
        for meta in metadata:
            text, program, op_name = "", None, ""
            for num, value in _fields(meta):
                if num == 2:
                    text = bytes(value).decode()
                elif num == 5:  # XStat
                    stat = dict(_fields(value))
                    what = stat_names.get(stat.get(1))
                    if what == "program_id":
                        program = stat.get(3, stat.get(4))
                    elif what == "tf_op":
                        op_name = (
                            bytes(stat[5]).decode() if 5 in stat
                            else stat_names.get(stat.get(7), "")
                        )
            if op_name:
                # ``<op_name>:<op type>``
                table[(program, text)] = op_name.rsplit(":", 1)[0]
    return out


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    names = op_names(path)
    host, devices = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dev = {"name": plane.name, "modules": [], "ops": []}
            table, runs = names.get(plane.name, {}), []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        start, dur = int(e.start_ns), int(e.duration_ns)
                        dev["modules"].append(
                            [module_name(e.name), start, dur]
                        )
                        runs.append((start, start + dur, program_id(e.name)))
            runs.sort()
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                i = 0
                for e in sorted(line.events, key=lambda e: e.start_ns):
                    start = int(e.start_ns)
                    while i < len(runs) and runs[i][1] <= start:
                        i += 1
                    program = (
                        runs[i][2] if i < len(runs) and runs[i][0] <= start
                        else None
                    )
                    dev["ops"].append([
                        trace_reduce.short_name(e.name), start,
                        int(e.duration_ns), table.get((program, e.name), ""),
                    ])
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    host.append([
                        e.name, int(e.start_ns), int(e.duration_ns),
                        {k: v for k, v in dict(e.stats).items()
                         if not k.startswith("_")},
                        line.name,
                    ])
    host.sort(key=lambda e: (e[1], -e[2]))
    return {"host": host, "devices": devices}


def load(trace_dir: str) -> dict | None:
    """The trace under ``trace_dir`` as plain data, parsed once a
    process; None where there is none."""
    if trace_dir not in _cache:
        try:
            path = trace_reduce.find_xplane(trace_dir)
        except FileNotFoundError:
            _cache[trace_dir] = None
        else:
            _cache[trace_dir] = load_xplane(path)
    return _cache[trace_dir]


def of_run(run: dict) -> dict | None:
    """The program's trace of a ``--trace 1`` run, for a metric's
    ``read(run)``."""
    if not run.get("trace"):
        return None
    return load(trace_dir_of(run))


# -- names --------------------------------------------------------------


def module_name(name: str) -> str:
    """``jit__decode(1234567)`` -> ``jit__decode``: the runtime appends
    the program's id."""
    return name.split("(", 1)[0].strip()


def program_id(name: str) -> int | None:
    """``jit__decode(1234567)`` -> 1234567."""
    digits = name.rpartition("(")[2].rstrip(")")
    return int(digits) if digits.isdigit() else None


def scope_path(op_name: str) -> tuple[list[str], str]:
    """``jit(f)/transpose(jvp(blk0))/gather_kv/transpose`` ->
    (["blk0", "gather_kv"], "bwd"): the known scopes of a path, outermost
    first, and the direction. JAX wraps only the outermost scope of a
    path in ``transpose(jvp(...))``, so every segment is unwrapped and
    the direction is taken from the whole path (the primitive
    ``transpose`` at a path's end has no parenthesis)."""
    direction = "bwd" if "transpose(" in op_name else "fwd"
    segments = (
        _WRAPPED.sub("", seg).rstrip(")") for seg in op_name.split("/")
    )
    return [seg for seg in segments if KNOWN.match(seg)], direction


# -- device reductions --------------------------------------------------


def device_ops(dev: dict, module: str | None = None) -> list[list]:
    """One device's operation events, containers left out; with
    ``module``, those that started inside a run of that program."""
    ops = [e for e in dev["ops"] if not trace_reduce.is_container(e[0])]
    if module is None:
        return ops
    runs = sorted(
        (s, s + d) for n, s, d in dev["modules"] if n == module
    )
    out, i = [], 0
    for e in sorted(ops, key=lambda e: e[1]):
        while i < len(runs) and runs[i][1] <= e[1]:
            i += 1
        if i < len(runs) and runs[i][0] <= e[1]:
            out.append(e)
    return out


def scope_seconds(trace: dict, module: str | None = None) -> dict:
    """Device seconds by innermost known scope and direction:
    {scope: {"fwd": s, "bwd": s}}, mean over devices. Each operation
    counts once, under the innermost scope of its path (a fusion carries
    the path of its root); what no scope covers is ``unscoped``."""
    table: dict[str, dict[str, float]] = {}
    n = max(len(trace["devices"]), 1)
    for dev in trace["devices"]:
        for _, _, dur, op_name in device_ops(dev, module):
            known, direction = scope_path(op_name)
            row = table.setdefault(
                known[-1] if known else UNSCOPED, {"fwd": 0.0, "bwd": 0.0}
            )
            row[direction] += dur / n / 1e9
    return table


def seconds_under(trace: dict, scope: str, module: str | None = None) -> float:
    """Device seconds of the operations with ``scope`` anywhere in
    their path (a name, or a prefix ending in ``.``: ``kBatchNorm.``),
    both directions, mean over devices."""
    def hit(seg: str) -> bool:
        return seg.startswith(scope) if scope.endswith(".") else seg == scope

    total = 0.0
    for dev in trace["devices"]:
        for _, _, dur, op_name in device_ops(dev, module):
            if any(hit(seg) for seg in scope_path(op_name)[0]):
                total += dur
    return total / max(len(trace["devices"]), 1) / 1e9


def ms_under_a_run(trace: dict | None, scope: str,
                   module: str) -> float | None:
    """Device milliseconds under ``scope`` inside one run of ``module``,
    the mean over its runs; None where there is no trace, the program
    did not run or no operation carries the scope."""
    if trace is None:
        return None
    runs = len(module_runs(trace, module))
    seconds = seconds_under(trace, scope, module)
    if not runs or not seconds:
        return None
    return 1000.0 * seconds / runs


def ms_under_a_step(trace: dict | None, scope: str,
                    module: str = "jit_chunk_fn") -> float | None:
    """Device milliseconds under ``scope`` a training step: the
    operations inside the runs of the trainer's chunk program, over the
    steps the program says it made (``steps`` of its
    ``singa/trainer.train`` spans)."""
    if trace is None:
        return None
    steps = sum(
        int(h[3].get("steps", 0)) for h in trace["host"]
        if h[0] == PREFIX + "trainer.train"
    )
    seconds = seconds_under(trace, scope, module)
    if not steps or not seconds:
        return None
    return 1000.0 * seconds / steps


def median_run_ms(trace: dict | None, module: str) -> float | None:
    """Median duration on the device of the runs of ``module``."""
    runs = module_runs(trace, module) if trace else []
    return _median(r["dur_ns"] for r in runs) / 1e6 if runs else None


def module_runs(trace: dict, name: str) -> list[dict]:
    """The runs of one compiled program on the first device that ran
    it: [{"start_ns", "dur_ns", "busy_ns"}], ``busy_ns`` the union of
    the operations inside the run."""
    for dev in trace["devices"]:
        runs = sorted((s, d) for n, s, d in dev["modules"] if n == name)
        if not runs:
            continue
        ops = sorted(device_ops(dev), key=lambda e: e[1])
        out, i = [], 0
        for start, dur in runs:
            inside = []
            while i < len(ops) and ops[i][1] < start + dur:
                if ops[i][1] >= start:
                    inside.append(ops[i])
                i += 1
            busy = sum(
                b - a for a, b in trace_reduce.union_intervals(
                    [e[:3] for e in inside]
                )
            )
            out.append({"start_ns": start, "dur_ns": dur, "busy_ns": busy})
        return out
    return []


# -- host reductions ----------------------------------------------------


def spans(trace: dict) -> list[dict]:
    """The ``singa/`` spans with their place in the nesting: [{"name"
    (without the prefix), "start_ns", "dur_ns", "attrs", "parent" (index
    or None), "children" (indices), "self_ns"}], in order of start.
    Nesting is by containment on one thread."""
    out = [
        {"name": n[len(PREFIX):], "start_ns": s, "dur_ns": d, "attrs": a,
         "thread": t, "parent": None, "children": []}
        for n, s, d, a, t in sorted(
            trace["host"], key=lambda e: (e[1], -e[2])
        )
    ]
    stacks: dict[str, list[int]] = {}
    for i, sp in enumerate(out):
        stack = stacks.setdefault(sp["thread"], [])
        while stack and (
            out[stack[-1]]["start_ns"] + out[stack[-1]]["dur_ns"]
            < sp["start_ns"] + sp["dur_ns"]
        ):
            stack.pop()
        if stack:
            sp["parent"] = stack[-1]
            out[stack[-1]]["children"].append(i)
        stack.append(i)
    for sp in out:
        covered = trace_reduce.union_intervals([
            [None, out[c]["start_ns"], out[c]["dur_ns"]]
            for c in sp["children"]
        ])
        sp["self_ns"] = sp["dur_ns"] - sum(b - a for a, b in covered)
    return out


def inside(nested: list[dict], i: int):
    """The indices of every span nested in ``nested[i]``, at any depth
    (``nested`` is what ``spans`` returned)."""
    for c in nested[i]["children"]:
        yield c
        yield from inside(nested, c)


def host_self(trace: dict) -> dict:
    """Per span name: {"n", "total_s", "self_s"} — a span's self time is
    its duration less what its child spans cover."""
    table: dict[str, dict] = {}
    for sp in spans(trace):
        row = table.setdefault(
            sp["name"], {"n": 0, "total_s": 0.0, "self_s": 0.0}
        )
        row["n"] += 1
        row["total_s"] += sp["dur_ns"] / 1e9
        row["self_s"] += sp["self_ns"] / 1e9
    return table


def gaps_by_span(trace: dict) -> dict:
    """The devices' idle gaps by the innermost ``singa/`` span covering
    the middle of each: {span name: seconds}, mean over devices; a gap
    no span covers under ``host_unannotated``."""
    marks = sorted(
        (s, s + d, n[len(PREFIX):]) for n, s, d, _, _ in trace["host"]
    )
    table: dict[str, float] = {}
    n = max(len(trace["devices"]), 1)
    for dev in trace["devices"]:
        merged = trace_reduce.union_intervals(
            [e[:3] for e in device_ops(dev)]
        )
        for (_, end), (start, _) in zip(merged, merged[1:]):
            mid, name = (end + start) // 2, "host_unannotated"
            for a, b, what in marks:
                if a > mid:
                    break
                if mid < b:
                    name = what  # the latest started that covers it
            table[name] = table.get(name, 0.0) + (start - end) / n / 1e9
    return table


# -- by hand --------------------------------------------------------------


def cut(trace: dict, start_ns: int, end_ns: int, min_ns: int = 0) -> dict:
    """A cut small enough to keep with the tests: the operations that
    started in [start_ns, end_ns) and lasted at least ``min_ns`` (a
    step holds thousands of async starts and dones of a few
    nanoseconds), with the program runs and host spans that overlap the
    window, whole."""
    def overlaps(start, dur):
        return start < end_ns and start + dur > start_ns

    return {
        "host": [h for h in trace["host"] if overlaps(h[1], h[2])],
        "devices": [{
            "name": dev["name"],
            "modules": [m for m in dev["modules"] if overlaps(m[1], m[2])],
            "ops": [
                o for o in dev["ops"]
                if start_ns <= o[1] < end_ns and o[2] >= min_ns
            ],
        } for dev in trace["devices"]],
    }


def unscoped_rows(trace: dict, module: str | None, top: int = 8) -> list:
    """What ``unscoped`` holds: seconds by kind of operation and
    ``op_name`` (numbers collapsed), the largest first."""
    table: dict[tuple, float] = {}
    for dev in trace["devices"]:
        for name, _, dur, op_name in device_ops(dev, module):
            if not scope_path(op_name)[0]:
                key = (trace_reduce.op_kind(name),
                       re.sub(r"\d+", "N", op_name) or "(no op_name)")
                table[key] = table.get(key, 0.0) + dur / 1e9
    n = max(len(trace["devices"]), 1)
    rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
    return [(kind, op_name, f"{v / n:.6f}") for (kind, op_name), v in rows]


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("--module", help="only operations inside this program")
    ap.add_argument("--json", help="keep a cut of the trace here")
    ap.add_argument("--start-ms", type=float, default=0.0)
    ap.add_argument("--end-ms", type=float, default=50.0)
    ap.add_argument("--min-ns", type=int, default=1000)
    args = ap.parse_args(argv)
    trace = load(args.trace_dir)
    if trace is None:
        raise SystemExit(f"no .xplane.pb under {args.trace_dir}")

    def table(title, rows):
        print(f"\n{title}")
        for row in rows:
            print("  " + "  ".join(str(c) for c in row))

    by_scope = scope_seconds(trace, args.module)
    total = sum(r["fwd"] + r["bwd"] for r in by_scope.values()) or 1.0
    table("device seconds by innermost scope (fwd, bwd, share)", [
        (k, f"{r['fwd']:.6f}", f"{r['bwd']:.6f}",
         f"{100 * (r['fwd'] + r['bwd']) / total:.2f}%")
        for k, r in sorted(
            by_scope.items(), key=lambda kv: -(kv[1]["fwd"] + kv[1]["bwd"])
        )
    ])
    table("what unscoped holds (kind, op_name, s)",
          unscoped_rows(trace, args.module))
    names = sorted({m[0] for d in trace["devices"] for m in d["modules"]})
    table("runs of each program (n, median ms, median busy ms)", [
        (n, len(r), f"{_median(x['dur_ns'] for x in r) / 1e6:.3f}",
         f"{_median(x['busy_ns'] for x in r) / 1e6:.3f}")
        for n in names for r in [module_runs(trace, n)]
    ])
    table("host spans (n, total s, self s)", [
        (k, r["n"], f"{r['total_s']:.6f}", f"{r['self_s']:.6f}")
        for k, r in sorted(host_self(trace).items())
    ])
    table("device idle gaps by span (s)", [
        (k, f"{v:.6f}")
        for k, v in sorted(gaps_by_span(trace).items(), key=lambda kv: -kv[1])
    ])
    if args.json:
        with open(args.json, "w") as f:
            json.dump(cut(
                trace, int(args.start_ms * 1e6), int(args.end_ms * 1e6),
                args.min_ns,
            ), f)
    return 0


def _median(values) -> float:
    values = list(values)
    return statistics.median(values) if values else 0.0


if __name__ == "__main__":
    raise SystemExit(main())
