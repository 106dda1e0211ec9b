"""From a profiler trace to numbers: device busy time, the operations
that took most of it, and the idle gaps named by what the host was doing.

Two steps, so that the second can be checked on a small recorded trace
(tests/benchmark/data): ``load_xplane`` turns the profiler's
``.xplane.pb`` into plain lists with ``jax.profiler.ProfileData``;
``summarize`` reduces those lists.

A trace, as plain data:
    {"planes": [{"name": str, "lines": [{"name": str,
        "events": [[name, start_ns, duration_ns], ...]}]}]}

Device planes are named ``/device:TPU:<n>``. Their ``XLA Ops`` line
holds one event per executed operation; where a device plane has no such
line (another backend's layout), every line but the step and module
summaries counts. Host planes hold the benchmark's own ``bench/<name>``
annotations (``run.py`` ``Spans``), on the same clock.
"""

from __future__ import annotations

import glob
import os

#: operations that only contain others (a scan's ``while`` holds every
#: step's operations as events of their own; a ``lax.cond`` runs as
#: ``cond.N.clone``): counting them would hide the bubbles between their
#: children and double every second
CONTAINERS = ("while", "conditional", "cond", "call")

#: lines of a device plane that summarize others and would double count
SUMMARY_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                 "Framework Name Scope", "Source code", "Async XLA Ops")
TOP = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """An event's name as the reduction keeps it: the operation's own
    name, without the HLO text the profiler appends (``%fusion.12 =
    f32[...] fusion(...)`` -> ``fusion.12``)."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def op_kind(name: str) -> str:
    """``fusion.2675`` -> ``fusion``: the compiler numbers its
    operations anew with every compile, and a step has thousands, so the
    table of where the time went is kept by kind."""
    stem, _, tail = name.rpartition(".")
    return stem if stem and tail.isdigit() else name


def is_container(name: str) -> bool:
    return name.split(".", 1)[0] in CONTAINERS


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device:
                events = [
                    [short_name(e.name), int(e.start_ns), int(e.duration_ns)]
                    for e in line.events
                ]
            else:
                # host threads hold thousands of runtime events: only
                # the benchmark's own annotations are wanted
                events = [
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events if e.name.startswith("bench/")
                ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_op_events(plane: dict) -> list[list]:
    """The per-operation events of one device plane, containers left
    out."""
    for line in plane["lines"]:
        if line["name"] == "XLA Ops":
            found = line["events"]
            break
    else:
        found = []
        for line in plane["lines"]:
            if line["name"] not in SUMMARY_LINES:
                found += line["events"]
    return [e for e in found if not is_container(e[0])]


def union_intervals(events: list[list]) -> list[tuple[int, int]]:
    """Merged [start, end) intervals of the events, in order."""
    merged: list[list[int]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return [(a, b) for a, b in merged]


def _host_annotations(trace: dict) -> list[list]:
    out = []
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            out += [e for e in line["events"] if e[0].startswith("bench/")]
    return sorted(out, key=lambda e: e[1])


def _covering(annotations: list[list], t: int) -> str:
    """The innermost (latest started) annotation that covers ``t``."""
    name = "host_unannotated"
    for n, start, dur in annotations:
        if start > t:
            break
        if t < start + dur:
            name = n[len("bench/"):]
    return name


def summarize(trace: dict, chips: int) -> dict:
    """-> {"busy_s" (mean over device planes), "span_s" (first to last
    device event), "device_ops": [[kind of operation, seconds], ...]
    (top TOP, mean over chips), "idle_gaps": [[what the host was doing, seconds], ...]}.
    Raises where no operation ran on a device."""
    device_planes = [
        p for p in trace["planes"] if p["name"].startswith("/device:TPU")
    ] or [p for p in trace["planes"] if p["name"].startswith("/device:")]
    annotations = _host_annotations(trace)
    busy_ns, op_ns, gap_ns = [], {}, {}
    first, last = None, None
    for plane in device_planes:
        events = device_op_events(plane)
        if not events:
            continue
        merged = union_intervals(events)
        busy_ns.append(sum(b - a for a, b in merged))
        first = merged[0][0] if first is None else min(first, merged[0][0])
        last = merged[-1][1] if last is None else max(last, merged[-1][1])
        for name, _, dur in events:
            kind = op_kind(name)
            op_ns[kind] = op_ns.get(kind, 0) + dur
        for (_, end), (start, _) in zip(merged, merged[1:]):
            what = _covering(annotations, (end + start) // 2)
            gap_ns[what] = gap_ns.get(what, 0) + (start - end)
    if not busy_ns:
        raise RuntimeError("the trace holds no operation on a device")
    n = max(len(busy_ns), 1)
    # a plane that ran nothing is a chip that sat idle: it counts as 0
    busy_s = sum(busy_ns) / max(chips, n) / 1e9

    def top(table: dict) -> list[list]:
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[k, v / n / 1e9] for k, v in rows]

    return {
        "busy_s": busy_s,
        "span_s": (last - first) / 1e9,
        "device_ops": top(op_ns),
        "idle_gaps": top(gap_ns),
    }


def describe(trace: dict, head: int = 8) -> str:
    """A reader's view of a trace: planes, lines, counts and the first
    events — what to look at by hand before trusting ``summarize``."""
    rows = []
    for plane in trace["planes"]:
        rows.append(f"plane {plane['name']}")
        for line in plane["lines"]:
            ev = line["events"]
            rows.append(f"  line {line['name']!r}: {len(ev)} events")
            for name, start, dur in ev[:head]:
                rows.append(f"    {start} +{dur}ns {name[:90]}")
    return "\n".join(rows)


def main(argv=None) -> int:
    """``python3 benchmark/trace_reduce.py <trace dir> [--json OUT
    --max-events N]``: describe a trace for a look by hand, and
    optionally keep a cut of it as plain data (tests/benchmark/data)."""
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("--json")
    ap.add_argument("--max-events", type=int, default=400)
    args = ap.parse_args(argv)
    trace = load_xplane(find_xplane(args.trace_dir))
    print(describe(trace))
    print(json.dumps(summarize(trace, 1), indent=1))
    if args.json:
        cut = {"planes": [
            {"name": p["name"], "lines": [
                {"name": l["name"], "events": l["events"][: args.max_events]}
                for l in p["lines"]
            ]} for p in trace["planes"]
        ]}
        with open(args.json, "w") as f:
            json.dump(cut, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
