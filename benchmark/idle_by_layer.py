"""The device's idle time of a traced run, split by the program's layers.

Each idle gap of the device is named by the innermost ``singa/`` span
covering its middle (``program_trace.gaps_by_span``'s rule); a layer's
share is the gaps named by its spans (``engine.*``, ``sched.*``) over the
traced window. The shares of all layers add up to the gaps between the
trace's first operation and its last, less those no span covers.
"""

from __future__ import annotations

from benchmark import program_trace, trace_reduce


def gaps_by_span(trace: dict) -> dict:
    """``program_trace.gaps_by_span`` in one sweep: {span name: seconds},
    the same table. That one walks the spans from the first for every
    gap, minutes on a serving cell's 3 s; here the gaps, in order, keep
    the spans that have started on a stack, popping those that have
    ended from its top: the top is then the latest started of those
    covering the gap, the one that table names it by."""
    marks = sorted(
        (s, s + d, n[len(program_trace.PREFIX):])
        for n, s, d, _, _ in trace["host"]
    )
    table: dict[str, float] = {}
    n = max(len(trace["devices"]), 1)
    for dev in trace["devices"]:
        merged = trace_reduce.union_intervals(
            [e[:3] for e in program_trace.device_ops(dev)]
        )
        stack: list[tuple] = []
        i = 0
        for (_, end), (start, _) in zip(merged, merged[1:]):
            mid = (end + start) // 2
            while i < len(marks) and marks[i][0] <= mid:
                stack.append(marks[i])
                i += 1
            while stack and stack[-1][1] <= mid:
                stack.pop()  # ended: it covers no later gap either
            name = stack[-1][2] if stack else "host_unannotated"
            table[name] = table.get(name, 0.0) + (start - end) / n / 1e9
    return table


def share(run: dict, layer: str) -> float | None:
    """Percent of the traced window in which the device idled under a
    span named ``<layer>.*``; None where there is no trace or the
    program names no such span."""
    trace = program_trace.of_run(run)
    window_s = (run.get("trace") or {}).get("window_s")
    prefix = layer + "."
    if trace is None or not window_s or not any(
        h[0].startswith(program_trace.PREFIX + prefix) for h in trace["host"]
    ):
        return None
    idle_s = sum(
        s for name, s in gaps_by_span(trace).items()
        if name.startswith(prefix)
    )
    return 100.0 * idle_s / window_s
