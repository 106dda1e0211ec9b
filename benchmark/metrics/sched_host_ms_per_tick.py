"""Host time of one scheduler tick: the program's own ``singa/sched.tick``
span less the ``singa/sched.pull`` inside it (the one place a tick waits
for the device), median over the traced ticks that dispatched one decode
and prefilled nothing. What is left is admission, the dispatch until the
call returns, the fan-out of the tokens and retirement. Moves
serve_tokens_per_s."""

import statistics

from benchmark import program_trace


def read(run):
    trace = program_trace.of_run(run)
    if trace is None:
        return None
    nested = program_trace.spans(trace)
    host_ns = []
    for i, tick in enumerate(nested):
        if tick["name"] != "sched.tick":
            continue
        held = [nested[j] for j in program_trace.inside(nested, i)]
        names = [sp["name"] for sp in held]
        if names.count("sched.dispatch") != 1 or "sched.prefill" in names:
            continue
        pull = sum(sp["dur_ns"] for sp in held if sp["name"] == "sched.pull")
        host_ns.append(tick["dur_ns"] - pull)
    if not host_ns:
        return None
    return statistics.median(host_ns) / 1e6
