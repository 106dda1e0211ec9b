"""Host time of one prefill chunk's hand-over, as the program names it:
the median duration of the traced ``singa/engine.prefill`` spans
(``Engine.prefill_chunk``: the chunk's buffer, its scalars and the
dispatch of ``jit__prefill`` until the call returns). The work that
``prefill_chunk_ms`` times from outside, through a wrapper a traced run
alone puts around the engine. None where the program names no such span.
Moves serve_tokens_per_s."""

import statistics

from benchmark import program_trace


def read(run):
    trace = program_trace.of_run(run)
    if trace is None:
        return None
    durations = [
        h[2] for h in trace["host"]
        if h[0] == program_trace.PREFIX + "engine.prefill"
    ]
    if not durations:
        return None
    return statistics.median(durations) / 1e6
