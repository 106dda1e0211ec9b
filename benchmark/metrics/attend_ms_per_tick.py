"""Device time a decode tick spends in attention, whatever implements
it: the operations under the scope ``attend`` of every block (the write
of the new K and V, the gather and ``cache_attend``, or the paged
kernel) inside a run of ``jit__decode``, mean over the traced runs.
Moves serve_tokens_per_s."""

from benchmark import program_trace


def read(run):
    return program_trace.ms_under_a_run(
        program_trace.of_run(run), "attend", "jit__decode"
    )
