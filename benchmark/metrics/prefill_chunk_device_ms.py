"""Time of one prefill chunk on the device: median duration of the runs
of ``jit__prefill`` on the device's ``XLA Modules`` line in the traced
window. A tick with a chunk in it lasts this much longer, and those
ticks are the tail of the gaps between tokens (``prefill_chunk_ms`` is
only the host's hand-over). Moves serve_itl_p95_ms."""

from benchmark import program_trace


def read(run):
    return program_trace.median_run_ms(
        program_trace.of_run(run), "jit__prefill"
    )
