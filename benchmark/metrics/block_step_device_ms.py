"""Time of one block step on the device: median duration of the runs of
``jit__block_step`` (one pass over every live slot's current block, a
model generated by diffusion over blocks) on the device's ``XLA
Modules`` line in the traced window. A caller's gap between tokens is
one or two of these, and a prefill chunk where a tick holds one. Moves
serve_itl_p95_ms."""

from benchmark import program_trace


def read(run):
    return program_trace.median_run_ms(
        program_trace.of_run(run), "jit__block_step"
    )
