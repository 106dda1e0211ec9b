"""Time of one decode program on the device: median duration of the runs
of ``jit__decode`` on the device's ``XLA Modules`` line in the traced
window. With ``sched_host_ms_per_tick`` it adds up to ``decode_tick_ms``.
Moves serve_itl_p95_ms."""

from benchmark import program_trace


def read(run):
    return program_trace.median_run_ms(
        program_trace.of_run(run), "jit__decode"
    )
