"""Device time a decode tick spends in the paged kernel, which reads the
slots' live K and V blocks in place: the operations under the scope
``paged_attention`` inside a run of ``jit__decode`` (one Mosaic call a
layer), mean over the traced runs. Part of ``attend_ms_per_tick``.
Silent where the engine chose the gather path (``gather_kv`` and
``cache_attend`` do the work there). Moves serve_tokens_per_s."""

from benchmark import program_trace


def read(run):
    return program_trace.ms_under_a_run(
        program_trace.of_run(run), "paged_attention", "jit__decode"
    )
