"""Device time a prefill chunk spends in the expert layers: the
operations under the scope ``moe`` of every expert block (router, the
held experts' products in whichever form the pass took, the combine,
the shared expert) inside a run of ``jit__prefill``, mean over the
traced runs. Moves serve_tokens_per_s."""

from benchmark import program_trace


def read(run):
    return program_trace.ms_under_a_run(
        program_trace.of_run(run), "moe", "jit__prefill"
    )
