"""Device time a block step spends in the expert layers: the operations
under the scope ``moe`` of every block (router, experts, combine) inside
a run of ``jit__block_step``, mean over the traced runs. Moves
serve_tokens_per_s."""

from benchmark import program_trace


def read(run):
    return program_trace.ms_under_a_run(
        program_trace.of_run(run), "moe", "jit__block_step"
    )
