"""Device time a decode tick spends gathering the slots' K and V out of
the paged pools into dense views: the operations under the scope
``gather_kv`` inside a run of ``jit__decode``, mean over the traced
runs. Silent once the fused kernel reads the blocks in place. Moves
serve_tokens_per_s."""

from benchmark import program_trace


def read(run):
    return program_trace.ms_under_a_run(
        program_trace.of_run(run), "gather_kv", "jit__decode"
    )
