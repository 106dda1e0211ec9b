"""Device time a block step spends gathering the slots' K and V out of
the paged pools into dense views (the block's own laid over them): the
operations under the scope ``gather_kv`` inside a run of
``jit__block_step``, mean over the traced runs. Part of
``attend_ms_per_block_step``. Silent once a paged kernel reads the
blocks in place. Moves serve_tokens_per_s."""

from benchmark import program_trace


def read(run):
    return program_trace.ms_under_a_run(
        program_trace.of_run(run), "gather_kv", "jit__block_step"
    )
