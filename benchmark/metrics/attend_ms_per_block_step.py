"""Device time a block step spends in attention, whatever implements
it: the operations under the scope ``attend`` of every block (the gather
of the slots' K and V with the block's own laid over, and
``cache_attend``) inside a run of ``jit__block_step``, mean over the
traced runs. Moves serve_tokens_per_s."""

from benchmark import program_trace


def read(run):
    return program_trace.ms_under_a_run(
        program_trace.of_run(run), "attend", "jit__block_step"
    )
