"""Device time of a training step under the BatchNorm layers, forward
and backward: the operations whose scope is a ``kBatchNorm.<layer>``
inside the runs of ``jit_chunk_fn``, over the steps those runs made (the
``steps`` of the program's ``singa/trainer.train`` spans). A fusion is
booked to the scope of its root. Moves train_step_ms."""

from benchmark import program_trace


def read(run):
    return program_trace.ms_under_a_step(
        program_trace.of_run(run), "kBatchNorm."
    )
