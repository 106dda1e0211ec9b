"""Host cost of handing the trainer a chunk: the benchmark's own span
around each ``Trainer.train_chunk`` call until it returns (enqueue, not
device time), over the chunk's steps. Moves train_step_ms."""


def read(run):
    rows = run["spans"].named("train_chunk")
    steps = sum(r[3]["steps"] for r in rows)
    if not steps:
        return None
    return 1000.0 * sum(r[2] - r[1] for r in rows) / steps
