"""Host time of one ``engine.prefill_chunk()`` call, median: the
benchmark's own span around the call until it returns. The call does not
wait for the device, so this is what the scheduler's loop pays to hand a
chunk over, not the chunk's device time (that waits for named scopes
inside the program). A tick with a chunk in it is the tail of the gaps
between tokens. Moves serve_itl_p95_ms."""

import statistics


def read(run):
    rows = run["spans"].named("prefill_chunk")
    if not rows:
        return None
    return 1000.0 * statistics.median(r[2] - r[1] for r in rows)
