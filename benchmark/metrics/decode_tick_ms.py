"""Time of one decode tick as a caller sees it: the benchmark's own span
around ``Scheduler.tick()``, median over the window's ticks in which the
engine decoded once and ran no prefill chunk (the decode program has one
fixed shape whatever the number of live slots). It holds the dispatch,
the device's work, the pull of the tokens and the scheduler's
bookkeeping, wherever the program puts its waits. Moves
serve_itl_p95_ms."""

import statistics


def read(run):
    rows = [
        r for r in run["spans"].named("tick")
        if r[3].get("decodes") == 1 and r[3].get("prefill_chunks") == 0
    ]
    if not rows:
        return None
    return 1000.0 * statistics.median(r[2] - r[1] for r in rows)
