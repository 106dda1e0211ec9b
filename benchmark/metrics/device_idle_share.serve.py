"""Share of the traced window in which no operation ran on the device:
1 - (union of device-operation intervals) / window, from the profiler's
trace of the first seconds of the window. Moves serve_tokens_per_s."""


def read(run):
    trace = run["trace"]
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
