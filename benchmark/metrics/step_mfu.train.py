"""The whole training step's share of the chip's bf16 peak: the
benchmark's own FLOP count of a step (benchmark/flops.py, 3x forward,
matrix products only) times steps a second, over chips times the peak of
benchmark/peaks.json. Moves train_step_ms."""

from benchmark import flops


def read(run):
    step_ms = run["end_to_end"].get("train_step_ms")
    if not step_ms:
        return None
    per_s = run["driver"].step_flops() * 1000.0 / step_ms
    return flops.mfu_percent(per_s, run["chips"], run["device_kind"])
