"""The served step's share of the chip's bf16 peak: forward FLOPs of
every token the window processed — prompt tokens prefilled and tokens
decoded, each at 2 x matrix-product parameters plus attention over the
positions really cached (benchmark/flops.py) — a second, over chips
times the peak of benchmark/peaks.json. Reads low: decode is bound by
bandwidth. It is the bound that stays when a kernel goes. Moves
serve_tokens_per_s."""

from benchmark import flops


def read(run):
    c = run["counters"]
    if not c.get("model_flops") or not c.get("window_s"):
        return None
    return flops.mfu_percent(
        c["model_flops"] / c["window_s"], run["chips"], run["device_kind"]
    )
