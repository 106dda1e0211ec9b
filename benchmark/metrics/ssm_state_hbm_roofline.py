"""The Mamba-2 layers' share of the chip's memory bandwidth in a decode
tick: the bytes they had to move a tick over the time they took.

Bytes (``hbm_nemotron_h.state_bytes_a_tick``): the live slots (the
program's ``state_slots_live`` counter, summed over the passes that the
traced window read, over those passes: ``run["traced_counters"]``) times, in every Mamba layer, the float32 state read
and written and the convolution's tail, plus each layer's weights: a
lower bound whatever computes the step, so the share cannot pass 100 %.
Time: ``mamba_ms_per_tick``'s. Peak: ``benchmark/peaks_hbm.json``. Moves
serve_tokens_per_s."""

from benchmark import hbm_nemotron_h, program_trace


def read(run):
    c = run.get("traced_counters") or {}
    ms = hbm_nemotron_h.ms_under_a_run(
        program_trace.of_run(run), "mamba", "jit__decode"
    )
    ticks = c.get("decode_ticks")
    if not ms or not ticks or not c.get("state_slots_live"):
        return None
    moved = hbm_nemotron_h.state_bytes_a_tick(
        run["config"], c["state_slots_live"] / ticks
    )
    return hbm_nemotron_h.share_of_hbm_peak(run, moved, ms)
