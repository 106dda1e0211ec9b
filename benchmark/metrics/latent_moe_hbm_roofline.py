"""The latent expert layers' share of the chip's memory bandwidth in a
decode tick: the bytes they had to read a tick over the time they took.

Bytes (``hbm_nemotron_h.latent_moe_bytes_a_tick``): the held experts
that at least one live token was routed to (the program's
``experts_hit`` counter, summed over the passes that the traced window
read and over the expert layers, over those passes:
``run["traced_counters"]``) times an expert's TWO matrices of latent x
width, plus in every expert layer the shared expert, the router and the
two latent projections: a lower bound whatever computes the layer, so
the share cannot pass 100 %. Time: ``moe_ms_per_tick``'s (device time
under the scope ``moe`` inside a run of ``jit__decode``). Peak:
``benchmark/peaks_hbm.json``. Moves serve_tokens_per_s."""

from benchmark import hbm_nemotron_h, program_trace


def read(run):
    c = run.get("traced_counters") or {}
    ms = program_trace.ms_under_a_run(
        program_trace.of_run(run), "moe", "jit__decode"
    )
    ticks = c.get("decode_ticks")
    if not ms or not ticks or not c.get("experts_hit"):
        return None
    moved = hbm_nemotron_h.latent_moe_bytes_a_tick(
        run["config"], c["experts_hit"] / ticks
    )
    return hbm_nemotron_h.share_of_hbm_peak(run, moved, ms)
