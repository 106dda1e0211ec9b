"""The expert layers' share of the chip's memory bandwidth in a decode
tick of a model that holds a SHARE of its experts: the bytes they had to
read a tick over the time they took.

Bytes (``bytes_a_tick``): the held experts that at least one live token
was routed to (the program's ``experts_hit`` counter, summed over the
passes that the traced window read and over the expert layers, over
those passes: ``run["traced_counters"]``, so that bytes and time cover
the same ticks) times an expert's
three matrices, plus in every expert layer the shared expert's three
and the router's one, in the weights' type. A lower bound on what any
implementation reads — a held expert that no token chose need not be
touched, one that a token chose must be read whole, and the shared
expert and the router run on every token — so the share cannot pass
100 % however the layer is computed. Time: ``moe_ms_per_tick``'s (device
time under the scope ``moe`` inside a run of ``jit__decode``). Peak:
``benchmark/peaks_hbm.json`` (``benchmark/hbm.py``). Moves
serve_tokens_per_s."""

from benchmark import hbm, program_trace


def bytes_a_tick(config: dict, experts_hit_a_tick: float) -> float:
    """What the expert layers of the whole model must read in one tick
    in which ``experts_hit_a_tick`` held experts, summed over the expert
    layers, were chosen by some live token."""
    size = hbm.DTYPE_BYTES[config["torch_dtype"]]
    d = config["hidden_size"]
    expert = 3 * d * config["moe_intermediate_size"] * size
    router = d * config["n_router_outputs"] * size
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    return experts_hit_a_tick * expert + layers * (
        config["n_shared_experts"] * expert + router
    )


def read(run):
    c = run.get("traced_counters") or {}
    ms = program_trace.ms_under_a_run(
        program_trace.of_run(run), "moe", "jit__decode"
    )
    ticks = c.get("decode_ticks")
    if not ms or not ticks or not c.get("experts_hit"):
        return None
    return 100.0 * bytes_a_tick(run["config"], c["experts_hit"] / ticks) / (
        ms / 1000.0 * run["chips"] * hbm.peak_bytes_per_s(run["device_kind"])
    )
