"""The expert layers' share of the chip's memory bandwidth in a block
step: the bytes they HAD to read a pass over the time they took.

Bytes: per layer, the experts that at least one live token was routed to
(the program's ``experts_hit`` counter, summed over the passes that the
traced window read and over the layers, over those passes:
``run["traced_counters"]``, so that bytes and time cover the same
passes) times an expert's three matrices (3 x hidden x
expert width, in the weights' type), plus the router's. That is a lower
bound on what any implementation of the layer reads — an expert no token
chose need not be touched, one that a token chose must be read whole —
so the share cannot pass 100 % however the layer is computed. Time:
``moe_ms_per_block_step``'s (device time under the scope ``moe`` inside
a run of ``jit__block_step``). Peak: ``benchmark/peaks_hbm.json``. Moves
serve_tokens_per_s."""

import json
import os

from benchmark import flops, program_trace

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peak_bytes_per_s(device_kind: str) -> float:
    """HBM bytes/s of one chip from ``peaks_hbm.json`` (beside
    ``peaks.json``), keyed by a substring of ``device_kind``; an unknown
    kind is an error. ``run.py`` refuses to run off a TPU, so the kind
    ``cpu`` is only ever the CPU rehearsal's, whose shares are plumbing
    and not numbers: it is handed the table's first row."""
    path = os.path.join(os.path.dirname(flops.__file__), "peaks_hbm.json")
    with open(path) as f:
        table = json.load(f)
    for row in table["peaks"]:
        if row["match"] in device_kind.lower() or device_kind == "cpu":
            return float(row["hbm_bytes_per_s"])
    raise ValueError(
        f"no memory bandwidth on record for device_kind {device_kind!r}: "
        "add it to benchmark/peaks_hbm.json with its source"
    )


def bytes_a_pass(config: dict, experts_hit_a_layer: float) -> float:
    """What the expert layers of the whole model must read in one pass
    in which ``experts_hit_a_layer`` experts of a layer (mean) were
    chosen by some token."""
    size = _BYTES[config["torch_dtype"]]
    d = config["hidden_size"]
    expert = 3 * d * config["moe_intermediate_size"] * size
    router = d * config["num_experts"] * size
    return config["num_hidden_layers"] * (
        experts_hit_a_layer * expert + router
    )


def read(run):
    c = run.get("traced_counters") or {}
    ms = program_trace.ms_under_a_run(
        program_trace.of_run(run), "moe", "jit__block_step"
    )
    steps = c.get("decode_ticks")
    if not ms or not steps or not c.get("experts_hit"):
        return None
    config = run["config"]
    hit = c["experts_hit"] / (steps * config["num_hidden_layers"])
    return 100.0 * bytes_a_pass(config, hit) / (
        ms / 1000.0 * run["chips"] * peak_bytes_per_s(run["device_kind"])
    )
