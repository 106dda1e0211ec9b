"""Latent attention's share of the chip's memory bandwidth in a decode
tick: the latent rows the tick's queries had to read over the time the
tick spent in attention.

Bytes (``bytes_a_tick``): the live slots' cached positions, the one each
writes in the tick among them (the program's ``cache_rows`` counter,
summed over the passes that the traced window read, over those passes:
``run["traced_counters"]``), times one latent — the
K/V latent and the rotary key, ``kv_lora_rank + qk_rope_head_dim``
values in the pool's type — in every layer. A lower bound whatever
attends: a row within a live query's reach must be read once a layer,
the zeros that fill a pool's row to whole tiles, a dense gathered view
and the rows beyond a sequence's end need not be; so the share cannot
pass 100 %. Time: ``attend_ms_per_tick``'s (device time under the scope
``attend`` inside a run of ``jit__decode``: the write, the gather or a
paged kernel, the products). Peak: ``benchmark/peaks_hbm.json``. Moves
serve_tokens_per_s."""

from benchmark import hbm, program_trace


def bytes_a_tick(config: dict, rows_a_tick: float) -> float:
    """What attention must read in one tick whose live slots hold
    ``rows_a_tick`` cached positions between them."""
    row = (
        config["kv_lora_rank"] + config["qk_rope_head_dim"]
    ) * hbm.DTYPE_BYTES[config["torch_dtype"]]
    return rows_a_tick * row * config["num_hidden_layers"]


def read(run):
    c = run.get("traced_counters") or {}
    ms = program_trace.ms_under_a_run(
        program_trace.of_run(run), "attend", "jit__decode"
    )
    ticks = c.get("decode_ticks")
    if not ms or not ticks or not c.get("cache_rows"):
        return None
    return 100.0 * bytes_a_tick(run["config"], c["cache_rows"] / ticks) / (
        ms / 1000.0 * run["chips"] * hbm.peak_bytes_per_s(run["device_kind"])
    )
