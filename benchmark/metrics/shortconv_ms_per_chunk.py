"""Device time a prefill chunk spends in the short-convolution layers:
the operations under the scope ``shortconv`` of every such block
(``in_proj``, ``conv`` from the slot's tail, ``out_proj``) inside a run
of ``jit__prefill``, mean over the traced runs. Moves
serve_tokens_per_s."""

from benchmark import hbm_nemotron_h, program_trace


def read(run):
    return hbm_nemotron_h.ms_under_a_run(
        program_trace.of_run(run), "shortconv", "jit__prefill"
    )
