"""Tokens a held expert draws in a decode tick: the token-expert pairs
that the ticks' live tokens routed to the experts held here (the
scheduler's ``held_pairs``, summed over the passes read and the expert
layers), over those passes, the experts held and the expert layers. The
deployment the cell stands for gives a held expert 32 times the tokens
(32 chips' requests meet at each); here it is slots x experts per token
over the router's width, and an expert that draws none is not read.
Prefill chunks are counted apart (``chunk_held_pairs`` in the counters).
Moves serve_tokens_per_s."""


def read(run):
    c, config = run["counters"], run["config"]
    if not c.get("decode_ticks") or c.get("held_pairs") is None:
        return None
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    return c["held_pairs"] / (
        c["decode_ticks"] * config["n_routed_experts"] * layers
    )
