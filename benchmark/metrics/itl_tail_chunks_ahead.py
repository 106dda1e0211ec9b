"""The prefill chunks the tail of the gaps between tokens waited behind:
the 95th percentile of ``Request.chunks_ahead`` (for each token after a
request's first, the chunks dispatched between the pass that delivered
the token before it and the pass that delivered it; 0 for each token
after the first that one pass delivers, as ``drivers/serve.py`` stamps a
gap of 0 for it) over the tokens of the requests finished in the window, its
traced and untraced seconds alike. Each chunk ahead of a token lengthens
its gap by the chunk's device time, so this is the count behind
serve_itl_p95_ms, read over every tick of the window. None where the
scheduler keeps no such count. Moves serve_itl_p95_ms."""

from benchmark.drivers.serve import percentile


def read(run):
    ahead = [
        n for req in getattr(run["driver"], "done", ())
        for n in (getattr(req, "chunks_ahead", None) or [])[1:]
    ]
    if not ahead:
        return None
    return percentile(ahead, 95)
