"""Tokens a slot's pass delivers: the scheduler's ``tokens_delivered``
(tokens that joined the contiguous unmasked prefix of their request)
over its ``block_passes`` (one a live slot a block step, commits
included). A whole block of B tokens takes ``steps`` denoising passes and
a commit, so B / (steps + 1) is what a setting allows; the block that
holds a prompt's tail has fewer positions to fill, and a request's last
block is never committed. Moves serve_tokens_per_s."""


def read(run):
    c = run["counters"]
    if not c.get("block_passes"):
        return None
    return c["tokens_delivered"] / c["block_passes"]
