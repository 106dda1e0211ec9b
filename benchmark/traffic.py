"""The one traffic generator for serving cells: a data file of
parameters in, a list of requests out.

A traffic file (``benchmark/traffic/<name>.json``) with ``"driver":
"serve"`` gives: ``callers`` (``drivers/serve.py`` is a closed loop: each
caller sends its next request when its last one finished),
``prompt_len`` and ``output_len`` as clipped log-normals (``median``,
``sigma``, ``min``, ``max``), ``pool`` (how many distinct requests a run can draw on) and
``greedy`` (temperature 0).

Every seed gets the SAME requests in the SAME order — the ``pool``
evenly spaced quantiles of each distribution, paired and ordered by
permutations fixed in the file (``shape_seed``) — with other token ids.
The seed changes what is said, not how much work there is or which
request meets which: in a closed loop the order alone moved the tokens
per second by 4 % between seeds (193.8 to 201.5; my chip runs, PR 23),
ten times what two runs of one seed differ by.
"""

from __future__ import annotations

import math
import statistics

import numpy as np


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a clipped log-normal."""
    nd = statistics.NormalDist()
    mu = math.log(dist["median"])
    q = [nd.inv_cdf((i + 0.5) / n) for i in range(n)]
    raw = np.exp(mu + dist["sigma"] * np.asarray(q))
    return np.clip(np.rint(raw), dist["min"], dist["max"]).astype(np.int64)


def request_shapes(traffic: dict) -> list[tuple[int, int]]:
    """The fixed (prompt length, output budget) pairs, in the order the
    callers take them."""
    n = traffic["pool"]
    fixed = np.random.default_rng(traffic["shape_seed"])
    prompts = quantile_lengths(traffic["prompt_len"], n)[fixed.permutation(n)]
    outputs = quantile_lengths(traffic["output_len"], n)[fixed.permutation(n)]
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


def requests(traffic: dict, vocab: int, seed: int) -> list[dict]:
    """The run's requests in the order callers take them: {"rid",
    "prompt" (int32 ids uniform over the vocabulary), "max_new_tokens"}.
    A run that needs more than ``pool`` goes round again."""
    rng = np.random.default_rng(seed)
    return [
        {
            "rid": i,
            "prompt": rng.integers(0, vocab, size=(p,)).astype(np.int32),
            "max_new_tokens": o,
        }
        for i, (p, o) in enumerate(request_shapes(traffic))
    ]
