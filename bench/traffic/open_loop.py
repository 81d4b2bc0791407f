"""Open loop: requests due on a fixed schedule whether or not the system
keeps up, at ``rate_per_s`` through a warm phase of ``warm_s`` and the
window. Keys: ``rate_per_s``, ``warm_s``, ``prompt`` and ``output``
(length laws, see ``common.lognormal_lengths``)."""
from __future__ import annotations

import numpy as np

from bench.traffic import common


def make(mix: dict, seed: int, seconds: float, vocab: int) -> common.Traffic:
    rng = np.random.default_rng([seed, 1])
    span = mix["warm_s"] + seconds
    n = max(int(round(mix["rate_per_s"] * span)), 1)
    gaps = common.poisson_gaps(rng, n, span)
    due = -mix["warm_s"] + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    plen = common.lognormal_lengths(rng, n, mix["prompt"])
    out = common.lognormal_lengths(rng, n, mix["output"])
    toks = common.prompts(rng, plen, vocab)
    return common.Traffic([common.Item(i, float(d), p, int(o))
                           for i, (d, p, o) in enumerate(zip(due, toks, out))])
