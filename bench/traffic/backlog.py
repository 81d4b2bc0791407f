"""Offline backlog: ``documents`` requests queued before the run, served
as fast as the system can; the window opens once ``open_after_finished``
of them have finished. Keys: ``documents``, ``open_after_finished``,
``prompt`` and ``output`` (length laws, see ``common.lognormal_lengths``)."""
from __future__ import annotations

import numpy as np

from bench.traffic import common


def make(mix: dict, seed: int, seconds: float, vocab: int) -> common.Traffic:
    rng = np.random.default_rng([seed, 2])
    n = mix["documents"]
    plen = common.lognormal_lengths(rng, n, mix["prompt"])
    out = common.lognormal_lengths(rng, n, mix["output"])
    toks = common.prompts(rng, plen, vocab)
    return common.Traffic(
        [common.Item(i, None, p, int(o))
         for i, (p, o) in enumerate(zip(toks, out))],
        open_after_finished=mix["open_after_finished"])
