"""Pieces every generator shares: seeded lengths, gaps and prompts.

Lengths and gaps are drawn as a fixed set of quantiles put in an order
drawn from the seed. Every seed then offers the same work, so runs with
different seeds differ by where work falls and not by how much there is.
The length law is the clipped, median-parameterised lognormal of
``repro/fleet/traffic.py::_heavy_tailed_len`` (copied, not imported, so the
yardstick cannot move with the program); the gaps are those of a Poisson
process, as there.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Item:
    """One request as the benchmark offers it. ``due_s`` is relative to the
    start of the window: negative for the warm phase, ``None`` for a backlog
    queued before the run."""

    rid: int
    due_s: float | None
    prompt: np.ndarray
    max_new: int


@dataclasses.dataclass
class Traffic:
    items: list[Item]
    # the window opens once this many requests have finished (0: at once)
    open_after_finished: int = 0


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(rng: np.random.Generator, n: int, law: dict
                      ) -> np.ndarray:
    """n lengths of the law ``{"median", "sigma", "lo", "hi"}``: lognormal
    quantiles, clipped to [lo, hi], in the seed's order."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    raw = np.rint(law["median"] * np.exp(law["sigma"] * z))
    return rng.permutation(np.clip(raw, law["lo"], law["hi"]).astype(int))


def poisson_gaps(rng: np.random.Generator, n: int, total_s: float
                 ) -> np.ndarray:
    """n exponential gaps in the seed's order, scaled to sum to total_s."""
    gaps = -np.log1p(-_quantiles(n))
    return rng.permutation(gaps * (total_s / gaps.sum()))


def prompts(rng: np.random.Generator, lengths: np.ndarray, vocab: int
            ) -> list[np.ndarray]:
    return [rng.integers(0, vocab, int(n), dtype=np.int32) for n in lengths]
