"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the finished requests, drawn from
the seed and holding the one with the most served tokens and the one with
the longest prompt, is run through the plain reference over each prompt
with its served tokens. For every served token the reference's best logit
at that position, less its logit of the served token, is the token's gap;
the widest gap is compared with the cell's limit. That holds for greedy
tokens, which the mixes serve. The control is the same reference in
float8: at the same positions, the gap of the token it puts first. The
reference is the configuration's family's (``bench/families/``).
"""
from __future__ import annotations

import numpy as np


def pad_length(max_len: int, q_block: int) -> int:
    """``max_len`` rounded up to the reference's ``q_block``."""
    return -(-max_len // q_block) * q_block


def sample(cases: list[tuple[int, np.ndarray, list[int]]], seed: int,
           target_tokens: int) -> list[tuple[int, np.ndarray, list[int]]]:
    """``cases`` are (rid, prompt, served tokens) of finished requests."""
    if not cases:
        return []
    rng = np.random.default_rng([seed, 3])
    order = [cases[i] for i in rng.permutation(len(cases))]
    must = {max(cases, key=lambda c: (len(c[2]), len(c[1]), -c[0]))[0],
            max(cases, key=lambda c: (len(c[1]), len(c[2]), -c[0]))[0]}
    out = [c for c in order if c[0] in must]
    n = sum(len(c[2]) for c in out)
    for c in order:
        if n >= target_tokens:
            break
        if c[0] not in must:
            out.append(c)
            n += len(c[2])
    return out


def _inputs(cases):
    seqs = [np.concatenate([p, np.asarray(t[:-1], np.int32)]).astype(np.int32)
            for _, p, t in cases]
    want = [np.arange(len(p) - 1, len(p) - 1 + len(t)) for _, p, t in cases]
    return seqs, want


def served_gaps(family, conf: dict, seed: int, cases,
                pad_to: int) -> np.ndarray:
    """Per served token: reference best logit less the served token's."""
    seqs, want = _inputs(cases)
    out = []
    for lg, (_, _, t) in zip(family.Reference(conf, seed).logits(
            seqs, want, pad_to), cases):
        lg = np.asarray(lg)
        out.append(lg.max(-1) - lg[np.arange(len(t)), np.asarray(t)])
    return np.concatenate(out) if out else np.zeros((0,))


def control_gaps(family, conf: dict, seed: int, cases,
                 pad_to: int) -> np.ndarray:
    """Per position: reference best logit less its logit of the token the
    float8 reference puts first."""
    seqs, want = _inputs(cases)
    ref = family.Reference(conf, seed).logits(seqs, want, pad_to)
    low = family.Reference(conf, seed, fp8=True).logits(seqs, want, pad_to)
    out = []
    for r, c in zip(ref, low):
        r, top = np.asarray(r), np.asarray(c).argmax(-1)
        out.append(r.max(-1) - r[np.arange(len(top)), top])
    return np.concatenate(out) if out else np.zeros((0,))
