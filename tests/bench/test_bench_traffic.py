"""Generators: deterministic per seed, the same work for every seed, and
lengths inside their limits."""
import json

import numpy as np
import pytest

from bench.traffic import backlog, common, open_loop
from benchutil import REPO

CHAT = json.loads((REPO / "bench/traffic/chat-over.json").read_text())
DOC = {"kind": "backlog", "documents": 256, "open_after_finished": 4,
       "prompt": {"median": 4096, "sigma": 0.35, "lo": 2048, "hi": 8192},
       "output": {"median": 64, "sigma": 0.6, "lo": 16, "hi": 256}}
BIG = 2**31 + 12345


def _shape(tr):
    return [(it.rid, it.due_s, it.prompt.tolist(), it.max_new)
            for it in tr.items]


@pytest.mark.parametrize("gen,mix", [(open_loop, CHAT), (backlog, DOC)])
def test_same_seed_same_traffic(gen, mix):
    a = gen.make(mix, BIG, 10, 1000)
    b = gen.make(mix, BIG, 10, 1000)
    assert _shape(a) == _shape(b)
    c = gen.make(mix, BIG + 1, 10, 1000)
    assert _shape(a) != _shape(c)


@pytest.mark.parametrize("gen,mix", [(open_loop, CHAT), (backlog, DOC)])
def test_every_seed_offers_the_same_work(gen, mix):
    a = gen.make(mix, 1, 10, 1000)
    b = gen.make(mix, BIG, 10, 1000)
    assert sorted(len(i.prompt) for i in a.items) == sorted(
        len(i.prompt) for i in b.items)
    assert sorted(i.max_new for i in a.items) == sorted(
        i.max_new for i in b.items)


@pytest.mark.parametrize("gen,mix", [(open_loop, CHAT), (backlog, DOC)])
def test_lengths_are_clipped(gen, mix):
    tr = gen.make(mix, 3, 30, 1000)
    p = np.array([len(i.prompt) for i in tr.items])
    o = np.array([i.max_new for i in tr.items])
    assert p.min() >= mix["prompt"]["lo"] and p.max() <= mix["prompt"]["hi"]
    assert o.min() >= mix["output"]["lo"] and o.max() <= mix["output"]["hi"]
    # the clip is reached: the laws' tails are wider than the limits
    assert p.max() == mix["prompt"]["hi"] or p.min() == mix["prompt"]["lo"]
    assert all(0 <= t < 1000 for i in tr.items[:20] for t in i.prompt)


def test_open_loop_arrivals_span_warm_phase_and_window_at_the_rate():
    tr = open_loop.make(CHAT, 7, 30, 1000)
    due = np.array([i.due_s for i in tr.items])
    assert np.all(np.diff(due) >= 0)
    assert due[0] == pytest.approx(-CHAT["warm_s"])
    assert due[-1] < 30
    span = CHAT["warm_s"] + 30
    assert len(due) == round(CHAT["rate_per_s"] * span)


def test_backlog_is_queued_before_the_run():
    tr = backlog.make(DOC, 7, 30, 1000)
    assert len(tr.items) == DOC["documents"]
    assert all(i.due_s is None for i in tr.items)
    assert tr.open_after_finished == DOC["open_after_finished"]


def test_lognormal_quantiles_have_the_median():
    rng = np.random.default_rng(0)
    law = {"median": 100, "sigma": 0.5, "lo": 1, "hi": 10_000}
    x = common.lognormal_lengths(rng, 101, law)
    assert np.median(x) == 100
