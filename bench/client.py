"""Drives the leased engine as a client would and records what it sees.

Requests go in through ``ServingExecutor.submit`` when due and the engine
advances through ``ServingExecutor.step``. After every step the client
reads, from the engine's host-side slot table, which requests hold a slot,
how far each prompt has been prefilled and how many tokens each request
has made visible, and stamps what changed with the host clock. The host
spans ``bench.step``, ``bench.submit`` and ``bench.wait`` go into the
profiler's trace, where the idle gaps of the device are put down to them.
"""
from __future__ import annotations

import time

import jax

from bench.window import Rec

CLOCK = time.perf_counter
SPAN = jax.profiler.TraceAnnotation


class Client:
    def __init__(self, ex, items):
        self.ex, self.eng = ex, ex.engine
        self.recs = {it.rid: Rec(it.rid, it.due_s, len(it.prompt), it.max_new)
                     for it in items}
        self.items = {it.rid: it for it in items}
        self.timed = sorted((it for it in items if it.due_s is not None),
                            key=lambda it: it.due_s)
        self.backlog = [it for it in items if it.due_s is None]
        self.prefill: list[tuple[float, int, int, int]] = []
        self.open: set[int] = set()
        self.finished = 0
        self.t0 = 0.0  # absolute clock of due time 0
        self._next = 0
        self._hits = self.eng.stats["prefix_hit_tokens"]

    # ------------------------------------------------------------------
    def submit(self, rid: int, now: float) -> None:
        from repro.serving.engine import Request

        it = self.items[rid]
        with SPAN("bench.submit"):
            self.ex.submit(Request(request_id=rid, prompt=it.prompt,
                                   max_new_tokens=it.max_new))
        self.recs[rid].submit = now
        self.open.add(rid)

    def submit_backlog(self) -> None:
        for it in self.backlog:
            self.submit(it.rid, CLOCK())

    def busy(self) -> bool:
        return bool(self.eng.queue) or any(
            r is not None for r in self.eng.active)

    def run(self, end: float, stop=None) -> None:
        """Serve until the clock reaches ``end`` or ``stop()`` holds."""
        while True:
            now = CLOCK()
            if now >= end or (stop is not None and stop()):
                return
            while (self._next < len(self.timed) and self.t0
                   + self.timed[self._next].due_s <= now):
                self.submit(self.timed[self._next].rid, now)
                self._next += 1
            if self.busy():
                with SPAN("bench.step"):
                    self.ex.step()
                self.observe(CLOCK())
                continue
            nxt = (self.t0 + self.timed[self._next].due_s
                   if self._next < len(self.timed) else end)
            with SPAN("bench.wait"):
                time.sleep(max(min(nxt, end) - CLOCK(), 0.0))

    def observe(self, now: float) -> None:
        eng = self.eng
        advances = []
        for s, req in enumerate(eng.active):
            if req is None:
                continue
            r = self.recs[req.request_id]
            if r.admit is None:
                r.admit = now
            st = eng._admitting.get(s)
            pos = st["pos"] if st is not None else r.plen
            if pos > r.pos:
                advances.append([r.rid, r.pos, pos])
                r.pos = pos
            n = len(eng.generated[s])
            if n > len(r.tok):
                r.tok.extend([now] * (n - len(r.tok)))
        for rid in [rid for rid in self.open if rid in eng.results]:
            r = self.recs[rid]
            if r.pos < r.plen:
                advances.append([rid, r.pos, r.plen])
                r.pos = r.plen
            n = len(eng.results[rid].tokens)
            if n > len(r.tok):
                r.tok.extend([now] * (n - len(r.tok)))
            if r.admit is None:
                r.admit = now
            r.done = now
            self.open.discard(rid)
            self.finished += 1
        # prefix-cache hits are restored, not prefilled: take them off this
        # step's advances, first come first
        hits = eng.stats["prefix_hit_tokens"]
        dh, self._hits = hits - self._hits, hits
        for adv in advances:
            take = min(dh, adv[2] - adv[1])
            adv[1] += take
            dh -= take
            if adv[2] > adv[1]:
                self.prefill.append((now, adv[0], adv[1], adv[2]))

    # ------------------------------------------------------------------
    def relative(self):
        """Records and prefill advances with times relative to ``t0``."""
        def rel(t):
            return None if t is None else t - self.t0

        recs = []
        for r in self.recs.values():
            recs.append(Rec(r.rid, r.due, r.plen, r.max_new, rel(r.submit),
                            rel(r.admit), rel(r.done),
                            [t - self.t0 for t in r.tok], r.pos))
        prefill = [(t - self.t0, rid, a, b) for t, rid, a, b in self.prefill]
        return recs, prefill
