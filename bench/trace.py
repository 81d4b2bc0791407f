"""Reduction of a profiler trace to intervals on the window's clock.

The JAX profiler writes ``<dir>/plugins/profile/<run>/*.xplane.pb``. Each
device is a plane ``/device:<KIND>:<n>`` whose line ``XLA Modules`` holds
one event per program launch and whose line ``XLA Ops`` holds one event per
operation. The benchmark's host spans (``bench.*``) sit on the host plane.
Every time is moved onto the window's clock by the span ``bench.window``,
which the client opens at a window time it records, and is kept in
seconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os

MODULES, OPS = "XLA Modules", "XLA Ops"
ANCHOR = "bench.window"


def merge(iv):
    """Union of (start, end) intervals, merged and sorted."""
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def overlap(iv, a: float, b: float) -> float:
    """Length of the merged intervals ``iv`` inside [a, b]."""
    return sum(max(min(e, b) - max(s, a), 0.0) for s, e in iv)


@dataclasses.dataclass
class Device:
    name: str
    modules: list[tuple[str, float, float]]
    ops: list[tuple[str, float, float]]

    def busy(self, close: float):
        return merge((s, e) for _, s, e in self.ops
                     if e > 0.0 and s < close)


@dataclasses.dataclass
class Trace:
    devices: list[Device]
    spans: list[tuple[str, float, float]]
    close: float

    def busy_s(self, within=None) -> float:
        """Seconds in which an operation ran, averaged over the devices,
        inside the window (or inside the merged intervals ``within``)."""
        within = within if within is not None else [(0.0, self.close)]
        tot = 0.0
        for d in self.devices:
            busy = d.busy(self.close)
            tot += sum(overlap(busy, a, b) for a, b in within)
        return tot / max(len(self.devices), 1)

    def _events(self, kind: str, match):
        for d in self.devices:
            for name, s, e in getattr(d, kind):
                if s >= 0.0 and e <= self.close and match(name):
                    yield name, s, e

    def time_of(self, kind: str, match) -> tuple[float, int]:
        """(seconds, launches) of the ``kind`` ("modules" or "ops") events
        inside the window whose name satisfies ``match``."""
        n, tot = 0, 0.0
        for _, s, e in self._events(kind, match):
            n += 1
            tot += e - s
        return tot, n

    def top_ops(self, k: int = 10) -> list[list]:
        acc: dict[str, float] = {}
        for name, s, e in self._events("ops", lambda _: True):
            acc[name] = acc.get(name, 0.0) + e - s
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / max(len(self.devices), 1)] for n, t in top]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """Idle time of the first device inside the window, put down to the
        host span that covers the middle of each gap."""
        if not self.devices:
            return []
        busy = self.devices[0].busy(self.close)
        edges = [0.0] + [x for iv in busy for x in iv] + [self.close]
        spans = sorted((s, e, n) for n, s, e in self.spans)
        acc: dict[str, float] = {}
        for a, b in zip(edges[::2], edges[1::2]):
            a, b = max(a, 0.0), min(b, self.close)
            if b <= a:
                continue
            mid = (a + b) / 2
            who = "other"
            for s, e, n in spans:
                if s <= mid <= e:
                    who = n
                elif s > mid:
                    break
            acc[who] = acc.get(who, 0.0) + b - a
        return [[n, t] for n, t in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:k]]


def _xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def planes_of(trace_dir: str):
    """The trace under ``trace_dir`` as [(plane name, {line name: [(event,
    start ns, end ns)]})]."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(_xplane(trace_dir))
    out = []
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(e.name, float(e.start_ns),
                                 float(e.start_ns) + float(e.duration_ns))
                                for e in line.events]
        out.append((plane.name, lines))
    return out


def reduce(planes, close: float, anchor: float = 0.0) -> Trace:
    """Planes (as ``planes_of`` gives them) to a Trace on the window's
    clock; the anchor span opened at window time ``anchor``. Raises when
    the anchor span is missing."""
    host = [ev for name, lines in planes if name.startswith("/host:")
            for evs in lines.values() for ev in evs
            if ev[0].startswith("bench.")]
    opened = [s for n, s, _ in host if n == ANCHOR]
    if not opened:
        raise ValueError(f"the trace holds no {ANCHOR!r} span")
    t0 = opened[0]

    def rel(evs):
        return sorted(((n, (s - t0) * 1e-9 + anchor,
                        (e - t0) * 1e-9 + anchor)
                       for n, s, e in evs), key=lambda x: x[1])

    devices = [Device(name, rel(lines.get(MODULES, [])),
                      rel(lines.get(OPS, [])))
               for name, lines in planes
               if name.startswith("/device:") and OPS in lines]
    spans = [x for x in rel(host) if x[0] != ANCHOR]
    return Trace(devices, spans, close)


def describe(planes, k: int = 12) -> list[str]:
    """Plane and line names with their busiest event names: what to look
    at before writing a reader against a new device's trace."""
    out = []
    for name, lines in planes:
        out.append(f"plane {name}")
        for ln, evs in lines.items():
            acc: dict[str, float] = {}
            for n, s, e in evs:
                acc[n] = acc.get(n, 0.0) + e - s
            top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
            out.append(f"  line {ln!r}: {len(evs)} events; top "
                       + "; ".join(f"{n[:80]} {t * 1e-9:.6f}s"
                                   for n, t in top))
    return out
