"""Window arithmetic: from what the client saw to end-to-end numbers.

All times are seconds relative to the window's start; the window is
``[0, close]``. A request is *due in the window* when its due time lies in
``[0, close)``; backlog requests have no due time and enter no latency tail.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Rec:
    """What the client saw of one request."""

    rid: int
    due: float | None          # None: queued before the run (backlog)
    plen: int
    max_new: int
    submit: float | None = None
    admit: float | None = None  # the step that first gave it a slot
    done: float | None = None
    tok: list[float] = dataclasses.field(default_factory=list)
    pos: int = 0                # prompt tokens known prefilled


def p95(xs) -> float | None:
    return float(np.percentile(np.asarray(xs, float), 95)) if len(xs) else None


def due_in(recs, close: float):
    return [r for r in recs if r.due is not None and 0.0 <= r.due < close]


def ttft_ms(recs, close: float) -> list[float]:
    """Due time to first visible token. One that has no first token when
    the window closes enters with its wait so far, so a stall cannot
    hide."""
    out = []
    for r in due_in(recs, close):
        first = r.tok[0] if r.tok and r.tok[0] <= close else close
        out.append((first - r.due) * 1e3)
    return out


def queue_wait_ms(recs, close: float) -> list[float]:
    """Due time to the step that admitted the request into a slot; one not
    admitted by the close enters with its wait so far."""
    out = []
    for r in due_in(recs, close):
        admit = r.admit if r.admit is not None and r.admit <= close else close
        out.append((admit - r.due) * 1e3)
    return out


def itl_ms(recs, close: float) -> list[float]:
    """Every gap between consecutive visible tokens of one request, both
    inside the window."""
    out = []
    for r in recs:
        t = np.asarray(r.tok, float)
        t = t[(t >= 0.0) & (t <= close)]
        out.extend((np.diff(t) * 1e3).tolist())
    return out


def tokens_in(recs, close: float) -> int:
    return sum(sum(1 for t in r.tok if 0.0 <= t <= close) for r in recs)


def prompt_tokens_in(prefill: list[tuple[float, int, int, int]],
                     close: float) -> int:
    """Real prompt tokens prefilled inside the window; ``prefill`` holds
    ``(time, rid, first position, end position)`` of each advance."""
    return sum(b - a for t, _, a, b in prefill if 0.0 <= t <= close)


def inflight(recs, close: float) -> list[tuple[float, float]]:
    """Merged intervals of the window in which some request was in flight
    (submitted and not yet finished)."""
    from bench.trace import merge

    return merge((max(r.submit, 0.0),
                  min(r.done if r.done is not None else close, close))
                 for r in recs if r.submit is not None)
