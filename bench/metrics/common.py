"""Arithmetic several readers share."""
from __future__ import annotations

from bench import window

FUSED_DECODE = "fused_step"
PREFILL_CHUNK = "prefill_chunk"


def is_module(name: str, program: str) -> bool:
    """A launch of the program named ``program`` (``jit_<program>(<id>)``
    or ``<program>(<id>)`` in the trace)."""
    base = name.split("(")[0]
    return base in (program, f"jit_{program}")


def decode_keys(ctx) -> list[int]:
    """Keys each decode-made token inside the window attended: token j >= 1
    of a request with an n-token prompt sat at position n + j - 1."""
    out = []
    for r in ctx.recs:
        for j, t in enumerate(r.tok):
            if j >= 1 and 0.0 <= t <= ctx.close:
                out.append(r.plen + j)
    return out


def module_time(ctx, program: str) -> tuple[float, int]:
    return ctx.trace.time_of("modules", lambda n: is_module(n, program))


def idle_share(ctx) -> float | None:
    """Per cent of the time with a request in flight in which no operation
    ran on the device."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    busy_iv = window.inflight(ctx.recs, ctx.close)
    total = sum(b - a for a, b in busy_iv)
    if total <= 0.0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s(busy_iv) / total)


def mfu(ctx, model_flops: float, seconds: float) -> float | None:
    if ctx.peaks is None or seconds <= 0.0 or model_flops <= 0:
        return None
    return 100.0 * model_flops / (seconds * ctx.peaks["bf16_flops"])
