"""Model FLOPs of the real prompt tokens prefilled in the window (causal
attention, one LM head row per chunk and request) over the prefill-chunk
programs' device time times the peak. Padding lowers it."""
from bench.metrics import common


def read(ctx):
    if ctx.trace is None:
        return None
    secs, _ = common.module_time(ctx, common.PREFILL_CHUNK)
    spans = [(a, b) for t, _, a, b in ctx.prefill if 0.0 <= t <= ctx.close]
    f = ctx.family.prefill_flops(ctx.conf, spans)
    return common.mfu(ctx, f, secs)
