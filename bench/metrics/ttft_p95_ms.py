"""95th percentile of due time to first visible token over every request
due in the window (host clock)."""
from bench import window


def read(ctx):
    return window.p95(window.ttft_ms(ctx.recs, ctx.close))
