"""95th percentile of every gap between consecutive visible tokens of one
request inside the window (host clock)."""
from bench import window


def read(ctx):
    return window.p95(window.itl_ms(ctx.recs, ctx.close))
