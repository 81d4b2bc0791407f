"""95th percentile of due time to the step that gave the request a slot,
over requests due in the window (host clock)."""
from bench import window


def read(ctx):
    return window.p95(window.queue_wait_ms(ctx.recs, ctx.close))
