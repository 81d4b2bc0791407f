"""Output tokens made visible inside the window, per second of window."""
from bench import window


def read(ctx):
    return window.tokens_in(ctx.recs, ctx.close) / ctx.close
