"""Real prompt tokens prefilled inside the window, per second of window;
partial prompts count for what was done, padding and prefix-cache hits do
not count."""
from bench import window


def read(ctx):
    return window.prompt_tokens_in(ctx.prefill, ctx.close) / ctx.close
