"""Host clock around ``ServingExecutor.warmup``: the engine's program
sweep, from the compile cache after a cell's first run."""


def read(ctx):
    return ctx.spans["warmup_s"]
