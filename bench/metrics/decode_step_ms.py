"""Device time of the fused paged decode program per launch, from the
trace."""
from bench.metrics import common


def read(ctx):
    if ctx.trace is None:
        return None
    secs, n = common.module_time(ctx, common.FUSED_DECODE)
    return secs / n * 1e3 if n else None
