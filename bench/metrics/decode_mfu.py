"""Model FLOPs of the tokens the decode steps made visible in the window
(weights, attention at live lengths, LM head) over the fused decode
program's device time times the peak."""
from bench.metrics import common


def read(ctx):
    if ctx.trace is None:
        return None
    secs, _ = common.module_time(ctx, common.FUSED_DECODE)
    f = ctx.family.decode_flops(ctx.conf, common.decode_keys(ctx))
    return common.mfu(ctx, f, secs)
