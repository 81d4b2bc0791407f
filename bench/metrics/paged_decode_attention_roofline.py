"""Share of its roofline the paged decode attention kernel reaches: the
least time the chip could take for the window's calls (the larger of
FLOPs over peak FLOP/s and K/V bytes over peak HBM bytes/s, at live
lengths) over the kernel's device time in the trace. Nothing to read where
the deployment did not bind a kernel for ``paged_decode_attention``."""
from bench.metrics import common

API = "paged_decode_attention"
KERNEL = "paged_decode"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    if ctx.manifest.get("apis", {}).get(API, {}).get("provider") \
            != "pallas-tpu":
        return None
    secs, n = ctx.trace.time_of("ops", lambda name: KERNEL in name)
    if not n or secs <= 0.0:
        return None
    keys = sum(common.decode_keys(ctx))
    f, b = ctx.family.paged_decode_attention_cost(ctx.conf, keys)
    least = max(f / ctx.peaks["bf16_flops"], b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
