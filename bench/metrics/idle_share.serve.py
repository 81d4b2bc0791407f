"""Per cent of the time with a request in flight in which the device ran
no operation (trace), in a cell served as requests arrive."""
from bench.metrics import common


def read(ctx):
    return common.idle_share(ctx)
