"""Process start to window start (host clock): weights, lease, deploy,
warmup and the warm phase."""


def read(ctx):
    return ctx.spans["setup_s"]
