"""Host clock around ``InvocationService.acquire_serving``: lease, container
deploy (tier probes, the metering ``decode`` compile) and engine boot."""


def read(ctx):
    return ctx.spans["deploy_s"]
