"""Set-up time: process start to the window's start (host clock). It holds
loading, graph and weight generation, attaching the tenants, and warming:
the compile, or the load from the persistent compilation cache."""


def read(ctx):
    return ctx.setup_s
