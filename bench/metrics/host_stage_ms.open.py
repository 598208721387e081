"""Host stage (`GraphServe.prepare_query` on the scheduler's workers): the
program's `host_busy_s` per accepted request, over the window less its
profiled part."""


def read(ctx):
    n = ctx.delta("accepted")
    return 1e3 * ctx.delta("host_busy_s") / n if n > 0 else None
