"""Pipeline scheduler: real requests per batch slot dispatched, over the
window less its profiled part (the program's slot counters)."""


def read(ctx):
    n = ctx.delta("slots_total")
    return 100.0 * ctx.delta("slots_filled") / n if n > 0 else None
