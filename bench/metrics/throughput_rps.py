"""Requests completed inside the window per second of the window (host
clock): all the work and all the time of the window."""


def read(ctx):
    t0, t1 = ctx.window
    n = sum(1 for s in ctx.served
            if s.finished is not None and t0 <= s.finished < t1)
    return n / (t1 - t0)
