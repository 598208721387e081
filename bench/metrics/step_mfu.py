"""Whole step: model operations of the requests completed per second, over
the chip's bf16 peak. Over the window less its profiled part (host clock);
operations from the model file's `work` over each request's real graph."""


def read(ctx):
    if ctx.peaks is None:
        return None
    t0, t1 = ctx.window
    p = ctx.profiled
    seconds = (t1 - t0) - ((p[1] - p[0]) if p else 0.0)
    ops = sum(ctx.work[s.tenant][0] for s in ctx.served if ctx.in_window(s))
    if seconds <= 0 or ops <= 0:
        return None
    return 100.0 * ops / seconds / ctx.peaks["bf16_flops_per_s"]
