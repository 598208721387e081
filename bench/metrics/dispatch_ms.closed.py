"""Device stage (`GraphServe._execute_batch`): the program's
`device_busy_s` per batch. That is host clock around the whole stage
(stacking, host-to-device copy, the plan, the wait, the copy back), not
device time. Over the window less its profiled part."""


def read(ctx):
    n = ctx.delta("batches")
    return 1e3 * ctx.delta("device_busy_s") / n if n > 0 else None
