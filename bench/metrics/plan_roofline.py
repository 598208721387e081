"""Plans and kernels: the least device time of the real requests the plan
served in the profiled window (each request's operations at the bf16 peak
or its bytes at HBM bandwidth, whichever is longer, from the model file's
`work` over the graph's real nodes and edges) over the device time of the
plan's executable in the trace. No padding, junk slot or dense N x N
aggregation is counted as work, so the share cannot pass 100% unless the
count is wrong."""

# the batched plan is `jax.jit(jax.vmap(_forward))` in core/models.py
PLAN = r"^jit__forward\b|^jit__forward\("


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    device_s = t.module_seconds(PLAN)
    least = [ctx.least_seconds(s.tenant) for s in ctx.served
             if ctx.in_window(s, profiled=True)]
    if device_s <= 0 or not least or least[0] is None:
        return None
    return 100.0 * sum(least) / device_s
