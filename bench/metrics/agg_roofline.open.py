"""Plans and kernels: the least time of the edge-list aggregations served
in the profiled part of the window over their device time there. Each
dispatch's least time is, per layer, the model file's `agg_work` over the
real nodes and edges the dispatch names (bytes at HBM bandwidth or
operations at the bf16 peak, whichever is longer); the count is the same
whatever implements the aggregation, so the share cannot pass 100% unless
the count is wrong."""
from benchlib import aggtrace


def read(ctx):
    agg = aggtrace.device_seconds(ctx)
    done = aggtrace.dispatches(ctx)
    if not agg or not done or ctx.peaks is None:
        return None
    peak, bw = ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"]
    least = sum(max(ops / peak, nbytes / bw)
                for d in done
                for ops, nbytes in ctx.cell.model.agg_work(
                    ctx.config, d.attrs["nodes"], d.attrs["edges"]))
    return 100.0 * least / agg
