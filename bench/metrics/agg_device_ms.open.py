"""Plans and kernels: device time of the edge-list aggregation per
dispatch (`benchlib/aggtrace.py`: the ops writing bucket + 1 rows), in the
profiled part of the window, over the edges dispatches whose plan call
ended there."""
from benchlib import aggtrace


def read(ctx):
    agg = aggtrace.device_seconds(ctx)
    n = len(aggtrace.dispatches(ctx))
    return 1e3 * agg / n if agg and n else None
