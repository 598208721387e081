"""Median latency of every request due in the window, from the moment it was
due to `finished_s` (host clock, the engine's own stamp)."""
import numpy as np


def read(ctx):
    lat = ctx.latencies()
    return 1e3 * float(np.percentile(lat, 50)) if lat.size else None
