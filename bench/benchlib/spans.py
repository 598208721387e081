"""Read the program's own spans for the `program_span` metrics.

GraphServe records its request and dispatch spans into one in-memory ring
per process (`repro.runtime.tracing`), on the engine's clock, the clock of
the window and the marks. The benchmark drives the program in its own
process, so a reader filters that ring once the window is over. A span
counts where the host-clock metrics count: it ends inside the window and
outside the window's profiled part. A program without the recorder has no
ring, and every reader then returns None.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def ring() -> Optional[List]:
    """The program's spans, oldest first; None without a recorder."""
    try:
        from repro.runtime.tracing import default_tracer
    except ImportError:
        return None
    return default_tracer().spans()


def counted(ctx, name: str, spans: Optional[List] = None) -> List:
    """`name`'s spans that end inside the window, outside its profiled
    part. `spans` defaults to the program's ring."""
    spans = ring() if spans is None else spans
    t0, t1 = ctx.window
    p = ctx.profiled
    return [s for s in spans or () if s.name == name and t0 <= s.end < t1
            and not (p is not None and p[0] <= s.end < p[1])]


def _ms(ctx, name: str, stat) -> Optional[float]:
    got = counted(ctx, name)
    return 1e3 * float(stat([s.end - s.start for s in got])) if got else None


def mean_ms(ctx, name: str) -> Optional[float]:
    """Mean length of the counted spans, in ms."""
    return _ms(ctx, name, np.mean)


def median_ms(ctx, name: str) -> Optional[float]:
    """Median length of the counted spans, in ms."""
    return _ms(ctx, name, np.median)


def bytes_per_request(ctx, name: str) -> Optional[float]:
    """The `bytes` of the counted spans of one dispatch stage over the real
    requests they carried (each span's `filled`)."""
    got = counted(ctx, name)
    filled = sum(s.attrs["filled"] for s in got)
    return sum(s.attrs["bytes"] for s in got) / filled if filled else None
