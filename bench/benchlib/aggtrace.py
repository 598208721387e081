"""Device time of the edge-list aggregation, for the `agg_*` metrics.

The program's edges plan (`forward_edges` in `core/models.py`) sums each
layer's messages into bucket + 1 rows, the spare row taking the padding
edges. So on a chip trace every op whose f32 output has bucket + 1 rows
belongs to the aggregation and to nothing else: the segment sums (their
gathers fused in) and the layout copies of their results. `trace.py`
keeps an op's name only up to " = "; this reads the run's profile again
for the whole HLO text, which holds the output shape.

The dispatches are the program's `dispatch.device` spans that carry the
`nodes` and `edges` they aggregated and end in the profiled part of the
window. A program without either reads nothing.
"""
from __future__ import annotations

import glob
import os
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence

from . import spans, trace

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def xplane(ctx) -> Optional[Path]:
    """The newest profile of this cell: the one this run wrote."""
    found = glob.glob(str(Path(ctx.cell.root) / "chiprun_out" / "bench_traces"
                          / f"{ctx.cell.name}.seed*" / "**" / "*.xplane.pb"),
                      recursive=True)
    return Path(max(found, key=os.path.getmtime)) if found else None


def load_ops(path: Path) -> List[trace.Event]:
    """The TPU planes' ops, named by their whole HLO text, and the host
    events, in seconds on the trace's clock."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = bool(_DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != "XLA Ops":
                continue
            out.extend(trace.Event(plane.name, line.name, e.name,
                                   e.start_ns * 1e-9, e.duration_ns * 1e-9)
                       for e in line.events)
    return out


def op_seconds(events: Sequence[trace.Event], rows: int) -> float:
    """Device seconds, inside the marked window, of the ops whose f32
    output has `rows` rows, averaged over the chips that ran any."""
    out = re.compile(rf"%\S+ = f32\[(?:\d+,)*{rows},\d+\]")
    window = [e for e in events if e.name == trace.WINDOW_SPAN
              and e.plane.startswith("/host:")]
    if not window:
        return 0.0
    w0, w1 = window[0].start, window[0].end
    per_chip: Dict[str, float] = {}
    for e in events:
        if _DEVICE_PLANE.match(e.plane) and out.match(e.name):
            per_chip[e.plane] = per_chip.get(e.plane, 0.0) + max(
                0.0, min(e.end, w1) - max(e.start, w0))
    busy = [v for v in per_chip.values() if v > 0]
    return sum(busy) / len(busy) if busy else 0.0


def device_seconds(ctx) -> Optional[float]:
    """The aggregation's device seconds in this run's profiled window;
    None where nothing matched."""
    path = xplane(ctx) if ctx.trace is not None else None
    if path is None:
        return None
    got = op_seconds(load_ops(path), ctx.config["serving"]["bucket"] + 1)
    return got if got > 0 else None


def dispatches(ctx) -> List:
    """The edges dispatches whose plan call ended in the profiled part."""
    p = ctx.profiled
    if p is None:
        return []
    return [s for s in spans.ring() or () if s.name == "dispatch.device"
            and s.attrs and "edges" in s.attrs and p[0] <= s.end < p[1]]
