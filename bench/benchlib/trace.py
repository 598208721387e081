"""Reduce a JAX profiler trace to the benchmark's device numbers.

The profiled part of a run is marked by a host span named `WINDOW_SPAN`.
Within it, for every TPU plane that ran anything:

  busy_s       the union of the intervals in which a device operation ran
               ("XLA Ops" line), so overlapping ops count once;
  window_s     the length of the marked span;
  module_s     device seconds per executable ("XLA Modules" line), by name;
  device_ops   the operations that took most device time, each named
               "<executable>/<op>" by the executable it ran inside;
  idle_gaps    the longest stretches with no device operation, each named
               after what the host was doing: the innermost host event
               (the program's Python functions appear as `$file:line name`)
               that covers most of the gap and is not a wait.

Planes' numbers are averaged over the chips that ran something. Events are
first read into plain `Event`s (`load_xplane`), so the reduction can be
checked on a small recorded trace (`bench/testdata/`).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.profiled_window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"
# host events that only wait say nothing about what keeps the chip idle
_WAITING = re.compile(r"wait|sleep|acquire|futex|select|poll", re.I)
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float                # seconds, on the trace's own clock
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


def load_xplane(path: Path) -> List[Event]:
    """Device ops and modules of the TPU planes, and every host event."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out: List[Event] = []
    for plane in pd.planes:
        device = bool(_DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in (_OPS_LINE, _MODULES_LINE):
                continue
            for e in line.events:
                name = e.name
                if device and line.name == _OPS_LINE:
                    name = name.split(" = ")[0]     # "%fusion.3 = f32[...]..."
                out.append(Event(plane.name, line.name, name,
                                 e.start_ns * 1e-9, e.duration_ns * 1e-9))
    return out


def find_xplane(logdir: Path) -> Optional[Path]:
    found = sorted(glob.glob(str(Path(logdir) / "**" / "*.xplane.pb"),
                             recursive=True))
    return Path(found[-1]) if found else None


def save_events(events: Sequence[Event], path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load_events(path: Path) -> List[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _clip(e: Event, w0: float, w1: float) -> Optional[Tuple[float, float]]:
    s, t = max(e.start, w0), min(e.end, w1)
    return (s, t) if t > s else None


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    chips: int
    module_s: Dict[str, float]
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    def module_seconds(self, pattern: str) -> float:
        """Device seconds of every executable whose name matches."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.module_s.items() if rx.search(k))


def summarize(events: Sequence[Event]) -> Optional[Summary]:
    """None where the trace has no marked window or no device activity."""
    spans = [e for e in events if e.name == WINDOW_SPAN
             and e.plane.startswith("/host:")]
    if not spans:
        return None
    w0, w1 = spans[0].start, spans[0].end
    planes = sorted({e.plane for e in events if _DEVICE_PLANE.match(e.plane)
                     and e.line == _OPS_LINE and _clip(e, w0, w1)})
    if not planes:
        return None
    busy = 0.0
    modules: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    first_busy: List[Tuple[float, float]] = []
    for i, plane in enumerate(planes):
        mine = [e for e in events if e.plane == plane]
        execs = sorted((e for e in mine if e.line == _MODULES_LINE),
                       key=lambda e: e.start)
        starts = [e.start for e in execs]
        clipped = []
        for e in mine:
            iv = _clip(e, w0, w1)
            if iv is None:
                continue
            if e.line == _OPS_LINE:
                clipped.append(iv)
                key = f"{_owner(execs, starts, e)}/{e.name}"
                ops[key] = ops.get(key, 0.0) + iv[1] - iv[0]
            elif e.line == _MODULES_LINE:
                modules[e.name] = modules.get(e.name, 0.0) + iv[1] - iv[0]
        merged = union(clipped)
        busy += sum(t - s for s, t in merged)
        if i == 0:
            first_busy = merged
    n = len(planes)
    host = [e for e in events if e.plane.startswith("/host:")
            and e.name != WINDOW_SPAN and e.dur > 0]
    return Summary(
        window_s=w1 - w0, busy_s=busy / n, chips=n,
        module_s={k: v / n for k, v in modules.items()},
        device_ops=sorted(((k, v / n) for k, v in ops.items()),
                          key=lambda kv: -kv[1])[:TOP],
        idle_gaps=_named_gaps(first_busy, w0, w1, host))


def _owner(execs: List[Event], starts: List[float], op: Event) -> str:
    """Name (without its fingerprint) of the executable an op ran inside."""
    i = bisect.bisect_right(starts, op.start) - 1
    if i >= 0 and execs[i].end >= op.start:
        return execs[i].name.split("(")[0]
    return "?"


def _named_gaps(busy: List[Tuple[float, float]], w0: float, w1: float,
                host: Sequence[Event]) -> List[Tuple[str, float]]:
    gaps, t = [], w0
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if w1 > t:
        gaps.append((t, w1))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    out = []
    for g0, g1 in gaps:
        cover = [(min(e.end, g1) - max(e.start, g0), e) for e in host
                 if not _WAITING.search(e.name)]
        cover = [(c, e) for c, e in cover if c > 0]
        most = [e for c, e in cover if c >= 0.5 * (g1 - g0)]
        if most:
            best = min(most, key=lambda e: e.dur).name
        elif cover:
            best = max(cover, key=lambda ce: ce[0])[1].name
        else:
            best = "no host event"
        out.append((f"idle: {best}", g1 - g0))
    return out
