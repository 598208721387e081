"""In-memory span recorder for GraphServe's request and dispatch paths.

A `Tracer` keeps a bounded ring of `Span` records: name, start, end, an
`id` (a request's uid or a dispatch's serial), a `parent` (for a request,
the serial of the dispatch that answered it) and an optional small `attrs`
dict of counts. Recording is always on; the ring is read in
process (`spans()`), and nothing is written out.

Times come from the engine's `Clock` (`perf_counter` in production, a fake
clock in tests), so spans share a clock with every other timestamp the
serving path takes. A live span (`Tracer.span`, a context manager) also
opens a `jax.profiler.TraceAnnotation` named `graphserve.<name>`, so it
lands in the profiler's own trace, on the device trace's clock, whenever a
profile is being taken. A span known only once it has ended (a request's
time in the queue) goes to the ring alone (`Tracer.record`).

Span names (`PERF.md` lists the metric that reads each):

  host               the scheduler's host stage of one request (id: uid)
  dispatch           one whole device-stage dispatch (id: serial)
  dispatch.stack     stacking the slots' resident features on the device
  dispatch.operands  stacking the resident operands on the device
  dispatch.device    the plan call through `block_until_ready`; on an
                     edge-list dispatch (DESIGN.md §16) its attrs `nodes`
                     and `edges` count the real nodes and directed edges
                     it aggregates, and `gather_rows` is the row count of
                     each slot's message gathers (its chunk slots)
  dispatch.d2h       copying the logits back and unpacking each answer
  request.queue      submission to the start of the answering dispatch
                     (id: uid, parent: serial; ring only)
  operands.edges     building and uploading one graph's edge-list operands,
                     at `attach`, after an update, or for a one-shot request
                     (id: graph_id, None for a one-shot request)

The `dispatch.*` spans tile `dispatch` (the sharded path has no
`.stack`: its replica stack of features is part of `.operands`); what
they leave uncovered is the dispatch's bookkeeping under the engine lock.
No dispatch sends anything to the device: every request's features are
there since its host stage.

Counters of the edge-list form (`GraphServe.summary()`):
`edge_operand_bytes`, the resident edge operands' device bytes, and
`edges_dispatched`, real directed edges aggregated, once per layer. Inside
the edges plan each layer's aggregation runs under the
`jax.named_scope("graphserve.agg.l<i>")`; its device ops are the ones
whose f32 output has bucket + 1 rows (the spare row of the padding
edges), which is how a device trace tells them apart.
"""
from __future__ import annotations

import collections
import threading
from typing import Deque, Dict, List, NamedTuple, Optional

import jax

from .clock import Clock

PREFIX = "graphserve."
CAPACITY = 1 << 16


class Span(NamedTuple):
    """One finished span (a tuple: cheap to make on the serving path)."""
    name: str
    start: float
    end: float
    id: Optional[int]
    parent: Optional[int] = None
    attrs: Optional[Dict[str, int]] = None


class LiveSpan:
    """A span being recorded, as `with tracer.span(...) as sp:`. `id` may
    be filled in inside the block; `start` and `end` stay readable after
    it, so a counter can accumulate from the very timestamps the ring
    holds."""

    __slots__ = ("_tracer", "_clock", "_ann", "name", "id", "parent",
                 "attrs", "start", "end")

    def __init__(self, tracer: "Tracer", clock: Clock, name: str,
                 id: Optional[int], parent: Optional[int],
                 attrs: Dict[str, int]):
        self._tracer, self._clock = tracer, clock
        self._ann = None
        self.name, self.id, self.parent, self.attrs = name, id, parent, attrs
        self.start = self.end = 0.0

    def __enter__(self) -> "LiveSpan":
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        self.start = self._clock.now()
        return self

    def __exit__(self, *exc) -> None:
        self.end = self._clock.now()
        self._ann.__exit__(*exc)
        self._tracer.record(self.name, self.start, self.end, self.id,
                            self.parent, self.attrs or None)


class Tracer:
    """A bounded ring of spans, safe to record into from any thread."""

    def __init__(self, capacity: int = CAPACITY):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self._ring: Deque[Span] = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()

    def span(self, name: str, clock: Clock, id: Optional[int] = None,
             parent: Optional[int] = None, **attrs: int) -> LiveSpan:
        """A live span on `clock`, recorded when its block ends."""
        return LiveSpan(self, clock, name, id, parent, attrs)

    def record(self, name: str, start: float, end: float, id: Optional[int],
               parent: Optional[int] = None,
               attrs: Optional[Dict[str, int]] = None) -> None:
        """Append a finished span; the oldest falls out of a full ring."""
        span = Span(name, start, end, id, parent, attrs)
        with self._lock:
            self._ring.append(span)

    def spans(self, name: Optional[str] = None) -> List[Span]:
        """A snapshot of the ring, oldest first; only `name`'s if given."""
        with self._lock:
            out = list(self._ring)
        return out if name is None else [s for s in out if s.name == name]


_DEFAULT = Tracer()


def default_tracer() -> Tracer:
    """The process's tracer: every engine built without one records here."""
    return _DEFAULT
