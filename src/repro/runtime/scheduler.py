"""Async two-stage pipeline scheduler for GraphServe (DESIGN.md §9).

GraphSplit at serving scale: the paper puts control-heavy graph work on the
host and dense compute on the accelerator, but the engine's sync path runs
both phases serially — `run()` only starts after every submit finished its
host work, so the device idles exactly during the preprocessing the split
exists to hide. The scheduler overlaps them as a two-stage pipeline:

  intake ──▶ HOST stage                 ──▶ ready ──▶ DEVICE stage
  bounded    worker threads running         bounded   one dispatcher thread
  queue      engine.prepare_submit /        buffer    grouping ready requests
             prepare_query (ladder.pad,     (per      by (model, bucket,
             operand build, CompactOperands batch     tier, agg backend,
             packing, CacheG lookups)       key)      fusion, shard count)
                                                      and driving
                                                      engine._execute_batch

Policies (all per `PipelineConfig`):

  * Batch window — the dispatcher prefers full batches: a key with fewer
    than `batch_slots` ready requests waits up to `window_ms` (measured
    from its OLDEST ready request) for stragglers while host work is still
    in flight, then dispatches partial. `window_ms=0` dispatches whatever
    is ready immediately.
  * Best-fill + fairness — key selection is `gnn_server.best_fill_key`:
    fullest key first, least-recently-dispatched model on ties, FIFO last
    (shared with the sync path, so both drivers batch identically).
  * Backpressure — both queues are bounded. A full intake queue makes
    `submit`/`query` either block (`backpressure="block"`, counted in
    `metrics["blocked"]`) or raise `QueueFull` (`"reject"`, counted in
    `metrics["rejected"]`); a full ready buffer blocks host workers, which
    in turn fills intake — pressure propagates to the caller instead of
    growing unbounded request state.
  * Determinism — `deterministic=True` forces one host worker and
    `window_ms=0` and runs the whole pipeline inline on the caller's
    thread (no threads at all): identical submission order then yields
    identical batch composition, which is what the differential test
    suites diff against the sequential path. Backpressure stays live —
    "block" drains inline instead of waiting on a thread.

Every engine contract survives the scheduler: plans/materializers are only
ever REPLAYED (zero recompiles, `assert_warm`), CacheG hit/miss accounting
is unchanged (worker races on a cold key may double-build; both count as
misses and the insert is version-checked), and tier fallback happens in the
host stage exactly as in the sync path. Under a §13 cache budget, HOST-stage
workers are also where spill faults surface: a `prepare_query` that misses
the device cache but hits the host-RAM spill store re-materializes the
compact form inside the host stage (counted `cache_spill_hits`, never an
`operand_cache_miss`), so eviction pressure converts into host-stage
latency, never device-stage stalls — and a budget-evicted entry re-inserted
by one worker may evict another graph mid-flight, which is safe for the
same reason racing double-builds are: requests carry their operand
snapshot, cache state only gates REUSE. The engine summary the scheduler
re-exports includes the cache residency/eviction/spill counters.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

from repro.core.graph import Graph

from .gnn_server import (BatchKey, GNNRequest, GraphServe, edf_best_fill_key)


class QueueFull(RuntimeError):
    """Raised by submit/query under `backpressure="reject"` when the intake
    queue is at `max_pending` — the caller sheds load instead of queueing."""


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    host_workers: int = 2          # threads running the engine's host stage
    window_ms: float = 2.0         # max wait to fill a partial batch
    max_pending: int = 64          # intake queue bound (host stage input)
    max_ready: int = 64            # ready buffer bound (device stage input)
    backpressure: str = "block"    # "block" | "reject" on a full intake
    deterministic: bool = False    # single worker, window=0, inline drive

    def __post_init__(self):
        if self.backpressure not in ("block", "reject"):
            raise ValueError("backpressure must be 'block' or 'reject', "
                             f"got {self.backpressure!r}")
        if self.host_workers < 1:
            raise ValueError("host_workers must be >= 1")
        if self.max_pending < 1 or self.max_ready < 1:
            raise ValueError("queue bounds must be >= 1")


@dataclasses.dataclass
class _Work:
    """One accepted intake item, before its host stage ran."""
    ticket: int
    kind: str                      # "submit" | "query"
    submitted_s: float             # intake time (latency includes queue wait)
    model: Optional[str] = None
    graph: Optional[Graph] = None
    graph_id: Optional[int] = None
    tier: Optional[str] = None
    fusion: Optional[str] = None
    deadline_ms: Optional[float] = None   # §14: relative to submitted_s
    tolerance: Optional[float] = None     # §14: tier-router budget (points)


# One ready-buffer entry: (arrival serial, arrival time, request). The
# serial is the FIFO tie-break best_fill_key sees; the arrival time anchors
# the key's batch window.
_Ready = Tuple[int, float, GNNRequest]


class PipelineScheduler:
    """Drives one GraphServe engine as a host/device pipeline.

    Use as a context manager (`with eng.scheduler(pc) as sched:`) or call
    `close()` explicitly; `drain()` blocks until every accepted request
    completed and returns them in ticket order. The sync engine API stays
    usable on the side — the scheduler only ever adds requests through the
    engine's prepare/_execute_batch stages, never through `engine.queue`.
    """

    def __init__(self, engine: GraphServe, pc: Optional[PipelineConfig] = None):
        pc = pc or PipelineConfig()
        if pc.deterministic:
            # reproducible batch composition: one worker (host order =
            # submission order) and no window (dispatch is a pure function
            # of the ready set, never of thread timing)
            pc = dataclasses.replace(pc, host_workers=1, window_ms=0.0)
        self.engine = engine
        self.pc = pc
        self.metrics = {"accepted": 0, "rejected": 0, "blocked": 0,
                        "completed": 0, "host_busy_s": 0.0}
        self._cond = threading.Condition()
        self._pending: Deque[_Work] = deque()
        self._ready: Dict[BatchKey, Deque[_Ready]] = {}
        self._ready_count = 0
        self._inflight_host = 0        # popped from intake, not yet ready
        self._arrival_serial = 0
        self._next_ticket = 0
        self._results: Dict[int, GNNRequest] = {}
        self._errors: Dict[int, BaseException] = {}
        self._closed = False
        self._threads: List[threading.Thread] = []
        if not pc.deterministic:
            for i in range(pc.host_workers):
                t = threading.Thread(target=self._host_loop,
                                     name=f"graphserve-host-{i}", daemon=True)
                t.start()
                self._threads.append(t)
            t = threading.Thread(target=self._dispatch_loop,
                                 name="graphserve-dispatch", daemon=True)
            t.start()
            self._threads.append(t)

    # ------------------------------------------------------------- intake
    def submit(self, g: Graph, *, model: str,
               tier: Optional[str] = None,
               fusion: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               tolerance: Optional[float] = None) -> int:
        """Enqueue a one-shot request; returns a ticket (see `drain`).
        `deadline_ms`/`tolerance` opt into the §14 SLO machinery — the
        deadline budget starts HERE, so intake queue wait spends it."""
        return self._accept(_Work(ticket=-1, kind="submit",
                                  submitted_s=self.engine.clock.now(),
                                  model=model, graph=g, tier=tier,
                                  fusion=fusion, deadline_ms=deadline_ms,
                                  tolerance=tolerance))

    def query(self, graph_id: int, *, tier: Optional[str] = None,
              fusion: Optional[str] = None,
              deadline_ms: Optional[float] = None,
              tolerance: Optional[float] = None) -> int:
        """Enqueue a query over an attached graph; returns a ticket."""
        return self._accept(_Work(ticket=-1, kind="query",
                                  submitted_s=self.engine.clock.now(),
                                  graph_id=graph_id, tier=tier,
                                  fusion=fusion, deadline_ms=deadline_ms,
                                  tolerance=tolerance))

    def _accept(self, w: _Work) -> int:
        gov = self.engine.governor
        with self._cond:
            if self._closed:
                raise RuntimeError("scheduler is closed")
            if gov is not None and gov.should_shed(len(self._pending)):
                # §14 governor shed: quality is already at the floor and
                # the queue keeps growing — drop through the existing
                # reject path regardless of the backpressure mode, counted
                # on both the scheduler and the engine
                self.metrics["rejected"] += 1
                self.engine._count("shed_requests")
                raise QueueFull(
                    f"SLO governor shedding at queue depth "
                    f"{len(self._pending)} (level {gov.level})")
            if len(self._pending) >= self.pc.max_pending:
                if self.pc.backpressure == "reject":
                    self.metrics["rejected"] += 1
                    raise QueueFull(
                        f"intake queue at max_pending={self.pc.max_pending}")
                self.metrics["blocked"] += 1
                if self.pc.deterministic:
                    # inline backpressure: advance the pipeline ourselves
                    # until intake has room (no threads to wait on)
                    while len(self._pending) >= self.pc.max_pending:
                        self._step_inline()
                else:
                    while (len(self._pending) >= self.pc.max_pending
                           and not self._closed):
                        self._cond.wait()
                    if self._closed:
                        raise RuntimeError("scheduler closed while blocked")
            w = dataclasses.replace(w, ticket=self._next_ticket)
            self._next_ticket += 1
            self._pending.append(w)
            self.metrics["accepted"] += 1
            self._cond.notify_all()
            return w.ticket

    # --------------------------------------------------------- host stage
    def _prepare(self, w: _Work) -> GNNRequest:
        if w.kind == "submit":
            return self.engine.prepare_submit(w.graph, model=w.model,
                                              tier=w.tier, fusion=w.fusion,
                                              submitted_s=w.submitted_s,
                                              deadline_ms=w.deadline_ms,
                                              tolerance=w.tolerance)
        return self.engine.prepare_query(w.graph_id, tier=w.tier,
                                         fusion=w.fusion,
                                         submitted_s=w.submitted_s,
                                         deadline_ms=w.deadline_ms,
                                         tolerance=w.tolerance)

    def _host_span(self):
        """The `host` span of one request's host stage, on the engine's
        clock and tracer; `host_busy_s` accumulates its length."""
        return self.engine.tracer.span("host", self.engine.clock)

    def _host_loop(self) -> None:
        while True:
            with self._cond:
                while not self._pending and not self._closed:
                    self._cond.wait()
                if not self._pending:
                    return                       # closed and drained
                w = self._pending.popleft()
                self._inflight_host += 1
                self._cond.notify_all()          # intake space freed
            req = err = None
            with self._host_span() as sp:
                try:
                    req = self._prepare(w)
                    sp.id = req.uid
                except BaseException as exc:     # noqa: BLE001 — recorded,
                    err = exc                    # re-raised by drain()
            with self._cond:
                self.metrics["host_busy_s"] += sp.end - sp.start
                if err is not None:
                    self._errors[w.ticket] = err
                    self._inflight_host -= 1
                    self.metrics["completed"] += 1
                    self._cond.notify_all()
                    continue
                while self._ready_count >= self.pc.max_ready and not self._closed:
                    self._cond.wait()            # ready full: block intake
                self._push_ready_locked(w.ticket, req)
                self._inflight_host -= 1
                self._cond.notify_all()

    def _push_ready_locked(self, ticket: int, req: GNNRequest) -> None:
        key = req.batch_key
        self._ready.setdefault(key, deque()).append(
            (self._arrival_serial, self.engine.clock.now(), req))
        self._arrival_serial += 1
        self._ready_count += 1
        self._results[ticket] = req

    # ------------------------------------------------------- device stage
    def _expire_ready_locked(self) -> int:
        """§14 expiry sweep over the ready buffer: requests whose deadline
        already passed complete flagged (engine `_complete_expired` —
        `deadline_missed=True`, no preds) instead of occupying batch
        slots. Returns how many were swept; callers re-check the ready
        count afterwards. Runs under `_cond`; the engine call takes the
        engine lock, which is always safe in this order (never the
        reverse)."""
        now = self.engine.clock.now()
        expired: List[GNNRequest] = []
        for key in list(self._ready):
            q = self._ready[key]
            keep = deque(item for item in q
                         if not (item[2].deadline_s is not None
                                 and item[2].deadline_s <= now))
            if len(keep) != len(q):
                expired.extend(item[2] for item in q
                               if item[2].deadline_s is not None
                               and item[2].deadline_s <= now)
                if keep:
                    self._ready[key] = keep
                else:
                    del self._ready[key]
        if expired:
            self._ready_count -= len(expired)
            self.engine._complete_expired(expired, now)
            self.metrics["completed"] += len(expired)
        return len(expired)

    def _select_locked(self) -> BatchKey:
        now = self.engine.clock.now()
        stats = {}
        for k, q in self._ready.items():
            slack = min((item[2].deadline_s - now
                         if item[2].deadline_s is not None else float("inf"))
                        for item in q)
            stats[k] = (len(q), q[0][0], slack)
        return edf_best_fill_key(stats, self.engine.sc.batch_slots,
                                 self.engine._last_dispatch,
                                 replica_slots=self.engine.sc.replica_groups)

    def _width(self, key: BatchKey) -> int:
        """Dispatch width of one batch key: sharded keys (§12) fill the
        replica rows of the mesh (§15; width-1 when `replica_groups` is
        1 — the shard axis occupies the dim a batch would use), unsharded
        keys fill the batch slots."""
        return (self.engine.sc.replica_groups if key[5]
                else self.engine.sc.batch_slots)

    def _take_locked(self, key: BatchKey) -> List[GNNRequest]:
        q = self._ready[key]
        n = min(self._width(key), len(q))
        batch = [q.popleft()[2] for _ in range(n)]
        if not q:
            del self._ready[key]
        self._ready_count -= n
        return batch

    def _dispatch_loop(self) -> None:
        window_s = self.pc.window_ms * 1e-3
        while True:
            with self._cond:
                batch = None
                while batch is None:
                    if self._ready_count == 0:
                        if (self._closed and not self._pending
                                and self._inflight_host == 0):
                            return
                        self._cond.wait()        # device idle: nothing ready
                        continue
                    if self._expire_ready_locked():
                        # §14: expired requests completed without a
                        # dispatch — ready space freed, re-evaluate
                        self._cond.notify_all()
                        continue
                    key = self._select_locked()
                    fill = len(self._ready[key])
                    unready = len(self._pending) + self._inflight_host
                    if (fill < self._width(key) and unready > 0
                            and window_s > 0):
                        # batch window: stragglers are still in the host
                        # stage — wait (bounded by the key's oldest arrival
                        # + window) for a fuller batch before going partial
                        deadline = self._ready[key][0][1] + window_s
                        now = self.engine.clock.now()
                        if now < deadline:
                            self._cond.wait(deadline - now)
                            continue
                    batch = self._take_locked(key)
                    self._cond.notify_all()      # ready space freed
            self.engine._execute_batch(batch)
            with self._cond:
                self.metrics["completed"] += len(batch)
                self._cond.notify_all()

    # ------------------------------------------------- deterministic drive
    def _step_inline(self) -> None:
        """Advance the inline pipeline by one step: prefer host work (FIFO),
        dispatch one best-fill batch when the ready buffer is full (or when
        only ready work remains). Deterministic mode only."""
        if self._pending and self._ready_count < self.pc.max_ready:
            w = self._pending.popleft()
            with self._host_span() as sp:
                req = self._prepare(w)           # inline: errors propagate
                sp.id = req.uid
            self.metrics["host_busy_s"] += sp.end - sp.start
            self._push_ready_locked(w.ticket, req)
            return
        if self._ready_count:
            self._expire_ready_locked()          # §14 sweep before select
        if self._ready_count:
            batch = self._take_locked(self._select_locked())
            self.engine._execute_batch(batch)
            self.metrics["completed"] += len(batch)

    # ------------------------------------------------------------ lifecycle
    def drain(self, timeout: Optional[float] = None) -> List[GNNRequest]:
        """Run/wait until every accepted request completed; return them in
        ticket order. Host-stage errors (earliest ticket first) are
        re-raised — and CONSUMED, so a caller that catches the error can
        call `drain()` again to retrieve the successfully completed
        requests (an errored ticket simply has no result)."""
        deadline = (time.perf_counter() + timeout
                    if timeout is not None else None)
        if self.pc.deterministic:
            while self._pending or self._ready_count:
                if deadline is not None and time.perf_counter() > deadline:
                    raise TimeoutError(
                        f"{len(self._pending) + self._ready_count} "
                        "request(s) still undispatched")
                self._step_inline()
        else:
            with self._cond:
                while self.metrics["completed"] < self.metrics["accepted"]:
                    left = (deadline - time.perf_counter()
                            if deadline is not None else None)
                    if left is not None and left <= 0:
                        raise TimeoutError(
                            f"{self.metrics['accepted'] - self.metrics['completed']}"
                            " request(s) still in flight")
                    self._cond.wait(left)
        if self._errors:
            errors, self._errors = self._errors, {}
            raise errors[min(errors)]
        return [self._results[t] for t in sorted(self._results)]

    def close(self) -> None:
        """Stop accepting, finish outstanding work, join the threads.
        Idempotent; the engine stays usable afterwards."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self.pc.deterministic:
            while self._pending or self._ready_count:
                self._step_inline()
        for t in self._threads:
            t.join()
        self._threads = []

    def __enter__(self) -> "PipelineScheduler":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -------------------------------------------------------------- metrics
    def summary(self) -> Dict[str, object]:
        """Engine summary (device_busy_s / dispatch_idle_fraction included)
        plus the pipeline's own counters under `"pipeline"`. Both are host
        clock around whole stages, not time the chip was busy."""
        s = self.engine.summary()
        s["pipeline"] = {
            "host_workers": self.pc.host_workers,
            "window_ms": self.pc.window_ms,
            "deterministic": self.pc.deterministic,
            "accepted": self.metrics["accepted"],
            "completed": self.metrics["completed"],
            "rejected": self.metrics["rejected"],
            "blocked": self.metrics["blocked"],
            "host_busy_s": self.metrics["host_busy_s"],
        }
        return s
