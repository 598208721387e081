"""Drive the system under test: GraphServe with its pipeline scheduler.

The benchmark builds one engine per run, registers the cell's model with
the benchmark's own weights, attaches the tenant graphs, warms the cell's
own shapes by serving its own kind of request, and then runs one measured
window through `PipelineScheduler.query`: the entry point a client calls.
Everything timed reads the engine's clock (`eng.clock`, the host's
`perf_counter`), the clock `GNNRequest.finished_s` is stamped on.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# a sleep never overshoots a due time by more than this, and the profiler
# window opens and closes within it
_TICK_S = 0.002
# the window's queues never block the generator: a refusal is a failure
_UNBOUNDED = 1 << 20


@dataclasses.dataclass
class Sent:
    tenant: int
    due: float                  # when the request was due (engine clock)
    sent: float                 # when the generator handed it over
    ticket: Optional[int]       # None: refused at intake


@dataclasses.dataclass
class Served:
    """What the benchmark keeps of one request after the engine is gone."""
    tenant: int
    due: float
    sent: float
    finished: Optional[float]   # None: never answered
    logits: Optional[np.ndarray]


def counters(eng, sch) -> Dict[str, float]:
    """The program's own counters: the scheduler's intake and host stage,
    the engine's device stage (host clock around `_execute_batch`)."""
    m, e = sch.metrics, eng.metrics
    return {"t": eng.clock.now(), "accepted": m["accepted"],
            "completed": m["completed"], "host_busy_s": m["host_busy_s"],
            "device_busy_s": e["device_busy_s"], "batches": e["batches"],
            "slots_filled": e["slots_filled"],
            "slots_total": e["slots_total"],
            "compiled_blobs": eng.compiled_blobs}


class Engine:
    """One GraphServe with one model and the cell's tenants attached. The
    configuration's optional `serving.server` group holds further
    `GraphServeConfig` fields (a cache budget, say) as they are named
    there."""

    def __init__(self, config: Dict, model, params, graphs: Sequence[Dict],
                 seed: int):
        from repro.core.graph import BucketLadder, Graph
        from repro.core.models import GNNConfig
        from repro.runtime.gnn_server import GraphServe, GraphServeConfig
        from repro.runtime.scheduler import PipelineConfig

        sv = config["serving"]
        self.tier = tier = sv["tier"]
        self.eng = GraphServe(
            GraphServeConfig(ladder=BucketLadder((sv["bucket"],)),
                             batch_slots=sv["batch_slots"],
                             return_logits=True, **sv.get("server", {})),
            seed=seed % (1 << 31))
        self.name = config["name"]
        self.eng.register_model(
            self.name, GNNConfig(**model.program_config(config)),
            params=params,
            tiers=("fp32",) if tier == "fp32" else ("fp32", tier),
            agg_backend=sv["agg_backend"], fusion=sv["fusion"])
        self.gids = [self.eng.attach(Graph(**g), model=self.name)
                     for g in graphs]
        self.pc = PipelineConfig(host_workers=sv["host_workers"],
                                 window_ms=sv["window_ms"],
                                 max_pending=_UNBOUNDED,
                                 max_ready=_UNBOUNDED, backpressure="reject")

    def warm(self, rounds: int = 2) -> None:
        """Serve the cell's own requests before the window: the first round
        misses CacheG once per tenant (operands built and made resident)
        and compiles the plan; the next runs the hit path the window runs."""
        with self.eng.scheduler(self.pc) as sch:
            for _ in range(rounds):
                for gid in self.gids:
                    sch.query(gid, tier=self.tier)
                sch.drain(timeout=1200)

    def scheduler(self):
        return self.eng.scheduler(self.pc)

    def query(self, sch, tenant: int) -> Optional[int]:
        from repro.runtime.scheduler import QueueFull
        try:
            return sch.query(self.gids[tenant], tier=self.tier)
        except QueueFull:
            return None


class Phases:
    """Marks the window, and opens the profiler over [start_at, stop_at)
    when asked. `tick` is called from the generator's loop; counters are
    read at every mark so host-clock metrics can leave the profiled part
    out."""

    def __init__(self, eng: Engine, sch,
                 profile: Optional[Tuple[float, float, str]],
                 backlog_every_s: Optional[float] = None):
        """`profile`: (start, stop) in seconds from the window's start, and
        the directory the profiler writes to; None profiles nothing.
        `backlog_every_s`: how often to sample the backlog (accepted -
        completed) into `backlog`; None samples nothing."""
        self.eng, self.sch = eng, sch
        self.profile = profile
        self.marks: Dict[str, Dict[str, float]] = {}
        self.backlog: List[Tuple[float, int]] = []   # (s into window, n)
        self._every = backlog_every_s
        self._ann = None
        self._t0 = 0.0
        self._next_sample = 0.0

    def begin(self, t0: float) -> None:
        self._t0 = self._next_sample = t0
        self.mark("window_start")

    def mark(self, name: str) -> None:
        self.marks[name] = counters(self.eng.eng, self.sch)

    def tick(self, now: float) -> None:
        if self._every is not None and now >= self._next_sample:
            m = self.sch.metrics
            self.backlog.append((now - self._t0,
                                 m["accepted"] - m["completed"]))
            self._next_sample += self._every
        if self.profile is None:
            return
        start, stop, logdir = self.profile
        start_at, stop_at = self._t0 + start, self._t0 + stop
        if self._ann is None and "profile_start" not in self.marks \
                and now >= start_at:
            import jax
            from . import trace
            jax.profiler.start_trace(logdir)
            self._ann = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
            self._ann.__enter__()
            self.mark("profile_start")
        elif self._ann is not None and now >= stop_at:
            self.stop()

    def stop(self) -> None:
        if self._ann is None:
            return
        import jax
        self.mark("profile_stop")
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()


def _wait_until(clock, phases: Phases, when: float) -> None:
    while True:
        now = clock.now()
        phases.tick(now)
        if now >= when:
            return
        time.sleep(min(when - now, _TICK_S))


def open_loop(eng: Engine, sch, phases: Phases,
              schedule: Sequence[Tuple[float, int]],
              seconds: float) -> Tuple[float, List[Sent]]:
    """Send each request when it is due, whatever the server is doing, and
    return when the window closes."""
    clock = eng.eng.clock
    sent: List[Sent] = []
    t0 = clock.now()
    phases.begin(t0)
    for offset, tenant in schedule:
        due = t0 + offset
        _wait_until(clock, phases, due)
        ticket = eng.query(sch, tenant)
        sent.append(Sent(tenant, due, clock.now(), ticket))
    _wait_until(clock, phases, t0 + seconds)
    return t0, sent


def closed_loop(eng: Engine, sch, phases: Phases, order: Sequence[int],
                in_flight: int, seconds: float) -> Tuple[float, List[Sent]]:
    """Keep `in_flight` requests outstanding until the window closes. The
    scheduler has no per-ticket completion notice, so the loop reads its
    public accepted/completed counts."""
    clock = eng.eng.clock
    sent: List[Sent] = []
    t0 = clock.now()
    end = t0 + seconds
    phases.begin(t0)
    i = 0
    while True:
        now = clock.now()
        if now >= end:
            break
        phases.tick(now)
        m = sch.metrics
        for _ in range(in_flight - (m["accepted"] - m["completed"])):
            tenant = order[i % len(order)]
            i += 1
            ticket = eng.query(sch, tenant)
            sent.append(Sent(tenant, now, clock.now(), ticket))
        time.sleep(_TICK_S)
    return t0, sent


def collect(sch, sent: List[Sent], drain_s: float
            ) -> Tuple[List[Served], int]:
    """Wait for every request sent (up to `drain_s` past the window), stop
    the scheduler's threads, and keep only host data: (served, host-stage
    errors). A host-stage error leaves every request unanswered, since the
    scheduler then hands results back without their tickets; a request
    that never ends leaves the threads running, to die with the process."""
    errors = 0
    done: List = []
    try:
        done = sch.drain(timeout=drain_s)
    except TimeoutError:
        pass
    except Exception:               # noqa: BLE001 — counted, run not correct
        errors = 1
        sch.close()
    else:
        sch.close()
    finished = dict(enumerate(done))
    out = []
    for s in sent:
        r = finished.get(s.ticket) if s.ticket is not None else None
        ok = r is not None and r.done and r.logits is not None
        out.append(Served(s.tenant, s.due, s.sent,
                          r.finished_s if ok else None,
                          np.array(r.logits) if ok else None))
    return out, errors


def peak_memory_bytes(devices) -> int:
    """Peak bytes in use on the fullest device, as the backend reports it
    (0 where it reports nothing, as the CPU does)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


class CompileCounter:
    """Counts JAX trace/lower/compile events while active: any inside the
    window means a shape was not warmed."""

    def __init__(self):
        from jax import monitoring
        self.active = False
        self.events: List[str] = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if self.active and event.startswith("/jax/core/compile/"):
            self.events.append(event)

    def close(self) -> None:
        from jax import monitoring
        self.active = False
        monitoring.unregister_event_duration_listener(self._on)
