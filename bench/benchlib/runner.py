"""One run of one cell: set up, measure one window, check, report.

    set-up   graphs and weights from the seed, engine, tenants attached,
             the cell's own requests served once per tenant and again
             (every operand resident, every program compiled or loaded)
    window   `seconds` of the cell's traffic through the pipeline
             scheduler; with trace on, the profiler covers its middle
    check    every answer of the window against the plain reference,
             which runs after the engine's state is freed
    report   each metric the cell lists, by its reader file

`setup_s` runs from process start to the window's start.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import shutil
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import check, graphs, serve, spec, trace, traffic

PROFILE_S = 4.0          # traced seconds, in the middle of the window
DRAIN_S = 60.0           # how long past the window an answer may come


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Context:
    """What a metric reader (`bench/metrics/<name>.py`) may read."""
    cell: spec.Cell
    config: Dict
    seconds: float
    setup_s: float
    window: Tuple[float, float]              # engine clock
    served: List[serve.Served]
    marks: Dict[str, Dict[str, float]]       # program counters at marks
    trace: Optional[trace.Summary]
    work: List[Tuple[float, float]]          # (ops, bytes) per tenant
    peaks: Optional[Dict]

    @property
    def profiled(self) -> Optional[Tuple[float, float]]:
        if "profile_stop" not in self.marks:
            return None
        return (self.marks["profile_start"]["t"],
                self.marks["profile_stop"]["t"])

    def delta(self, key: str) -> float:
        """Growth of a program counter over the window, less its growth
        over the profiled part: host-clock metrics leave tracing out."""
        d = self.marks["window_end"][key] - self.marks["window_start"][key]
        if self.profiled is not None:
            d -= (self.marks["profile_stop"][key]
                  - self.marks["profile_start"][key])
        return d

    def in_window(self, s: serve.Served, profiled: bool = False) -> bool:
        """Finished inside the window; outside (or inside, with
        `profiled`) the traced part."""
        if s.finished is None or not self.window[0] <= s.finished < self.window[1]:
            return False
        p = self.profiled
        inside = p is not None and p[0] <= s.finished < p[1]
        return inside if profiled else not inside

    def latencies(self) -> np.ndarray:
        """Seconds from due time to `finished_s` of every answered request
        due in the window."""
        t0, t1 = self.window
        return np.array([s.finished - s.due for s in self.served
                         if s.finished is not None and t0 <= s.due < t1])

    def least_seconds(self, tenant: int) -> Optional[float]:
        """Least device time of one request: its operations at the bf16
        peak or its bytes at HBM bandwidth, whichever is longer."""
        if self.peaks is None:
            return None
        ops, nbytes = self.work[tenant]
        return max(ops / self.peaks["bf16_flops_per_s"],
                   nbytes / self.peaks["hbm_bytes_per_s"])


def config_for(cell: spec.Cell, rehearse: bool) -> Dict:
    """The cell's configuration as run; a rehearsal overlays the file's
    tiny `rehearsal` sizes, group by group."""
    config = cell.config
    if not rehearse:
        return config
    out = dict(config)
    for k, v in config["rehearsal"].items():
        out[k] = {**config[k], **v} if isinstance(v, dict) else v
    return out


def _weight_key(seed: int):
    import jax
    return jax.random.PRNGKey(
        int(np.random.default_rng([int(seed), 2]).integers(0, 2**31 - 1)))


def make_inputs(cell: spec.Cell, config: Dict, seed: int):
    """Tenant graphs `seed`..`seed+tenants-1` and the weights."""
    gs = [graphs.planetoid_like(**config["graph"], seed=int(seed) + i)
          for i in range(config["serving"]["tenants"])]
    return gs, cell.model.init_params(_weight_key(seed), config)


@contextlib.contextmanager
def matmul_precision(precision: str):
    """Run the program at the matmul precision its configuration states.
    Process-wide, not a thread-local context, because the scheduler's own
    threads trace and run the plans; restored on the way out."""
    import jax
    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", precision)
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", before)


def device_info(cell: spec.Cell, rehearse: bool):
    import jax
    devices = jax.devices()
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
    if not rehearse:
        if d.platform != "tpu":
            raise NoChip(f"no accelerator: JAX found {d.platform}")
        if len(devices) < cell.chips:
            raise NoChip(f"cell needs {cell.chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:cell.chips], info


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             traced: bool, *, t_process: float, rehearse: bool = False,
             precision: Optional[str] = None, log=print) -> Tuple[Dict, Dict]:
    """(result line, side information: generator lateness and counts).
    `precision` overrides the configuration's matmul precision, for the
    control readings only."""
    cell = spec.load_cell(root, workload)
    config = config_for(cell, rehearse)
    with matmul_precision(precision or config["matmul_precision"]):
        return _run(cell, config, root, workload, seed, seconds, traced,
                    t_process, rehearse, log)


def _run(cell, config, root, workload, seed, seconds, traced, t_process,
         rehearse, log):
    used, info = device_info(cell, rehearse)
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    peaks = (spec.maybe_peaks(root, info["kind"]) if rehearse
             else spec.peaks_for(root, info["kind"]))
    sv = config["serving"]
    marks = [("start", t_process), ("devices", time.perf_counter())]
    gs, params = make_inputs(cell, config, seed)
    marks.append(("inputs", time.perf_counter()))
    eng = serve.Engine(config, cell.model, params, gs, seed)
    marks.append(("attach", time.perf_counter()))
    eng.warm()
    marks.append(("warm", time.perf_counter()))

    profile = None
    logdir = Path(root) / "chiprun_out" / "bench_traces" / f"{workload}.seed{seed}"
    if traced:
        shutil.rmtree(logdir, ignore_errors=True)
        p = min(PROFILE_S, seconds / 2)
        profile = ((seconds - p) / 2, (seconds + p) / 2, str(logdir))
    compiles = serve.CompileCounter()
    sch = eng.scheduler()
    phases = serve.Phases(eng, sch, profile)
    compiles.active = True
    t0, sent = traffic.drive(eng, sch, phases, cell.params, sv["tenants"],
                             seconds, seed)
    phases.stop()
    phases.mark("window_end")
    served, errors = serve.collect(sch, sent, DRAIN_S)
    compiles.close()
    new_traces = eng.eng.compiled_blobs - phases.marks["window_start"][
        "compiled_blobs"]
    memory_peak = serve.peak_memory_bytes(used)
    del eng, sch, phases.eng, phases.sch
    gc.collect()

    refs = check.reference_logits(cell.model, config, params, gs)
    cmp = check.compare(served, refs)
    summary = None
    if traced:
        path = trace.find_xplane(logdir)
        summary = trace.summarize(trace.load_xplane(path)) if path else None

    ctx = Context(cell=cell, config=config, seconds=seconds,
                  setup_s=t0 - t_process, window=(t0, t0 + seconds),
                  served=served, marks=phases.marks, trace=summary,
                  work=[cell.model.work(config, g["num_nodes"],
                                        g["edge_index"].shape[1]) for g in gs],
                  peaks=peaks)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = cell.reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    refused = sum(s.ticket is None for s in sent)
    unanswered = sum(s.finished is None for s in served)
    limit = config["correct"]["max_err_share"]
    checks = {
        "max_err_share": {"value": cmp["max_err_share"], "limit": limit},
        "unanswered": {"value": unanswered, "limit": 0},
        "host_errors": {"value": errors, "limit": 0},
        "new_traces_in_window": {"value": new_traces, "limit": 0},
        "compile_events_in_window": {"value": len(compiles.events),
                                     "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {**info, "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(sent),
              "failed": unanswered, "metrics": metrics,
              "device": device}
    if traced and summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks
    late = np.array([s.sent - s.due for s in sent]) if sent else np.zeros(1)
    lat = ctx.latencies()
    side = {"generator_late_ms": {"p50": 1e3 * float(np.median(late)),
                                  "p99": 1e3 * float(np.percentile(late, 99)),
                                  "max": 1e3 * float(late.max())},
            "latency_ms": ({f"p{q}": 1e3 * float(np.percentile(lat, q))
                            for q in (50, 90, 95, 99)} if lat.size else {}),
            "sent": len(sent), "refused": refused,
            "answered": len(served) - unanswered,
            "compared": cmp["compared"], "setup_s": t0 - t_process,
            "setup_parts_s": {b[0]: b[1] - a[1]
                              for a, b in zip(marks, marks[1:])}}
    return result, side
