#!/usr/bin/env python3
"""Find an open cell's knee, once, on the chip: the highest offered rate the
server sustains, that is, at which the backlog (requests accepted and not
completed, sampled every half second) averages no more than one batch
higher over the window's last third than over its first third.

    python3 bench/sweep.py --workload gcn-cora.open --closed-rps 40 --seconds 15 --seed 5

Offers 50% to 110% of the cell's closed-loop throughput in 10% steps, one
window each, on one engine set up once; prints a JSON line per rate and
the knee. The cell's rate is then 0.8 x knee, written into
`bench/cells/<cell>.json`. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

from benchlib import boot

BACKLOG_EVERY_S = 0.5


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--closed-rps", type=float, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    boot.prepare(args.rehearse)

    from benchlib import runner, serve, spec
    cell = spec.load_cell(boot.ROOT, args.workload)
    runner.device_info(cell, args.rehearse)
    config = runner.config_for(cell, args.rehearse)
    sv = config["serving"]
    gs, params = runner.make_inputs(cell, config, args.seed)
    with runner.matmul_precision(config["matmul_precision"]):
        knee = sweep(args, sv, cell.params,
                     serve.Engine(config, cell.model, params, gs, args.seed))
    print(json.dumps({"workload": args.workload, "knee_rps": knee,
                      "rate_rps": 0.8 * knee if knee else None}), flush=True)
    return 0


def sweep(args, sv, mix, eng) -> float:
    import numpy as np

    from benchlib import runner, serve, traffic
    eng.warm()
    knee = None
    for k, frac in enumerate(np.round(np.arange(0.5, 1.11, 0.1), 2)):
        rate = float(frac * args.closed_rps)
        sch = eng.scheduler()
        phases = serve.Phases(eng, sch, None,
                              backlog_every_s=BACKLOG_EVERY_S)
        t0, sent = traffic.drive(eng, sch, phases,
                                 {**mix, "rate_rps": rate}, sv["tenants"],
                                 args.seconds, args.seed + k)
        phases.mark("window_end")
        start, end = phases.marks["window_start"], phases.marks["window_end"]
        backlog = [m["accepted"] - m["completed"] for m in (start, end)]
        served, errors = serve.collect(sch, sent, runner.DRAIN_S)
        lat = np.array([s.finished - s.due for s in served
                        if s.finished is not None])
        thirds = [float(np.mean([n for t, n in phases.backlog
                                 if lo <= 3 * t / args.seconds < lo + 1]))
                  for lo in (0, 2)]
        ok = thirds[1] - thirds[0] <= sv["batch_slots"] and not errors
        if ok:
            knee = rate
        print(json.dumps({
            "frac": float(frac), "rate_rps": rate, "sent": len(sent),
            "backlog_start": backlog[0], "backlog_end": backlog[1],
            "backlog_first_third": thirds[0], "backlog_last_third": thirds[1],
            "sustained": ok, "errors": errors,
            "p50_ms": 1e3 * float(np.median(lat)) if lat.size else None,
            "p95_ms": 1e3 * float(np.percentile(lat, 95)) if lat.size else None,
            "dispatch_ms": 1e3 * (end["device_busy_s"] - start["device_busy_s"])
            / max(end["batches"] - start["batches"], 1)}), flush=True)
    return knee


if __name__ == "__main__":
    sys.exit(main())
