#!/usr/bin/env python3
"""GraphServe chip benchmark: one run of one cell.

    python3 bench/run.py --workload gcn-cora.closed --seed 7 --seconds 20 --trace 0

Sets up the cell named in BENCHMARK.json (graphs and weights from --seed),
warms its own shapes, measures one window of --seconds, checks every answer
against the plain reference, and prints as its last stdout line one JSON
object: correct, attempted, failed, metrics (the cell's end-to-end metrics,
or with --trace 1 its per-layer metrics), device, [breakdown], checks.
Each number compared for `correct` is also printed beside its limit as the
last lines of stderr. Exits non-zero, printing no result, without a TPU or
with fewer chips than the cell asks for.

CPU rehearsal at the configuration's tiny size, Pallas in interpret mode;
it prints a `rehearsal` line and never the result line:

    python3 bench/run.py --workload gcn-cora.open --seed 1 --seconds 2 --trace 0 --rehearse
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from benchlib import boot  # noqa: E402


def err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size; prints no result")
    args = ap.parse_args()
    try:
        err(f"compile cache: {boot.prepare(args.rehearse)}")
        from benchlib.runner import NoChip, run_cell
        try:
            result, side = run_cell(boot.ROOT, args.workload, args.seed,
                                    args.seconds, bool(args.trace),
                                    t_process=T_PROCESS,
                                    rehearse=args.rehearse, log=err)
        except NoChip as e:
            raise boot.Refused(str(e)) from e
    except boot.Refused as e:
        err(f"bench/run.py: {e}")
        return 3
    print(json.dumps({"run": side}), flush=True)
    for name, c in result["checks"].items():
        err(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    if args.rehearse:
        print(json.dumps({"rehearsal": result}), flush=True)
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
