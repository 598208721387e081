#!/usr/bin/env python3
"""Readings that set the limit of `correct`, on the chip, in one process.

    python3 bench/control.py --config gcn-cora --seeds 1,2,...,12 --control-seeds 1,2,3 [--program-steps]

For each seed, the program's error share (max |served - reference| / max
|reference|) over a short closed-loop window at the cell's own load, as a
benchmark run computes it: the lower reading is the largest of these. Then,
on the control seeds:

  control.high     the control: the plain reference with its products at
                   "high" (three bf16 passes), in the program's place; its
                   smallest reading is the upper one;
  program.high     with --program-steps: the program run at "high", the
                   same step taken inside it;
  program.default  with --program-steps: the program at JAX's default
                   precision (one bf16 pass on a TPU), the step a later
                   change would be tempted by.

Each prints one JSON line; the benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from benchlib import boot  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--program-steps", action="store_true",
                    help="on the control seeds, also run the program at "
                         "'high' and at the default precision")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    boot.prepare(args.rehearse)

    from benchlib import check, runner, spec
    workload = f"{args.config}.closed"
    cell = spec.load_cell(boot.ROOT, workload)
    config = runner.config_for(cell, args.rehearse)

    def program(seed, precision=None):
        res, side = runner.run_cell(boot.ROOT, workload, seed, args.seconds,
                                    False, t_process=time.perf_counter(),
                                    rehearse=args.rehearse,
                                    precision=precision, log=lambda m: None)
        return {"reading": "program." + (precision
                                         or config["matmul_precision"]),
                "seed": seed,
                "max_err_share": res["checks"]["max_err_share"]["value"],
                "compared": side["compared"], "answered": side["answered"],
                "device": res["device"]["kind"]}

    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(program(seed)), flush=True)
    for seed in (int(s) for s in args.control_seeds.split(",") if s):
        gs, params = runner.make_inputs(cell, config, seed)
        with runner.matmul_precision(config["matmul_precision"]):
            ref = check.reference_logits(cell.model, config, params, gs)
            low = check.reference_logits(cell.model, config, params, gs,
                                         precision="high")
        print(json.dumps({"reading": "control.high", "seed": seed,
                          "max_err_share": max(check.err_share(a, b)
                                               for a, b in zip(low, ref))}),
              flush=True)
        if args.program_steps:
            print(json.dumps(program(seed, "high")), flush=True)
            print(json.dumps(program(seed, "default")), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
