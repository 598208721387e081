"""Benchmark harness entry point — one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--dataset cora]
                                          [--bench-json BENCH_gnn.json]
                                          [--only FAMILY]

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.record) and
writes benchmarks/results.json. ``--bench-json`` additionally writes the
serving-throughput, CacheG operand-bytes, quality-tier, pipeline-overlap,
grasp, fused-layer, sharded-serving, cache-pressure, and SLO-serving rows
to a standalone file (CI
uploads it as the ``BENCH_gnn`` artifact per push to track the perf
trajectory; the repo-root BENCH_gnn.json is a committed point-in-time
snapshot — schema in benchmarks/README.md). ``--only`` runs a single
benchmark family from the registry below (any family, not just the CI
legs); an unknown name lists the known ones. The roofline report
(§Roofline) is generated separately by launch/dryrun.py (needs the
512-device placeholder env).
"""
from __future__ import annotations

import argparse
import json
import os


def _families(args, datasets, gnn_paper, lm_subs):
    """`--only` registry: family name -> thunk running it with the SAME
    arguments the full suite would use (so an `--only` row is comparable
    to the corresponding full-run row). One entry per independent
    benchmark family; dataset-parameterized families honor --dataset."""
    q = args.quick
    return {
        "fig20": lambda: [gnn_paper.fig20_progressive(d) for d in datasets],
        "fig21": lambda: [gnn_paper.fig21_tile_scaling(d) for d in datasets],
        "fig22": lambda: [gnn_paper.fig22_path_comparison(d)
                          for d in datasets],
        "density_crossover": gnn_paper.fig22_density_crossover,
        "energy": lambda: [gnn_paper.energy_proxy(d) for d in datasets],
        "accuracy": lambda: [gnn_paper.accuracy_table(d) for d in datasets],
        "serving": gnn_paper.serving_throughput,
        "operand_pipeline": lambda: gnn_paper.operand_pipeline(
            cap=1024 if q else 2048, n_queries=4 if q else 6),
        "quality_tiers": lambda: gnn_paper.quality_tiers(
            epochs=12 if q else 60, n_queries=3 if q else 6),
        "pipeline_overlap": lambda: gnn_paper.pipeline_overlap(
            n_requests=16 if q else 24),
        "grasp_serving": lambda: gnn_paper.grasp_serving(
            cap=512 if q else 1024, n_queries=2 if q else 4),
        "fused_layers": lambda: gnn_paper.fused_layers(quick=q),
        "sharded_serving": lambda: gnn_paper.sharded_serving(quick=q),
        "partition_quality": lambda: gnn_paper.partition_quality(quick=q),
        "cache_pressure": lambda: gnn_paper.cache_pressure(quick=q),
        "slo_serving": lambda: gnn_paper.slo_serving(quick=q),
        "lm_subs": lambda: (lm_subs.ssd_vs_sequential(),
                            lm_subs.moe_dispatch_paths(),
                            lm_subs.serving_bucket_reuse()),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the slow accuracy-table training runs")
    ap.add_argument("--dataset", default="cora",
                    choices=["cora", "citeseer", "both"])
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "results.json"))
    ap.add_argument("--bench-json", default=None, metavar="BENCH_gnn.json",
                    help="also write the serving-throughput and CacheG "
                         "operand-bytes rows to this path (repo-root "
                         "BENCH_gnn.json in CI) for perf-trajectory tracking")
    ap.add_argument("--only", default=None, metavar="FAMILY",
                    help="run a single benchmark family (e.g. CI's "
                         "interpret leg runs `--only fused_layers`, the "
                         "multi-device leg `--only sharded_serving`); an "
                         "unknown name lists the registry")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import gnn_paper, lm_subs
    from .common import ROWS

    datasets = (["cora", "citeseer"] if args.dataset == "both"
                else [args.dataset])
    families = _families(args, datasets, gnn_paper, lm_subs)
    if args.only is not None and args.only not in families:
        ap.error(f"unknown benchmark family {args.only!r}; known families: "
                 f"{', '.join(sorted(families))}")
    print("name,us_per_call,derived")
    if args.only is not None:
        families[args.only]()
        _write(args, ROWS)
        return
    for ds in datasets:
        gnn_paper.fig20_progressive(ds)
        gnn_paper.fig22_path_comparison(ds)
        gnn_paper.fig21_tile_scaling(ds)
        gnn_paper.energy_proxy(ds)
        if not args.quick:
            gnn_paper.accuracy_table(ds)
    gnn_paper.fig22_density_crossover()
    gnn_paper.serving_throughput()
    # --quick drops to a 1024 rung so CI stays fast; the full run exercises
    # the paper-scale cap-2048 GAT case (2 x 16 MB eager masks per query)
    families["operand_pipeline"]()
    # quality tiers (DESIGN.md §8): short training in --quick mode — the
    # per-tier latency/bytes/accuracy-delta rows still land in BENCH_gnn.json
    families["quality_tiers"]()
    # async pipeline scheduler vs sync run() (DESIGN.md §9): online mixed
    # kind/bucket/tier stream; fewer requests in --quick keeps CI ~fast
    families["pipeline_overlap"]()
    # GraSp agg backend vs dense per density (DESIGN.md §10); the smaller
    # --quick rung still exercises the batched bitmap_spmm dispatch
    families["grasp_serving"]()
    # fused per-layer kernels vs per-op dispatch (DESIGN.md §11)
    families["fused_layers"]()
    # sharded serving of a partitioned giant graph (DESIGN.md §12):
    # throughput vs shard count with compressed halo exchange
    families["sharded_serving"]()
    # §15 partitioner quality, replica-group scaling, delta-halo bytes —
    # the acceptance asserts run IN the benchmark
    families["partition_quality"]()
    # bounded cache hierarchy under churn + GrAd delta updates
    # (DESIGN.md §13): eviction/spill-fault costs and delta-vs-rebuild
    families["cache_pressure"]()
    # SLO-aware serving (DESIGN.md §14): deadline hit-rate static vs
    # governed + measured-EWMA vs roofline-only backend routing
    families["slo_serving"]()
    families["lm_subs"]()
    _write(args, ROWS)


def _write(args, rows) -> None:
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"# wrote {len(rows)} rows -> {args.out}")

    if args.bench_json:
        perf = [r for r in rows
                if r["name"].startswith(("serve/", "operand_pipeline/",
                                         "quality_tiers/",
                                         "pipeline_overlap/",
                                         "grasp_serving/",
                                         "fused_layers/",
                                         "sharded_serving/",
                                         "partition_quality/",
                                         "cache_pressure/",
                                         "slo_serving/"))]
        with open(args.bench_json, "w") as f:
            json.dump({"rows": perf}, f, indent=1)
        print(f"# wrote {len(perf)} perf rows -> {args.bench_json}")


if __name__ == "__main__":
    main()
