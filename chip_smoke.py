#!/usr/bin/env python3
"""GraphServe end to end on a TPU, through the entry points a user calls.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # four chips: the sharded path only

One chip: a `GraphServe` with the ladder (1024, 3072) registers the paper's
four models at their published Cora widths (`configs/gnn.py`: gcn, gat,
sage-mean, sage-max; 1433 -> 64 -> 7, 8 GAT heads, fan-out 10), each with
the fp32 / int8 / int8+grax tiers, GCN with `agg_backend="auto"`. It warms
every plan, attaches a Cora-shaped graph and queries it in every tier and
both fusion modes, routes one block-sparse graph through the GraSp backend,
sends submits through the pipeline scheduler, and checks `assert_warm()`.

Four chips: Cora auto-shards 4 ways (gcn, gat, sage-mean at fp32 and int8),
then gcn runs 2 replica groups x 2 shards on the 2x2 mesh. The placement
must be `shard_map` over 4 devices.

Every answer is checked against the edge-list reference
`core.models.forward_baseline` run on one device at "highest" matmul
precision: fp32 tiers by max abs error, quantized tiers by argmax agreement.
One JSON line per check; the last line is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
A failed check raises, so the script exits non-zero before that line. It
refuses to run off the chip, and refuses `REPRO_KERNEL_MODE` other than
`pallas`: either would swap the Pallas kernels for their reference twins.

CPU rehearsal at a tiny size (kernels in interpret mode; ends with
`rehearsal passed` and never prints the ok line):

    JAX_PLATFORMS=cpu REPRO_PALLAS_INTERPRET=1 python chip_smoke.py --tiny
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        REPRO_PALLAS_INTERPRET=1 python chip_smoke.py --tiny --chips 4

Every graph is generated from `--seed`; weights are random from the same
seed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

MODELS = ("gcn", "gat", "sage-mean", "sage-max")
TIERS = ("fp32", "int8", "int8+grax")
FUSIONS = ("none", "layer")

# fp32 tolerance, as a fraction of the reference's largest |logit|. The TPU
# runs an fp32 XLA matmul as one bf16 pass by default (8 significant bits,
# unit roundoff 2^-9 per operand), and a two-layer GNN chains four matmuls
# (combine, aggregate per layer): about 4 * 2 * 2^-9 = 1.6% of the
# magnitudes each sum runs through. 5% leaves room for cancellation and for
# the int8 halo wire of the sharded path (at most 1/254 of the exchanged
# tensor's absmax per layer), while a wrong head, mask, row or block gives
# errors of the order of the logits themselves.
FP32_TOL_REL = 5e-2
# Quantized tiers are approximations: their argmax must agree with the fp32
# reference on at least this share of nodes.
QUANT_AGREE_MIN = 0.9

TINY_FEATS = 48


def emit(row: dict) -> None:
    print(json.dumps(row, sort_keys=True), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def model_cfg(name: str, tiny: bool):
    from repro.configs.gnn import GNN_MODELS
    cfg = GNN_MODELS[name]()
    return dataclasses.replace(cfg, in_feats=TINY_FEATS) if tiny else cfg


def cora_graph(seed: int, tiny: bool, n_tiny: int):
    from repro.data.graphs import cora_like, planetoid_like
    if tiny:
        return planetoid_like(num_nodes=n_tiny, num_edges=2 * n_tiny,
                              num_feats=TINY_FEATS, num_classes=7, seed=seed)
    return cora_like(seed)


def reference_logits(cfg, params, g, pg) -> np.ndarray:
    """`forward_baseline` (edge-list gathers and segment sums) on one device
    over the same graph: GCN and GAT over its edges plus self loops, SAGE
    over the neighbourhoods the engine sampled — `sage_sample_adjacency` is
    seeded, so they are re-derived from the padded adjacency `pg.adj`."""
    import jax
    import jax.numpy as jnp

    from repro.core.graph import add_self_loops
    from repro.core.masks import sage_sample_adjacency
    from repro.core.models import forward_baseline
    n = g.num_nodes
    if cfg.kind == "sage":
        sample = sage_sample_adjacency(pg.adj, n,
                                       max_neighbors=cfg.max_neighbors)
        dst, src = np.nonzero(sample[:n, :n])
        ei = np.stack([src, dst]).astype(np.int32)
    else:
        ei = add_self_loops(g.edge_index, n)
    dev = jax.devices()[0]
    x = jax.device_put(jnp.asarray(g.features), dev)
    with jax.default_matmul_precision("highest"):
        out = jax.jit(forward_baseline, static_argnums=(1, 4))(
            params, cfg, x, jax.device_put(jnp.asarray(ei), dev), n)
    return np.asarray(out)


def compare(tier: str, got: np.ndarray, ref: np.ndarray) -> dict:
    """fp32: max abs error within FP32_TOL_REL of max|ref|; quantized
    tiers: argmax agreement of at least QUANT_AGREE_MIN."""
    check(got.shape == ref.shape, f"logits shape {got.shape} vs {ref.shape}")
    check(bool(np.isfinite(got).all()), "non-finite logits")
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    if tier == "fp32":
        err = float(np.abs(got - ref).max())
        tol = FP32_TOL_REL * float(np.abs(ref).max())
        check(err <= tol, f"fp32 max abs error {err} > tolerance {tol}")
        return {"max_abs_err": err, "tol": tol, "argmax_agreement": agree}
    check(agree >= QUANT_AGREE_MIN,
          f"{tier} argmax agreement {agree} < {QUANT_AGREE_MIN}")
    return {"argmax_agreement": agree, "min_agreement": QUANT_AGREE_MIN}


def served(eng, uid: int):
    eng.run()
    return next(r for r in eng.finished if r.uid == uid)


def one_chip(seed: int, tiny: bool, want_mode: str) -> None:
    from repro.core.graph import BucketLadder
    from repro.data.graphs import clustered_like
    from repro.kernels.ops import bitmap_spmm_mode
    from repro.runtime.gnn_server import GraphServe, GraphServeConfig
    from repro.runtime.scheduler import PipelineConfig

    small, top = (512, 640) if tiny else (1024, 3072)
    eng = GraphServe(GraphServeConfig(ladder=BucketLadder((small, top)),
                                      return_logits=True), seed=seed)
    for name in MODELS:
        eng.register_model(name, model_cfg(name, tiny), tiers=TIERS,
                           agg_backend="auto" if name == "gcn" else "dense")
    t0 = time.perf_counter()
    blobs = eng.warmup()
    warm_s = time.perf_counter() - t0
    emit({"phase": "warmup", "compiled_blobs": blobs, "warmup_s": warm_s,
          "buckets": [small, top]})

    g = cora_graph(seed, tiny, n_tiny=600)
    gids, refs = {}, {}
    for name in MODELS:
        gids[name] = eng.attach(g, model=name)
        e = eng.models[name]
        refs[name] = reference_logits(e.cfg, e.params, g,
                                      eng.graphs[gids[name]][1])
        for tier in TIERS:
            for fusion in FUSIONS:
                r = served(eng, eng.query(gids[name], tier=tier,
                                          fusion=fusion))
                s = eng.summary()
                row = {"phase": "serve", "model": name, "tier": tier,
                       "fusion": fusion, "bucket": r.bucket,
                       "kernel_mode": bitmap_spmm_mode(),
                       "backend": r.backend,
                       "backend_fallbacks": s["backend_fallbacks"],
                       "tier_fallbacks": s["tier_fallbacks"],
                       "compiled_blobs": s["compiled_blobs"],
                       "warmup_s": warm_s,
                       **compare(tier, r.logits, refs[name])}
                emit(row)
                check(row["kernel_mode"] == want_mode,
                      f"kernel mode {row['kernel_mode']} != {want_mode}")
                check(row["backend_fallbacks"] == 0, "backend fallback")
                check(row["tier_fallbacks"] == 0, "tier fallback")
                check((r.tier, r.fusion) == (tier, fusion),
                      f"served {(r.tier, r.fusion)}, asked {(tier, fusion)}")
                check(row["compiled_blobs"] == blobs, "recompiled")

    # A block-diagonal graph at the small rung: the auto rule must route it
    # through the GraSp block-skip kernel, fused and unfused.
    sparse = clustered_like(num_nodes=small, num_feats=eng.models[
        "gcn"].cfg.in_feats, num_classes=7, seed=seed)
    gid = eng.attach(sparse, model="gcn")
    e = eng.models["gcn"]
    ref = reference_logits(e.cfg, e.params, sparse, eng.graphs[gid][1])
    for fusion in FUSIONS:
        r = served(eng, eng.query(gid, tier="fp32", fusion=fusion))
        s = eng.summary()
        emit({"phase": "grasp", "model": "gcn", "fusion": fusion,
              "bucket": r.bucket, "backend": r.backend,
              "grasp_batches": s["grasp_batches"],
              "backend_fallbacks": s["backend_fallbacks"],
              **compare("fp32", r.logits, ref)})
        check(r.backend == "grasp", f"sparse graph served {r.backend}")
        check(s["backend_fallbacks"] == 0, "backend fallback")

    # One-shot submits through the pipeline scheduler (host workers +
    # dispatcher thread), every model, fp32 and int8.
    with eng.scheduler(PipelineConfig(host_workers=2, window_ms=2.0)) as sch:
        asked = [(name, tier) for name in MODELS for tier in ("fp32", "int8")]
        for name, tier in asked:
            sch.submit(g, model=name, tier=tier)
        done = sch.drain(timeout=600)
    check(len(done) == len(asked), f"{len(done)} of {len(asked)} submits")
    for (name, tier), r in zip(asked, done):
        emit({"phase": "submit", "model": name, "tier": r.tier,
              **compare(tier, r.logits, refs[name])})
        check((r.model, r.tier) == (name, tier), "submit routed elsewhere")
    eng.assert_warm()
    s = eng.summary()
    emit({"phase": "summary", "requests": s["requests"],
          "compiled_blobs": s["compiled_blobs"], "batches": s["batches"],
          "grasp_batches": s["grasp_batches"],
          "backend_fallbacks": s["backend_fallbacks"],
          "tier_fallbacks": s["tier_fallbacks"]})


def four_chips(seed: int, tiny: bool) -> None:
    from repro.core.graph import BucketLadder
    from repro.runtime.gnn_server import GraphServe, GraphServeConfig

    ladder = BucketLadder((128, 256)) if tiny else BucketLadder()
    g = cora_graph(seed, tiny, n_tiny=300)

    def sharded(eng, gid, shards, tiers, name):
        check(eng.summary()["shard_counts"].get(gid) == shards,
              f"{name}: not sharded {shards} ways")
        e = eng.models[name]
        ref = reference_logits(e.cfg, e.params, g, eng.graphs[gid][1])
        for tier in tiers:
            uids = [eng.query(gid, tier=tier)
                    for _ in range(eng.sc.replica_groups)]
            before = eng.summary()["sharded_batches"]
            eng.run()
            s = eng.summary()
            placed = s["sharded_placement"][shards]
            for uid in uids:
                r = next(x for x in eng.finished if x.uid == uid)
                emit({"phase": "sharded", "model": name, "tier": r.tier,
                      "shards": shards, "replicas": eng.sc.replica_groups,
                      "shard_cap": r.bucket, **placed,
                      "dispatches": s["sharded_batches"] - before,
                      **compare(tier, r.logits, ref)})
                check(r.tier == tier and r.shards == shards,
                      f"served {(r.tier, r.shards)}")
            check(placed == {"placement": "shard_map", "devices": 4},
                  f"placement {placed}")
            check(s["sharded_batches"] - before == 1,
                  "replica rows not packed into one dispatch")

    eng = GraphServe(GraphServeConfig(ladder=ladder, shard_counts=(4,),
                                      return_logits=True), seed=seed)
    for name in ("gcn", "gat", "sage-mean"):
        eng.register_model(name, model_cfg(name, tiny),
                           tiers=("fp32", "int8"))
        sharded(eng, eng.attach(g, model=name), 4, ("fp32", "int8"), name)

    eng = GraphServe(GraphServeConfig(ladder=ladder, shard_counts=(2,),
                                      replica_groups=2, return_logits=True),
                     seed=seed)
    eng.register_model("gcn", model_cfg("gcn", tiny), tiers=("fp32",))
    sharded(eng, eng.attach(g, model="gcn"), 2, ("fp32",), "gcn")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at a tiny size; never prints ok")
    args = ap.parse_args()

    forced = os.environ.get("REPRO_KERNEL_MODE", "")
    check(forced in ("", "pallas"),
          f"REPRO_KERNEL_MODE={forced!r} would bypass the Pallas kernels")
    check((SRC / "repro").is_dir(), f"no repro package under {SRC}")
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if not args.tiny:
        check(platform == "tpu", f"no TPU: JAX found {platform}")
    check(len(devs) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, "
          f"JAX found {len(devs)}")
    want_mode = "pallas" if platform == "tpu" else "interpret"
    if args.chips == 4:
        four_chips(args.seed, args.tiny)
    else:
        one_chip(args.seed, args.tiny, want_mode)
    if args.tiny:
        print("rehearsal passed", flush=True)
        return
    emit({"ok": True, "device": {"platform": platform,
                                 "kind": devs[0].device_kind,
                                 "count": len(devs)}})


if __name__ == "__main__":
    main()
