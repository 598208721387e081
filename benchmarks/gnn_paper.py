"""Paper-table reproductions (one function per table/figure).

Fig. 20 — progressive technique speedups per model (GCN / GAT / SAGE-max).
Fig. 21 — Series-1 vs Series-2 NPU: analytic MXU-tile-count scaling.
Fig. 22 — CPU vs GPU vs NPU: gather-path vs dense-path on one backend.
Fig. 23 / energy — bytes-moved proxy (no power rails on CPU).
Accuracy table — FP32 vs QuantGr vs GrAx accuracies per model.
Serving — GraphServe engine throughput over mixed-size multi-graph traffic.
CacheG — `operand_pipeline`: host→device operand bytes + per-query latency,
the SymG compact transfer vs a directed graph's eager dense upload, both
cached on the device (DESIGN.md §7).
Tiers — `quality_tiers`: per-tier (fp32 / int8 / int8+grax) latency, operand
bytes, and accuracy delta through GraphServe (DESIGN.md §8).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.gnn import GNN_MODELS
from repro.core.graph import add_self_loops, pad_graph
from repro.core.layers import Techniques
from repro.core.models import (GNNConfig, build_operands, calibrate_quant,
                               derive_tier_operands, evaluate,
                               forward_baseline, forward_grannite,
                               init_params, train_node_classifier)
from repro.core.sparsity import sparsity_report
from repro.data.graphs import cora_like, citeseer_like

from .common import record, time_fn
from .tpu_model import analyze as tpu_analyze

KEY = jax.random.PRNGKey(0)


def _setup(kind: str, dataset: str = "cora", **cfg_kw):
    g = cora_like() if dataset == "cora" else citeseer_like()
    pg = pad_graph(g)
    cfg = GNN_MODELS[kind](dataset)
    if cfg_kw:
        cfg = dataclasses.replace(cfg, **cfg_kw)
    params = init_params(KEY, cfg)
    return g, pg, cfg, params


# ------------------------------------------------------------------ Fig 20


def fig20_progressive(dataset: str = "cora") -> List[Dict]:
    """Cumulative technique stacks per model; speedup over the baseline
    (out-of-the-box gather/scatter mapping).

    Two columns per stack:
      * cpu  — measured CPU wall-clock (honest, but CPUs are GOOD at gathers
               — the paper's own Fig. 8 premise — so the ordering inverts);
      * tpu  — modelled accelerator latency from the compiled HLO
               (benchmarks.tpu_model): MXU-rate dense FLOPs, full-HBM bytes,
               gather/scatter bytes at the serialized DSP-analogue rate.
               This column carries the paper's comparison.
    """
    rows = []

    def _bench(label, fn, *args, base=None):
        ti = time_fn(fn, *args)
        mi = tpu_analyze(fn, *args)["t_model_s"]
        if base is None:
            rows.append(record(label, ti, f"tpu_model={mi*1e6:.0f}us 1.00x"))
        else:
            t0, m0 = base
            rows.append(record(
                label, ti,
                f"cpu {t0/ti:.2f}x | tpu_model {m0/mi:.2f}x"))
        return ti, mi

    # --- GCN: baseline -> +StaGr/GraphSplit -> +GrAd/NodePad -> +GraSp ->
    #          +QuantGr  (paper: 1.51x -> 1.4x -> 1.1x -> 2.7x)
    g, pg, cfg, params = _setup("gcn", dataset)
    x = jnp.asarray(pg.features)
    ei = jnp.asarray(add_self_loops(g.edge_index, g.num_nodes))
    ops_ = build_operands(pg, cfg, grasp=True)
    ops_q = dataclasses.replace(ops_, quant=calibrate_quant(params, cfg, x, ops_))

    base = jax.jit(lambda p, xx: forward_baseline(p, cfg, xx, ei, pg.capacity))
    b = _bench(f"fig20/gcn/{dataset}/baseline", base, params, x)

    stacks = [
        ("stagr+graphsplit", ops_, Techniques(stagr=True, graphsplit=True)),
        ("grad+nodepad", ops_, Techniques(stagr=True, graphsplit=True,
                                          grad_dynamic=True)),
        ("grasp", ops_, Techniques(stagr=True, graphsplit=True,
                                   grad_dynamic=True, grasp=True)),
        ("quantgr", ops_q, Techniques(stagr=True, graphsplit=True,
                                      grad_dynamic=True, quantgr=True)),
    ]
    for name, op, t in stacks:
        if t.grad_dynamic:
            # GrAd: Â is an argument (runtime input)
            fn = jax.jit(lambda p, xx, na: forward_grannite(
                p, cfg, xx, dataclasses.replace(op, norm_adj=na), t))
            _bench(f"fig20/gcn/{dataset}/{name}", fn, params, x, op.norm_adj,
                   base=b)
        else:
            fn = jax.jit(lambda p, xx: forward_grannite(p, cfg, xx, op, t))
            _bench(f"fig20/gcn/{dataset}/{name}", fn, params, x, base=b)

    # --- GAT: baseline -> EffOp -> +GrAx1 -> +GrAx2  (paper: 3x -> 7.6x)
    g, pg, cfg, params = _setup("gat", dataset)
    x = jnp.asarray(pg.features)
    ei = jnp.asarray(add_self_loops(g.edge_index, g.num_nodes))
    ops_ = build_operands(pg, cfg)
    base = jax.jit(lambda p, xx: forward_baseline(p, cfg, xx, ei, pg.capacity))
    b = _bench(f"fig20/gat/{dataset}/baseline", base, params, x)
    for name, t in [
            ("effop", Techniques(effop=True)),
            ("effop+grax1", Techniques(effop=True, grax1=True)),
            ("effop+grax1+grax2", Techniques(effop=True, grax1=True,
                                             grax2=True))]:
        fn = jax.jit(lambda p, xx: forward_grannite(p, cfg, xx, ops_, t))
        _bench(f"fig20/gat/{dataset}/{name}", fn, params, x, base=b)

    # --- SAGE-max: baseline -> EffOp -> GrAx3 (paper: 2x -> 3.2x; NOT cumulative)
    g, pg, cfg, params = _setup("sage-max", dataset)
    x = jnp.asarray(pg.features)
    ei = jnp.asarray(g.edge_index)
    ops_ = build_operands(pg, cfg)
    base = jax.jit(lambda p, xx: forward_baseline(p, cfg, xx, ei, pg.capacity))
    b = _bench(f"fig20/sage-max/{dataset}/baseline", base, params, x)
    for name, t in [("effop", Techniques(effop=True)),
                    ("grax3", Techniques(effop=True, grax3=True))]:
        fn = jax.jit(lambda p, xx: forward_grannite(p, cfg, xx, ops_, t))
        _bench(f"fig20/sage-max/{dataset}/{name}", fn, params, x, base=b)
    return rows


# ------------------------------------------------------------------ Fig 22


def fig22_path_comparison(dataset: str = "cora") -> List[Dict]:
    """Gather/scatter path (the CPU/DSP-analogue) vs GraNNite dense path
    (the NPU/MXU-analogue) for all three layer types. The `cpu` column is
    the measured host wall-clock of each path (the paper's CPU bar); the
    `tpu_model` column prices the same compiled HLOs with accelerator
    constants (the paper's NPU bar)."""
    rows = []
    for kind in ("gcn", "gat", "sage-mean"):
        g, pg, cfg, params = _setup(kind, dataset)
        x = jnp.asarray(pg.features)
        ei = jnp.asarray(add_self_loops(g.edge_index, g.num_nodes)
                         if kind != "sage-mean" else g.edge_index)
        ops_ = build_operands(pg, cfg)
        t = {"gcn": Techniques(stagr=True),
             "gat": Techniques.full_gat(),
             "sage-mean": Techniques.full_sage()}[kind]
        slow = jax.jit(lambda p, xx: forward_baseline(p, cfg, xx, ei,
                                                      pg.capacity))
        fast = jax.jit(lambda p, xx: forward_grannite(p, cfg, xx, ops_, t))
        ts = time_fn(slow, params, x)              # CPU executes the model
        mf = tpu_analyze(fast, params, x)["t_model_s"]   # NPU-analogue
        ms = tpu_analyze(slow, params, x)["t_model_s"]
        rows.append(record(f"fig22/{kind}/{dataset}/cpu_gather_path", ts,
                           "1.00x (measured host)"))
        rows.append(record(
            f"fig22/{kind}/{dataset}/tpu_dense_path", mf,
            f"{ts/mf:.2f}x vs cpu | {ms/mf:.2f}x vs gather-on-accel"))
    return rows


# ------------------------------------------------------------------ Fig 21


def fig21_tile_scaling(dataset: str = "cora") -> List[Dict]:
    """Series-1 (2 NPU tiles) vs Series-2 (4 tiles): analytic roofline-model
    throughput scaling for GCN under the full GraNNite stack. CacheG keeps
    Â and the weights SRAM-resident (the paper's own technique — implemented
    on the serving path by the operand pipeline, DESIGN.md §7 and
    `operand_pipeline` below), so DRAM traffic is activations only; compute
    scales with tile count. The paper
    observes 1.7x (not the ideal 2x) because the small graph leaves the
    wider part partially idle — reproduced here as the memory-bound floor
    that does NOT scale with tiles."""
    rows = []
    g = cora_like() if dataset == "cora" else citeseer_like()
    pg = pad_graph(g)
    n, f, h = pg.capacity, g.features.shape[1], 64
    flops = 2.0 * n * f * h + 2.0 * n * n * h          # combine + aggregate
    # QuantGr int8 datapath; CacheG: only activations cross DRAM
    bytes_dram = n * f + n * h * 2                     # x in, h/out streams
    for name, tiles in (("series1", 2), ("series2", 4)):
        peak = 8e12 * tiles            # int8 MAC/s per tile (FlexNN-class)
        bw = 68e9                      # LPDDR5x-class shared bandwidth
        t_comp = flops / peak
        t_mem = bytes_dram / bw
        t = max(t_comp, t_mem)
        rows.append(record(f"fig21/gcn/{dataset}/{name}", t,
                           f"tiles={tiles} comp={t_comp*1e6:.0f}us "
                           f"mem={t_mem*1e6:.0f}us"))
    r = rows[0]["us_per_call"] / rows[1]["us_per_call"]
    record(f"fig21/gcn/{dataset}/scaling", 0.0,
           f"{r:.2f}x (paper: 1.7x; ideal 2x broken by the mem floor)")
    return rows


# ------------------------------------------------------------- accuracy


def accuracy_table(dataset: str = "cora", epochs: int = 100) -> List[Dict]:
    """Top-1 accuracy per model: FP32 dense path, QuantGr INT8 (GCN), and
    GrAx approximations (GAT / SAGE-max). Paper baselines: GCN 80.8, GAT
    81.3, SAGE-max 79.3, SAGE-mean 75.5 (real Cora; ours is shape-matched
    synthetic, so ABSOLUTE numbers differ — the DELTAS are the claim)."""
    rows = []
    for kind in ("gcn", "gat", "sage-max", "sage-mean"):
        g, pg, cfg, _ = _setup(kind, dataset)
        ops_ = build_operands(pg, cfg)
        t_plain = {"gcn": Techniques(stagr=True),
                   "gat": Techniques(effop=True),
                   "sage-max": Techniques(effop=True),
                   "sage-mean": Techniques(stagr=True)}[kind]

        def fwd(p, x, _t=t_plain, _c=cfg, _o=ops_):
            return forward_grannite(p, _c, x, _o, _t)

        if kind.startswith("sage"):
            # train on the edge-list path (the paper trains in PyG and
            # deploys; the dense F=1433 masked-max is an inference form —
            # training it on one CPU core is prohibitive), evaluate the
            # DEPLOYED dense path: the deltas are the paper's claim
            ei = jnp.asarray(g.edge_index)

            def fwd_train(p, x, _c=cfg, _e=ei, _n=pg.capacity):
                return forward_baseline(p, _c, x, _e, _n)
        else:
            fwd_train = fwd

        params = train_node_classifier(KEY, cfg, pg, fwd_train, epochs=epochs)
        acc = evaluate(cfg, params, pg, fwd)
        rows.append(record(f"accuracy/{kind}/{dataset}/fp32", 0.0,
                           f"{acc:.4f}"))

        if kind == "gcn":
            x = jnp.asarray(pg.features)
            ops_q = dataclasses.replace(
                ops_, quant=calibrate_quant(params, cfg, x, ops_))
            t_q = Techniques(stagr=True, quantgr=True)

            def fwd_q(p, xx):
                return forward_grannite(p, cfg, xx, ops_q, t_q)

            acc_q = evaluate(cfg, params, pg, fwd_q)
            rows.append(record(f"accuracy/{kind}/{dataset}/quantgr_int8", 0.0,
                               f"{acc_q:.4f} (delta {acc_q-acc:+.4f})"))
        if kind in ("gat", "sage-max"):
            t_x = (Techniques(effop=True, grax1=True, grax2=True)
                   if kind == "gat" else Techniques(effop=True, grax3=True))

            def fwd_x(p, xx):
                return forward_grannite(p, cfg, xx, ops_, t_x)

            acc_x = evaluate(cfg, params, pg, fwd_x)
            rows.append(record(f"accuracy/{kind}/{dataset}/grax", 0.0,
                               f"{acc_x:.4f} (delta {acc_x-acc:+.4f})"))
    return rows


def fig22_density_crossover() -> List[Dict]:
    """Where the dense-masked rewrite wins even at BYTES granularity.

    At Cora's sparsity (≈0.14% density) the dense GAT multiplies FLOPs
    ~500× over the edge-list form, so a bandwidth-only accelerator model
    shows an inversion — the paper's GAT win comes from the DSP being
    *latency*-bound on serialized gathers, which a bytes-rate model is too
    conservative to capture. This sweep raises edge density and shows the
    crossover where the dense path wins under our conservative model too —
    the technique's win condition (E·F comparable to N²)."""
    import numpy as np
    from repro.data.graphs import planetoid_like
    rows = []
    n = 1024
    for avg_deg in (4, 32, 128):
        g = planetoid_like(num_nodes=n, num_edges=n * avg_deg // 2,
                           num_feats=64, num_classes=5, seed=3)
        pg = pad_graph(g)
        cfg = GNNConfig(kind="gat", in_feats=64, hidden=32, num_classes=5,
                        heads=4)
        params = init_params(KEY, cfg)
        x = jnp.asarray(pg.features)
        ei = jnp.asarray(add_self_loops(g.edge_index, g.num_nodes))
        ops_ = build_operands(pg, cfg)
        slow = jax.jit(lambda p, xx: forward_baseline(p, cfg, xx, ei,
                                                      pg.capacity))
        fast = jax.jit(lambda p, xx: forward_grannite(
            p, cfg, xx, ops_, Techniques.full_gat()))
        ms = tpu_analyze(slow, params, x)["t_model_s"]
        mf = tpu_analyze(fast, params, x)["t_model_s"]
        rows.append(record(
            f"fig22x/gat/deg{avg_deg}/dense_vs_gather", mf,
            f"{ms/mf:.2f}x (gather path {ms*1e6:.0f}us)"))
    return rows


# ------------------------------------------------------------- serving


def serving_throughput(dataset: str = "cora", *, n_requests: int = 12,
                       seed: int = 0) -> List[Dict]:
    """GraphServe engine under mixed-size multi-tenant traffic.

    Submits `n_requests` graphs of varied sizes across a 3-rung NodePad
    ladder for two model kinds, warms the (kind, bucket) plan cache, then
    drains the queue batched; reports requests/s, p50/p99 latency, the
    compiled-blob count, and batch occupancy. The zero-recompile contract
    (`assert_warm`) is enforced, not just measured.
    """
    from repro.core.graph import BucketLadder
    from repro.data.graphs import planetoid_like
    from repro.runtime.gnn_server import GraphServe, GraphServeConfig

    rng = np.random.default_rng(seed)
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(128, 256, 384)),
                          batch_slots=4)
    eng = GraphServe(sc, seed=seed)
    in_feats, classes = 64, 7
    eng.register_model("gcn", GNNConfig(kind="gcn", in_feats=in_feats,
                                        hidden=64, num_classes=classes))
    eng.register_model("gat", GNNConfig(kind="gat", in_feats=in_feats,
                                        hidden=64, num_classes=classes,
                                        heads=8))
    eng.warmup()

    for i in range(n_requests):
        n = int(rng.integers(48, 380))
        g = planetoid_like(num_nodes=n, num_edges=3 * n, num_feats=in_feats,
                           num_classes=classes, seed=seed + i,
                           train_per_class=2)
        eng.submit(g, model="gcn" if i % 2 == 0 else "gat")
    eng.run()
    eng.assert_warm()

    s = eng.summary()
    rows = [
        record(f"serve/gnn/{dataset}/throughput_rps", 0.0,
               f"{s['throughput_rps']:.1f} requests/s over "
               f"{s['requests']} mixed-size graphs"),
        record(f"serve/gnn/{dataset}/latency", s["p50_latency_ms"] * 1e-3,
               f"p50={s['p50_latency_ms']:.1f}ms p99="
               f"{s['p99_latency_ms']:.1f}ms"),
        record(f"serve/gnn/{dataset}/compiled_blobs", 0.0,
               f"{s['compiled_blobs']} (= kinds x buckets x (2 fusion-mode "
               f"plans + CacheG materializer + GrAd delta patcher), zero "
               f"recompiles after warmup)"),
        record(f"serve/gnn/{dataset}/batch_occupancy", 0.0,
               f"{s['batch_occupancy']:.2f} of {sc.batch_slots} slots"),
        record(f"serve/gnn/{dataset}/operand_bytes_h2d", 0.0,
               f"{s['operand_bytes_h2d']} B (CacheG compact transfer, "
               f"{s['cacheg_fallbacks']} fallbacks)"),
    ]
    return rows


def operand_pipeline(dataset: str = "cora", *, cap: int = 2048,
                     n_queries: int = 6, seed: int = 0) -> List[Dict]:
    """CacheG compact transfer vs the eager fallback (DESIGN.md §7).

    Attaches one graph at a `cap`-capacity rung and queries it repeatedly
    with GAT, twice: undirected ("cacheg"), and with one extra edge whose
    reverse is absent ("eager"). The undirected graph uploads one SymG
    bit-packed adjacency on its first query and materializes the two
    dense (cap, cap) float32 masks on the device; the directed one cannot
    take SymG, so its masks are built on the host and uploaded (2 x 16 MB
    at cap=2048). Both are cached per structure version, so every later
    query moves zero operand bytes either way. Reports bytes moved,
    hit/miss counts, and per-query wall-clock for both; the paper's Fig. 21
    scaling argument (only activations cross DRAM) rests on exactly this
    pipeline.
    """
    import time as _time

    from repro.core.graph import BucketLadder, Graph
    from repro.data.graphs import planetoid_like
    from repro.runtime.gnn_server import GraphServe, GraphServeConfig

    n = int(cap * 3 / 4)
    g = planetoid_like(num_nodes=n, num_edges=3 * n, num_feats=16,
                       num_classes=5, seed=seed, train_per_class=2)
    adj = np.zeros((n, n), bool)
    adj[g.edge_index[1], g.edge_index[0]] = True
    i, j = np.argwhere(~adj & ~adj.T & ~np.eye(n, dtype=bool))[0]
    directed = Graph(edge_index=np.concatenate(
        [g.edge_index, np.array([[i], [j]], np.int32)], axis=1),
        num_nodes=n, features=g.features)
    rows, stats = [], {}
    for mode, graph in (("eager", directed), ("cacheg", g)):
        sc = GraphServeConfig(ladder=BucketLadder(buckets=(cap,)),
                              batch_slots=2)
        eng = GraphServe(sc, seed=seed)
        eng.register_model("gat", GNNConfig(kind="gat", in_feats=16,
                                            hidden=16, num_classes=5,
                                            heads=4))
        eng.warmup()
        gid = eng.attach(graph, model="gat")
        t0 = _time.perf_counter()
        for _ in range(n_queries):
            eng.query(gid)
            eng.run()
        wall = _time.perf_counter() - t0
        eng.assert_warm()
        s = eng.summary()
        stats[mode] = s
        rows.append(record(
            f"operand_pipeline/{mode}/cap{cap}/query", wall / n_queries,
            f"{s['operand_bytes_h2d']} operand B h2d over {n_queries} "
            f"queries (hits={s['operand_cache_hits']} "
            f"misses={s['operand_cache_misses']})"))
    ratio = (stats["eager"]["operand_bytes_h2d"]
             / max(stats["cacheg"]["operand_bytes_h2d"], 1))
    rows.append(record(
        f"operand_pipeline/cap{cap}/bytes_reduction", 0.0,
        f"{ratio:.0f}x fewer host->device operand bytes with CacheG"))
    return rows


def quality_tiers(dataset: str = "cora", *, epochs: int = 60,
                  n_queries: int = 6, seed: int = 0) -> List[Dict]:
    """Quality-tier serving table (DESIGN.md §8): per-tier latency, operand
    bytes, and accuracy delta vs fp32 for GCN / GAT / SAGE-max through one
    warm GraphServe engine — the latency/quality frontier Step 3 trades on.

    Columns: `us_per_call` is the ACCELERATOR-MODEL per-forward latency of
    the tier's compiled plan (benchmarks.tpu_model: int8 dots at the 2x MXU
    rate, s8 operand bytes — QuantGr's claim; same convention as the
    analytic fig21 rows), because that is the latency column the tier
    frontier is judged on. The measured host wall-clock rides in `derived`
    as `host_p50=`: CPUs have no int8 GEMM path (XLA widens s8 dots to
    s32), so the measured int8 rows invert on CPU — the same caveat as
    fig20, where the tpu_model column also carries the comparison.
    `derived` further reports `acc_delta` (percentage points vs the fp32
    tier on the held-out split) and `bytes_h2d` (operand bytes this tier's
    queries moved — 0 after the shared CacheG entry materializes, whichever
    tier paid the miss).
    """
    from repro.core.graph import BucketLadder
    from repro.data.graphs import planetoid_like
    from repro.runtime.gnn_server import (STANDARD_TIERS, GraphServe,
                                          GraphServeConfig, tier_techniques)

    in_feats, classes, n = 64, 7, 200
    g = planetoid_like(num_nodes=n, num_edges=3 * n, num_feats=in_feats,
                       num_classes=classes, seed=seed, train_per_class=5)
    rows = []
    for kind in ("gcn", "gat", "sage"):
        cfg = GNNConfig(kind=kind, in_feats=in_feats, hidden=64,
                        num_classes=classes, heads=8,
                        aggregator="max" if kind == "sage" else "mean")
        sc = GraphServeConfig(ladder=BucketLadder(buckets=(256,)),
                              batch_slots=1)
        eng = GraphServe(sc, seed=seed)

        # train the fp32 dense path, then serve the SAME params per tier
        pg = eng.sc.ladder.pad(g)
        ops_ = build_operands(pg, cfg, lean=True)
        t_fp32 = tier_techniques(kind)["fp32"]

        def fwd(p, x, _c=cfg, _o=ops_, _t=t_fp32):
            return forward_grannite(p, _c, x, _o, _t)

        params = train_node_classifier(KEY, cfg, pg, fwd, epochs=epochs)

        eng.register_model(kind, cfg, params, tiers=STANDARD_TIERS)
        eng.warmup()
        gid = eng.attach(g, model=kind)         # calibrate + quality audit
        e = eng.models[kind]
        x1 = jnp.asarray(pg.features)

        base_model_s = None
        for tier in STANDARD_TIERS:
            t = e.tiers[tier]
            cal = e.calibrations.get(tier)
            # price the forward the engine actually serves: for QuantGr GCN
            # tiers the cached int8 Â enters as a runtime INPUT (1-byte
            # rows), exactly like the device-resident tier cache feeds it
            tops = (derive_tier_operands(jnp.asarray(ops_.norm_adj))
                    if (kind == "gcn" and t.quantgr) else None)
            mi = tpu_analyze(
                lambda xx, _t=t, _q=cal, _to=tops: forward_grannite(
                    params, cfg, xx, ops_, _t, quant=_q, tier_ops=_to),
                x1)["t_model_s"]
            if base_model_s is None:
                base_model_s = mi
            b0 = eng.metrics["operand_bytes_h2d"]
            for _ in range(n_queries):
                eng.query(gid, tier=tier)
                eng.run()
            db = eng.metrics["operand_bytes_h2d"] - b0
            p50_s = eng.summary()["tiers"][tier]["p50_latency_ms"] * 1e-3
            delta = e.accuracy_delta.get(tier, 0.0)
            rows.append(record(
                f"quality_tiers/{kind}/{dataset}/{tier}", mi,
                f"{base_model_s/mi:.2f}x vs fp32 (tpu_model) "
                f"host_p50={p50_s*1e6:.0f}us (CPU, no int8 GEMM) "
                f"acc_delta={delta:+.2f}pts bytes_h2d={db}"))
        eng.assert_warm()
    return rows


def pipeline_overlap(dataset: str = "cora", *, n_requests: int = 24,
                     batch_slots: int = 4, seed: int = 0) -> List[Dict]:
    """Async two-stage pipeline scheduler vs synchronous `run()` (DESIGN.md
    §9) under an ONLINE stream of mixed kind/bucket/tier requests.

    Arrival model: requests become visible one at a time (an online server
    cannot peek at future traffic). The sync driver is what bare
    `submit()+run()` gives such a server — it pads, builds/packs operands,
    then blocks on the device batch before touching the next request, so
    (a) the device idles through every request's host work and (b) each
    dispatch is a 1-of-`batch_slots` batch whose junk slots still pay full
    width. The scheduler sees the SAME arrival order but overlaps host
    workers with the device stage and lets the batch window coalesce
    arrivals into fuller batches. Three rows: the online sync baseline,
    the async pipeline, and an offline submit-all `run()` (the batching
    upper bound no online scheduler can beat). Two claims, each against
    the sync driver where it is meaningful: THROUGHPUT — async beats the
    online `run()` baseline (batch window + overlap vs 1-of-N junk-width
    batches); DISPATCH IDLE — async's `dispatch_idle_fraction` (host
    clock: the share of the span with no dispatch in progress) lands far
    below the offline `run()`'s, whose device sits provably idle through
    the entire host submit loop (the online driver's junk-slot batches
    keep its device busy on WASTED width, so its idle fraction measures
    waste, not overlap). Fresh identically-warmed engines each mode;
    `assert_warm()` is enforced, so the win is scheduling, never
    recompilation differences.
    """
    import time as _time

    from repro.core.graph import BucketLadder
    from repro.data.graphs import planetoid_like
    from repro.runtime.gnn_server import GraphServe, GraphServeConfig
    from repro.runtime.scheduler import PipelineConfig

    in_feats, classes = 64, 7
    rng = np.random.default_rng(seed)
    cal = planetoid_like(num_nodes=200, num_edges=600, num_feats=in_feats,
                         num_classes=classes, seed=seed + 10_000,
                         train_per_class=5)
    traffic = []
    for i in range(n_requests):
        kind = "gcn" if i % 2 == 0 else "gat"
        n = int(rng.integers(300, 900))
        tier = ("fp32", "int8")[int(rng.integers(2))] if kind == "gcn" else None
        traffic.append((kind, tier, planetoid_like(
            num_nodes=n, num_edges=3 * n, num_feats=in_feats,
            num_classes=classes, seed=seed + i, train_per_class=2)))

    def build():
        sc = GraphServeConfig(ladder=BucketLadder(buckets=(512, 1024)),
                              batch_slots=batch_slots)
        eng = GraphServe(sc, seed=seed)
        eng.register_model("gcn", GNNConfig(kind="gcn", in_feats=in_feats,
                                            hidden=16, num_classes=classes),
                           tiers=("fp32", "int8"))
        eng.register_model("gat", GNNConfig(kind="gat", in_feats=in_feats,
                                            hidden=16, num_classes=classes,
                                            heads=4))
        eng.warmup()
        eng.calibrate("gcn", cal)               # int8 tier serves for real
        return eng

    def run_mode(eng, mode):
        """One timed pass of the whole stream; returns (wall, idle, occ).
        The engine stays warm across passes — only metric DELTAS over this
        pass are read, so repeated passes measure scheduling, not state."""
        m0 = (eng.metrics["device_busy_s"], eng.metrics["slots_filled"],
              eng.metrics["slots_total"])
        t0 = _time.perf_counter()
        if mode == "sync":
            for kind, tier, g in traffic:       # online: drain per arrival
                eng.submit(g, model=kind, tier=tier)
                eng.run()
        elif mode == "offline":
            for kind, tier, g in traffic:       # oracle: full future known
                eng.submit(g, model=kind, tier=tier)
            eng.run()
        else:
            pc = PipelineConfig(host_workers=2, window_ms=25.0,
                                max_pending=n_requests,
                                max_ready=n_requests)
            with eng.scheduler(pc) as sched:
                for kind, tier, g in traffic:
                    sched.submit(g, model=kind, tier=tier)
                sched.drain()
        wall = _time.perf_counter() - t0
        eng.assert_warm()                       # overlap, not recompiles
        busy = eng.metrics["device_busy_s"] - m0[0]
        occ = ((eng.metrics["slots_filled"] - m0[1])
               / max(eng.metrics["slots_total"] - m0[2], 1))
        return wall, max(0.0, 1.0 - busy / wall), occ

    # this box is shared/noisy: interleave 3 reps across modes on
    # persistently-warm engines and keep each mode's best pass, so one bad
    # scheduling patch cannot decide the comparison
    engines = {mode: build() for mode in ("sync", "async", "offline")}
    stats = {}
    for _ in range(3):
        for mode, eng in engines.items():
            res = run_mode(eng, mode)
            if mode not in stats or res[0] < stats[mode][0]:
                stats[mode] = res
    rows = []
    for mode, (wall, idle, occ) in stats.items():
        rows.append(record(
            f"pipeline_overlap/{mode}/{dataset}/throughput",
            wall / n_requests,
            f"{n_requests / wall:.1f} req/s over {n_requests} mixed "
            f"kind/bucket/tier requests, dispatch_idle={idle:.2f} "
            f"occupancy={occ:.2f} (best of 3 interleaved passes)"))
    (ws, _, _), (wa, ai, _) = stats["sync"], stats["async"]
    (wo, oi, _) = stats["offline"]
    rows.append(record(
        f"pipeline_overlap/{dataset}/speedup", 0.0,
        f"{ws / wa:.2f}x async vs online run(); dispatch_idle "
        f"{oi:.2f} (submit-all run()) -> {ai:.2f} (pipelined); "
        f"offline oracle wall at {wo / wa:.2f}x of async"))
    return rows


def grasp_serving(dataset: str = "cora", *, cap: int = 1024,
                  n_queries: int = 4, batch_slots: int = 2,
                  seed: int = 0) -> List[Dict]:
    """GraSp aggregation backend vs dense through GraphServe (DESIGN.md
    §10): dense-vs-grasp latency and operand bytes per graph density.

    Serves community-clustered GCN graphs of falling density (high-density
    graphs scatter cross-community edges until the block bitmap fills) at
    one `cap` rung through two identically-warmed engines — `dense` forced
    and `auto` — with batched queries (batch >= 2, the bitmap_spmm path in
    a vmapped plan). Columns: `us_per_call` is the measured per-query
    wall-clock (CPU caveat: the ref/interpret kernel cannot skip blocks,
    so the MEASURED column may invert, exactly like fig20's gather rows);
    `derived` carries the backend the rule picked, the MODELLED
    aggregation costs (`select_agg_backend` — the same constants as the
    fig21 analytic rows; this column carries the claim: grasp beats dense
    at low density), the block stats, and the operand bytes each mode
    moved."""
    import time as _time

    from repro.core.graph import BucketLadder
    from repro.core.sparsity import block_stats, select_agg_backend
    from repro.data.graphs import clustered_like
    from repro.runtime.gnn_server import GraphServe, GraphServeConfig

    in_feats, classes, hidden = 16, 5, 16
    n = int(cap * 7 / 8)
    cases = [("dense02", 0.5, 0.30), ("mid01", 0.10, 0.05),
             ("sparse003", 0.03, 0.0)]
    rows = []

    def build(mode):
        sc = GraphServeConfig(ladder=BucketLadder(buckets=(cap,)),
                              batch_slots=batch_slots)
        eng = GraphServe(sc, seed=seed)
        eng.register_model("gcn", GNNConfig(kind="gcn", in_feats=in_feats,
                                            hidden=hidden,
                                            num_classes=classes),
                           agg_backend=mode)
        eng.warmup()
        return eng

    engines = {mode: build(mode) for mode in ("dense", "auto")}
    for label, density, cross in cases:
        g = clustered_like(num_nodes=n, num_feats=in_feats,
                           num_classes=classes, within_density=density,
                           cross_frac=cross, seed=seed)
        pg = engines["auto"].sc.ladder.pad(g)
        st = block_stats(pg.norm_adj)
        choice, dense_s, grasp_s = select_agg_backend(
            cap, hidden, nnz_blocks=st["nnz_blocks"],
            max_row_nnz=st["max_row_nnz"])
        elem_density = g.num_edges / (n * n)
        for mode, eng in engines.items():
            gid = eng.attach(g, model="gcn")
            b0 = eng.metrics["operand_bytes_h2d"]
            # one untimed dispatch first: the once-per-(graph, version)
            # work (operand materialization, block compaction, backend
            # rule) belongs to attach-time, not to the steady-state
            # per-query latency this row claims to measure
            eng.query(gid)
            eng.run()
            t0 = _time.perf_counter()
            for _ in range(n_queries):
                for _ in range(batch_slots):    # full batches per dispatch
                    eng.query(gid)
                eng.run()
            wall = (_time.perf_counter() - t0) / (n_queries * batch_slots)
            eng.assert_warm()
            db = eng.metrics["operand_bytes_h2d"] - b0
            backend = ("dense" if mode == "dense" else choice)
            rows.append(record(
                f"grasp_serving/{mode}/{dataset}/{label}", wall,
                f"backend={backend} model dense={dense_s*1e6:.1f}us "
                f"grasp={grasp_s*1e6:.1f}us ({dense_s/grasp_s:.2f}x) "
                f"elem_density={elem_density:.4f} "
                f"block_density={st['block_density']:.2f} bytes_h2d={db}"))
            eng.detach(gid)
    s = engines["auto"].summary()
    rows.append(record(
        f"grasp_serving/{dataset}/dispatch", 0.0,
        f"grasp_batches={s['grasp_batches']} "
        f"backend_fallbacks={s['backend_fallbacks']} over mixed-density "
        f"auto traffic, zero recompiles (batched bitmap_spmm plan, "
        f"batch={batch_slots}; on a CPU host the kernel routing is 'ref', "
        f"so every grasp REQUEST also counts a backend_fallback — the "
        f"skip grid only runs on TPU/interpret)"))
    return rows


def sharded_serving(dataset: str = "synthetic", *, quick: bool = True,
                    n_queries: Optional[int] = None,
                    seed: int = 0) -> List[Dict]:
    """Sharded serving of a partitioned giant graph (DESIGN.md §12):
    throughput vs device count with compressed halo exchange.

    One community-clustered GCN graph larger than any single ladder rung
    is served at shard counts 1/2/4/8 — count 1 through the ordinary
    unsharded engine at the full-capacity bucket (the baseline), counts
    >= 2 through engines configured to auto-shard (`shard_counts=(s,)`),
    each warmed before traffic. `us_per_call` is the measured per-query
    wall-clock; `modelled_s` (hence `measured_vs_modelled`) is
    `core.partition.modelled_sharded_latency` — per-shard compute at the
    derated MXU roofline plus one compressed-halo collective per
    exchanged layer width at the host-link bandwidth. The scaling CLAIM
    lives in the modelled column: per-shard compute falls ~1/S while the
    int8 wire term grows slowly, so modelled throughput is monotone in
    the device count. The measured column shows what this host actually
    did — on a 1-CPU box every shard computes serially under a
    vmap-simulated axis (placement is recorded per row), so measured
    throughput only follows the model on the CI multi-device leg
    (XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
    import time as _time

    from repro.core.graph import BucketLadder
    from repro.core.partition import (modelled_sharded_latency,
                                      partition_graph)
    from repro.core.models import sharded_exchange_widths
    from repro.data.graphs import clustered_like
    from repro.runtime.gnn_server import GraphServe, GraphServeConfig

    in_feats, hidden, classes = 16, 256, 5
    all_buckets = (128, 256, 512, 1024, 2048)
    # n is picked so every doubling of the shard count halves the ladder
    # bucket (1800 -> loads 1800/900/450/225 -> buckets 2048/1024/512/256):
    # the full sharded capacity S x bucket stays constant, so the modelled
    # per-shard aggregation cost genuinely falls ~1/S instead of being
    # masked by bucket-floor over-padding at high shard counts. hidden=256
    # keeps that aggregation term above the collective latency floor —
    # smaller widths would make the model (correctly) report that sharding
    # a trivial graph is all wire and no win.
    n = 1800
    cfg = GNNConfig(kind="gcn", in_feats=in_feats, hidden=hidden,
                    num_classes=classes)
    n_queries = n_queries if n_queries is not None else (2 if quick else 4)
    g = clustered_like(num_nodes=n, num_feats=in_feats,
                       num_classes=classes, within_density=0.02,
                       cross_frac=0.05, seed=seed)
    full_ladder = BucketLadder(buckets=all_buckets)
    rows, modelled_rps = [], []
    for shards in (1, 2, 4, 8):
        load = -(-n // shards)
        bucket = full_ladder.bucket_for(load)
        if shards == 1:
            sc = GraphServeConfig(ladder=BucketLadder(buckets=(bucket,)),
                                  batch_slots=1)
            part = partition_graph(g.edge_index, n, 1, shard_cap=bucket)
        else:
            # a one-rung ladder the graph EXCEEDS, so attach() must take
            # the sharded path at exactly this shard count
            sc = GraphServeConfig(ladder=BucketLadder(buckets=(bucket,)),
                                  batch_slots=1, shard_counts=(shards,))
        eng = GraphServe(sc, seed=seed)
        eng.register_model("gcn", cfg)
        eng.warmup()
        gid = eng.attach(g, model="gcn", calibrate=False)
        if shards > 1:
            part = eng._sharded[gid][0]
        # untimed first query: once-per-(graph, version) work (operand /
        # shard-slice build) is attach-time cost, not steady-state latency
        eng.query(gid)
        eng.run()
        t0 = _time.perf_counter()
        for _ in range(n_queries):
            eng.query(gid)
            eng.run()
        wall = (_time.perf_counter() - t0) / n_queries
        eng.assert_warm()
        modelled = modelled_sharded_latency(
            part, in_feats=in_feats, hidden=hidden, classes=classes,
            exchange_widths=sharded_exchange_widths(cfg))
        modelled_rps.append(1.0 / modelled)
        s = eng.summary()
        placed = s["sharded_placement"].get(
            shards, {"placement": "unsharded", "devices": 1})
        rows.append(record(
            f"sharded_serving/gcn/{dataset}/shards{shards}", wall,
            f"devices={placed['devices']} "
            f"placement={placed['placement']} bucket={bucket} "
            f"modelled_rps={1.0 / modelled:.0f} "
            f"halo_bytes={s['halo_bytes_exchanged']} "
            f"exact_bytes={s['collective_bytes_exact']} "
            f"cut_edges={part.cut_edges}",
            modelled_s=modelled))
        eng.detach(gid)
    mono = all(b >= a for a, b in zip(modelled_rps, modelled_rps[1:]))
    rows.append(record(
        f"sharded_serving/gcn/{dataset}/scaling", 0.0,
        f"modelled_rps={'/'.join(f'{r:.0f}' for r in modelled_rps)} over "
        f"1/2/4/8 shards monotone={mono} (per-shard aggregation ~1/S, "
        f"int8 halo wire grows ~2(S-1)/S; compressed wire is 4x cheaper "
        f"than exact fp32)"))
    return rows


def partition_quality(dataset: str = "synthetic", *, quick: bool = True,
                      seed: int = 0) -> List[Dict]:
    """Partitioner quality and §15 serving wins (DESIGN.md §15): multilevel
    coarsen+refine vs the §12 greedy streaming cut, replica-group
    throughput scaling, and delta-halo vs full-halo exchange bytes under
    edge churn.

    Three row groups, each carrying its acceptance assert IN the benchmark
    (a regression fails the CI leg, not just a dashboard):

      * cut/ — greedy vs multilevel on the community-clustered serving
        graph at 4 and 8 shards: cut_edges, halo rows, and the bytes a
        SPARSE per-layer halo gather would move (4 B x halo rows x
        exchanged widths; the dense full-row psum the plan ships is
        partition-independent, so halo rows are where cut quality turns
        into wire). Asserts the multilevel cut is STRICTLY below greedy.
        Also one measured serving row per method — same graph, same
        queries, engines differing only in `partition_method`.
      * replica/ — one 2-shard layout dispatched at replica_groups R =
        1/2/4 over the same query stream: measured per-query wall,
        dispatch count (must be ceil(N/R) — the §15 packing claim), and
        modelled rps R/modelled_latency (replica rows share no
        collectives, so an R x S mesh runs them concurrently at the
        single-replica latency). Asserts modelled rps monotone in R and
        the measured dispatch counts exactly ceil(N/R).
      * delta/ — a churn loop of one-pair GrAd deltas against a sharded
        graph: `delta_halo_bytes_exchanged` (dirty boundary rows through
        `compressed_psum_delta`'s pricing) vs `delta_halo_bytes_full`
        (re-exchanging every operand row). Asserts delta < full.
    """
    import time as _time

    from repro.core.graph import BucketLadder
    from repro.core.partition import (modelled_sharded_latency,
                                      partition_graph)
    from repro.core.models import sharded_exchange_widths
    from repro.data.graphs import clustered_like
    from repro.runtime.gnn_server import GraphServe, GraphServeConfig

    in_feats, hidden, classes = 16, 64, 5
    n = 1800
    cfg = GNNConfig(kind="gcn", in_feats=in_feats, hidden=hidden,
                    num_classes=classes)
    g = clustered_like(num_nodes=n, num_feats=in_feats, num_classes=classes,
                       within_density=0.02, cross_frac=0.05, seed=seed)
    widths = sharded_exchange_widths(cfg)
    rows = []

    # ---- cut quality: greedy vs multilevel ---------------------------
    for shards, bucket in ((4, 512),) if quick else ((4, 512), (8, 256)):
        parts = {m: partition_graph(g.edge_index, n, shards,
                                    shard_cap=bucket, method=m)
                 for m in ("greedy", "multilevel")}
        assert parts["multilevel"].cut_edges < parts["greedy"].cut_edges, (
            "multilevel refinement must strictly beat the greedy "
            "streaming cut on a community-clustered graph",
            parts["multilevel"].cut_edges, parts["greedy"].cut_edges)
        for m, p in parts.items():
            halo_rows = sum(len(h) for h in p.halo)
            sparse_bytes = 4 * halo_rows * sum(widths)
            rows.append(record(
                f"partition_quality/cut/{dataset}/shards{shards}/{m}", 0.0,
                f"cut_edges={p.cut_edges} halo_rows={halo_rows} "
                f"sparse_halo_bytes={sparse_bytes} "
                f"loads={'/'.join(str(int(x)) for x in p.loads)}"))
    # measured serving, same traffic, partition_method the only knob
    n_q = 2 if quick else 4
    for m in ("greedy", "multilevel"):
        sc = GraphServeConfig(ladder=BucketLadder(buckets=(512,)),
                              batch_slots=1, shard_counts=(4,),
                              partition_method=m)
        eng = GraphServe(sc, seed=seed)
        eng.register_model("gcn", cfg)
        eng.warmup()
        gid = eng.attach(g, model="gcn", calibrate=False)
        part = eng._sharded[gid][0]
        eng.query(gid)
        eng.run()                  # untimed: slice-build is attach cost
        t0 = _time.perf_counter()
        for _ in range(n_q):
            eng.query(gid)
            eng.run()
        wall = (_time.perf_counter() - t0) / n_q
        eng.assert_warm()
        rows.append(record(
            f"partition_quality/serve/{dataset}/{m}", wall,
            f"cut_edges={part.cut_edges} shards=4 bucket=512",
            modelled_s=modelled_sharded_latency(
                part, in_feats=in_feats, hidden=hidden, classes=classes,
                exchange_widths=widths)))
        eng.detach(gid)

    # ---- replica-group scaling --------------------------------------
    small = clustered_like(num_nodes=200, num_feats=in_feats,
                           num_classes=classes, within_density=0.05,
                           cross_frac=0.1, seed=seed + 1)
    n_q = 4 if quick else 8
    modelled_rps = []
    for r in (1, 2, 4):
        sc = GraphServeConfig(ladder=BucketLadder(buckets=(128,)),
                              batch_slots=1, shard_counts=(2,),
                              replica_groups=r)
        eng = GraphServe(sc, seed=seed)
        eng.register_model("gcn", cfg)
        eng.warmup()
        gid = eng.attach(small, model="gcn", calibrate=False)
        eng.query(gid)
        eng.run()
        part = eng._sharded[gid][0]
        before = eng.metrics["sharded_batches"]
        t0 = _time.perf_counter()
        for _ in range(n_q):
            eng.query(gid)
        eng.run()
        wall = (_time.perf_counter() - t0) / n_q
        eng.assert_warm()
        dispatches = eng.metrics["sharded_batches"] - before
        assert dispatches == -(-n_q // r), (
            "replica packing must dispatch ceil(N/R) sharded batches",
            dispatches, n_q, r)
        lat = modelled_sharded_latency(part, in_feats=in_feats,
                                       hidden=hidden, classes=classes,
                                       exchange_widths=widths)
        modelled_rps.append(r / lat)
        rows.append(record(
            f"partition_quality/replica/{dataset}/r{r}", wall,
            f"dispatches={dispatches} queries={n_q} "
            f"occupancy={eng.summary()['batch_occupancy']:.2f} "
            f"modelled_rps={r / lat:.0f}", modelled_s=lat))
        eng.detach(gid)
    assert all(b > a for a, b in zip(modelled_rps, modelled_rps[1:])), (
        "replica rows share no collectives: modelled throughput must "
        "rise monotonically with R", modelled_rps)

    # ---- delta-halo vs full-halo bytes under churn ------------------
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(128,)),
                          batch_slots=1, shard_counts=(2,))
    eng = GraphServe(sc, seed=seed)
    eng.register_model("gcn", cfg)
    eng.warmup()
    gid = eng.attach(small, model="gcn", calibrate=False)
    eng.query(gid)
    eng.run()
    part = eng._sharded[gid][0]
    rng = np.random.default_rng(seed)
    churn = 4 if quick else 12
    done = 0
    while done < churn:
        u, v = rng.integers(0, 200, size=2)
        if u == v:
            continue
        adj = eng.graphs[gid][1].adj
        pair = [(int(u), int(v))]
        ok = eng.update_delta(
            gid, add_edges=pair if adj[u, v] == 0 else None,
            remove_edges=pair if adj[u, v] != 0 else None)
        done += bool(ok)
    s = eng.summary()
    assert 0 < s["delta_halo_bytes_exchanged"] < s["delta_halo_bytes_full"], (
        "dirty-row exchange must move strictly fewer bytes than full "
        "halo re-exchange", s["delta_halo_bytes_exchanged"],
        s["delta_halo_bytes_full"])
    eng.query(gid)
    eng.run()
    eng.assert_warm()           # churn never left the warm patch traces
    rows.append(record(
        f"partition_quality/delta/{dataset}/churn{churn}", 0.0,
        f"delta_bytes={s['delta_halo_bytes_exchanged']} "
        f"full_bytes={s['delta_halo_bytes_full']} "
        f"dirty_rows={s['delta_dirty_rows']} "
        f"saving={s['delta_halo_bytes_full'] / max(s['delta_halo_bytes_exchanged'], 1):.0f}x "
        f"shards={part.shards}"))
    eng.detach(gid)
    return rows


# ------------------------------------------------------- energy / GraSp


def energy_proxy(dataset: str = "cora") -> List[Dict]:
    """Fig. 23 proxy: bytes moved per inference (dominant energy driver on
    edge parts). Dense vs ZVC/GraSp-compressed operand traffic."""
    g = cora_like() if dataset == "cora" else citeseer_like()
    pg = pad_graph(g)
    rep = sparsity_report(pg.norm_adj)
    rows = [
        record(f"energy/{dataset}/adj_dense_bytes", 0.0,
               str(rep["dense_bytes"])),
        record(f"energy/{dataset}/adj_zvc_bytes", 0.0, str(rep["zvc_bytes"])),
        record(f"energy/{dataset}/adj_block_bytes", 0.0,
               str(rep["block_compacted_bytes"])),
        record(f"energy/{dataset}/flop_skip_fraction", 0.0,
               f"{rep['flop_skip_fraction']:.3f}"),
        record(f"energy/{dataset}/zvc_saving", 0.0,
               f"{rep['dense_bytes']/max(rep['zvc_bytes'],1):.1f}x"),
    ]
    return rows


# ------------------------------------------------- fused layers (§11)


def fused_layers(quick: bool = True) -> List[Dict]:
    """Fused per-layer kernels vs unfused per-op dispatch (DESIGN.md §11).

    Two rows per (kind, tier, backend) hot combination. The `unfused` row
    is the per-op forward (`fusion="none"`); the `fused` row is the same
    tier math as one fused kernel pass per layer (`fusion="layer"`).
    Columns:

      * us_per_call — measured CPU wall-clock. On CPU both modes lower to
        near-identical XLA (the fused ref twins ARE the unfused math), so
        this column is a sanity check, not the claim.
      * tpu_model speedup (in `derived`) — the claim. The unfused forward
        is priced from its compiled HLO (`benchmarks.tpu_model`); the
        fused forward reuses the SAME MXU/VPU terms (fusion never changes
        FLOPs) with the HBM term re-priced to the bytes the fused kernels
        actually move: per layer, kernel operands + output only — every
        intermediate (H strips, attention logits, re-quantized H) lives in
        VMEM scratch across grid steps and never crosses HBM. The fused
        cost carries NO serialized-gather term: every fused-kernel load is
        a block-granular pipelined DMA (BlockSpec index maps /
        scalar-prefetch descriptors), which is exactly the row-granularity
        serialization the GATHER_BW term models — eliminating it is the
        GraSp/EffOp dispatch win.
      * interp_grid (in `derived`) — measured wall-clock of the fused
        forward with the REAL Pallas grids on the interpret backend
        (REPRO_KERNEL_MODE=interpret). Orders slower than XLA by design;
        recorded so CI trends catch grid-structure regressions, never
        compared against the XLA columns.

    `measured_vs_modelled` lands on both rows (see benchmarks/common.record).
    """
    import os

    from repro.core.graph import Graph
    from repro.core.models import calibrate_tier
    from repro.runtime.gnn_server import tier_techniques

    from .tpu_model import HBM_BW

    rows: List[Dict] = []
    reps = 2 if quick else 5
    f32, i8 = 4, 1

    def _graph(n, fin, *, band=None, seed=0):
        rng = np.random.default_rng(seed)
        if band is None:
            m = n * 6
            ei = rng.integers(0, n, size=(2, m)).astype(np.int32)
            ei = np.concatenate([ei, ei[::-1]], axis=1)
        else:
            # banded ring: block-sparse-friendly clustered structure
            src = np.repeat(np.arange(n, dtype=np.int32), band)
            dst = (src + np.tile(np.arange(1, band + 1, dtype=np.int32), n)
                   ) % n
            ei = np.concatenate([np.stack([src, dst]),
                                 np.stack([dst, src])], axis=1)
        feats = rng.standard_normal((n, fin)).astype(np.float32)
        return Graph(edge_index=ei, num_nodes=n, features=feats)

    def _bench(label, cfg, params, x, ops_, t, quant, tops, fused_bytes):
        kw = dict(quant=quant, tier_ops=tops)
        unfused = jax.jit(lambda p, xx: forward_grannite(
            p, cfg, xx, ops_, t, fusion="none", **kw))
        fused = jax.jit(lambda p, xx: forward_grannite(
            p, cfg, xx, ops_, t, fusion="layer", **kw))
        tu = time_fn(unfused, params, x, warmup=1, repeats=reps)
        tf = time_fn(fused, params, x, warmup=1, repeats=reps)
        a = tpu_analyze(unfused, params, x)
        t_unf = a["t_model_s"]
        t_fus = max(a["t_mxu_s"] + a["t_vpu_s"], fused_bytes / HBM_BW)
        # interpret-grid timing: fresh jits trace through the REAL Pallas
        # grids (the kernel mode is read at trace time — kernels/ops.py)
        prev = os.environ.get("REPRO_KERNEL_MODE")
        os.environ["REPRO_KERNEL_MODE"] = "interpret"
        try:
            igrid = jax.jit(lambda p, xx: forward_grannite(
                p, cfg, xx, ops_, t, fusion="layer", **kw))
            ti = time_fn(igrid, params, x, warmup=1, repeats=2)
        finally:
            if prev is None:
                os.environ.pop("REPRO_KERNEL_MODE", None)
            else:
                os.environ["REPRO_KERNEL_MODE"] = prev
        rows.append(record(
            f"fused_layers/{label}/unfused", tu,
            f"tpu_model={t_unf * 1e6:.2f}us hbm_bytes={a['bytes']:.0f}",
            modelled_s=t_unf))
        rows.append(record(
            f"fused_layers/{label}/fused", tf,
            f"tpu_model={t_fus * 1e6:.2f}us "
            f"speedup={t_unf / t_fus:.2f}x "
            f"hbm_bytes={fused_bytes} interp_grid={ti * 1e6:.0f}us",
            modelled_s=t_fus))

    # serving-bucket shapes: hidden-heavy enough that the eliminated
    # inter-op intermediates dominate the (shared) Â / mask reads
    fin, hidden, classes, heads = 128, 512, 16, 4
    cap = 256

    # --- GCN dense: fp32 + int8 tiers --------------------------------
    pg = pad_graph(_graph(230, fin), capacity=cap)
    cfg = GNNConfig(kind="gcn", in_feats=fin, hidden=hidden,
                    num_classes=classes)
    params = init_params(KEY, cfg)
    ops_ = build_operands(pg, cfg)
    x = jnp.asarray(pg.features)
    tt = tier_techniques("gcn")
    nb_adj = cap * cap * f32
    fb = ((nb_adj + cap * fin * f32 + fin * hidden * f32 + hidden * f32
           + cap * hidden * f32)
          + (nb_adj + cap * hidden * f32 + hidden * classes * f32
             + classes * f32 + cap * classes * f32))
    _bench("gcn/fp32/dense", cfg, params, x, ops_, tt["fp32"],
           None, None, fb)

    quant = calibrate_tier(params, cfg, x, ops_)
    tops = derive_tier_operands(ops_.norm_adj)
    nb_aq = cap * cap * i8 + cap * f32        # int8 Â + row scales
    fb8 = ((nb_aq + cap * fin * f32 + fin * hidden * i8 + hidden * f32
            + cap * hidden * f32)
           + (nb_aq + cap * hidden * f32 + hidden * classes * i8
              + classes * f32 + cap * classes * f32))
    _bench("gcn/int8/dense", cfg, params, x, ops_, tt["int8"],
           quant, tops, fb8)

    # --- GCN grasp: banded structure at a paper-scale rung -----------
    capg, hg = 1024, 128
    pgb = pad_graph(_graph(1000, fin, band=3, seed=1), capacity=capg)
    cfgb = GNNConfig(kind="gcn", in_feats=fin, hidden=hg,
                     num_classes=classes)
    paramsb = init_params(KEY, cfgb)
    opsb = build_operands(pgb, cfgb, grasp=True)
    bsp = opsb.block_sparse
    nb_bsp = sum(int(np.asarray(a).nbytes)
                 for a in (bsp.blocks, bsp.block_cols, bsp.counts))
    fbg = ((nb_bsp + capg * fin * f32 + fin * hg * f32 + hg * f32
            + capg * hg * f32)
           + (nb_bsp + capg * hg * f32 + hg * classes * f32
              + classes * f32 + capg * classes * f32))
    _bench("gcn/fp32/grasp", cfgb, paramsb, jnp.asarray(pgb.features),
           opsb, dataclasses.replace(tt["fp32"], grasp=True),
           None, None, fbg)

    # --- GAT dense: fp32 + int8 tiers --------------------------------
    cfg_g = GNNConfig(kind="gat", in_feats=fin, hidden=hidden,
                      num_classes=classes, heads=heads)
    params_g = init_params(KEY, cfg_g)
    ops_g = build_operands(pg, cfg_g)
    tt_g = tier_techniques("gat")
    nb_bias = cap * cap * f32
    fb_gat = ((cap * fin * f32 + fin * hidden * f32 + 2 * hidden * f32
               + nb_bias + hidden * f32 + cap * hidden * f32)
              + (cap * hidden * f32 + hidden * classes * f32
                 + 2 * classes * f32 + nb_bias + classes * f32
                 + cap * classes * f32))
    _bench("gat/fp32/dense", cfg_g, params_g, x, ops_g, tt_g["fp32"],
           None, None, fb_gat)

    quant_g = calibrate_tier(params_g, cfg_g, x, ops_g)
    # precombined fusion: the int8 combine runs unfused (x + wq read, H
    # written), then the fused attention grid re-reads H (twice: alpha
    # reductions + the combine matmul stream) — only the N^2-per-head
    # attention intermediates fuse away
    fb_gat8 = ((cap * fin * f32 + fin * hidden * i8
                + 3 * cap * hidden * f32 + nb_bias + hidden * f32
                + cap * hidden * f32)
               + (cap * hidden * f32 + hidden * classes * i8
                  + 3 * cap * classes * f32 + nb_bias + classes * f32
                  + cap * classes * f32))
    _bench("gat/int8/dense", cfg_g, params_g, x, ops_g, tt_g["int8"],
           quant_g, None, fb_gat8)

    # --- SAGE mean: fp32 ---------------------------------------------
    cfg_s = GNNConfig(kind="sage", in_feats=fin, hidden=hidden,
                      num_classes=classes, aggregator="mean")
    params_s = init_params(KEY, cfg_s)
    ops_s = build_operands(pg, cfg_s)
    fb_sage = ((nb_adj + cap * fin * f32 + 2 * fin * hidden * f32
                + hidden * f32 + cap * hidden * f32)
               + (nb_adj + cap * hidden * f32 + 2 * hidden * classes * f32
                  + classes * f32 + cap * classes * f32))
    _bench("sage/fp32/dense", cfg_s, params_s, x, ops_s,
           tier_techniques("sage")["fp32"], None, None, fb_sage)
    return rows


# --------------------------------------------- cache pressure (§13)


def cache_pressure(dataset: str = "synthetic", *, quick: bool = True,
                   seed: int = 0) -> List[Dict]:
    """Bounded CacheG memory hierarchy under churn (DESIGN.md §13).

    Three claims, one row each:

      * churn — attach/query cycles over more tenants than the byte
        budget admits. The derived column reports the peak
        `cache_resident_bytes` seen after EVERY step against the budget
        (the §13 invariant — also enforced, bit-level, by
        tests/test_cache_pressure.py), plus eviction/spill-fault counts.
        `assert_warm` holds throughout: eviction and re-materialization
        replay warm blobs, they never trace.
      * spill_fault vs warm_hit — per-query wall-clock when the operands
        must re-materialize from the host-RAM spill form vs when they
        are device-resident. The gap is the fault penalty: one compact
        SymG transfer + on-device materialization, zero host repacking.
      * delta_update vs full_rebuild — end-to-end (update + next query)
        for a single undirected edge flip via `update_delta` (GrAd
        device-side patch; the next query HITS the patched entry) vs
        `update()` (invalidates; the next query rebuilds from scratch).
        The differential suite proves both end bit-identical; this row
        reports what the equivalence costs.
    """
    import time as _time

    from repro.core.graph import BucketLadder
    from repro.data.graphs import planetoid_like
    from repro.runtime.cache import estimate_dense_entry_bytes
    from repro.runtime.gnn_server import GraphServe, GraphServeConfig

    cap, fin, classes = 128, 32, 5
    entry = estimate_dense_entry_bytes(1, cap)      # gcn: one Â field
    budget = 3 * entry + entry // 2                 # ~3 resident tenants

    def _g(i):
        n = 48 + (i * 13) % 70
        return planetoid_like(num_nodes=n, num_edges=3 * n, num_feats=fin,
                              num_classes=classes, seed=seed + i,
                              train_per_class=2)

    def _engine(**kw):
        sc = GraphServeConfig(ladder=BucketLadder(buckets=(cap,)),
                              batch_slots=1, return_logits=True, **kw)
        eng = GraphServe(sc, seed=seed)
        eng.register_model("gcn", GNNConfig(kind="gcn", in_feats=fin,
                                            hidden=16, num_classes=classes))
        eng.warmup()
        return eng

    rows: List[Dict] = []

    # --- churn: more tenants than the budget admits ------------------
    n_graphs = 6 if quick else 12
    n_cycles = 30 if quick else 120
    eng = _engine(device_cache_budget_bytes=budget)
    gids = [eng.attach(_g(i), model="gcn") for i in range(n_graphs)]
    rng = np.random.default_rng(seed)
    peak = 0
    t0 = _time.perf_counter()
    for _ in range(n_cycles):
        eng.query(gids[int(rng.integers(n_graphs))])
        eng.run()
        peak = max(peak, eng.summary()["cache_resident_bytes"])
    wall = _time.perf_counter() - t0
    eng.assert_warm()
    s = eng.summary()
    assert peak <= budget, (peak, budget)
    rows.append(record(
        f"cache_pressure/{dataset}/churn", wall / n_cycles,
        f"peak_resident={peak}B <= budget={budget}B over {n_cycles} "
        f"cycles x {n_graphs} tenants, evictions={s['cache_evictions']} "
        f"spill_hits={s['cache_spill_hits']}, zero recompiles"))

    # --- spill fault vs warm hit -------------------------------------
    eng = _engine(device_cache_budget_bytes=2 * entry + entry // 2)
    a, b, c = (eng.attach(_g(i), model="gcn") for i in range(3))
    for gid in (a, b, c):                           # first-touch misses
        eng.query(gid)
        eng.run()
    reps = 3 if quick else 10
    t_fault = t_hit = 0.0
    for _ in range(reps):
        for gid in (b, c):                          # evicts `a` (budget=2)
            eng.query(gid)
            eng.run()
        t0 = _time.perf_counter()
        eng.query(a)                                # faults on the spill form
        eng.run()
        t_fault += _time.perf_counter() - t0
        t0 = _time.perf_counter()
        eng.query(a)                                # device-resident now
        eng.run()
        t_hit += _time.perf_counter() - t0
    eng.assert_warm()
    s = eng.summary()
    rows.append(record(
        f"cache_pressure/{dataset}/spill_fault", t_fault / reps,
        f"{t_fault / max(t_hit, 1e-9):.2f}x the warm hit "
        f"({s['cache_spill_hits']} faults served from the host spill "
        f"form, {s['operand_bytes_h2d']} compact B h2d)"))
    rows.append(record(
        f"cache_pressure/{dataset}/warm_hit", t_hit / reps,
        f"device-resident query ({s['operand_cache_hits']} hits)"))

    # --- GrAd delta patch vs full rebuild ----------------------------
    # at a paper-scale rung: the full path re-normalizes the whole
    # (cap, cap) Â on the host and re-uploads it; the delta path renorms
    # only the touched rows device-side
    cap_d = 512 if quick else 1024
    nd = int(cap_d * 3 / 4)
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(cap_d,)),
                          batch_slots=1, return_logits=True)
    eng = GraphServe(sc, seed=seed)
    eng.register_model("gcn", GNNConfig(kind="gcn", in_feats=fin,
                                        hidden=16, num_classes=classes))
    eng.warmup()
    g = planetoid_like(num_nodes=nd, num_edges=3 * nd, num_feats=fin,
                       num_classes=classes, seed=seed + 1,
                       train_per_class=2)
    gid = eng.attach(g, model="gcn")
    eng.query(gid)
    eng.run()
    adj = eng.graphs[gid][1].adj
    j = int(np.flatnonzero(adj[0] == 0)[1])         # absent pair (0, j)
    pair = (0, j)
    t0 = _time.perf_counter()
    for _ in range(reps):
        eng.update_delta(gid, add_edges=[pair])
        eng.query(gid)
        eng.run()
        eng.update_delta(gid, remove_edges=[pair])
        eng.query(gid)
        eng.run()
    t_delta = (_time.perf_counter() - t0) / (2 * reps)
    cols = np.array([[0, j], [j, 0]], dtype=g.edge_index.dtype).T
    ei_plus = np.concatenate([g.edge_index, cols], axis=1)
    t0 = _time.perf_counter()
    for _ in range(reps):
        eng.update(gid, ei_plus, g.num_nodes, g.features)
        eng.query(gid)
        eng.run()
        eng.update(gid, g.edge_index, g.num_nodes, g.features)
        eng.query(gid)
        eng.run()
    t_full = (_time.perf_counter() - t0) / (2 * reps)
    eng.assert_warm()
    s = eng.summary()
    rows.append(record(
        f"cache_pressure/{dataset}/delta_update", t_delta,
        f"{t_full / max(t_delta, 1e-9):.2f}x vs full rebuild "
        f"({s['delta_updates']} patched, {s['delta_fallbacks']} fallbacks; "
        f"next query hits the patched entry)"))
    rows.append(record(
        f"cache_pressure/{dataset}/full_rebuild", t_full,
        "update() baseline: invalidate + rebuild on the next query"))
    return rows


def slo_serving(dataset: str = "synthetic", *, quick: bool = True,
                seed: int = 0) -> List[Dict]:
    """SLO-aware serving (DESIGN.md §14): deadline hit-rate under load for
    static-tier vs governed serving, and measured-EWMA vs roofline-only
    agg-backend routing.

    Three row groups:

      * static / governed — the SAME bursty deadline-carrying stream served
        by a fixed-fp32 engine and by one with an `SLOGovernor`. Each
        request's `deadline_ms` is set from a short calibration pass (a
        multiple of the measured fp32 batch latency), so queue wait inside
        a burst is what blows budgets. The derived column reports the
        deadline hit rate, rolling p99, tier downgrades taken, and how the
        served-tier mix shifted — on this CPU box int8's QuantGr kernels
        are not guaranteed faster, so the row reports what trading quality
        for latency actually bought rather than asserting it.
      * backend_routing — an `agg_backend="auto"` engine serving a mixed
        sparse/dense stream. The first sparse request routes on the
        roofline alone (cold bank); once BOTH backends hold measured
        samples at the bucket, the same probe re-routes on measured EWMA
        (`select_agg_backend(measured=...)`). The derived column reports
        both choices, both measured latencies, and `ewma_vs_model` — the
        ratio that exposes how far the analytic model sits from this
        box's reality (the BENCH grasp-regression guard, as a trend row).
    """
    import time as _time

    from repro.core.graph import BucketLadder
    from repro.data.graphs import planetoid_like
    from repro.runtime.gnn_server import GraphServe, GraphServeConfig
    from repro.runtime.slo import SLOConfig

    rows: List[Dict] = []
    in_feats, classes = 32, 5
    cal = planetoid_like(num_nodes=200, num_edges=600, num_feats=in_feats,
                         num_classes=classes, seed=seed + 10_000,
                         train_per_class=5)

    def _engine(slo=None):
        # 2-slot batches: an 8-deep burst takes 4 dispatches, so the tail
        # of each burst pays real queue wait — that is the load knob
        sc = GraphServeConfig(ladder=BucketLadder(buckets=(256,)),
                              batch_slots=2)
        eng = GraphServe(sc, seed=seed, slo=slo)
        eng.register_model("gcn", GNNConfig(kind="gcn", in_feats=in_feats,
                                            hidden=32, num_classes=classes),
                           tiers=("fp32", "int8"))
        eng.warmup()
        eng.calibrate("gcn", cal)
        return eng

    n_requests = 24 if quick else 64
    burst = 8
    rng = np.random.default_rng(seed)
    traffic = [planetoid_like(num_nodes=int(rng.integers(100, 240)),
                              num_edges=600, num_feats=in_feats,
                              num_classes=classes, seed=seed + i,
                              train_per_class=2)
               for i in range(n_requests)]

    # calibration pass: measured fp32 latency sets the deadline scale, so
    # the SAME relative pressure applies whatever box runs this; 2.5x one
    # batch means roughly the back half of each 4-dispatch burst is at risk
    probe = _engine()
    l0 = len(probe.metrics["latency_s"])
    for i in range(4):
        probe.submit(traffic[i], model="gcn", tier="fp32")
        probe.run()
    base_s = float(np.median(probe.metrics["latency_s"][l0:]))
    deadline_ms = 2.5 * base_s * 1e3

    slo = SLOConfig(target_p99_ms=deadline_ms, window=16, min_samples=4,
                    breach_checks=2, clear_checks=4,
                    max_queue_depth=4 * burst, ladder=("fp32", "int8"))
    for mode, eng in (("static", _engine()),
                      ("governed", _engine(slo=slo))):
        m0 = (eng.metrics["deadline_misses"], len(eng.metrics["latency_s"]),
              len(eng.finished))
        t0 = _time.perf_counter()
        for i in range(0, n_requests, burst):
            for g in traffic[i:i + burst]:      # burst arrival: queue wait
                eng.submit(g, model="gcn", deadline_ms=deadline_ms)
            eng.run()
        wall = _time.perf_counter() - t0
        eng.assert_warm()
        misses = eng.metrics["deadline_misses"] - m0[0]
        lats = np.asarray(eng.metrics["latency_s"][m0[1]:])
        tiers = [r.tier for r in eng.finished[m0[2]:]]
        s = eng.summary()
        rows.append(record(
            f"slo_serving/{mode}/{dataset}/hit_rate", wall / n_requests,
            f"hit_rate={1 - misses / n_requests:.2f} "
            f"({n_requests - misses}/{n_requests} under "
            f"{deadline_ms:.1f}ms), p99={np.percentile(lats, 99) * 1e3:.1f}ms, "
            f"downgrades={s['slo_downgrades']}, "
            f"int8_served={sum(t == 'int8' for t in tiers)}"))

    # --- EWMA-measured vs roofline-only backend routing ------------------
    from repro.data.graphs import clustered_like

    cap, fin = 512, 64
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(cap,)), batch_slots=2)
    eng = GraphServe(sc, seed=seed)
    eng.register_model("gcn", GNNConfig(kind="gcn", in_feats=fin, hidden=64,
                                        num_classes=classes),
                       agg_backend="auto")
    eng.warmup()
    n = cap - 64

    def _sparse(i):       # community-clustered: block-sparse, roofline grasp
        return clustered_like(num_nodes=n, num_feats=fin,
                              num_classes=classes, within_density=0.03,
                              cross_frac=0.0, seed=seed + 100 + i)

    def _dense(i):        # cross-community scatter fills the bitmap: dense
        return clustered_like(num_nodes=n, num_feats=fin,
                              num_classes=classes, within_density=0.5,
                              cross_frac=0.3, seed=seed + 200 + i)

    uid = eng.submit(_sparse(0), model="gcn")
    eng.run()
    roofline_pick = next(r for r in eng.finished if r.uid == uid).backend
    for i in range(4 if quick else 8):          # measure BOTH backends
        eng.submit(_sparse(i + 1), model="gcn")
        eng.submit(_dense(i), model="gcn")
        eng.run()
    pair = eng._measured_agg_pair("gcn", cap)
    uid = eng.submit(_sparse(99), model="gcn")
    eng.run()
    measured_pick = next(r for r in eng.finished if r.uid == uid).backend
    eng.assert_warm()
    s = eng.summary()
    d_ms = f"{pair[0] * 1e3:.2f}" if pair[0] is not None else "n/a"
    g_ms = f"{pair[1] * 1e3:.2f}" if pair[1] is not None else "n/a"
    rows.append(record(
        f"slo_serving/{dataset}/backend_routing",
        pair[0] if pair[0] is not None else 0.0,
        f"roofline_pick={roofline_pick} measured_pick={measured_pick} "
        f"(dense={d_ms}ms grasp={g_ms}ms measured; "
        f"flipped={measured_pick != roofline_pick}), "
        f"ewma_vs_model={s['ewma_vs_model']:.1f}x"))
    return rows
