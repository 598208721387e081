"""GraSp aggregation backend dispatch (DESIGN.md §10).

The serving-reachable block-sparse path: per-(graph, bucket) backend
selection by the density/cost rule, batched `bitmap_spmm` plans, the
device-derived structure cache next to CacheG, forced-mode fallbacks, and
the `backend_fallbacks` observability contract. The Pallas grid itself is
exercised because conftest routes kernels through interpret mode; a
dedicated CI leg re-runs this file with `REPRO_PALLAS_INTERPRET=1` set
explicitly so the routing never silently regresses to the ref fallback.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import BucketLadder, pad_graph
from repro.core.models import GNNConfig, build_plan, prepare_host_operands
from repro.core.sparsity import (agg_cost_model, block_stats,
                                 compact_block_sparse, from_block_sparse,
                                 grasp_max_nnz, pad_block_sparse,
                                 select_agg_backend, stack_block_sparse,
                                 to_block_sparse)
from repro.data.graphs import clustered_like, planetoid_like
from repro.runtime.gnn_server import GraphServe, GraphServeConfig

IN_FEATS, CLASSES = 16, 4


def _engine(mode, *, buckets=(1024,), batch_slots=2, hidden=8, seed=0):
    sc = GraphServeConfig(ladder=BucketLadder(buckets=buckets),
                          batch_slots=batch_slots, return_logits=True)
    eng = GraphServe(sc, seed=seed)
    eng.register_model("gcn", GNNConfig(kind="gcn", in_feats=IN_FEATS,
                                        hidden=hidden, num_classes=CLASSES),
                       agg_backend=mode)
    eng.warmup()
    return eng


def _sparse_graph(seed=1, n=900, density=0.05):
    return clustered_like(num_nodes=n, num_feats=IN_FEATS,
                          num_classes=CLASSES, within_density=density,
                          seed=seed)


def _scattered_graph(seed=2, n=900):
    return planetoid_like(num_nodes=n, num_edges=40 * n, num_feats=IN_FEATS,
                          num_classes=CLASSES, seed=seed, train_per_class=2)


# ----------------------------------------------------------- structure layer


def test_pad_and_stack_block_sparse_roundtrip(rng):
    budget = grasp_max_nnz(256)
    mats, sps = [], []
    for s in range(3):
        a = ((rng.random((256, 256)) < 0.04)
             * rng.random((256, 256))).astype(np.float32)
        mats.append(a)
        sps.append(pad_block_sparse(to_block_sparse(a), budget))
    for a, sp in zip(mats, sps):
        np.testing.assert_array_equal(from_block_sparse(sp), a)
    stacked = stack_block_sparse(sps)
    assert stacked.blocks.shape == (3,) + tuple(sps[0].blocks.shape)
    for b, a in enumerate(mats):
        single = dataclasses.replace(
            stacked, blocks=np.asarray(stacked.blocks[b]),
            block_cols=np.asarray(stacked.block_cols[b]),
            counts=np.asarray(stacked.counts[b]),
            bitmap=np.asarray(stacked.bitmap[b]))
        np.testing.assert_array_equal(from_block_sparse(single), a)


def test_bitmap_spmm_batched_entry(rng):
    """The public batched kernel entry (one vmap over the single-graph
    wrapper, the same lowering a batched ExecutionPlan produces) equals
    the per-graph dense matmuls."""
    from repro.kernels import ops as kops
    budget = grasp_max_nnz(256)
    mats = [((rng.random((256, 256)) < 0.04)
             * rng.random((256, 256))).astype(np.float32) for _ in range(3)]
    hs = rng.standard_normal((3, 256, 48)).astype(np.float32)
    stacked = stack_block_sparse(
        [pad_block_sparse(to_block_sparse(a), budget) for a in mats])
    got = kops.bitmap_spmm_batched(stacked, jnp.asarray(hs))
    want = np.stack([a @ h for a, h in zip(mats, hs)])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-4)


def test_pad_block_sparse_rejects_over_budget(rng):
    a = rng.random((256, 256)).astype(np.float32)   # fully dense: max_nnz=2
    sp = to_block_sparse(a)
    with pytest.raises(ValueError, match="budget"):
        pad_block_sparse(sp, sp.max_nnz - 1)


def test_device_compactor_matches_host_compaction(rng):
    """`compact_block_sparse` (jnp, device-side) and `to_block_sparse` +
    `pad_block_sparse` (numpy, host-side) produce structures that densify
    to the same matrix and agree on counts/bitmap — the two build paths of
    DESIGN.md §10 must be interchangeable."""
    a = ((rng.random((384, 384)) < 0.03)
         * rng.random((384, 384))).astype(np.float32)
    budget = grasp_max_nnz(384)
    st = block_stats(a)
    if st["max_row_nnz"] > budget:      # keep the fixture eligible
        a[:, 256:] = 0.0
        st = block_stats(a)
    host = pad_block_sparse(to_block_sparse(a), budget)
    dev, counts_true = compact_block_sparse(jnp.asarray(a), max_nnz=budget)
    np.testing.assert_array_equal(np.asarray(dev.counts), host.counts)
    np.testing.assert_array_equal(np.asarray(dev.bitmap), host.bitmap)
    np.testing.assert_array_equal(np.asarray(counts_true),
                                  host.bitmap.sum(axis=1))
    np.testing.assert_array_equal(
        from_block_sparse(dataclasses.replace(
            dev, blocks=np.asarray(dev.blocks),
            block_cols=np.asarray(dev.block_cols),
            counts=np.asarray(dev.counts))), a)


# --------------------------------------------------------------- cost rule


def test_select_backend_density_rule():
    """Low block density at a large bucket → grasp; a block-row over the
    budget → ineligible → dense regardless of mode; tiny buckets → dense
    (per-step overhead dominates)."""
    backend, dense_s, grasp_s = select_agg_backend(
        1024, 16, nnz_blocks=8, max_row_nnz=1)
    assert backend == "grasp" and grasp_s < dense_s
    cb = 1024 // 128
    backend, _, _ = select_agg_backend(
        1024, 16, nnz_blocks=cb * cb, max_row_nnz=cb)
    assert backend == "dense"
    backend, _, _ = select_agg_backend(
        1024, 16, nnz_blocks=cb * cb, max_row_nnz=cb, mode="grasp")
    assert backend == "dense"               # forced mode cannot override
    backend, _, _ = select_agg_backend(128, 16, nnz_blocks=1, max_row_nnz=1)
    assert backend == "dense"


def test_grasp_budget_monotone_and_bounded():
    prev = 0
    for cap in (128, 256, 384, 512, 1024, 2048, 4096):
        b = grasp_max_nnz(cap)
        assert b >= prev and 1 <= b <= max(cap // 128, 1)
        prev = b


def test_agg_cost_model_monotone_in_nnz():
    costs = [agg_cost_model(1024, 64, nnz_blocks=k, max_nnz=2)[1]
             for k in (1, 4, 16, 64)]
    assert costs == sorted(costs)


# ------------------------------------------------------------ serving paths


def test_auto_mode_batched_grasp_matches_dense(rng):
    """The acceptance path: a GCN in `auto` mode serves a low-density
    clustered graph through the batched `bitmap_spmm` plan (batch >= 2),
    logits equal the dense backend within fp32 tolerance, and mixed
    dense/grasp traffic replays with zero recompiles after warmup."""
    g_sparse, g_scatter = _sparse_graph(), _scattered_graph()
    engines = {m: _engine(m) for m in ("dense", "auto")}
    for eng in engines.values():
        gid_s = eng.attach(g_sparse, model="gcn")
        gid_d = eng.attach(g_scatter, model="gcn")
        eng.query(gid_s)
        eng.query(gid_s)                    # same key → one batch of 2
        eng.query(gid_d)
        eng.submit(g_sparse, model="gcn")   # one-shot intake path too
        eng.run()
        eng.assert_warm()
    s = engines["auto"].summary()
    assert s["grasp_batches"] >= 2          # batched query pair + submit
    assert s["backend_fallbacks"] == 0
    assert engines["dense"].summary()["grasp_batches"] == 0
    backs = {r.uid: r.backend for r in engines["auto"].finished}
    assert "grasp" in backs.values() and "dense" in backs.values()
    ref = {r.uid: r.logits for r in engines["dense"].finished}
    for r in engines["auto"].finished:
        np.testing.assert_allclose(r.logits, ref[r.uid], atol=1e-4,
                                   rtol=1e-4)


def test_grasp_structure_cached_per_version_and_released():
    """The block structure is derived ONCE per (graph, version) from the
    cached Â, `update()` invalidates it, and `detach()` releases it — the
    same lifecycle as the CacheG operand and int8-Â caches."""
    eng = _engine("grasp")
    g = _sparse_graph()
    gid = eng.attach(g, model="gcn")
    eng.query(gid)
    eng.run()
    assert (gid, 0) in eng._cache.view("grasp")
    traces = eng._block_compactor.trace_count
    eng.query(gid)
    eng.query(gid)
    eng.run()
    eng.assert_warm()
    assert eng._block_compactor.trace_count == traces   # replayed, not rebuilt
    assert len(eng._cache.view("grasp")) == 1                   # one entry, reused
    g2 = _sparse_graph(seed=7)
    eng.update(gid, g2.edge_index, g2.num_nodes, g2.features)
    assert (gid, 0) not in eng._cache.view("grasp")
    eng.query(gid)
    eng.run()
    assert (gid, 1) in eng._cache.view("grasp")
    eng.detach(gid)
    assert not eng._cache.view("grasp")                         # regression: released


def test_forced_mode_ineligible_graph_counts_backend_fallback():
    eng = _engine("grasp")
    gid = eng.attach(_scattered_graph(), model="gcn")   # blocks all dense
    eng.query(gid)
    eng.query(gid)          # cached decision: still one count per request
    eng.run()
    eng.assert_warm()
    s = eng.summary()
    assert s["grasp_batches"] == 0
    assert s["backend_fallbacks"] == 2
    assert all(r.backend == "dense" for r in eng.finished)


def test_eager_engine_builds_structure_on_host(rng):
    """A directed graph's operands are still built eagerly on the host
    (counted in `cacheg_fallbacks`), but a one-shot grasp request over it
    derives its block structure on the device like any other: only the
    dense operands cross the link, and logits match the dense backend."""
    eng = _engine("grasp")
    eng_d = _engine("dense")
    g = _sparse_graph()
    # one extra edge whose reverse is absent makes the graph directed
    adj = np.zeros((g.num_nodes, g.num_nodes), bool)
    adj[g.edge_index[1], g.edge_index[0]] = True
    i, j = np.argwhere(~adj & ~adj.T & ~np.eye(g.num_nodes, dtype=bool))[0]
    g = dataclasses.replace(g, edge_index=np.concatenate(
        [g.edge_index, np.array([[i], [j]], np.int32)], axis=1))
    for e in (eng, eng_d):
        e.submit(g, model="gcn")
        e.submit(g, model="gcn")
        e.run()
        e.assert_warm()
    s = eng.summary()
    assert s["grasp_batches"] == 1
    assert s["cacheg_fallbacks"] == 2
    ref = {r.uid: r.logits for r in eng_d.finished}
    for r in eng.finished:
        assert r.backend == "grasp"
        np.testing.assert_allclose(r.logits, ref[r.uid], atol=1e-4,
                                   rtol=1e-4)
    # the host product is the eager operand set alone (scheduler host stage)
    ho = prepare_host_operands(pad_graph(g, capacity=1024),
                               eng.models["gcn"].cfg)
    assert ho.fallback and ho.compact is None
    assert s["operand_bytes_h2d"] == 2 * ho.nbytes


def test_backend_fallback_counts_ref_mode_dense_run(monkeypatch):
    """A grasp dispatch while the kernel routing is in `ref` mode runs the
    aggregation as plain XLA (no skip grid) — `backend_fallbacks` must
    surface it (satellite: a silent densify is observable, never
    invisible)."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    from repro.kernels.ops import bitmap_spmm_mode
    assert bitmap_spmm_mode() == "ref"
    eng = _engine("grasp", buckets=(256,))
    gid = eng.attach(_sparse_graph(n=200, density=0.03), model="gcn")
    eng.query(gid)
    eng.query(gid)
    eng.run()
    eng.assert_warm()
    s = eng.summary()
    assert s["grasp_batches"] == 1
    # per-REQUEST unit (mirrors tier_fallbacks): both requests in the one
    # dispatch ran their aggregation dense under the ref routing
    assert s["backend_fallbacks"] == 2


def test_quant_tiers_always_resolve_dense():
    """QuantGr tiers aggregate through the cached int8 Â; the grasp backend
    never applies to them, consistently per plan, so mixed-tier traffic
    over one grasp-mode model stays warm."""
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(256,)), batch_slots=2,
                          return_logits=True)
    eng = GraphServe(sc, seed=0)
    eng.register_model("gcn", GNNConfig(kind="gcn", in_feats=IN_FEATS,
                                        hidden=8, num_classes=CLASSES),
                       tiers=("fp32", "int8"), agg_backend="grasp")
    eng.warmup()
    gid = eng.attach(_sparse_graph(n=200, density=0.03), model="gcn")
    eng.query(gid, tier="fp32")
    eng.query(gid, tier="int8")
    eng.query(gid, tier="int8")
    eng.run()
    eng.assert_warm()
    by_tier = {r.tier: r.backend for r in eng.finished}
    assert by_tier["fp32"] == "grasp"
    assert by_tier["int8"] == "dense"


def test_non_gcn_kinds_resolve_dense():
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(256,)), batch_slots=2)
    eng = GraphServe(sc, seed=0)
    eng.register_model("gat", GNNConfig(kind="gat", in_feats=IN_FEATS,
                                        hidden=8, num_classes=CLASSES,
                                        heads=2), agg_backend="auto")
    eng.warmup()
    eng.submit(_sparse_graph(n=200, density=0.03), model="gat")
    eng.run()
    eng.assert_warm()
    assert all(r.backend == "dense" for r in eng.finished)
    assert eng.summary()["grasp_batches"] == 0


def test_register_model_rejects_unknown_backend_mode():
    from repro.core.layers import Techniques
    eng = GraphServe(GraphServeConfig(ladder=BucketLadder(buckets=(128,))))
    with pytest.raises(ValueError, match="agg_backend"):
        eng.register_model("m", GNNConfig(kind="gcn", in_feats=4,
                                          num_classes=2),
                           agg_backend="sparse")
    with pytest.raises(ValueError, match="backend"):
        build_plan(GNNConfig(kind="gcn", in_feats=4, num_classes=2), 128,
                   Techniques(stagr=True), backend="csr")


def test_scheduler_pipeline_serves_grasp_warm():
    """The async pipeline groups ready requests by the 4-field batch key:
    mixed dense/grasp traffic through the deterministic scheduler equals
    the engine's own sequential answers and replays warm."""
    from repro.runtime.scheduler import PipelineConfig
    eng = _engine("auto")
    eng_ref = _engine("auto")
    g_s, g_d = _sparse_graph(), _scattered_graph()
    with eng.scheduler(PipelineConfig(deterministic=True)) as sched:
        for g in (g_s, g_s, g_d, g_s):
            sched.submit(g, model="gcn")
        out = sched.drain()
    assert eng.summary()["grasp_batches"] >= 1
    eng.assert_warm()
    uids = []
    for g in (g_s, g_s, g_d, g_s):
        uids.append(eng_ref.submit(g, model="gcn"))
    eng_ref.run()
    ref = {r.uid: r for r in eng_ref.finished}
    for r, uid in zip(out, uids):
        assert r.backend == ref[uid].backend
        np.testing.assert_allclose(r.logits, ref[uid].logits, atol=1e-5)
