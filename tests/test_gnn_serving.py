"""GraphServe engine: bucket ladder, zero-recompile contract, batched
correctness, GrAd re-bucket policy, and the serving benchmark row."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import BucketLadder, Graph, pad_graph
from repro.core.models import (GNNConfig, build_operands, build_plan,
                               forward_grannite, stack_operands)
from repro.data.graphs import dynamic_graph_stream, planetoid_like
from repro.runtime.gnn_server import (DEFAULT_TECHNIQUES, GraphServe,
                                      GraphServeConfig)

BUCKETS = (128, 256, 384)                   # >= 3 bucket sizes
SIZES = [50, 120, 200, 300, 130, 60, 250, 380, 90]   # >= 8 mixed requests
IN_FEATS, CLASSES = 32, 5


def _graph(n, seed):
    return planetoid_like(num_nodes=n, num_edges=3 * n, num_feats=IN_FEATS,
                          num_classes=CLASSES, seed=seed, train_per_class=2)


@pytest.fixture(scope="module")
def engine():
    sc = GraphServeConfig(ladder=BucketLadder(buckets=BUCKETS),
                          batch_slots=3, return_logits=True)
    eng = GraphServe(sc, seed=0)
    eng.register_model("gcn", GNNConfig(kind="gcn", in_feats=IN_FEATS,
                                        hidden=16, num_classes=CLASSES))
    eng.register_model("gat", GNNConfig(kind="gat", in_feats=IN_FEATS,
                                        hidden=16, num_classes=CLASSES,
                                        heads=4))
    eng.warmup()
    for i, n in enumerate(SIZES):
        eng.submit(_graph(n, i), model="gcn" if i % 2 == 0 else "gat")
    eng.run()
    return eng


# ------------------------------------------------------------- bucket ladder


def test_ladder_selects_smallest_fitting_bucket():
    lad = BucketLadder(buckets=BUCKETS)
    assert lad.bucket_for(1) == 128
    assert lad.bucket_for(128) == 128
    assert lad.bucket_for(129) == 256
    assert lad.bucket_for(384) == 384
    with pytest.raises(ValueError):
        lad.bucket_for(385)
    with pytest.raises(ValueError):
        BucketLadder(buckets=(100,))        # not tile-aligned


def test_ladder_slack_reserves_headroom():
    lad = BucketLadder(buckets=BUCKETS, slack=0.5)
    assert lad.bucket_for(100) == 256       # 100 * 1.5 -> next rung
    # slack is headroom, not a hard cap: the top rung still admits
    assert lad.bucket_for(380) == 384


def test_stack_padded_rejects_mixed_buckets():
    """A dispatch stacks its slots' device features with one program per
    bucket: slots of two buckets are refused, slots of one bucket stack
    to (slots, cap, F)."""
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(128, 256)),
                          batch_slots=2)
    eng = GraphServe(sc, seed=0)
    eng.register_model("gcn", GNNConfig(kind="gcn", in_feats=IN_FEATS,
                                        hidden=16, num_classes=CLASSES))
    a = eng.prepare_submit(_graph(50, 0), model="gcn")
    b = eng.prepare_submit(_graph(200, 1), model="gcn")
    assert (a.bucket, b.bucket) == (128, 256)
    with pytest.raises(ValueError):
        eng._stack_x([a.x, b.x])
    with pytest.raises(ValueError):
        eng._execute_batch([a, b])
    assert eng._stack_x([a.x, a.x]).shape == (2, 128, IN_FEATS)


# ----------------------------------------------------- zero-recompile serving


def test_compiled_blobs_equal_distinct_plans(engine):
    # after warmup: one plan trace per (kind, bucket, fusion mode) — warmup
    # pre-traces BOTH fusion modes (DESIGN.md §11) — plus one CacheG
    # materializer trace and one GrAd delta-patcher trace (§13) per
    # (kind, bucket); the 9 mixed-size requests all replayed warm blobs
    assert engine.compiled_blobs == len(engine.models) * len(BUCKETS) * 4
    engine.assert_warm()
    s = engine.summary()
    assert s["requests"] == len(SIZES)
    assert s["compiled_blobs"] == len(engine.models) * len(BUCKETS) * 4


def test_requests_span_all_buckets(engine):
    assert {r.bucket for r in engine.finished} == set(BUCKETS)


def test_batched_logits_match_single_graph(engine):
    """Engine (vmapped, batched) output == single-graph forward_grannite."""
    for r in engine.finished:
        e = engine.models[r.model]
        ref = forward_grannite(e.params, e.cfg, jnp.asarray(r.pg.features),
                               r.ops, e.techniques)
        np.testing.assert_allclose(
            r.logits, np.asarray(ref)[: r.pg.num_nodes], atol=1e-5)
        np.testing.assert_array_equal(
            r.preds, np.asarray(ref)[: r.pg.num_nodes].argmax(-1))


def test_junk_slot_padding_never_leaks(engine):
    # 9 requests over (kind, bucket) groups with batch_slots=3 means at
    # least one partial batch ran with repeated junk slots; every finished
    # request must still carry its own prediction length
    for r in engine.finished:
        assert r.preds.shape == (r.pg.num_nodes,)
        assert r.done


# ------------------------------------------------------------ GrAd re-bucket


def test_dynamic_stream_rebuckets_exactly_once():
    base = _graph(100, 7)
    sc = GraphServeConfig(ladder=BucketLadder(buckets=BUCKETS),
                          batch_slots=1, return_logits=True)
    eng = GraphServe(sc, seed=0)
    eng.register_model("gcn", GNNConfig(kind="gcn", in_feats=IN_FEATS,
                                        hidden=16, num_classes=CLASSES))
    eng.warmup(buckets=(128,))              # only the starting rung is warm
    gid = eng.attach(base, model="gcn")
    assert eng.graphs[gid][1].capacity == 128

    blobs_before = eng.compiled_blobs
    # 100 -> 160 nodes: crosses the 128-bucket boundary exactly once
    for ei, n, feats in dynamic_graph_stream(base, steps=6,
                                             edges_per_step=32,
                                             nodes_per_step=10, seed=3):
        eng.update(gid, ei, n, feats)
        eng.query(gid)
    eng.run()

    s = eng.summary()
    assert s["rebucket_events"] == 1
    assert eng.graphs[gid][1].capacity == 256
    # exactly one new compile: the (gcn, 256) plan the graph grew into.
    # (The stream adds DIRECTED edges, so CacheG's SymG transfer falls back
    # to the eager dense upload — no new materializer trace at 256.)
    assert s["cacheg_fallbacks"] > 0
    assert eng.compiled_blobs == blobs_before + 1

    # predictions after the re-bucket must equal a fresh pad_graph at the
    # new capacity (value-identical GrAd state, no drift through the move)
    final = eng.finished[-1]
    fresh = pad_graph(Graph(edge_index=ei, num_nodes=n, features=feats),
                      capacity=256)
    e = eng.models["gcn"]
    ref = forward_grannite(e.params, e.cfg, jnp.asarray(fresh.features),
                           build_operands(fresh, e.cfg, lean=True),
                           e.techniques)
    np.testing.assert_allclose(final.logits, np.asarray(ref)[:n], atol=1e-5)
    np.testing.assert_array_equal(final.preds,
                                  np.asarray(ref)[:n].argmax(-1))


# ----------------------------------------------------------- plan / operands


def test_plan_trace_count_tracks_compiles():
    cfg = GNNConfig(kind="gcn", in_feats=8, hidden=8, num_classes=3)
    plan = build_plan(cfg, 128, DEFAULT_TECHNIQUES["gcn"], batch_size=2)
    params = {"l1": {"w": jnp.zeros((8, 8)), "b": jnp.zeros((8,))},
              "l2": {"w": jnp.zeros((8, 3)), "b": jnp.zeros((3,))}}
    pg = pad_graph(_graph(50, 0), capacity=128)
    ops = stack_operands([build_operands(pg, cfg, lean=True)] * 2)
    x = jnp.zeros((2, 128, 8))
    assert plan.trace_count == 0
    plan(params, x, ops)
    assert plan.trace_count == 1
    plan(params, x, ops)                    # warm replay: no new trace
    assert plan.trace_count == 1
    # params are runtime args, so the plan's identity is the full config —
    # models sharing (cfg, capacity, batch, techniques, backend, fusion,
    # shards) share one blob; "dense" is the default aggregation backend
    # (DESIGN.md §10), "none" the default fusion mode (§11), and 0 shards
    # the unsharded path (§12)
    assert plan.key == (cfg, 128, 2, DEFAULT_TECHNIQUES["gcn"], "dense",
                        "none", 0)


def test_identical_models_share_one_blob():
    """Params are runtime args: two tenants with the same (cfg, techniques)
    must share a compiled plan per bucket, not double the jit cache."""
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(128,)), batch_slots=2)
    eng = GraphServe(sc, seed=0)
    cfg = GNNConfig(kind="gcn", in_feats=IN_FEATS, hidden=16,
                    num_classes=CLASSES)
    eng.register_model("tenant_a", cfg)
    eng.register_model("tenant_b", cfg)
    eng.warmup()
    # one shared plan trace per fusion mode (warmup pre-traces both,
    # DESIGN.md §11) + one CacheG materializer trace + one GrAd
    # delta-patcher trace (§13) for the bucket — shared across tenants too
    assert eng.compiled_blobs == 4
    eng.submit(_graph(50, 0), model="tenant_a")
    eng.submit(_graph(60, 1), model="tenant_b")
    eng.run()
    eng.assert_warm()
    assert len(eng.finished) == 2


def test_stack_operands_batches_grasp_rejects_offline_quant():
    """GraSp structures batch (DESIGN.md §10: leaves gain a leading B);
    only the per-graph OFFLINE QuantGr form stays un-batchable, with an
    error naming its source (`calibrate_quant`)."""
    pg = pad_graph(_graph(50, 0), capacity=128)
    cfg = GNNConfig(kind="gcn", in_feats=IN_FEATS, hidden=16,
                    num_classes=CLASSES)
    ops = build_operands(pg, cfg, grasp=True)
    stacked = stack_operands([ops, ops])
    assert stacked.block_sparse is not None
    assert stacked.block_sparse.blocks.shape == \
        (2,) + tuple(ops.block_sparse.blocks.shape)
    assert stacked.norm_adj.shape == (2, 128, 128)
    bad = dataclasses.replace(ops, block_sparse=None, quant={"l1": object()})
    with pytest.raises(ValueError, match="calibrate_quant"):
        stack_operands([bad, bad])
    # mixed grasp/dense sets cannot share one vmapped dispatch
    with pytest.raises(ValueError, match="mix"):
        stack_operands([ops, dataclasses.replace(ops, block_sparse=None)])


# The seeded SymG round-trip sweep that lived here was promoted to a real
# hypothesis property: tests/test_properties_serving.py::
# test_symg_roundtrip_lossless (runs in CI; deepened by the nightly profile).


# -------------------------------------------------------- benchmark output


def test_serving_benchmark_emits_throughput_rows():
    from benchmarks import gnn_paper
    rows = gnn_paper.serving_throughput(n_requests=8, seed=1)
    names = [r["name"] for r in rows]
    assert any("throughput_rps" in n for n in names)
    assert any("requests/s" in r["derived"] for r in rows)
    lat = [r for r in rows if n_matches(r["name"], "latency")][0]
    assert "p50=" in lat["derived"] and "p99=" in lat["derived"]
    blobs = [r for r in rows if n_matches(r["name"], "compiled_blobs")][0]
    # 2 kinds x 3 buckets x (2 fusion-mode plans + CacheG materializer
    # + GrAd delta patcher, §13)
    assert blobs["derived"].startswith("24 ")


def n_matches(name, suffix):
    return name.endswith(suffix)
