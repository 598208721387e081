"""Quality-tier serving (DESIGN.md §8): tier registry, QuantGr calibration,
fp32 fallback, zero recompiles over mixed-tier traffic, CacheG sharing
across tiers, and the GrAx3 exactness condition."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import BucketLadder, pad_graph
from repro.core.layers import Techniques
from repro.core.models import (GNNConfig, build_operands, calibrate_tier,
                               forward_grannite, train_node_classifier)
from repro.data.graphs import planetoid_like
from repro.runtime.gnn_server import (STANDARD_TIERS, GraphServe,
                                      GraphServeConfig, tier_techniques)

IN_FEATS, CLASSES = 16, 4


def _graph(n, seed=0):
    return planetoid_like(num_nodes=n, num_edges=3 * n, num_feats=IN_FEATS,
                          num_classes=CLASSES, seed=seed, train_per_class=4)


def _cfg(kind, **kw):
    return GNNConfig(kind=kind, in_feats=IN_FEATS, hidden=16,
                     num_classes=CLASSES, heads=4, **kw)


def _engine(kind, *, tiers=STANDARD_TIERS, buckets=(128,), batch_slots=2,
            params=None, **cfg_kw):
    sc = GraphServeConfig(ladder=BucketLadder(buckets=buckets),
                          batch_slots=batch_slots, return_logits=True)
    eng = GraphServe(sc, seed=0)
    eng.register_model(kind, _cfg(kind, **cfg_kw), params, tiers=tiers)
    eng.warmup()
    return eng


def _trained_gcn_params(pg, cfg, epochs=40):
    ops = build_operands(pg, cfg, lean=True)
    t = tier_techniques("gcn")["fp32"]

    def fwd(p, x):
        return forward_grannite(p, cfg, x, ops, t)

    return train_node_classifier(jax.random.PRNGKey(0), cfg, pg, fwd,
                                 epochs=epochs)


# ----------------------------------------------------- int8 vs fp32 quality


def test_int8_tier_matches_fp32_on_trained_model():
    """On a TRAINED model (well-separated logits, realistic activation
    ranges) the int8 tier serves logits within quantization tolerance of
    fp32 and an accuracy delta within the paper's ~1-point envelope."""
    g = _graph(100, seed=3)
    cfg = _cfg("gcn")
    pg = pad_graph(g, capacity=128)
    params = _trained_gcn_params(pg, cfg)

    eng = _engine("gcn", params=params)
    gid = eng.attach(g, model="gcn")        # runs calibration + quality audit
    eng.query(gid, tier="fp32")
    eng.query(gid, tier="int8")
    eng.run()
    eng.assert_warm()

    out = {r.tier: r.logits for r in eng.finished}
    rel = (np.linalg.norm(out["int8"] - out["fp32"])
           / np.linalg.norm(out["fp32"]))
    assert rel < 0.05                       # INT8 round-trip error envelope
    agree = (out["int8"].argmax(-1) == out["fp32"].argmax(-1)).mean()
    assert agree > 0.95
    delta = eng.summary()["accuracy_delta_vs_fp32"]["gcn"]["int8"]
    assert abs(delta) <= 1.5                # percentage points (held-out)


@pytest.mark.parametrize("kind", ["gcn", "gat", "sage"])
def test_every_kind_serves_all_standard_tiers(kind):
    eng = _engine(kind, aggregator="max" if kind == "sage" else "mean")
    gid = eng.attach(_graph(100), model=kind)
    for tier in STANDARD_TIERS:
        eng.query(gid, tier=tier)
    eng.run()
    eng.assert_warm()
    assert {r.tier for r in eng.finished} == set(STANDARD_TIERS)
    deltas = eng.summary()["accuracy_delta_vs_fp32"][kind]
    assert set(deltas) == {"int8", "int8+grax"}


# -------------------------------------------------- plan / blob accounting


def test_tier_plans_counted_in_compiled_blobs():
    """Warmup compiles one plan per DISTINCT tier Techniques per bucket
    AND per fusion mode (both pre-traced, DESIGN.md §11) — GCN's int8+grax
    aliases int8 (no GrAx variant), so 3 named tiers cost 2×2 plan traces —
    plus the shared CacheG materializer trace and, for QuantGr GCN tiers,
    the per-bucket tier-operand deriver (int8 Â), all inside the
    zero-recompile contract."""
    eng = _engine("gcn")
    # (fp32 + int8(=int8+grax)) × 2 fusion modes, materializer, int8-Â
    # deriver, plus the §13 GrAd delta patcher AND its tier row-requant
    # trace (a QuantGr GCN tier keeps a derived int8 Â to patch)
    assert eng.compiled_blobs == 2 * 2 + 1 + 1 + 1 + 1
    eng = _engine("gat")
    # no deriver (model-level quant), patcher only — no tier form to patch
    assert eng.compiled_blobs == 3 * 2 + 1 + 1
    # untier'd registration stays a single-plan engine (back-compat):
    # fp32-only means no int8 Â, so the patcher warms without the requant
    eng = _engine("gcn", tiers=None)
    assert eng.compiled_blobs == 1 * 2 + 1 + 1


def test_zero_recompiles_across_mixed_tier_traffic():
    """Mixed sizes AND mixed tiers: after warmup, no request sequence may
    trace anything new — the tier registry is pre-compiled, calibration is
    pure value work, and fallback reuses the warm fp32 plan."""
    eng = _engine("gat", buckets=(128, 256), batch_slots=2)
    blobs = eng.compiled_blobs
    gid = eng.attach(_graph(100, seed=1), model="gat")
    rng = np.random.default_rng(0)
    for i in range(10):
        tier = STANDARD_TIERS[int(rng.integers(3))]
        if i % 3 == 2:
            eng.submit(_graph(int(rng.integers(60, 250)), seed=i),
                       model="gat", tier=tier)
        else:
            eng.query(gid, tier=tier)
    eng.run()
    eng.assert_warm()
    assert eng.compiled_blobs == blobs
    assert len(eng.finished) == 10
    # every request kept its resolved tier and its own output length
    for r in eng.finished:
        assert r.tier in STANDARD_TIERS
        assert r.preds.shape == (r.pg.num_nodes,)


def test_mixed_tier_requests_never_share_a_batch():
    """Tier is part of the batch key — a dispatch can't mix compiled
    variants, so 4 alternating-tier requests at batch_slots=4 must run as
    two partial batches, not one full one."""
    eng = _engine("gcn", batch_slots=4)
    gid = eng.attach(_graph(100), model="gcn")
    for tier in ("fp32", "int8", "fp32", "int8"):
        eng.query(gid, tier=tier)
    eng.run()
    eng.assert_warm()
    assert eng.metrics["batches"] == 2
    assert eng.metrics["slots_filled"] == 4


# ------------------------------------------------ CacheG shared across tiers


def test_operand_cache_shared_across_tiers():
    """The operand cache key carries no tier: fp32 and int8 queries of one
    attached graph share ONE device-resident fp32 entry, and the derived
    int8 Â is quantized once per structure version (one tier-cache entry
    reused by both QuantGr tier names), never per query."""
    eng = _engine("gcn")
    gid = eng.attach(_graph(100), model="gcn")
    eng.query(gid, tier="fp32")             # structure miss
    eng.query(gid, tier="int8")             # HIT — same fp32 operands
    eng.query(gid, tier="int8+grax")        # HIT — and reuses the int8 Â
    eng.query(gid, tier="int8")             # HIT
    eng.run()
    eng.assert_warm()
    s = eng.summary()
    assert s["operand_cache_misses"] == 1
    assert s["operand_cache_hits"] == 3
    assert len(eng._cache.view("operand")) == 1
    assert len(eng._cache.view("tier")) == 1
    # update() invalidates BOTH caches under the same version key
    g2 = _graph(110, seed=9)
    eng.update(gid, g2.edge_index, g2.num_nodes, g2.features)
    assert len(eng._cache.view("operand")) == 0
    assert len(eng._cache.view("tier")) == 0
    eng.query(gid, tier="int8")
    eng.run()
    eng.assert_warm()
    assert len(eng._cache.view("tier")) == 1


# ------------------------------------------------- fallback-before-calibrate


def test_uncalibrated_quant_tier_falls_back_to_fp32():
    """submit() never calibrates; an int8 request on an uncalibrated model
    must serve through the fp32 plan (counted, not an error) and flip to
    real int8 once calibrate() runs."""
    g = _graph(100)
    eng = _engine("gcn")
    eng.submit(g, model="gcn", tier="int8")
    eng.run()
    eng.assert_warm()
    assert eng.finished[-1].tier == "fp32"
    assert eng.summary()["tier_fallbacks"] == 1

    eng.calibrate("gcn", g)
    eng.submit(g, model="gcn", tier="int8")
    eng.run()
    eng.assert_warm()                       # calibration added NO traces
    assert eng.finished[-1].tier == "int8"
    assert eng.summary()["tier_fallbacks"] == 1     # no new fallback


def test_attach_calibrates_once_per_model_tier():
    eng = _engine("gcn")
    e = eng.models["gcn"]
    assert e.calibrations == {}
    eng.attach(_graph(100, seed=1), model="gcn")
    cal = e.calibrations["int8"]
    # alias tiers (identical Techniques) share ONE calibration pytree and
    # one audit, exactly like they share a compiled plan
    assert e.calibrations["int8+grax"] is cal
    deltas = dict(e.accuracy_delta)
    eng.attach(_graph(90, seed=2), model="gcn")     # second attach: no-op
    assert e.calibrations["int8"] is cal
    # ...including the quality audit: advertised deltas keep their first
    # calibration graph instead of silently drifting to a new one
    assert e.accuracy_delta == deltas
    eng.calibrate("gcn", _graph(90, seed=2))        # explicit, non-forced
    assert e.calibrations["int8"] is cal
    assert e.accuracy_delta == deltas
    # deferred mode leaves the model uncalibrated
    eng2 = _engine("gat")
    eng2.attach(_graph(100), model="gat", calibrate=False)
    assert eng2.models["gat"].calibrations == {}


def test_unknown_tier_and_missing_fp32_are_errors():
    eng = _engine("gcn")
    gid = eng.attach(_graph(100), model="gcn")
    with pytest.raises(KeyError):
        eng.query(gid, tier="bf16")
    with pytest.raises(ValueError):
        eng.register_model("bad", _cfg("gcn"),
                           tiers={"int8": tier_techniques("gcn")["int8"]})
    # the fallback tier must be servable uncalibrated: a QuantGr 'fp32'
    # would fall back to itself and run its plan with quant=None,
    # recompiling a trace warmup compiled against a calibration pytree
    with pytest.raises(ValueError):
        eng.register_model("bad2", _cfg("gcn"),
                           tiers={"fp32": tier_techniques("gcn")["int8"]})
    with pytest.raises(ValueError):
        eng.register_model(
            "bad3", _cfg("gcn"),
            techniques=dataclasses.replace(tier_techniques("gcn")["fp32"],
                                           quantgr=True))


# ------------------------------------------------------- custom tier registry


def test_custom_grax_only_tier_needs_no_calibration():
    """A non-QuantGr tier (pure GrAx approximation) serves immediately —
    no calibration, no fallback — through its own compiled plan."""
    std = tier_techniques("sage")
    tiers = {"fp32": std["fp32"],
             "grax": dataclasses.replace(std["fp32"], grax3=True)}
    eng = _engine("sage", tiers=tiers, aggregator="max")
    gid = eng.attach(_graph(100), model="sage")
    eng.query(gid, tier="grax")
    eng.run()
    eng.assert_warm()
    assert eng.finished[-1].tier == "grax"
    assert eng.summary()["tier_fallbacks"] == 0


# ----------------------------------------------------------- GrAx3 exactness


def test_grax3_sage_max_equivalence_small_graphs():
    """GrAx3 (mask-mul + maxpool) equals the exact additive-mask max
    whenever aggregated features are >= 0 — the paper's stated condition,
    guaranteed here by the ReLU'd pooling layer. Checked on several small
    graphs through the full forward."""
    cfg = _cfg("sage", aggregator="max")
    key = jax.random.PRNGKey(1)
    from repro.core.models import init_params
    params = init_params(key, cfg)
    for seed, n in ((0, 40), (1, 60), (2, 100)):
        pg = pad_graph(_graph(n, seed=seed), capacity=128)
        ops = build_operands(pg, cfg, lean=True)
        x = jnp.asarray(pg.features)
        exact = forward_grannite(params, cfg, x, ops, Techniques(effop=True))
        grax = forward_grannite(params, cfg, x, ops,
                                Techniques(effop=True, grax3=True))
        np.testing.assert_allclose(np.asarray(grax), np.asarray(exact),
                                   atol=1e-5)


def test_grax3_tier_logits_match_fp32_tier_through_engine():
    """End-to-end: the int8+grax SAGE-max tier differs from fp32 only by
    quantization (GrAx3 itself is exact post-ReLU), so tier logits stay
    within the INT8 envelope."""
    eng = _engine("sage", aggregator="max")
    gid = eng.attach(_graph(80), model="sage")
    eng.query(gid, tier="fp32")
    eng.query(gid, tier="int8+grax")
    eng.run()
    out = {r.tier: r.logits for r in eng.finished}
    rel = (np.linalg.norm(out["int8+grax"] - out["fp32"])
           / np.linalg.norm(out["fp32"]))
    assert rel < 0.05


# ----------------------------------------------------- calibration invariance


def test_calibration_pytree_structure_is_graph_independent():
    """The warmup contract: calibrate_tier's pytree structure must be a
    function of the model config alone, so a plan warmed on a placeholder
    calibration replays warm on the real one."""
    cfg = _cfg("sage", aggregator="max")
    from repro.core.models import init_params
    params = init_params(jax.random.PRNGKey(0), cfg)
    structs = []
    for n, cap in ((30, 128), (100, 128), (200, 256)):
        pg = pad_graph(_graph(n, seed=n), capacity=cap)
        ops = build_operands(pg, cfg, lean=True)
        cal = calibrate_tier(params, cfg, jnp.asarray(pg.features), ops)
        structs.append(jax.tree_util.tree_structure(cal))
        shapes = [leaf.shape for leaf in jax.tree_util.tree_leaves(cal)]
        assert all(cap not in s for s in shapes)    # model-shaped only
    assert structs[0] == structs[1] == structs[2]


def test_per_tier_latency_metrics_reported():
    eng = _engine("gcn")
    gid = eng.attach(_graph(100), model="gcn")
    for tier in ("fp32", "int8", "int8", "fp32"):
        eng.query(gid, tier=tier)
    eng.run()
    tiers = eng.summary()["tiers"]
    assert set(tiers) == {"fp32", "int8"}
    for st in tiers.values():
        assert st["requests"] == 2
        assert st["p50_latency_ms"] > 0
        assert st["p99_latency_ms"] >= st["p50_latency_ms"]
        assert st["throughput_rps"] > 0
