"""Multi-device GraphSplit (DESIGN.md §12): N-way partitioner invariants,
sharded-vs-single-device differentials, auto-shard serving, and the
compressed-halo cost model.

Differential discipline: BOTH sides of every comparison are jitted — XLA's
CPU backend strength-reduces divisions to reciprocal multiplies, which
shifts int8 round() boundaries between jitted and eager runs, so a
jitted-vs-eager comparison tests the compiler, not the sharding. The
sharded plans here run vmap-simulated (1 CPU device); the CI multi-device
leg re-runs this file under XLA_FLAGS=--xla_force_host_platform_device_count=8
where the same plans place under shard_map.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.graph import BucketLadder, Graph, pad_graph
from repro.core.models import (GNNConfig, build_operands, build_plan,
                               build_sharded_operands, build_sharded_plan,
                               calibrate_tier, forward_grannite, init_params,
                               sharded_exchange_widths, stack_shard_slices,
                               unshard_logits)
from repro.core.partition import (GraphShards, modelled_sharded_latency,
                                  partition_for_ladder, partition_graph)
from repro.data.graphs import clustered_like
from repro.runtime.gnn_server import (GraphServe, GraphServeConfig,
                                      tier_techniques)

IN_FEATS, CLASSES = 12, 4


def _graph(n, seed):
    return clustered_like(num_nodes=n, num_feats=IN_FEATS,
                          num_classes=CLASSES, within_density=0.05,
                          cross_frac=0.1, seed=seed)


def _cfg(kind, **kw):
    base = dict(in_feats=IN_FEATS, hidden=16, num_classes=CLASSES)
    if kind == "gat":
        base["heads"] = 4
    base.update(kw)
    return GNNConfig(kind=kind, **base)


# --------------------------------------------------------------- partitioner


def test_partition_invariants():
    g = _graph(300, 0)
    part = partition_graph(g.edge_index, 300, 3, shard_cap=128)
    assert part.full_rows == 384
    assert part.loads.sum() == 300 and (part.loads <= 128).all()
    # perm is a permutation of the slot space
    np.testing.assert_array_equal(np.sort(part.perm), np.arange(384))
    # shard s's slot range holds only its own nodes (padding aside)
    for s in range(3):
        rows = part.perm[s * 128:(s + 1) * 128]
        own = rows[rows < 300]
        assert len(own) == part.loads[s]
        assert (part.assignment[own] == s).all()
    # halo sets are exactly the remote in-neighbors of each shard
    src, dst = g.edge_index
    for s in range(3):
        expect = np.unique(src[(part.assignment[dst] == s)
                               & (part.assignment[src] != s)])
        np.testing.assert_array_equal(part.halo[s], expect)
    assert part.cut_edges == int(
        (part.assignment[src] != part.assignment[dst]).sum())
    # deterministic: the serving cache keys partitions by structure version
    again = partition_graph(g.edge_index, 300, 3, shard_cap=128)
    np.testing.assert_array_equal(part.perm, again.perm)
    assert part.cut_edges == again.cut_edges


def test_partition_cut_beats_random_split():
    """The greedy affinity placement must beat a round-robin strawman on a
    community-structured graph — otherwise it isn't an edge-cut heuristic."""
    g = _graph(256, 1)           # two 128-node communities, 10% cross edges
    part = partition_graph(g.edge_index, 256, 2, shard_cap=128)
    src, dst = g.edge_index
    rr = (np.arange(256) % 2)
    rr_cut = int((rr[src] != rr[dst]).sum())
    assert part.cut_edges < rr_cut


def test_partition_cap_errors():
    g = _graph(64, 2)
    with pytest.raises(ValueError, match="exceeds the shard bucket"):
        partition_graph(g.edge_index, 64, 2, shard_cap=128, max_load=200)
    with pytest.raises(ValueError, match="cannot hold"):
        partition_graph(g.edge_index, 64, 2, shard_cap=128, max_load=16)
    with pytest.raises(ValueError, match="shards must be"):
        partition_graph(g.edge_index, 64, 0, shard_cap=128)


def test_partition_for_ladder_picks_smallest_fitting_count():
    lad = BucketLadder(buckets=(128, 256))
    g = _graph(300, 3)
    # 2 shards -> load 150 -> bucket 256 (fits): chosen over 4
    part = partition_for_ladder(g.edge_index, 300, lad, (4, 2))
    assert (part.shards, part.shard_cap) == (2, 256)
    # only 8 configured -> load 38 -> bucket 128
    part = partition_for_ladder(g.edge_index, 300, lad, (8,))
    assert (part.shards, part.shard_cap) == (8, 128)
    # nothing fits
    with pytest.raises(ValueError, match="fits no configured shard count"):
        partition_for_ladder(g.edge_index, 3000, lad, (2,))
    # shard count 1 is the unsharded path, never a partition
    with pytest.raises(ValueError, match="fits no configured shard count"):
        partition_for_ladder(g.edge_index, 300, lad, (1,))


# ------------------------------------------------- plan-level differentials


def _sharded_logits(cfg, t, params, g, part, *, compress, quant=None,
                    rng_seed=7):
    slices = build_sharded_operands(g, part, cfg,
                                    rng=np.random.default_rng(rng_seed))
    x, ops, mask = stack_shard_slices(slices)
    plan = build_sharded_plan(cfg, part.shard_cap, part.shards, t,
                              compress=compress)
    out = plan(params, x, ops, quant, node_mask=mask)
    return unshard_logits(np.asarray(out), part)


def _reference_logits(cfg, t, params, g, capacity, *, quant=None,
                      rng_seed=7):
    pg = pad_graph(g, capacity=capacity)
    ops = build_operands(pg, cfg, lean=True,
                         rng=np.random.default_rng(rng_seed))
    fwd = jax.jit(lambda p, x, o, q: forward_grannite(p, cfg, x, o, t,
                                                      quant=q))
    return np.asarray(fwd(params, jnp.asarray(pg.features), ops, quant))


@pytest.mark.parametrize("kind", ["gcn", "gat", "sage"])
@pytest.mark.parametrize("compress", [False, True])
def test_sharded_matches_single_device_fp32(kind, compress):
    """2-shard forward == jitted full-capacity forward, per kind.

    compress=False is numerically tight (same math, reassociated adds);
    compress=True adds only the int8 wire error (<= scale/2 per halo
    element, amplified once through layer 2) — the documented tolerance."""
    cfg = _cfg(kind)
    t = tier_techniques(kind)["fp32"]
    g = _graph(200, 4)
    part = partition_graph(g.edge_index, 200, 2, shard_cap=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    got = _sharded_logits(cfg, t, params, g, part, compress=compress)
    ref = _reference_logits(cfg, t, params, g, part.full_rows)[:200]
    tol = 0.05 if compress else 5e-6
    np.testing.assert_allclose(got, ref, atol=tol)


@pytest.mark.parametrize("tier", ["int8", "int8+grax"])
def test_sharded_gcn_int8_exact_vs_unsharded(tier):
    """QuantGr GCN: row blocks carry COMPLETE Â rows, so per-row scales —
    and hence every int8 rounding decision — match the single-device trace
    bit-for-bit. With the wire uncompressed the sharded int8 forward is
    EXACTLY the unsharded one (0.0), not merely close."""
    cfg = _cfg("gcn")
    t = tier_techniques("gcn")[tier]
    g = _graph(200, 5)
    part = partition_graph(g.edge_index, 200, 2, shard_cap=128)
    params = init_params(jax.random.PRNGKey(1), cfg)
    pg = pad_graph(g, capacity=part.full_rows)
    ops = build_operands(pg, cfg, lean=True)
    cal = calibrate_tier(params, cfg, jnp.asarray(pg.features), ops)
    got = _sharded_logits(cfg, t, params, g, part, compress=False, quant=cal)
    ref = _reference_logits(cfg, t, params, g, part.full_rows,
                            quant=cal)[:200]
    np.testing.assert_array_equal(got, ref)


def test_sharded_sage_max_pooling_matches():
    cfg = _cfg("sage", aggregator="max")
    t = tier_techniques("sage")["fp32"]
    g = _graph(150, 6)
    part = partition_graph(g.edge_index, 150, 2, shard_cap=128)
    params = init_params(jax.random.PRNGKey(2), cfg)
    got = _sharded_logits(cfg, t, params, g, part, compress=False)
    ref = _reference_logits(cfg, t, params, g, part.full_rows)[:150]
    np.testing.assert_allclose(got, ref, atol=5e-6)


def test_four_shards_match_two_shards():
    """Shard count is a placement choice, not a numerics choice."""
    cfg = _cfg("gcn")
    t = tier_techniques("gcn")["fp32"]
    g = _graph(400, 8)
    params = init_params(jax.random.PRNGKey(3), cfg)
    p2 = partition_graph(g.edge_index, 400, 2, shard_cap=256)
    p4 = partition_graph(g.edge_index, 400, 4, shard_cap=128)
    assert p2.full_rows == p4.full_rows == 512
    a = _sharded_logits(cfg, t, params, g, p2, compress=False)
    b = _sharded_logits(cfg, t, params, g, p4, compress=False)
    np.testing.assert_allclose(a, b, atol=5e-6)


# ---------------------------------------------------------------- cost model


def _fake_part(shards, shard_cap):
    n = shards * shard_cap
    return GraphShards(shards=shards, shard_cap=shard_cap, num_nodes=n,
                       assignment=np.zeros(n, np.int32),
                       perm=np.arange(n), halo=(), loads=np.array([n]),
                       cut_edges=0)


def test_modelled_latency_monotone_when_compute_dominates():
    """At constant full capacity, doubling shards halves the dominant
    O(C x full) aggregation; with compressed halos the wire term stays
    small enough that modelled throughput rises monotonically 1->8 — the
    scaling claim the sharded_serving benchmark asserts on real rows."""
    widths = sharded_exchange_widths(_cfg("gcn", hidden=256, num_classes=5))
    lat = [modelled_sharded_latency(_fake_part(s, 2048 // s), in_feats=16,
                                    hidden=256, classes=5,
                                    exchange_widths=widths)
           for s in (1, 2, 4, 8)]
    assert all(b < a for a, b in zip(lat, lat[1:])), lat
    # 1-shard partitions pay no wire: compressed == exact at S=1
    one_c = modelled_sharded_latency(_fake_part(1, 2048), in_feats=16,
                                     hidden=256, classes=5,
                                     exchange_widths=widths, compress=True)
    one_e = modelled_sharded_latency(_fake_part(1, 2048), in_feats=16,
                                     hidden=256, classes=5,
                                     exchange_widths=widths, compress=False)
    assert one_c == one_e


def test_modelled_latency_compression_wins_on_wire():
    p = _fake_part(4, 512)
    kw = dict(in_feats=16, hidden=256, classes=5,
              exchange_widths=(256, 5))
    assert modelled_sharded_latency(p, compress=True, **kw) < \
        modelled_sharded_latency(p, compress=False, **kw)


# ------------------------------------------------------------ serving engine


BUCKET = 128


@pytest.fixture(scope="module")
def engine():
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(BUCKET,)),
                          batch_slots=2, shard_counts=(2, 4),
                          return_logits=True)
    eng = GraphServe(sc, seed=0)
    eng.register_model("gcn", _cfg("gcn"), tiers=("fp32", "int8"))
    eng.warmup()
    return eng


def test_auto_shard_attach_and_query(engine):
    """A graph over the top bucket auto-shards on attach; its logits match
    a jitted single-device forward at the partition's full capacity within
    the compressed-halo tolerance; serving it recompiles nothing."""
    g = _graph(200, 10)
    gid = engine.attach(g, model="gcn")
    part = engine._sharded[gid][0]
    assert (part.shards, part.shard_cap) == (2, BUCKET)
    assert engine.summary()["shard_counts"] == {gid: 2}
    uid = engine.query(gid)
    engine.run()
    engine.assert_warm()
    r = [f for f in engine.finished if f.uid == uid][0]
    assert r.shards == 2 and r.bucket == BUCKET
    e = engine.models["gcn"]
    ref = _reference_logits(e.cfg, e.tiers["fp32"], e.params, g,
                            part.full_rows)[:200]
    np.testing.assert_allclose(r.logits, ref, atol=0.05)
    np.testing.assert_array_equal(r.preds, r.logits.argmax(-1))
    engine.detach(gid)


def test_mixed_traffic_soak_zero_recompile(engine):
    """Sharded + unsharded + both tiers interleaved: every dispatch replays
    a warm blob (the §12 acceptance soak), shard slices serve from cache
    after the first query, and halo byte accounting is exact."""
    big = engine.attach(_graph(260, 11), model="gcn")    # 4 x 128
    small = engine.attach(_graph(60, 12), model="gcn")   # unsharded
    part = engine._sharded[big][0]
    assert part.shards == 4
    before = {k: engine.metrics[k] for k in
              ("sharded_batches", "halo_bytes_exchanged",
               "collective_bytes_compressed", "collective_bytes_exact")}
    n_big = 0
    for i in range(8):
        tier = "int8" if i % 2 else "fp32"
        gid = big if i % 3 else small
        n_big += gid == big
        engine.query(gid, tier=tier)
    engine.run()
    engine.assert_warm()
    s = engine.summary()
    assert s["sharded_batches"] == before["sharded_batches"] + n_big
    # every sharded dispatch moves the same compressed halo volume:
    # 2(S-1)/S of (full_rows x width) int8 elements per exchanged layer
    e = engine.models["gcn"]
    elems = sum(part.full_rows * w for w in sharded_exchange_widths(e.cfg))
    comp = int(2 * (part.shards - 1) / part.shards * elems)
    assert s["halo_bytes_exchanged"] == \
        before["halo_bytes_exchanged"] + n_big * comp
    assert s["collective_bytes_compressed"] == \
        before["collective_bytes_compressed"] + n_big * comp
    assert s["collective_bytes_exact"] == \
        before["collective_bytes_exact"] + n_big * 4 * comp
    # shard slices were cut once and replayed from the CacheG shard cache
    assert engine.metrics["operand_cache_hits"] > 0
    engine.detach(big)
    engine.detach(small)


def test_sharded_rejects_fused_dispatch(engine):
    gid = engine.attach(_graph(200, 13), model="gcn")
    with pytest.raises(ValueError, match="fus"):
        engine.query(gid, fusion="layer")
    engine.detach(gid)


def test_update_crosses_the_sharding_boundary_both_ways():
    """GrAd on a sharded graph: shrink back into the ladder (leaves the
    sharded path), grow past it again (re-enters at a new shard count) —
    each crossing is one rebucket event and queries stay correct."""
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(BUCKET,)),
                          batch_slots=1, shard_counts=(2, 4),
                          return_logits=True)
    eng = GraphServe(sc, seed=0)
    eng.register_model("gcn", _cfg("gcn"))
    eng.warmup()
    g0 = _graph(200, 14)
    gid = eng.attach(g0, model="gcn")
    assert eng._sharded[gid][0].shards == 2
    blobs = eng.compiled_blobs

    g1 = _graph(90, 15)          # shrink: back into the 128 ladder
    assert eng.update(gid, g1.edge_index, 90, g1.features) is True
    assert eng.summary()["shard_counts"] == {}
    eng.query(gid)

    g2 = _graph(300, 16)         # grow: off the top again, now 4 shards
    assert eng.update(gid, g2.edge_index, 300, g2.features) is True
    part = eng._sharded[gid][0]
    assert (part.shards, part.shard_cap) == (4, BUCKET)
    eng.query(gid)
    eng.run()
    eng.assert_warm()
    assert eng.compiled_blobs == blobs       # every bucket/shard pre-traced
    assert eng.summary()["rebucket_events"] == 2

    e = eng.models["gcn"]
    final = eng.finished[-1]
    ref = _reference_logits(e.cfg, e.tiers["fp32"], e.params, g2,
                            part.full_rows)[:300]
    np.testing.assert_allclose(final.logits, ref, atol=0.05)

    # same (shards, shard_cap) after a pure value update: no rebucket
    g3 = _graph(290, 17)
    assert eng.update(gid, g3.edge_index, 290, g3.features) is False
    eng.detach(gid)
    assert eng.summary()["shard_counts"] == {}


def test_oversized_graph_without_shard_counts_still_raises():
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(BUCKET,)))
    eng = GraphServe(sc, seed=0)
    eng.register_model("gcn", _cfg("gcn"))
    with pytest.raises(ValueError):
        eng.attach(_graph(200, 18), model="gcn")


def test_summary_exposes_shard_observability(engine):
    s = engine.summary()
    for k in ("shard_counts", "sharded_batches", "halo_bytes_exchanged",
              "collective_bytes_compressed", "collective_bytes_exact",
              "delta_halo_bytes_exchanged", "delta_halo_bytes_full",
              "delta_dirty_rows"):
        assert k in s, k


# ---------------------------------------------------------- replica groups


def _replica_engine(replicas):
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(BUCKET,)),
                          batch_slots=2, shard_counts=(2, 4),
                          return_logits=True, replica_groups=replicas)
    eng = GraphServe(sc, seed=0)
    eng.register_model("gcn", _cfg("gcn"), tiers=("fp32",))
    eng.warmup()
    return eng

def test_replica_groups_widen_sharded_dispatch():
    """§15 replica groups: with replica_groups=R the engine packs up to R
    same-key sharded requests into ONE plan call — N queries run in
    ceil(N/R) sharded batches instead of N — and every request's logits
    are BIT-identical to the width-1 engine's (the replica axis carries no
    collectives, so width is a throughput knob, never a numerics knob)."""
    e1, e2 = _replica_engine(1), _replica_engine(2)
    g = _graph(200, 20)
    want = {}
    for eng in (e1, e2):
        gid = eng.attach(g, model="gcn")
        before = eng.metrics["sharded_batches"]
        uids = [eng.query(gid) for _ in range(5)]
        eng.run()
        eng.assert_warm()
        done = {r.uid: r.logits for r in eng.finished}
        got = [done[u] for u in uids]
        if eng is e1:
            assert eng.metrics["sharded_batches"] - before == 5
            want = got
        else:
            # ceil(5 / 2) = 3 dispatches; the odd batch pads its replica
            # slot (2/6 + padded slot counted against occupancy)
            assert eng.metrics["sharded_batches"] - before == 3
            assert eng.metrics["slots_filled"] == 5
            assert eng.metrics["slots_total"] == 6
            for a, b in zip(want, got):
                np.testing.assert_array_equal(a, b)
        eng.detach(gid)


@pytest.mark.parametrize("replicas,n_nodes,shards", [(1, 260, 4),
                                                     (2, 200, 2)])
def test_summary_reports_sharded_placement(replicas, n_nodes, shards):
    """Where a sharded dispatch ran is observable, never a silent fallback:
    under shard_map on shards x replicas devices when the host has them
    (the multi-device leg), under the one-device vmap otherwise."""
    eng = _replica_engine(replicas)
    gid = eng.attach(_graph(n_nodes, 23), model="gcn")
    assert eng._sharded[gid][0].shards == shards
    eng.query(gid)
    eng.run()
    placed = eng.summary()["sharded_placement"][shards]
    need = shards * replicas
    if len(jax.devices()) >= need:
        assert placed == {"placement": "shard_map", "devices": need}
    else:
        assert placed == {"placement": "vmap", "devices": 1}
    eng.detach(gid)


def test_partition_method_config_reaches_attach():
    """`GraphServeConfig.partition_method` selects the attach()-time
    partitioner: "greedy" reproduces the §12 streaming cut verbatim,
    the default reproduces the §15 multilevel cut."""
    g = _graph(260, 21)
    lad = BucketLadder(buckets=(BUCKET,))
    for method in ("multilevel", "greedy"):
        sc = GraphServeConfig(ladder=lad, shard_counts=(2, 4),
                              partition_method=method)
        eng = GraphServe(sc, seed=0)
        eng.register_model("gcn", _cfg("gcn"))
        gid = eng.attach(g, model="gcn")
        direct = partition_for_ladder(g.edge_index, g.num_nodes, lad,
                                      (2, 4), method=method)
        np.testing.assert_array_equal(eng._sharded[gid][0].assignment,
                                      direct.assignment)
        assert eng._sharded[gid][0].cut_edges == direct.cut_edges


# ------------------------------------------------------- halo-delta bytes


def test_sharded_delta_halo_byte_accounting():
    """§15 halo-delta exchange accounting: a one-pair cross-shard GrAd
    delta dirties exactly its boundary rows, the summary prices the dirty
    exchange STRICTLY below a full halo re-exchange (both through
    `ring_psum_nbytes` at the exact-fp32 rate the rebuild-exact operand
    patch requires), and the patched graph still serves correct logits."""
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(BUCKET,)),
                          batch_slots=1, shard_counts=(2, 4),
                          return_logits=True)
    eng = GraphServe(sc, seed=0)
    eng.register_model("gcn", _cfg("gcn"))
    eng.warmup()
    g = _graph(200, 22)
    gid = eng.attach(g, model="gcn")
    eng.query(gid)
    eng.run()                     # cut + cache the shard slices
    part = eng._sharded[gid][0]
    s0 = np.flatnonzero(part.assignment == 0)
    s1 = np.flatnonzero(part.assignment == 1)
    adj = eng.graphs[gid][1].adj
    pair = next((int(u), int(v)) for u in s0[:20] for v in s1[:20]
                if adj[u, v] == 0)
    assert eng.update_delta(gid, add_edges=[pair]) is True
    s = eng.summary()
    assert s["delta_dirty_rows"] >= 2          # both endpoints now boundary
    assert 0 < s["delta_halo_bytes_exchanged"] < s["delta_halo_bytes_full"]
    # exact ratio: k dirty rows of the (full x full) operand matrices plus
    # k entries of D^-1/2, so delta/full == k/full_rows (int truncation)
    want = s["delta_halo_bytes_full"] * s["delta_dirty_rows"] / part.full_rows
    assert abs(s["delta_halo_bytes_exchanged"] - want) <= 2
    uid = eng.query(gid)
    eng.run()
    eng.assert_warm()
    r = [f for f in eng.finished if f.uid == uid][0]
    e = eng.models["gcn"]
    g2 = eng._sharded[gid][0], eng._sharded[gid][1]
    ref = _reference_logits(e.cfg, e.tiers["fp32"], e.params, g2[1],
                            part.full_rows)[:200]
    np.testing.assert_allclose(r.logits, ref, atol=0.05)
    # an interior flip (both endpoints shard 0, no cross-shard neighbors
    # gained) moves NO delta bytes
    inter = [u for u in s0
             if not (adj[u, :200] != 0)[part.assignment != 0].any()]
    if len(inter) >= 2:
        u, v = int(inter[0]), int(inter[1])
        before = eng.summary()["delta_halo_bytes_exchanged"]
        assert eng.update_delta(
            gid, add_edges=[(u, v)] if adj[u, v] == 0 else None,
            remove_edges=[(u, v)] if adj[u, v] != 0 else None) is True
        after = eng.summary()
        assert after["delta_halo_bytes_exchanged"] == before
    eng.detach(gid)
