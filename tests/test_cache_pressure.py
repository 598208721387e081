"""Cache-churn soak + §13 bounded-hierarchy invariants (tier-1, no deps).

The DESIGN.md §13 contract under sustained multi-tenant churn: with a
`device_cache_budget_bytes` sized for a handful of graphs, hundreds of
attach/query/detach cycles must (a) never push `cache_resident_bytes` past
the budget at ANY step, (b) keep the per-entry byte ledger consistent
(sum of entry costs == resident bytes), (c) conserve eviction outcomes
(evictions == spilled + dropped), (d) answer every evicted graph
BIT-IDENTICALLY after re-materialization from its host-RAM spill form, and
(e) trace nothing after warmup — eviction, spill, re-materialization, and
GrAd delta patching all replay warm blobs. Plus the update-before-first-
query counter regression and the sharded delta differential.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.graph import BucketLadder, Graph, edge_index_from_adjacency
from repro.core.models import (GNNConfig, OPERAND_FIELDS,
                               build_sharded_operands)
from repro.data.graphs import planetoid_like
from repro.runtime.cache import (CacheAdmissionError, DeviceCacheManager,
                                 estimate_dense_entry_bytes)
from repro.runtime.gnn_server import GraphServe, GraphServeConfig

IN_FEATS, CLASSES = 12, 4
BUCKET = 128
# one gcn fp32 operand entry at bucket 128 (1 field + 4 holes)
ENTRY = estimate_dense_entry_bytes(1, BUCKET)


def _graph(n, seed):
    return planetoid_like(num_nodes=n, num_edges=3 * n, num_feats=IN_FEATS,
                          num_classes=CLASSES, seed=seed, train_per_class=1)


def _engine(budget, *, spill=True, admission="evict", tiers=None,
            shard_counts=()):
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(BUCKET,)),
                          batch_slots=2, return_logits=True,
                          device_cache_budget_bytes=budget,
                          spill_to_host=spill, admission=admission,
                          shard_counts=shard_counts)
    eng = GraphServe(sc, seed=0)
    eng.register_model("gcn", GNNConfig(kind="gcn", in_feats=IN_FEATS,
                                        hidden=8, num_classes=CLASSES),
                       tiers=tiers)
    eng.warmup()
    return eng


def _assert_invariants(eng):
    cm = eng._cache
    with eng._lock:
        sizes = cm.entry_sizes()
        resident = cm.resident_bytes
        ev, sp, dr = cm.evictions, cm.spilled, cm.dropped
    assert sum(sizes.values()) == resident
    if eng.sc.device_cache_budget_bytes is not None:
        assert resident <= eng.sc.device_cache_budget_bytes
    assert ev == sp + dr


# ------------------------------------------------------------- churn soak


def test_churn_soak_respects_budget_at_every_step():
    """200 attach/query/detach cycles under a budget fitting ~8 graphs:
    the §13 invariants hold after EVERY step, and nothing traces."""
    budget = 8 * ENTRY + 8 * ENTRY // 4          # ~8 primaries + derived room
    eng = _engine(budget, tiers=("fp32", "int8"))
    eng.calibrate("gcn", _graph(64, seed=999))
    blobs = eng.compiled_blobs
    live = []
    for i in range(200):
        gid = eng.attach(_graph(20 + (i % 40), seed=i), model="gcn")
        live.append(gid)
        _assert_invariants(eng)
        eng.query(gid, tier="int8" if i % 3 else "fp32")
        eng.run()
        _assert_invariants(eng)
        if i % 5 == 4:                           # churn: detach the oldest
            eng.detach(live.pop(0))
            _assert_invariants(eng)
    cm = eng._cache
    assert cm.evictions > 0                      # the soak exercised pressure
    assert eng.compiled_blobs == blobs
    eng.assert_warm()
    for gid in live:
        eng.detach(gid)
    _assert_invariants(eng)


def test_evicted_graph_answers_bit_identically_via_spill():
    """Budget fits 2 graphs; 5 attach+query. Re-querying the evicted ones
    must fault into the host-RAM spill store, re-materialize, and return
    logits BIT-identical to the first (pre-eviction) answer — warm."""
    eng = _engine(2 * ENTRY + ENTRY // 2)
    gids, first = [], {}
    for i in range(5):
        gid = eng.attach(_graph(30 + i, seed=100 + i), model="gcn")
        gids.append(gid)
        eng.query(gid)
        first[gid] = np.asarray(eng.run()[-1].logits)
    cm = eng._cache
    assert cm.evictions >= 3 and cm.spilled >= 3
    for gid in gids:
        eng.query(gid)
        np.testing.assert_array_equal(np.asarray(eng.run()[-1].logits),
                                      first[gid])
    assert eng.metrics["cache_spill_hits"] >= 3
    # a spill fault is NOT a structure miss: one miss per (graph, version)
    assert eng.metrics["operand_cache_misses"] == len(gids)
    eng.assert_warm()
    _assert_invariants(eng)


def test_spill_disabled_drops_and_rebuilds():
    """spill_to_host=False: every capacity eviction drops (conservation
    pins evictions == dropped), the spill store stays empty, and the next
    query is an honest full-rebuild miss."""
    eng = _engine(ENTRY + ENTRY // 2, spill=False)
    g1 = eng.attach(_graph(30, seed=1), model="gcn")
    g2 = eng.attach(_graph(31, seed=2), model="gcn")
    eng.query(g1)
    lg1 = np.asarray(eng.run()[-1].logits)
    eng.query(g2)
    eng.run()
    cm = eng._cache
    assert cm.evictions == cm.dropped >= 1 and cm.spilled == 0
    assert cm.spill_entries == 0
    misses = eng.metrics["operand_cache_misses"]
    eng.query(g1)
    np.testing.assert_array_equal(np.asarray(eng.run()[-1].logits), lg1)
    assert eng.metrics["operand_cache_misses"] == misses + 1
    assert eng.metrics["cache_spill_hits"] == 0
    eng.assert_warm()


# -------------------------------------------------------------- admission


def test_admission_rejects_entry_that_can_never_fit():
    """A graph whose projected primary entry exceeds the WHOLE budget is
    rejected at attach() under either policy — caching it is impossible."""
    for policy in ("evict", "reject"):
        eng = _engine(ENTRY // 2, admission=policy)
        with pytest.raises(CacheAdmissionError):
            eng.attach(_graph(30, seed=1), model="gcn")
        assert eng.metrics["cache_admission_rejects"] == 1
        assert eng.graphs == {}


def test_admission_reject_policy_refuses_overflow_evict_admits():
    """Same pressure, two policies: "evict" admits and lets insert-time
    eviction make room; "reject" refuses an attach that would overflow the
    CURRENT residency."""
    evict = _engine(ENTRY + ENTRY // 2, admission="evict")
    a = evict.attach(_graph(30, seed=1), model="gcn")
    evict.query(a)
    evict.run()
    b = evict.attach(_graph(31, seed=2), model="gcn")   # admitted
    evict.query(b)
    evict.run()
    assert evict._cache.evictions >= 1

    reject = _engine(ENTRY + ENTRY // 2, admission="reject")
    a = reject.attach(_graph(30, seed=1), model="gcn")
    reject.query(a)
    reject.run()
    with pytest.raises(CacheAdmissionError):
        reject.attach(_graph(31, seed=2), model="gcn")
    assert reject.metrics["cache_admission_rejects"] == 1
    reject.detach(a)                                    # frees residency
    c = reject.attach(_graph(32, seed=3), model="gcn")
    reject.query(c)
    reject.run()
    assert reject._cache.evictions == 0


def test_unbudgeted_engine_never_evicts():
    """No budget configured: the manager is pure bookkeeping — residency
    grows, nothing evicts, attach never rejects (the pre-§13 behavior)."""
    eng = _engine(None)
    for i in range(6):
        gid = eng.attach(_graph(25 + i, seed=i), model="gcn")
        eng.query(gid)
        eng.run()
    cm = eng._cache
    assert cm.evictions == 0 and cm.resident_bytes >= 6 * ENTRY
    _assert_invariants(eng)


# ----------------------------------------- update-before-first-query (fix)


def test_update_before_first_query_pins_counters():
    """Regression: update() on an attached-but-never-queried graph retires
    cache keys that were never populated. That must be a counter no-op —
    no eviction/spill/drop movement, no phantom hit — and the first query
    after the update is exactly ONE miss, the second exactly one hit."""
    eng = _engine(8 * ENTRY)
    gid = eng.attach(_graph(30, seed=7), model="gcn")
    g2 = _graph(34, seed=8)
    eng.update(gid, g2.edge_index, g2.num_nodes, g2.features)
    cm = eng._cache
    assert (cm.evictions, cm.spilled, cm.dropped) == (0, 0, 0)
    assert eng.metrics["operand_cache_misses"] == 0
    assert eng.metrics["operand_cache_hits"] == 0
    eng.query(gid)
    eng.run()
    assert eng.metrics["operand_cache_misses"] == 1
    assert eng.metrics["operand_cache_hits"] == 0
    eng.query(gid)
    eng.run()
    assert eng.metrics["operand_cache_misses"] == 1
    assert eng.metrics["operand_cache_hits"] == 1
    assert (cm.evictions, cm.spilled, cm.dropped) == (0, 0, 0)
    # update_delta with a RESIDENT entry patches it under the new key: the
    # next query is a HIT — no rebuild, no phantom miss, no counter drift
    adj = eng.graphs[gid][1].adj
    iu, ju = np.nonzero(np.triu(adj[:34, :34], 1))
    assert eng.update_delta(gid, remove_edges=[(int(iu[0]), int(ju[0]))])
    assert (cm.evictions, cm.spilled, cm.dropped) == (0, 0, 0)
    eng.query(gid)
    eng.run()
    assert eng.metrics["operand_cache_misses"] == 1
    assert eng.metrics["operand_cache_hits"] == 2
    assert eng.metrics["delta_updates"] == 1
    # and update_delta BEFORE any query of the new version is the no-op
    # counter case: nothing resident to patch, nothing counted as evicted
    g3 = _graph(36, seed=9)
    eng.update(gid, g3.edge_index, g3.num_nodes, g3.features)
    iu, ju = np.nonzero(np.triu(eng.graphs[gid][1].adj[:36, :36], 1))
    assert eng.update_delta(gid, remove_edges=[(int(iu[0]), int(ju[0]))])
    assert (cm.evictions, cm.spilled, cm.dropped) == (0, 0, 0)
    eng.query(gid)
    eng.run()
    assert eng.metrics["operand_cache_misses"] == 2
    eng.assert_warm()


# --------------------------------------------------------- sharded deltas


def test_sharded_delta_patches_slices_and_halo():
    """§13 on the §12 path: update_delta over an auto-sharded graph keeps
    the partition, patches the cached slice tuple device-side, and the
    patched blocks are BIT-identical to a fresh `build_sharded_operands`
    over the same partition — plus the halo sets track the new edges."""
    eng = _engine(None, shard_counts=(2,))
    g = _graph(200, seed=3)                      # > bucket 128: auto-shards
    gid = eng.attach(g, model="gcn")
    eng.query(gid)
    eng.run()
    part0 = eng._sharded[gid][0]
    pg = eng.graphs[gid][1]
    adj = pg.adj
    iu, ju = np.nonzero(np.triu(adj[:200, :200], 1))
    add = [(0, 150)] if adj[0, 150] == 0 else [(1, 151)]
    rem = [(int(iu[0]), int(ju[0]))]
    assert eng.update_delta(gid, add_edges=add, remove_edges=rem)
    assert eng.metrics["delta_updates"] == 1
    part1, g1 = eng._sharded[gid]
    assert np.array_equal(part1.perm, part0.perm)     # partition KEPT
    ver = eng._graph_version[gid]
    patched = eng._cache.view("shard")[(gid, ver)]
    cfg = eng.models["gcn"].cfg
    ref = build_sharded_operands(g1, part1, cfg)
    for s, r in zip(patched, ref):
        for f in OPERAND_FIELDS["gcn"]:
            np.testing.assert_array_equal(np.asarray(getattr(s.ops, f)),
                                          np.asarray(getattr(r.ops, f)))
    # halo observability patched host-side: matches a from-scratch halo
    # computation over the SAME assignment and the NEW edges
    new_ei = edge_index_from_adjacency(eng.graphs[gid][1].adj, 200)
    assert sorted(map(tuple, new_ei.T.tolist())) == sorted(
        map(tuple, g1.edge_index.T.tolist()))
    eng.query(gid)
    eng.run()
    eng.assert_warm()


# -------------------------------------------------- manager unit behavior


def test_manager_rejects_oversized_entry_without_breaking_budget():
    cm = DeviceCacheManager(budget_bytes=100)
    assert not cm.put("operand", (0, 0), "big", nbytes=101)
    assert cm.resident_bytes == 0
    assert cm.put("operand", (0, 0), "ok", nbytes=60)
    assert cm.put("operand", (1, 0), "ok2", nbytes=60)  # evicts (0, 0)
    assert cm.resident_bytes == 60
    assert cm.evictions == 1 and cm.dropped == 1        # no spill_fn
    assert cm.get("operand", (0, 0)) is None
    assert cm.get("operand", (1, 0)) == "ok2"


def test_manager_derived_evicts_before_primary_and_lru_groups():
    """Eviction order: least-recently-used GRAPH first; within the victim
    graph, derived forms before the primary they hang off."""
    cm = DeviceCacheManager(budget_bytes=100)
    cm.put("operand", (0, 0), "p0", nbytes=40)
    cm.put("tier", (0, 0), "d0", nbytes=10)
    cm.put("operand", (1, 0), "p1", nbytes=40)
    cm.put("operand", (2, 0), "p2", nbytes=15)   # needs 5 bytes freed
    # graph 0 is the coldest GROUP; its DERIVED form is the first victim —
    # the 10-byte tier entry covers the need, the primary stays resident
    assert cm.get("tier", (0, 0)) is None
    assert cm.get("operand", (0, 0)) == "p0"
    assert cm.get("operand", (1, 0)) == "p1"
    # group-LRU across graphs: graph 0 was just touched, so the next
    # squeeze takes graph 1's primary even though it was inserted later
    cm.get("operand", (0, 0))
    cm.put("operand", (3, 0), "p3", nbytes=40)
    assert cm.get("operand", (1, 0)) is None
    assert cm.get("operand", (0, 0)) == "p0"


def test_manager_invalidate_is_not_an_eviction():
    cm = DeviceCacheManager(budget_bytes=100)
    cm.put("operand", (0, 0), "p", nbytes=40,
           spill_fn=lambda: "packed")
    cm.put("tier", (0, 0), "d", nbytes=10)
    assert cm.invalidate((0, 0)) == 2
    assert cm.resident_bytes == 0
    assert (cm.evictions, cm.spilled, cm.dropped) == (0, 0, 0)
    assert cm.invalidate((0, 0)) == 0            # idempotent no-op


def test_manager_spill_roundtrip_and_conservation():
    cm = DeviceCacheManager(budget_bytes=50)
    cm.put("operand", (0, 0), "p0", nbytes=40, spill_fn=lambda: "packed0")
    cm.put("operand", (1, 0), "p1", nbytes=40)   # evicts+spills (0, 0)
    assert cm.spilled == 1 and cm.spill_entries == 1
    assert cm.spill_get("operand", (0, 0)) == "packed0"
    assert cm.spill_hits == 1
    # non-destructive: a re-insert + re-eviction reuses the stored form
    cm.put("operand", (0, 0), "p0", nbytes=40, spill_fn=lambda: "packed0")
    cm.put("operand", (1, 0), "p1", nbytes=40)
    assert cm.evictions == cm.spilled + cm.dropped
    assert cm.spill_get("operand", (0, 0)) == "packed0"


def test_manager_budget_validation():
    with pytest.raises(ValueError):
        DeviceCacheManager(budget_bytes=0)
    with pytest.raises(ValueError):
        DeviceCacheManager(budget_bytes=-5)
