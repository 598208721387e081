"""CacheG operand pipeline (DESIGN.md §7): SymG bit-packed transfer, device
materialization, the device-resident operand and feature caches, byte
accounting, and the satellite fixes that ride along (grow() supervision
carry, vectorized SAGE sampling, bucket-rule dedup)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.models as models_mod
import repro.runtime.gnn_server as server_mod
from repro.core.graph import (BucketLadder, Graph, is_symmetric_adjacency,
                              node_bucket, pack_adjacency_bits, pad_graph,
                              required_capacity, symg_pack_adjacency_bits,
                              triangular_nbits)
from repro.core.masks import sage_sample_adjacency
from repro.core.models import (GNNConfig, build_operands, build_plan,
                               compact_operands, forward_grannite,
                               materialize_operands, operand_nbytes,
                               _unpack_adjacency)
from repro.core.partition import transfer_cost
from repro.data.graphs import clustered_like, planetoid_like
from repro.runtime.cache import estimate_dense_entry_bytes
from repro.runtime.gnn_server import GraphServe, GraphServeConfig

IN_FEATS, CLASSES = 16, 4


def _graph(n, seed=0):
    return planetoid_like(num_nodes=n, num_edges=3 * n, num_feats=IN_FEATS,
                          num_classes=CLASSES, seed=seed, train_per_class=2)


def _cfg(kind):
    return GNNConfig(kind=kind, in_feats=IN_FEATS, hidden=16,
                     num_classes=CLASSES, heads=4)


def _engine(*kinds, buckets=(128,), batch_slots=2):
    sc = GraphServeConfig(ladder=BucketLadder(buckets=buckets),
                          batch_slots=batch_slots, return_logits=True)
    eng = GraphServe(sc, seed=0)
    for kind in kinds:
        eng.register_model(kind, _cfg(kind))
    eng.warmup()
    return eng


# ------------------------------------------------- SymG bit-pack round trip


@pytest.mark.parametrize("cap", [128, 256])
def test_symg_bits_device_roundtrip(cap):
    """pack (host, triangular bits) -> upload -> unpack (device) is lossless
    for an undirected 0/1 adjacency, at exactly cap(cap+1)/2 bits."""
    pg = pad_graph(_graph(cap - 30, seed=1), capacity=cap)
    assert is_symmetric_adjacency(pg.adj)
    packed = symg_pack_adjacency_bits(pg.adj)
    assert packed.nbytes == triangular_nbits(cap) // 8
    co = compact_operands(pg, _cfg("gcn"))
    np.testing.assert_array_equal(np.asarray(co.packed), packed)
    np.testing.assert_array_equal(np.asarray(_unpack_adjacency(co)), pg.adj)


def test_full_bitpack_device_roundtrip():
    """The non-SymG (directed / SAGE-sample) row-major packing round-trips."""
    rng = np.random.default_rng(3)
    adj = (rng.random((128, 128)) < 0.05).astype(np.float32)
    co = models_mod.CompactOperands(
        packed=jnp.asarray(pack_adjacency_bits(adj)),
        degree=jnp.zeros((128,), jnp.float32),
        num_nodes=jnp.asarray(128, jnp.int32),
        capacity=128, fields=("sample_mask",), triangular=False)
    np.testing.assert_array_equal(np.asarray(_unpack_adjacency(co)), adj)


def test_symg_pack_rejects_directed():
    adj = np.zeros((128, 128), np.float32)
    adj[3, 7] = 1.0                         # no reverse edge
    with pytest.raises(ValueError):
        symg_pack_adjacency_bits(adj)


# ------------------------------------- compact == eager operand equivalence


@pytest.mark.parametrize("kind", ["gcn", "gat", "sage"])
def test_materialized_operands_match_eager(kind):
    pg = pad_graph(_graph(100), capacity=128)
    cfg = _cfg(kind)
    eager = build_operands(pg, cfg, lean=True)
    mat = materialize_operands(compact_operands(pg, cfg))
    for f in ("norm_adj", "mask_mult", "bias_add", "sample_mask",
              "mean_mask"):
        a, b = np.asarray(getattr(eager, f)), np.asarray(getattr(mat, f))
        assert a.shape == b.shape, (kind, f)
        np.testing.assert_allclose(a, b, atol=1e-6, err_msg=f"{kind}/{f}")


def test_materialized_gcn_matches_eager_with_explicit_self_loop():
    """An explicit (i, i) edge in edge_index must not double-count in the
    CacheG degree: both paths add self loops idempotently."""
    g = _graph(100)
    loops = np.array([[0, 5], [0, 5]], np.int32)
    pg = pad_graph(Graph(edge_index=np.concatenate([g.edge_index, loops],
                                                   axis=1),
                         num_nodes=g.num_nodes, features=g.features),
                   capacity=128)
    cfg = _cfg("gcn")
    eager = build_operands(pg, cfg, lean=True)
    mat = materialize_operands(compact_operands(pg, cfg))
    np.testing.assert_allclose(np.asarray(eager.norm_adj),
                               np.asarray(mat.norm_adj), atol=1e-6)


@pytest.mark.parametrize("kind", ["gcn", "gat", "sage"])
def test_batched_cacheg_matches_eager_path(kind):
    """Same params, same graphs: the CacheG engine's batched logits equal
    the single-graph plan run on eagerly host-built operands within fp32
    tolerance."""
    graphs = [_graph(n, seed=i) for i, n in enumerate([60, 110, 90])]
    eng = _engine(kind)
    uids = [eng.submit(g, model=kind) for g in graphs]
    eng.run()
    eng.assert_warm()
    got = {r.uid: r.logits for r in eng.finished}
    e = eng.models[kind]
    plan = build_plan(e.cfg, 128, e.techniques)
    for uid, g in zip(uids, graphs):
        pg = pad_graph(g, capacity=128)
        ref = plan(e.params, jnp.asarray(pg.features),
                   build_operands(pg, e.cfg, lean=True))
        np.testing.assert_allclose(got[uid], np.asarray(ref)[:g.num_nodes],
                                   atol=1e-5)


# --------------------------------------------- device-resident operand cache


def test_repeated_query_skips_host_operand_build(monkeypatch):
    """After the first query of an attached graph, later queries perform ZERO
    host-side operand construction (neither eager nor compact) and move zero
    operand bytes."""
    eng = _engine("gat")
    gid = eng.attach(_graph(100), model="gat")

    calls = {"eager": 0, "compact": 0}
    real_build, real_compact = models_mod.build_operands, models_mod.compact_operands

    def count_build(*a, **k):
        calls["eager"] += 1
        return real_build(*a, **k)

    def count_compact(*a, **k):
        calls["compact"] += 1
        return real_compact(*a, **k)

    # the host stage lives in core.models.prepare_host_operands (the
    # pipeline split, DESIGN.md §9), so the build fns are intercepted there
    monkeypatch.setattr(models_mod, "build_operands", count_build)
    monkeypatch.setattr(models_mod, "compact_operands", count_compact)

    eng.query(gid)                          # structure miss: one compact build
    eng.run()
    assert calls == {"eager": 0, "compact": 1}
    bytes_after_miss = eng.metrics["operand_bytes_h2d"]

    for _ in range(4):                      # warm hits: no host work at all
        eng.query(gid)
    eng.run()
    eng.assert_warm()
    assert calls == {"eager": 0, "compact": 1}
    assert eng.metrics["operand_bytes_h2d"] == bytes_after_miss
    s = eng.summary()
    assert s["operand_cache_misses"] == 1
    assert s["operand_cache_hits"] == 4


def test_update_invalidates_operand_cache():
    """update() bumps the structure version: the next query re-materializes
    exactly once and serves the NEW structure, never the stale cache."""
    eng = _engine("gcn")
    g = _graph(100)
    gid = eng.attach(g, model="gcn")
    eng.query(gid)
    eng.run()
    assert eng.summary()["operand_cache_misses"] == 1

    # add undirected edges (keeps the SymG path live) between real nodes
    extra = np.array([[0, 1, 2, 3], [5, 6, 7, 8]], np.int32)
    ei = np.concatenate([g.edge_index, extra, extra[::-1]], axis=1)
    eng.update(gid, ei, g.num_nodes, g.features)
    eng.query(gid)
    eng.query(gid)                          # second query hits the new entry
    eng.run()
    eng.assert_warm()
    s = eng.summary()
    assert s["operand_cache_misses"] == 2
    assert s["operand_cache_hits"] == 1
    assert s["cacheg_fallbacks"] == 0

    # the served logits reflect the updated structure
    e = eng.models["gcn"]
    fresh = pad_graph(Graph(edge_index=ei, num_nodes=g.num_nodes,
                            features=g.features), capacity=128)
    ref = forward_grannite(e.params, e.cfg, jnp.asarray(fresh.features),
                           build_operands(fresh, e.cfg, lean=True),
                           e.techniques)
    np.testing.assert_allclose(eng.finished[-1].logits,
                               np.asarray(ref)[: g.num_nodes], atol=1e-5)


def _directed(g):
    """`g` plus one edge whose reverse is absent."""
    adj = np.zeros((g.num_nodes, g.num_nodes), bool)
    adj[g.edge_index[1], g.edge_index[0]] = True
    pairs = np.argwhere(~adj & ~adj.T & ~np.eye(g.num_nodes, dtype=bool))
    i, j = pairs[0]                          # guaranteed-absent pair
    ei = np.concatenate([g.edge_index,
                         np.array([[i], [j]], np.int32)], axis=1)
    return Graph(edge_index=ei, num_nodes=g.num_nodes, features=g.features)


def test_directed_graph_falls_back_to_eager_upload():
    """A directed GCN graph cannot take the SymG transfer; the engine serves
    it through the eager dense upload (counted) without breaking warmth."""
    eng = _engine("gcn")
    gid = eng.attach(_directed(_graph(100)), model="gcn")
    eng.query(gid)
    eng.query(gid)                          # fallback ops still cache-hit
    eng.run()
    eng.assert_warm()
    s = eng.summary()
    assert s["cacheg_fallbacks"] == 1
    assert s["operand_cache_hits"] == 1


# ------------------------------------- one operand routine, two intakes


def _gcn_auto_engine():
    """GCN under the density/cost rule at a bucket where a clustered graph
    routes grasp and a scattered one dense."""
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(512,)),
                          batch_slots=2, return_logits=True)
    eng = GraphServe(sc, seed=0)
    eng.register_model("gcn", GNNConfig(kind="gcn", in_feats=IN_FEATS,
                                        hidden=8, num_classes=CLASSES),
                       agg_backend="auto")
    return eng


@pytest.mark.parametrize("case", ["gcn-dense", "gcn-grasp", "gat", "sage"])
def test_one_shot_and_attached_requests_get_one_operand_set(case):
    """For the same graph, a one-shot submit and a query of the attached
    graph resolve their operands through one routine: the same backend,
    equal operand pytrees (block structure and tier operands included),
    and equal logits."""
    kind = case.split("-")[0]
    if kind == "gcn":
        eng = _gcn_auto_engine()
        g = (clustered_like(num_nodes=450, num_feats=IN_FEATS,
                            num_classes=CLASSES, within_density=0.03, seed=1)
             if case == "gcn-grasp" else
             planetoid_like(num_nodes=450, num_edges=40 * 450,
                            num_feats=IN_FEATS, num_classes=CLASSES, seed=2,
                            train_per_class=2))
    else:
        eng, g = _engine(kind), _graph(100)
    one = eng.prepare_submit(g, model=kind)
    attached = eng.prepare_query(eng.attach(g, model=kind))
    assert one.backend == attached.backend == (
        "grasp" if case == "gcn-grasp" else "dense")
    leaves, tree = jax.tree_util.tree_flatten((one.ops, one.tier_ops))
    leaves2, tree2 = jax.tree_util.tree_flatten((attached.ops,
                                                 attached.tier_ops))
    assert tree == tree2
    for a, b in zip(leaves, leaves2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    eng._push(one)
    eng._push(attached)
    eng.run()
    np.testing.assert_array_equal(one.logits, attached.logits)


def test_a_build_that_outlives_its_version_inserts_nothing(monkeypatch):
    """A host stage whose build finishes after `update()` has bumped the
    version serves the snapshot it read and caches nothing, at the dead
    key or the new one; the next query builds under the new version."""
    eng = _engine("gcn")
    g = _graph(100)
    gid = eng.attach(g, model="gcn")
    build = eng._device_operands

    def racing(cfg, pg):
        ops = build(cfg, pg)
        eng.update(gid, g.edge_index, g.num_nodes, g.features)
        return ops

    monkeypatch.setattr(eng, "_device_operands", racing)
    req = eng.prepare_query(gid)
    assert eng._graph_version[gid] == 1
    assert eng._cache.entry_sizes() == {}
    monkeypatch.undo()
    eng._push(req)
    eng.query(gid)
    eng.run()
    assert set(eng._cache.view("operand")) == {(gid, 1)}
    assert eng.summary()["operand_cache_misses"] == 2
    np.testing.assert_array_equal(eng.finished[0].logits,
                                  eng.finished[1].logits)
    eng.assert_warm()


def test_detach_releases_cache_and_graph():
    eng = _engine("gcn")
    gid = eng.attach(_graph(100), model="gcn")
    eng.query(gid)
    eng.run()
    assert len(eng._cache.view("operand")) == 1
    eng.detach(gid)
    assert eng._cache.view("operand") == {} and gid not in eng.graphs
    eng.detach(gid)                         # idempotent


# --------------------------------------------- device-resident features

FEATURE_BYTES = 128 * IN_FEATS * 4          # one padded (cap, F) fp32 buffer


def _sent(eng):
    return eng.summary()["feature_bytes_h2d"]


def test_attached_features_cross_the_link_once_per_version():
    """The features of an attached graph go to the device on the first
    query of each structure version and never again: later queries, and
    the dispatches that serve them, send none."""
    eng = _engine("gcn")
    g = _graph(100)
    gid = eng.attach(g, model="gcn")
    assert _sent(eng) == 0
    eng.query(gid)
    eng.run()
    assert _sent(eng) == FEATURE_BYTES
    first = eng.finished[-1].logits
    for _ in range(4):
        eng.query(gid)
    eng.run()
    assert _sent(eng) == FEATURE_BYTES
    assert eng.summary()["operand_cache_hits"] == 4
    np.testing.assert_array_equal(eng.finished[-1].logits, first)
    eng.update(gid, g.edge_index, g.num_nodes, g.features)
    eng.query(gid)
    eng.query(gid)
    eng.run()
    assert _sent(eng) == 2 * FEATURE_BYTES
    eng.assert_warm()


def test_one_shot_requests_send_their_features_in_the_host_stage():
    """Each one-shot submit puts its features on the device once, before
    any dispatch; a finished request holds no device features."""
    eng = _engine("gcn")
    for i in range(3):
        req = eng.prepare_submit(_graph(60 + i, seed=i), model="gcn")
        assert req.x is not None and req.x.shape == (128, IN_FEATS)
        assert _sent(eng) == (i + 1) * FEATURE_BYTES
        eng._push(req)
    sent = _sent(eng)
    eng.run()
    assert _sent(eng) == sent
    assert eng.finished and all(r.done and r.x is None
                                for r in eng.finished)
    eng.assert_warm()


@pytest.mark.parametrize("kind", ["gcn", "gat"])
def test_update_delta_carries_the_features(kind):
    """An edge delta moves the cached features to the new version: no new
    feature bytes, and the answers equal a full update() of the same
    edges."""
    g = _graph(100)
    patched, rebuilt = _engine(kind), _engine(kind)
    gids = [e.attach(g, model=kind) for e in (patched, rebuilt)]
    for e, gid in zip((patched, rebuilt), gids):
        e.query(gid)
        e.run()
    adj = patched.graphs[gids[0]][1].adj
    iu, ju = np.nonzero(np.triu(adj[:100, :100], 1))
    pair = (int(iu[0]), int(ju[0]))
    assert patched.update_delta(gids[0], remove_edges=[pair])
    assert patched.metrics["delta_updates"] == 1
    assert _sent(patched) == FEATURE_BYTES
    assert len(patched._cache.view("features")) == 1
    assert patched._cache._entries[("features", (gids[0], 1))].remat_s == \
        transfer_cost(FEATURE_BYTES)
    keep = ~(((g.edge_index[0] == pair[0]) & (g.edge_index[1] == pair[1]))
             | ((g.edge_index[0] == pair[1]) & (g.edge_index[1] == pair[0])))
    rebuilt.update(gids[1], g.edge_index[:, keep], g.num_nodes, g.features)
    for e, gid in zip((patched, rebuilt), gids):
        e.query(gid)
        e.run()
    assert _sent(patched) == FEATURE_BYTES
    assert _sent(rebuilt) == 2 * FEATURE_BYTES
    np.testing.assert_allclose(patched.finished[-1].logits,
                               rebuilt.finished[-1].logits, atol=1e-6)
    patched.assert_warm()


def test_detach_releases_the_features():
    eng = _engine("gcn")
    keep = eng.attach(_graph(90, seed=3), model="gcn")
    eng.query(keep)
    eng.run()
    before = eng.summary()["cache_resident_bytes"]
    gid = eng.attach(_graph(100), model="gcn")
    eng.query(gid)
    eng.run()
    feats = eng._cache.view("features")
    assert set(feats) == {(keep, 0), (gid, 0)}
    assert eng._cache.entry_sizes()[("features", (gid, 0))] == FEATURE_BYTES
    # rebuilding the features is a transfer, not a device re-derivation:
    # ties inside a key group evict the cheaper derived forms first
    assert eng._cache._entries[("features", (gid, 0))].remat_s == \
        transfer_cost(FEATURE_BYTES) > 0
    eng.detach(gid)
    assert set(eng._cache.view("features")) == {(keep, 0)}
    assert eng.summary()["cache_resident_bytes"] == before


def test_evicted_features_come_back_and_answer_the_same():
    """Under a budget that holds one tenant, querying the other evicts the
    first one's features (before its operands, never spilled); its next
    query puts them on the device again and answers bit for bit the
    same."""
    entry = estimate_dense_entry_bytes(1, 128)
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(128,)),
                          batch_slots=2, return_logits=True,
                          device_cache_budget_bytes=entry + entry // 2)
    eng = GraphServe(sc, seed=0)
    eng.register_model("gcn", _cfg("gcn"))
    eng.warmup()
    a = eng.attach(_graph(100, seed=1), model="gcn")
    b = eng.attach(_graph(90, seed=2), model="gcn")
    eng.query(a)
    first = np.asarray(eng.run()[-1].logits)
    eng.query(b)
    eng.run()
    assert set(eng._cache.view("features")) == {(b, 0)}
    assert eng._cache.spill_entries == 1     # a's operands, not features
    assert eng.summary()["cache_resident_bytes"] <= entry + entry // 2
    eng.query(a)
    np.testing.assert_array_equal(np.asarray(eng.run()[-1].logits), first)
    assert _sent(eng) == 3 * FEATURE_BYTES
    assert eng.metrics["cache_spill_hits"] == 1
    eng.assert_warm()


# ------------------------------------------------------ h2d byte accounting


def test_operand_bytes_accounting_matches_array_sizes():
    """operand_bytes_h2d is exactly the nbytes of what each path uploads,
    once per structure version: packed bits + degree + num_nodes for
    CacheG, the five dense fields for a directed graph's eager fallback."""
    cap, n_q = 128, 3
    g = _graph(100)

    eng = _engine("gat")
    gid = eng.attach(g, model="gat")
    for _ in range(n_q):
        eng.query(gid)
    eng.run()
    compact_expected = (triangular_nbits(cap) // 8    # SymG bit-packed adj
                        + cap * 4                     # degree float32
                        + 4)                          # num_nodes int32
    assert eng.summary()["operand_bytes_h2d"] == compact_expected

    directed = _directed(g)
    eng = _engine("gat")
    gid = eng.attach(directed, model="gat")
    for _ in range(n_q):
        eng.query(gid)
    eng.run()
    pg = pad_graph(directed, capacity=cap)
    eager = operand_nbytes(build_operands(pg, _cfg("gat"), lean=True))
    assert eager == 2 * 4 * cap * cap + 3 * 4         # 2 masks + 3 holes
    s = eng.summary()
    assert s["operand_bytes_h2d"] == eager
    assert s["cacheg_fallbacks"] == 1
    assert s["operand_cache_hits"] == n_q - 1
    # the compact transfer beats the eager upload by far more than the
    # acceptance floor even on a single cold miss
    assert eager / compact_expected > 10


# ------------------------------------------------------- satellite: grow()


def test_grow_preserves_supervision_arrays():
    """Re-bucketing an attached graph must carry labels/train/test masks;
    new nodes come up unlabeled (-1 / False)."""
    lad = BucketLadder(buckets=(128, 256))
    g = _graph(100)
    pg = lad.pad(g)
    assert pg.capacity == 128

    n_new = 150                             # outgrows 128 -> re-bucket to 256
    feats = np.zeros((n_new, IN_FEATS), np.float32)
    feats[: g.num_nodes] = g.features
    ei = g.edge_index
    grown, rebucketed = lad.grow(pg, ei, n_new, feats)
    assert rebucketed and grown.capacity == 256
    np.testing.assert_array_equal(grown.labels[: g.num_nodes],
                                  g.labels)
    assert (grown.labels[g.num_nodes:] == -1).all()
    np.testing.assert_array_equal(grown.train_mask[: g.num_nodes],
                                  g.train_mask)
    np.testing.assert_array_equal(grown.test_mask[: g.num_nodes],
                                  g.test_mask)
    assert not grown.train_mask[g.num_nodes:].any()
    assert not grown.test_mask[g.num_nodes:].any()


# ---------------------------------------------- satellite: SAGE vectorized


def test_sage_sampler_vectorized_semantics():
    rng_adj = np.random.default_rng(5)
    cap, n, k = 128, 100, 6
    adj = (rng_adj.random((cap, cap)) < 0.15).astype(np.float32)
    np.fill_diagonal(adj, 0.0)

    s1 = sage_sample_adjacency(adj, n, max_neighbors=k,
                               rng=np.random.default_rng(7))
    s2 = sage_sample_adjacency(adj, n, max_neighbors=k,
                               rng=np.random.default_rng(7))
    np.testing.assert_array_equal(s1, s2)   # seeded-rng determinism

    off_diag = s1 - np.diag(np.diag(s1))
    assert (off_diag.sum(axis=1) <= k).all()          # cap respected
    assert (off_diag <= adj).all()                    # sampled ⊆ neighbors
    assert (np.diag(s1)[:n] == 1.0).all()             # include_self
    assert (s1[n:] == 0).all()                        # padded rows inert
    # rows with <= k neighbors keep every neighbor
    few = adj[:n].sum(axis=1) <= k
    np.testing.assert_array_equal(off_diag[:n][few], adj[:n][few])


def test_sage_sampler_no_neighbors_and_zero_k():
    adj = np.zeros((128, 128), np.float32)
    out = sage_sample_adjacency(adj, 10, max_neighbors=4)
    assert (np.diag(out)[:10] == 1.0).all() and out.sum() == 10
    out = sage_sample_adjacency(adj, 10, max_neighbors=0, include_self=False)
    assert out.sum() == 0


# ------------------------------------------ satellite: bucket-rule dedup


def test_bucket_rules_share_required_capacity():
    """node_bucket and BucketLadder.bucket_for both round up the same
    admission target (the slack rule lives in ONE place)."""
    lad = BucketLadder(buckets=(128, 256, 512, 1024, 2048, 4096), slack=0.5)
    for n in (10, 100, 170, 300, 683, 1365):
        want = required_capacity(n, lad.slack)
        nb = node_bucket(n, slack=lad.slack)
        assert nb >= want and nb % 128 == 0
        assert lad.bucket_for(n) >= want
        # whenever the free-form tile multiple is itself a rung, the two
        # rules agree exactly — the admission target is computed once
        if nb in lad.buckets:
            assert lad.bucket_for(n) == nb
