"""Property-based differential serving suite (DESIGN.md §9 / §10).

Hypothesis-driven randomized properties over the whole serving stack:
random graphs × kinds (GCN/GAT/SAGE) × quality tiers served through the
deterministic pipeline scheduler must equal the sequential single-request
forward; the `grasp` aggregation backend must match the `dense` backend
across kinds × edge densities × tiers; fused per-layer serving
(`fusion="layer"`, DESIGN.md §11) must equal unfused serving over the same
traffic; the N-way sharded forward (DESIGN.md §12) must equal the
single-device forward across kinds × tiers × shard counts × halo wire
formats; the CacheG/SymG pack→unpack
transfer forms (including the budget-padded GraSp block form) must
round-trip losslessly; NodePad's admission rule and the per-bucket
`grasp_max_nnz` budget must be monotone. Skipped without hypothesis
(tier-1 stays dependency-light); CI installs requirements-dev so these
EXECUTE there, and the scheduled nightly job deepens `max_examples` via
the `nightly` profile registered in conftest.py. Tests here deliberately
carry no per-test `max_examples` so the active profile controls depth;
determinism comes from hypothesis' own seeding plus the engine's
deterministic scheduler mode.

The seeded SymG round-trip sweep formerly in test_gnn_serving.py was
promoted into `test_symg_roundtrip_lossless` here.
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, strategies as st  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.graph import (BucketLadder, Graph,  # noqa: E402
                              edge_index_from_adjacency, node_bucket,
                              pad_graph, required_capacity, symg_pack,
                              symg_unpack)
from repro.core.models import (OPERAND_FIELDS, GNNConfig,  # noqa: E402
                               _unpack_adjacency,
                               build_operands, build_sharded_operands,
                               build_sharded_plan, calibrate_tier,
                               compact_operands, forward_grannite,
                               init_params, stack_shard_slices,
                               unshard_logits)
from repro.core.partition import partition_graph  # noqa: E402
from repro.core.sparsity import (from_block_sparse, grasp_max_nnz,  # noqa: E402
                                 pad_block_sparse, stack_block_sparse,
                                 to_block_sparse)
from repro.data.graphs import planetoid_like  # noqa: E402
from repro.runtime.gnn_server import (STANDARD_TIERS, GraphServe,  # noqa: E402
                                      GraphServeConfig, tier_techniques)
from repro.runtime.scheduler import PipelineConfig  # noqa: E402

IN_FEATS, CLASSES = 12, 4
BUCKETS = (128, 256)
KINDS = ("gcn", "gat", "sage")


def _graph(n, seed):
    return planetoid_like(num_nodes=n, num_edges=3 * n, num_feats=IN_FEATS,
                          num_classes=CLASSES, seed=seed, train_per_class=1)


# Warm engines are expensive (one compile sweep per kind) and hypothesis
# runs many examples: build each kind's engine once at module scope and let
# every example serve on it — examples only ever REPLAY warm plans, which
# assert_warm re-checks at the end of each one.
_ENGINES = {}


def _engine(kind, agg_backend="dense"):
    key = (kind, agg_backend)
    if key not in _ENGINES:
        sc = GraphServeConfig(ladder=BucketLadder(buckets=BUCKETS),
                              batch_slots=3, return_logits=True)
        eng = GraphServe(sc, seed=0)
        eng.register_model(kind, GNNConfig(
            kind=kind, in_feats=IN_FEATS, hidden=8, num_classes=CLASSES,
            heads=2, aggregator="max" if kind == "sage" else "mean"),
            tiers=STANDARD_TIERS, agg_backend=agg_backend)
        eng.warmup()
        eng.calibrate(kind, _graph(64, seed=999))   # quant tiers live
        _ENGINES[key] = eng
    return _ENGINES[key]


# ------------------------------------------- differential: async == single


@st.composite
def traffic(draw):
    kind = draw(st.sampled_from(KINDS))
    k = draw(st.integers(1, 5))
    reqs = [(draw(st.integers(10, 200)),             # num_nodes
             draw(st.integers(0, 2 ** 16)),          # graph seed
             draw(st.sampled_from((None,) + STANDARD_TIERS)))
            for _ in range(k)]
    return kind, reqs


@given(traffic())
def test_async_batched_logits_equal_sequential(case):
    """The tentpole differential: ANY mix of graph sizes and tiers served
    batched through the deterministic pipeline scheduler equals the
    sequential single-request forward, and replays entirely warm."""
    kind, reqs = case
    eng = _engine(kind)
    with eng.scheduler(PipelineConfig(deterministic=True)) as sched:
        for n, seed, tier in reqs:
            sched.submit(_graph(n, seed), model=kind, tier=tier)
        out = sched.drain()
    assert len(out) == len(reqs) and all(r.done for r in out)
    e = eng.models[kind]
    for r in out:
        ref = forward_grannite(e.params, e.cfg, jnp.asarray(r.pg.features),
                               r.ops, e.tiers[r.tier],
                               quant=e.calibrations.get(r.tier),
                               tier_ops=r.tier_ops)
        np.testing.assert_allclose(r.logits,
                                   np.asarray(ref)[: r.pg.num_nodes],
                                   atol=2e-5)
        np.testing.assert_array_equal(
            r.preds, np.asarray(ref)[: r.pg.num_nodes].argmax(-1))
    eng.assert_warm()


# ------------------------------------------- differential: grasp == dense


@st.composite
def backend_traffic(draw):
    kind = draw(st.sampled_from(KINDS))
    k = draw(st.integers(1, 4))
    reqs = []
    for _ in range(k):
        n = draw(st.integers(10, 200))
        density = draw(st.floats(0.01, 0.5))
        edges = max(int(density * n * n), 1)
        reqs.append((n, edges, draw(st.integers(0, 2 ** 16)),
                     draw(st.sampled_from((None,) + STANDARD_TIERS))))
    return kind, reqs


@given(backend_traffic())
def test_grasp_backend_logits_equal_dense(case):
    """DESIGN.md §10 differential: ANY mix of graph sizes, edge densities
    (0.01–0.5) and tiers served through the forced-grasp engine's
    deterministic pipeline equals the dense engine's sequential forward
    within fp32 tolerance (block-sum accumulation order differs, so this
    is allclose, not bit-equality), and both engines replay entirely warm.
    Non-GCN kinds and QuantGr tiers resolve dense on the grasp engine too
    — the rule, not an error path."""
    kind, reqs = case
    eng_g = _engine(kind, "grasp")
    eng_d = _engine(kind, "dense")
    graphs = [planetoid_like(num_nodes=n, num_edges=e, num_feats=IN_FEATS,
                             num_classes=CLASSES, seed=seed,
                             train_per_class=1)
              for n, e, seed, _ in reqs]
    with eng_g.scheduler(PipelineConfig(deterministic=True)) as sched:
        for g, (_, _, _, tier) in zip(graphs, reqs):
            sched.submit(g, model=kind, tier=tier)
        out = sched.drain()
    eng_g.assert_warm()
    uids = [eng_d.submit(g, model=kind, tier=tier)
            for g, (_, _, _, tier) in zip(graphs, reqs)]
    eng_d.run()
    eng_d.assert_warm()
    ref = {r.uid: r for r in eng_d.finished}
    for r, uid in zip(out, uids):
        assert ref[uid].backend == "dense"
        if kind != "gcn" or eng_g.models[kind].tiers[r.tier].quantgr:
            assert r.backend == "dense"
        np.testing.assert_allclose(r.logits, ref[uid].logits,
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_array_equal(r.preds, ref[uid].preds)


# ------------------------------------------- differential: fused == unfused


@st.composite
def fusion_traffic(draw):
    kind = draw(st.sampled_from(KINDS))
    k = draw(st.integers(1, 4))
    reqs = [(draw(st.integers(10, 200)),             # num_nodes
             draw(st.integers(0, 2 ** 16)),          # graph seed
             draw(st.sampled_from((None,) + STANDARD_TIERS)),
             draw(st.sampled_from((None, "none", "layer"))))
            for _ in range(k)]
    return kind, reqs


@given(fusion_traffic())
def test_fused_serving_logits_equal_unfused(case):
    """DESIGN.md §11 differential: ANY mix of graph sizes, tiers and fusion
    modes served through the deterministic pipeline equals the UNFUSED
    sequential forward, and the engine replays entirely warm — fusion is a
    pre-traced plan dimension, never a recompile. Tolerance is looser than
    the unfused differential (2e-4 vs 2e-5) because the fused GAT kernel
    folds the attention mask additively before softmax instead of applying
    an exact where-mask after."""
    kind, reqs = case
    eng = _engine(kind)
    with eng.scheduler(PipelineConfig(deterministic=True)) as sched:
        for n, seed, tier, fusion in reqs:
            sched.submit(_graph(n, seed), model=kind, tier=tier,
                         fusion=fusion)
        out = sched.drain()
    assert len(out) == len(reqs) and all(r.done for r in out)
    e = eng.models[kind]
    for r, (_, _, _, fusion) in zip(out, reqs):
        assert r.fusion == (fusion or "none")
        ref = forward_grannite(e.params, e.cfg, jnp.asarray(r.pg.features),
                               r.ops, e.tiers[r.tier],
                               quant=e.calibrations.get(r.tier),
                               tier_ops=r.tier_ops, fusion="none")
        np.testing.assert_allclose(r.logits,
                                   np.asarray(ref)[: r.pg.num_nodes],
                                   rtol=2e-4, atol=2e-4)
    eng.assert_warm()


# ------------------------------------------ differential: sharded == single


SHARD_CAP = 128

# One compiled sharded plan + jitted reference per (kind, tier, shards,
# compress): shapes are fixed by the key, so every hypothesis example
# replays warm traces (same economics as the module-scope engines above).
_SHARDED = {}


def _sharded_setup(kind, tier, shards, compress):
    key = (kind, tier, shards, compress)
    if key not in _SHARDED:
        cfg = GNNConfig(kind=kind, in_feats=IN_FEATS, hidden=8,
                        num_classes=CLASSES, heads=2,
                        aggregator="max" if kind == "sage" else "mean")
        t = tier_techniques(kind)[tier]
        plan = build_sharded_plan(cfg, SHARD_CAP, shards, t,
                                  compress=compress)
        ref = jax.jit(lambda p, x, o, q: forward_grannite(p, cfg, x, o, t,
                                                          quant=q))
        params = init_params(jax.random.PRNGKey(0), cfg)
        _SHARDED[key] = (cfg, t, plan, ref, params)
    return _SHARDED[key]


@st.composite
def sharded_case(draw):
    kind = draw(st.sampled_from(KINDS))
    shards = draw(st.sampled_from((2, 4)))
    return (kind,
            draw(st.sampled_from(STANDARD_TIERS)),
            shards,
            draw(st.integers(20, SHARD_CAP * shards)),  # num_nodes
            draw(st.integers(0, 2 ** 16)),              # graph seed
            draw(st.booleans()))                        # compressed halos


@given(sharded_case())
def test_sharded_forward_equals_single_device(case):
    """DESIGN.md §12 differential: ANY (kind, tier, shard count, graph,
    halo wire format) partitioned through the greedy edge-cut and run under
    the sharded plan equals the jitted single-device forward at the
    partition's full capacity. Both sides jitted (the discipline from the
    QuantGr suites: XLA's reciprocal-multiply lowering shifts int8 round()
    boundaries between jitted and eager code). Uncompressed halos are
    numerically tight — the exchange is a psum of disjoint blocks;
    compressed halos admit the documented int8 wire error."""
    kind, tier, shards, n, seed, compress = case
    cfg, t, plan, ref, params = _sharded_setup(kind, tier, shards, compress)
    g = _graph(n, seed)
    part = partition_graph(g.edge_index, n, shards, shard_cap=SHARD_CAP)
    slices = build_sharded_operands(g, part, cfg,
                                    rng=np.random.default_rng(seed))
    x, ops, mask = stack_shard_slices(slices)
    pg = pad_graph(g, capacity=part.full_rows)
    rops = build_operands(pg, cfg, lean=True,
                          rng=np.random.default_rng(seed))
    quant = (calibrate_tier(params, cfg, jnp.asarray(pg.features), rops)
             if t.quantgr else None)
    got = unshard_logits(
        np.asarray(plan(params, x, ops, quant, node_mask=mask)), part)
    want = np.asarray(ref(params, jnp.asarray(pg.features), rops, quant))[:n]
    tol = 0.05 if compress else (2e-5 if tier == "fp32" else 2e-3)
    np.testing.assert_allclose(got, want, atol=tol)


# cache replica plans per (kind, tier, shards, compress, R) — same warm-
# trace economics as _SHARDED
_REPLICA = {}


def _replica_plan(kind, tier, shards, compress, replicas):
    key = (kind, tier, shards, compress, replicas)
    if key not in _REPLICA:
        cfg, t, _, _, params = _sharded_setup(kind, tier, shards, compress)
        _REPLICA[key] = (build_sharded_plan(cfg, SHARD_CAP, shards, t,
                                            compress=compress,
                                            replicas=replicas),
                         cfg, t, params)
    return _REPLICA[key]


@st.composite
def replica_case(draw):
    kind = draw(st.sampled_from(KINDS))
    return (kind,
            draw(st.sampled_from(STANDARD_TIERS)),
            draw(st.sampled_from((2, 3))),              # replicas
            draw(st.integers(20, SHARD_CAP * 2)),       # num_nodes
            draw(st.integers(0, 2 ** 16)),              # graph seed
            draw(st.booleans()))                        # compressed halos


@given(replica_case())
def test_replica_dispatch_bit_identical_to_single(case):
    """DESIGN.md §15: replica-group dispatch is a WIDTH concern, never a
    numerics concern — each replica row of an R-wide sharded plan returns
    the BIT-identical logits of the single-replica plan on the same
    operands (the replica axis carries no collectives; halo psums name
    only the shard axis). Holds for every kind, tier, and wire format, on
    DIFFERENT graphs per row."""
    kind, tier, replicas, n, seed, compress = case
    shards = 2
    cfg, t, plan1, _, params = _sharded_setup(kind, tier, shards, compress)
    planr, _, _, _ = _replica_plan(kind, tier, shards, compress, replicas)
    rows = []
    for r in range(replicas):
        nr = 20 + (n + 17 * r) % (SHARD_CAP * shards - 20)
        g = _graph(nr, seed + r)
        part = partition_graph(g.edge_index, nr, shards,
                               shard_cap=SHARD_CAP)
        slices = build_sharded_operands(g, part, cfg,
                                        rng=np.random.default_rng(seed + r))
        rows.append(stack_shard_slices(slices))
    quant = None
    if t.quantgr:
        g0 = _graph(n, seed)
        pg = pad_graph(g0, capacity=shards * SHARD_CAP)
        rops = build_operands(pg, cfg, lean=True,
                              rng=np.random.default_rng(seed))
        quant = calibrate_tier(params, cfg, jnp.asarray(pg.features), rops)
    xs = jnp.stack([r[0] for r in rows])
    ops = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls),
                                 *[r[1] for r in rows])
    masks = jnp.stack([r[2] for r in rows])
    wide = np.asarray(planr(params, xs, ops, quant, node_mask=masks))
    for r, (x1, o1, m1) in enumerate(rows):
        single = np.asarray(plan1(params, x1, o1, quant, node_mask=m1))
        np.testing.assert_array_equal(wide[r], single)


# --------------------------------------------------- pack/unpack round-trips


@given(st.integers(1, 3), st.floats(0.0, 0.3), st.integers(0, 2 ** 16))
def test_block_sparse_pad_stack_roundtrip(cb, density, seed):
    """Budget-padding and batch-stacking the GraSp block form is lossless:
    every padded structure densifies back to its source matrix, and the
    stacked form is the same pytree with a leading batch dim."""
    rng = np.random.default_rng(seed)
    n = cb * 128
    mats = [((rng.random((n, n)) < density) * rng.random((n, n))
             ).astype(np.float32) for _ in range(2)]
    budget = max(grasp_max_nnz(n),
                 *(to_block_sparse(a).max_nnz for a in mats))
    sps = [pad_block_sparse(to_block_sparse(a), budget) for a in mats]
    for a, sp in zip(mats, sps):
        assert sp.max_nnz == budget
        np.testing.assert_array_equal(from_block_sparse(sp), a)
    stacked = stack_block_sparse(sps)
    assert stacked.blocks.shape[0] == 2
    assert stacked.block_cols.shape == (2, cb, budget)


@given(st.integers(2, 60), st.integers(0, 2 ** 16))
def test_symg_roundtrip_lossless(n, seed):
    """SymG pack/unpack is lossless and stores exactly the n(n+1)/2 upper
    triangle (promoted from the seeded sweep in test_gnn_serving.py)."""
    rng = np.random.default_rng(seed)
    m = rng.random((n, n)).astype(np.float32)
    sym = (m + m.T) / 2
    packed, nn = symg_pack(sym)
    assert packed.size == n * (n + 1) // 2
    np.testing.assert_allclose(symg_unpack(packed, nn), sym, atol=1e-6)


@given(st.integers(10, 150), st.integers(0, 2 ** 16))
def test_compact_transfer_bits_roundtrip(n, seed):
    """CacheG's bit-packed transfer form reproduces the exact 0/1 adjacency
    through the device-side unpack, padding included."""
    g = _graph(n, seed)
    pg = pad_graph(g, capacity=node_bucket(n))
    co = compact_operands(pg, GNNConfig(kind="gcn", in_feats=IN_FEATS,
                                        num_classes=CLASSES))
    np.testing.assert_array_equal(np.asarray(_unpack_adjacency(co)), pg.adj)


# -------------------------------------------------- NodePad admission rule


@given(st.integers(1, 4000), st.integers(0, 4000),
       st.floats(0.0, 0.5, allow_nan=False), st.floats(0.0, 0.5,
                                                       allow_nan=False))
def test_required_capacity_monotone(n, dn, s1, s2):
    """`required_capacity` is monotone in BOTH arguments (more nodes or more
    slack can never need less room), always admits the graph itself, and
    `node_bucket` rounds it to a tile multiple without undershooting."""
    lo_s, hi_s = sorted((s1, s2))
    assert required_capacity(n, lo_s) >= n
    assert required_capacity(n + dn, lo_s) >= required_capacity(n, lo_s)
    assert required_capacity(n, hi_s) >= required_capacity(n, lo_s)
    b = node_bucket(n, slack=lo_s)
    assert b % 128 == 0 and b >= required_capacity(n, lo_s)


@given(st.integers(1, 384), st.integers(1, 384))
def test_ladder_admission_monotone(a, b):
    """A bigger graph never lands in a smaller rung, and every rung covers
    the slack-adjusted requirement."""
    lad = BucketLadder(buckets=(128, 256, 384))
    lo, hi = min(a, b), max(a, b)
    assert lad.bucket_for(lo) <= lad.bucket_for(hi)
    assert lad.bucket_for(lo) >= required_capacity(lo, lad.slack)


@given(st.integers(1, 64), st.integers(0, 64))
def test_grasp_budget_monotone(cb, dcb):
    """The per-bucket GraSp block-list budget never shrinks as capacity
    grows (a graph eligible at one rung stays eligible after a re-bucket)
    and never exceeds the bucket's column-block count."""
    lo, hi = cb * 128, (cb + dcb) * 128
    assert grasp_max_nnz(lo) <= grasp_max_nnz(hi)
    assert 1 <= grasp_max_nnz(lo) <= cb


# ------------------------------------- GrAd delta-update differential (§13)


@given(st.sampled_from(("gcn", "gat", "sage")),
       st.sampled_from(("fp32", "int8")),
       st.integers(20, 100), st.integers(0, 2 ** 16),
       st.integers(0, 5), st.integers(0, 5))
def test_delta_update_equals_full_rebuild(kind, tier, n, seed, n_add, n_rm):
    """update_delta ≡ full rebuild, across kinds × tiers × delta shapes:
    after a random symmetric edge delta, the patched device operands (and
    for GCN int8, the patched int8 Â — bit-for-bit) and the served logits
    must equal a FRESH attach of the post-delta structure. SAGE exercises
    the documented fallback (sampled mask: `update()` under the hood), and
    the zero-recompile contract holds through patch and fallback alike."""
    eng = _engine(kind)
    rng = np.random.default_rng(seed)
    g = _graph(n, seed)
    gid = eng.attach(g, model=kind)
    gid2 = None
    try:
        eng.query(gid, tier=tier)
        eng.run()
        pg = eng.graphs[gid][1]
        iu, ju = np.triu_indices(n, 1)
        on = pg.adj[iu, ju] != 0
        absent = np.flatnonzero(~on)
        present = np.flatnonzero(on)
        add = [(int(iu[k]), int(ju[k])) for k in
               rng.choice(absent, size=min(n_add, len(absent)),
                          replace=False)] if len(absent) else []
        rm = [(int(iu[k]), int(ju[k])) for k in
              rng.choice(present, size=min(n_rm, len(present)),
                         replace=False)] if len(present) else []
        if not add and not rm:
            assert eng.update_delta(gid) is True        # vacuous no-op
            return
        applied = eng.update_delta(gid, add_edges=add, remove_edges=rm)
        assert applied is (kind != "sage")
        eng.query(gid, tier=tier)
        r1 = eng.run()[-1]
        eng.assert_warm()
        pg1 = eng.graphs[gid][1]
        gid2 = eng.attach(Graph(
            edge_index=edge_index_from_adjacency(pg1.adj, n),
            num_nodes=n, features=g.features), model=kind)
        eng.query(gid2, tier=tier)
        r2 = eng.run()[-1]
        eng.assert_warm()
        k1 = (gid, eng._graph_version[gid])
        k2 = (gid2, eng._graph_version[gid2])
        o1, o2 = eng._cache.view("operand")[k1], eng._cache.view("operand")[k2]
        for f in OPERAND_FIELDS[kind]:
            np.testing.assert_array_equal(np.asarray(getattr(o1, f)),
                                          np.asarray(getattr(o2, f)))
        if kind == "gcn" and tier == "int8":
            t1 = eng._cache.view("tier")[k1]
            t2 = eng._cache.view("tier")[k2]
            np.testing.assert_array_equal(np.asarray(t1.agg_aq),
                                          np.asarray(t2.agg_aq))
            np.testing.assert_array_equal(np.asarray(t1.agg_a_scale),
                                          np.asarray(t2.agg_a_scale))
        np.testing.assert_array_equal(np.asarray(r1.logits),
                                      np.asarray(r2.logits))
    finally:
        eng.detach(gid)
        if gid2 is not None:
            eng.detach(gid2)


# --------------------------------- §13 byte-accounting under interleavings


_BUDGETED = {}


def _budgeted_engine():
    """One budgeted module-scope engine (warm engines are expensive): a
    budget fitting ~2 bucket-128 GCN primaries, so random interleavings
    exercise eviction and spill constantly."""
    if "eng" not in _BUDGETED:
        from repro.runtime.cache import estimate_dense_entry_bytes
        entry = estimate_dense_entry_bytes(1, 128)
        sc = GraphServeConfig(ladder=BucketLadder(buckets=(128,)),
                              batch_slots=2, return_logits=True,
                              device_cache_budget_bytes=2 * entry + 40_000)
        eng = GraphServe(sc, seed=0)
        eng.register_model("gcn", GNNConfig(
            kind="gcn", in_feats=IN_FEATS, hidden=8, num_classes=CLASSES),
            tiers=("fp32", "int8"))
        eng.warmup()
        eng.calibrate("gcn", _graph(64, seed=999))
        _BUDGETED["eng"] = eng
    return _BUDGETED["eng"]


# ------------------------------------- §14 SLO serving under a fake clock


@st.composite
def slo_traffic(draw):
    k = draw(st.integers(1, 6))
    return [(draw(st.integers(10, 200)),                 # num_nodes
             draw(st.integers(0, 2 ** 16)),              # graph seed
             draw(st.sampled_from((None, 0.0, 5.0, 1e6))),  # deadline_ms
             draw(st.floats(0.0, 0.02)))                 # inter-arrival gap s
            for _ in range(k)]


@given(slo_traffic())
def test_every_request_completes_exactly_once_with_deadline_flag(reqs):
    """§14 liveness: under ANY mix of deadlines (none / already-expired /
    tight / loose) and arrival gaps on a virtual clock, every accepted
    request completes EXACTLY once, `deadline_missed` is always a bool,
    dropped answers happen only on missed deadlines, deadline-free requests
    can never be flagged, and nothing recompiles."""
    from clockwork import FakeClock
    eng = _engine("gcn")
    clk = FakeClock(default_batch_s=1e-3)
    eng.clock = clk
    with eng.scheduler(PipelineConfig(deterministic=True)) as sched:
        for n, seed, deadline_ms, gap in reqs:
            sched.submit(_graph(n, seed), model="gcn",
                         deadline_ms=deadline_ms)
            clk.advance(gap)
        out = sched.drain()
    assert len(out) == len(reqs)
    assert len({r.uid for r in out}) == len(reqs)        # exactly once
    for r, (_, _, deadline_ms, _) in zip(out, reqs):
        assert r.done and r.deadline_missed in (True, False)
        if r.preds is None:
            assert r.deadline_missed                     # drops ⇒ missed
        if deadline_ms is None:
            assert not r.deadline_missed and r.preds is not None
        if deadline_ms == 1e6:
            assert not r.deadline_missed                 # loose never misses
    eng.assert_warm()


@given(st.lists(st.floats(1e-6, 1e3), min_size=1, max_size=30),
       st.floats(0.01, 0.99),
       st.one_of(st.none(), st.floats(1e-9, 1e6)))
def test_latency_bank_prediction_bounded_by_samples(xs, alpha, seed):
    """§14 bank invariant: however wrong the roofline seed is, once a key
    has samples its prediction is a convex combination of them — always
    within [min, max] of what was observed, seed excluded by construction."""
    from repro.runtime.ewma import LatencyBank
    bank = LatencyBank(alpha=alpha)
    key = ("m", 128, "fp32", "dense", "none", 0)
    if seed is not None:
        bank.seed(key, seed)
        assert bank.predict(key) == seed                 # cold: seed verbatim
    for i, x in enumerate(xs):
        bank.observe(key, x)
        p = bank.predict(key)
        assert min(xs[: i + 1]) <= p <= max(xs[: i + 1])
    assert bank.samples(key) == len(xs)


_ROUTER = {}


@st.composite
def router_state(draw):
    tiers = [t for t in STANDARD_TIERS if t != "fp32"]
    return (draw(st.floats(0.0, 10.0)),                  # tolerance
            {t: draw(st.one_of(st.none(), st.floats(-8.0, 8.0)))
             for t in tiers},                            # accuracy deltas
            {t: draw(st.booleans()) for t in tiers},     # calibrated?
            [(draw(st.sampled_from(STANDARD_TIERS)),
              draw(st.floats(1e-7, 1e-2)),
              draw(st.booleans()))                       # measured vs seed
             for _ in range(draw(st.integers(0, 6)))])


@given(router_state())
def test_tier_router_never_selects_unservable_tier(state):
    """§14 router safety: whatever the (delta, calibration, bank) state,
    the tolerance router returns a tier that is servable RIGHT NOW — its
    measured delta fits the tolerance and QuantGr tiers are calibrated —
    so `_resolve_tier` passes it through without the fp32 fallback."""
    tolerance, deltas, calibrated, costs = state
    if "eng" not in _ROUTER:
        # router-only engine: never warmed, never dispatched — the router
        # reads registry + bank state only, so no compile sweep is needed
        eng = GraphServe(GraphServeConfig(
            ladder=BucketLadder(buckets=BUCKETS), batch_slots=3))
        eng.register_model("gcn", GNNConfig(
            kind="gcn", in_feats=IN_FEATS, hidden=8, num_classes=CLASSES),
            tiers=STANDARD_TIERS)
        _ROUTER["eng"] = eng
    eng = _ROUTER["eng"]
    from repro.runtime.ewma import LatencyBank
    e = eng.models["gcn"]
    e.accuracy_delta.clear()
    e.calibrations.clear()
    eng.bank = LatencyBank()
    for t, d in deltas.items():
        if d is not None:
            e.accuracy_delta[t] = d
    for t, c in calibrated.items():
        if c:
            e.calibrations[t] = {}
    for t, cost, measured in costs:
        key = ("gcn", 128, t, "dense", "none", 0)
        if measured:
            eng.bank.observe(key, cost)
        else:
            eng.bank.seed(key, cost)
    pick = eng._tier_for_tolerance("gcn", tolerance, 128)
    if pick != "fp32":
        assert abs(e.accuracy_delta[pick]) <= tolerance
        if e.tiers[pick].quantgr:
            assert pick in e.calibrations
    fallbacks = eng.metrics["tier_fallbacks"]
    assert eng._resolve_tier("gcn", pick) == pick        # servable as-is
    assert eng.metrics["tier_fallbacks"] == fallbacks


# --------------------------------- §13 byte-accounting under interleavings


@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 3),
                          st.integers(0, 2 ** 10)),
                min_size=1, max_size=12))
def test_cache_byte_accounting_under_random_interleavings(ops):
    """After EVERY attach/query/update_delta/detach in a random sequence:
    the per-entry ledger sums to `cache_resident_bytes`, residency never
    exceeds the budget, and evictions == spilled + dropped. Nothing ever
    traces (eviction/spill/patch replay warm blobs)."""
    eng = _budgeted_engine()
    cm = eng._cache
    slots = {}
    try:
        for op, slot, seed in ops:
            if op == 0 and slot not in slots:
                slots[slot] = eng.attach(_graph(30 + slot, seed % 7),
                                         model="gcn")
            elif op == 1 and slot in slots:
                eng.query(slots[slot], tier="int8" if seed % 2 else "fp32")
                eng.run()
            elif op == 2 and slot in slots:
                gid = slots[slot]
                pg = eng.graphs[gid][1]
                rng = np.random.default_rng(seed)
                i, j = rng.choice(pg.num_nodes, size=2, replace=False)
                pair = [(int(min(i, j)), int(max(i, j)))]
                if pg.adj[i, j]:
                    eng.update_delta(gid, remove_edges=pair)
                else:
                    eng.update_delta(gid, add_edges=pair)
            elif op == 3 and slot in slots:
                eng.detach(slots.pop(slot))
            with eng._lock:
                sizes = cm.entry_sizes()
                resident = cm.resident_bytes
                ev, sp, dr = cm.evictions, cm.spilled, cm.dropped
            assert sum(sizes.values()) == resident
            assert resident <= eng.sc.device_cache_budget_bytes
            assert ev == sp + dr
    finally:
        for gid in slots.values():
            eng.detach(gid)
    eng.assert_warm()
