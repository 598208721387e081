"""GraphServe on the edge-list form (DESIGN.md §16) against the plain
reference (`forward_baseline`: full-neighbour mean, L layers, eval
BatchNorm, fp32 at "highest", no padding, cache or batching), on seeded
random weights with non-zero biases and BatchNorm statistics, through
`attach`/`query`. The device's memory is steered in the test: 1 MiB holds
the dense operands of bucket 128 and not those of bucket 256."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as graph_mod
from repro.core import layers
from repro.core.graph import BucketLadder, Graph, edge_rung, pad_graph
from repro.core.models import (GNNConfig, build_operands, edge_operands,
                               forward_baseline, forward_grannite,
                               init_params)
from repro.data.graphs import planetoid_like
from repro.runtime import gnn_server
from repro.runtime.gnn_server import GraphServe, GraphServeConfig

IN_FEATS, HIDDEN, CLASSES = 12, 256, 5
DENSE_BUCKET, EDGE_BUCKET = 128, 256


@pytest.fixture(autouse=True)
def small_device(monkeypatch):
    monkeypatch.setattr(gnn_server, "device_memory_bytes", lambda: 1 << 20)


def _cfg(**kw):
    return GNNConfig(kind="sage", in_feats=IN_FEATS, hidden=HIDDEN,
                     num_classes=CLASSES, num_layers=3, batch_norm=True,
                     max_neighbors=None, **kw)


def _params(cfg, seed=0):
    """init_params with every bias and BatchNorm statistic made non-zero."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 100)
    out = {}
    for name, leaves in sorted(params.items()):
        out[name] = {}
        for leaf, v in sorted(leaves.items()):
            key, k = jax.random.split(key)
            lo, hi = {"gamma": (0.5, 1.5), "var": (0.5, 2.0)}.get(
                leaf, (-0.2, 0.2))
            if leaf in ("w_self", "w_neigh"):
                out[name][leaf] = v
            else:
                out[name][leaf] = jax.random.uniform(k, v.shape, v.dtype,
                                                     lo, hi)
    return out


def _graph(n, e, seed, lonely=5):
    """A Planetoid-shaped graph whose last `lonely` nodes have no edges."""
    g = planetoid_like(num_nodes=n, num_edges=e, num_feats=IN_FEATS,
                       num_classes=CLASSES, seed=seed, train_per_class=2)
    ei = g.edge_index
    keep = (ei < n - lonely).all(axis=0)
    return dataclasses.replace(g, edge_index=ei[:, keep])


def _reference(params, cfg, g):
    with jax.default_matmul_precision("highest"):
        out = forward_baseline(params, cfg, jnp.asarray(g.features),
                               jnp.asarray(g.edge_index), g.num_nodes)
    return np.asarray(out)


def _engine(cfg, params, *, buckets=(EDGE_BUCKET,), slots=2, **reg):
    eng = GraphServe(GraphServeConfig(ladder=BucketLadder(buckets=buckets),
                                      batch_slots=slots, return_logits=True))
    reg.setdefault("agg_backend", "auto")
    eng.register_model("sage", cfg, params=params, **reg)
    return eng


def _serve(eng, gids):
    uids = [eng.query(gid) for gid in gids]
    done = {r.uid: r for r in eng.run()}
    return [done[u] for u in uids]


def _close(got, ref):
    assert got.shape == ref.shape
    assert np.abs(got - ref).max() <= 1e-5 * np.abs(ref).max()


def test_three_layers_match_the_reference_on_one_rung():
    """Two tenants of one bucket, different edge counts, one edge rung, in
    one dispatch: each answer is its own graph's, with nodes that have no
    in-edges reading a mean of 0."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _engine(cfg, params)
    gs = [_graph(200, 700, seed=1), _graph(230, 720, seed=2)]
    counts = [g.num_edges for g in gs]
    assert counts[0] != counts[1]
    assert edge_rung(counts[0]) == edge_rung(counts[1])
    gids = [eng.attach(g, model="sage") for g in gs]
    assert all(not eng.graphs[gid][1].dense for gid in gids)
    got = _serve(eng, gids)
    assert eng.metrics["batches"] == 1
    assert all(r.backend == "edges" for r in got)
    for r, g in zip(got, gs):
        assert np.bincount(g.edge_index[1], minlength=g.num_nodes).min() == 0
        _close(r.logits, _reference(params, cfg, g))


def test_dense_bucket_serves_the_same_model_densely():
    """Where the dense operands fit, the same three-layer model with
    BatchNorm serves on the dense plan, against the same reference."""
    cfg = _cfg()
    params = _params(cfg, seed=3)
    eng = _engine(cfg, params, buckets=(DENSE_BUCKET, EDGE_BUCKET))
    g = _graph(100, 300, seed=4)
    gid = eng.attach(g, model="sage")
    assert eng.graphs[gid][1].dense
    (r,) = _serve(eng, [gid])
    assert r.backend == "dense"
    _close(r.logits, _reference(params, cfg, g))


def test_update_then_query_serves_the_new_graph():
    cfg = _cfg()
    params = _params(cfg, seed=5)
    eng = _engine(cfg, params)
    gid = eng.attach(_graph(180, 600, seed=6), model="sage")
    _serve(eng, [gid])
    g2 = _graph(210, 900, seed=7)
    assert not eng.update(gid, g2.edge_index, g2.num_nodes, g2.features)
    (r,) = _serve(eng, [gid])
    _close(r.logits, _reference(params, cfg, g2))
    # an edge delta has no dense form to patch: it rebuilds through update
    assert not eng.update_delta(gid, add_edges=[[0, 205], [3, 4]],
                                remove_edges=g2.edge_index[:, :2].T)
    assert eng.metrics["delta_fallbacks"] == 1
    held = eng.graphs[gid][1]
    g3 = Graph(edge_index=held.edge_index, num_nodes=g2.num_nodes,
               features=g2.features)
    assert {(0, 205), (205, 0), (3, 4)} <= set(map(tuple, g3.edge_index.T))
    (r,) = _serve(eng, [gid])
    _close(r.logits, _reference(params, cfg, g3))
    assert eng.update_delta(gid, add_edges=[[0, 205]])    # already there


@pytest.mark.parametrize("cfg,backend", [
    (_cfg(), "dense"),
    (_cfg(), "grasp"),
    (GNNConfig(kind="gcn", in_feats=IN_FEATS, num_classes=CLASSES), "auto"),
    (dataclasses.replace(_cfg(), max_neighbors=10), "auto"),
])
def test_no_edge_form_above_the_fit_limit_is_refused(cfg, backend):
    eng = _engine(cfg, None, agg_backend=backend)
    with pytest.raises(ValueError, match="do not fit"):
        eng.attach(_graph(200, 700, seed=8), model="sage")
    assert not eng.graphs


@pytest.mark.parametrize("reg", [{"fusion": "layer"},
                                 {"tiers": ("fp32", "int8")}])
def test_fused_and_quantized_models_are_refused(reg):
    eng = _engine(_cfg(), None, **reg)
    with pytest.raises(ValueError, match="unfused fp32"):
        eng.attach(_graph(200, 700, seed=9), model="sage")


def test_a_fused_request_on_an_edge_graph_is_refused():
    eng = _engine(_cfg(), None)
    gid = eng.attach(_graph(200, 700, seed=10), model="sage")
    with pytest.raises(ValueError, match="fusion='none'"):
        eng.query(gid, fusion="layer")


def test_no_dense_array_is_built(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("a dense (cap, cap) array was built")

    for name in ("dense_adjacency", "gcn_norm_adjacency", "mean_adjacency"):
        monkeypatch.setattr(graph_mod, name, refuse)
    cfg = _cfg()
    eng = _engine(cfg, _params(cfg))
    gid = eng.attach(_graph(200, 700, seed=11), model="sage")
    eng.submit(_graph(150, 500, seed=12), model="sage")
    eng.query(gid)
    assert len(eng.run()) == 2
    pg = eng.graphs[gid][1]
    assert pg.adj is None and pg.norm_adj is None


def test_warmup_compiles_no_dense_plan_at_an_edge_bucket():
    """At a bucket only the edge form holds, warmup compiles the edges plan
    at the rungs of the graphs attached there and nothing dense or fused;
    a model without an edge form is skipped there, not failed. Queries then
    replay warm."""
    cfg = _cfg()
    eng = _engine(cfg, _params(cfg), buckets=(DENSE_BUCKET, EDGE_BUCKET))
    eng.register_model("gcn", GNNConfig(kind="gcn", in_feats=IN_FEATS,
                                        num_classes=CLASSES))
    gids = [eng.attach(_graph(200, 700, seed=16), model="sage"),
            eng.attach(_graph(230, 720, seed=17), model="sage")]
    eng.warmup()
    at_edge_bucket = {(k[0].kind, k[4], k[5]) for k in eng._plans
                      if k[1] == EDGE_BUCKET}
    assert at_edge_bucket == {("sage", "edges", "none")}
    _serve(eng, gids)
    eng.assert_warm()


def test_edge_operand_bytes_grow_with_edges():
    """Two graphs of one bucket, the second with twice the edges: at most
    2.1 times the edge-operand bytes."""
    cfg = _cfg()
    sizes = []
    for e in (1000, 2000):
        eng = _engine(cfg, None)
        g = _graph(240, e, seed=13, lonely=0)
        eng.attach(g, model="sage")
        sizes.append((g.num_edges, eng.summary()["edge_operand_bytes"]))
    (e1, b1), (e2, b2) = sizes
    assert 1.9 < e2 / e1 < 2.1
    assert b2 <= 2.1 * b1


def _parent_init(key, cfg):
    """The two-layer parameter trees as the code before layer counts made
    them."""
    k1, k2 = jax.random.split(key)
    if cfg.kind == "gcn":
        return {"l1": layers.gcn_init(k1, cfg.in_feats, cfg.hidden),
                "l2": layers.gcn_init(k2, cfg.hidden, cfg.num_classes)}
    if cfg.kind == "gat":
        per_head = cfg.hidden // cfg.heads
        return {"l1": layers.gat_init(k1, cfg.in_feats, per_head, cfg.heads),
                "l2": layers.gat_init(k2, cfg.heads * per_head,
                                      cfg.num_classes, 1)}
    return {"l1": layers.sage_init(k1, cfg.in_feats, cfg.hidden,
                                   aggregator=cfg.aggregator),
            "l2": layers.sage_init(k2, cfg.hidden, cfg.num_classes,
                                   aggregator=cfg.aggregator)}


def _parent_forward(params, cfg, x, ops_, t):
    """The two-layer unfused dense forwards as the code before layer counts
    ran them."""
    if cfg.kind == "gcn":
        h = jax.nn.relu(layers.gcn_grannite(params["l1"], x, ops_.norm_adj, t))
        return layers.gcn_grannite(params["l2"], h, ops_.norm_adj, t)
    if cfg.kind == "gat":
        per_head = cfg.hidden // cfg.heads
        h = jax.nn.elu(layers.gat_grannite(
            params["l1"], x, ops_.mask_mult, ops_.bias_add, t,
            heads=cfg.heads, out_feats=per_head))
        return layers.gat_grannite(params["l2"], h, ops_.mask_mult,
                                   ops_.bias_add, t, heads=1,
                                   out_feats=cfg.num_classes)
    h = jax.nn.relu(layers.sage_grannite(
        params["l1"], x, ops_.sample_mask, ops_.mean_mask, t,
        aggregator=cfg.aggregator))
    return layers.sage_grannite(params["l2"], h, ops_.sample_mask,
                                ops_.mean_mask, t, aggregator=cfg.aggregator)


@pytest.mark.parametrize("kind,extra", [("gcn", {}), ("gat", {"heads": 4}),
                                        ("sage", {}),
                                        ("sage", {"aggregator": "max"})])
def test_two_layer_models_are_unchanged(kind, extra):
    cfg = GNNConfig(kind=kind, in_feats=IN_FEATS, hidden=16,
                    num_classes=CLASSES, **extra)
    key = jax.random.PRNGKey(7)
    got, want = init_params(key, cfg), _parent_init(key, cfg)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    pg = pad_graph(_graph(100, 300, seed=14), capacity=DENSE_BUCKET)
    ops_ = build_operands(pg, cfg, lean=True)
    x = jnp.asarray(pg.features)
    t = gnn_server.tier_techniques(kind)["fp32"]
    np.testing.assert_array_equal(
        np.asarray(forward_grannite(got, cfg, x, ops_, t)),
        np.asarray(_parent_forward(want, cfg, x, ops_, t)))


def test_edge_operands_pad_into_a_spare_row():
    g = _graph(100, 300, seed=15)
    pg = pad_graph(g, capacity=DENSE_BUCKET, dense=False)
    eo = edge_operands(pg)
    e = g.num_edges
    rung = edge_rung(e)
    assert eo.src.shape == eo.dst.shape == (rung,)
    dst = np.asarray(eo.dst)
    assert (dst[e:] == DENSE_BUCKET).all() and (np.diff(dst) >= 0).all()
    deg = np.bincount(g.edge_index[1], minlength=DENSE_BUCKET)
    np.testing.assert_allclose(np.asarray(eo.inv_deg),
                               np.where(deg > 0, 1 / np.maximum(deg, 1), 0),
                               rtol=1e-7)
    with pytest.raises(ValueError, match="outside"):
        graph_mod.edge_arrays(np.array([[0], [100]]), 100, DENSE_BUCKET)


def test_arxiv_preset_is_ogbs_sage():
    from repro.configs.gnn import sage
    cfg = sage("arxiv")
    assert (cfg.in_feats, cfg.hidden, cfg.num_classes, cfg.num_layers) == \
        (128, 256, 40, 3)
    assert cfg.batch_norm and cfg.max_neighbors is None
    n_params = sum(int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(
        init_params(jax.random.PRNGKey(0), cfg)))
    # 2 (128*256 + 256*256 + 256*40) weights, 552 biases, 4 * 512 BatchNorm
    assert n_params == 219688


def test_layer_counts_are_sages():
    with pytest.raises(ValueError, match="SAGE"):
        GNNConfig(kind="gcn", in_feats=4, num_layers=3)
    with pytest.raises(ValueError, match="SAGE"):
        GNNConfig(kind="gat", in_feats=4, batch_norm=True)
