"""The dispatch's compiled operand stack (`core.models.OperandStacker`): the
same exact copies as the eager `stack_operands` / `stack_tier_operands`,
the same host-side refusals, and no trace or compile event once
`GraphServe.warmup()` has run."""
import dataclasses

import jax
import numpy as np
import pytest
from jax import monitoring

from repro.core.graph import BucketLadder, pad_graph
from repro.core.models import (GNNConfig, build_operand_stacker,
                               build_operands, derive_tier_operands,
                               stack_operands, stack_tier_operands)
from repro.core.sparsity import grasp_max_nnz, pad_block_sparse
from repro.data.graphs import planetoid_like
from repro.runtime.gnn_server import GraphServe, GraphServeConfig

IN_FEATS, CLASSES, CAP = 16, 4, 256
CFGS = {"gcn": GNNConfig(kind="gcn", in_feats=IN_FEATS, hidden=8,
                         num_classes=CLASSES),
        "gat": GNNConfig(kind="gat", in_feats=IN_FEATS, hidden=8,
                         num_classes=CLASSES, heads=2)}


def _graph(n, seed):
    return planetoid_like(num_nodes=n, num_edges=3 * n, num_feats=IN_FEATS,
                          num_classes=CLASSES, seed=seed, train_per_class=2)


def _slot_sets(case):
    """Three slots of one dispatch, the last a junk repeat: (operand sets,
    tier operand sets or None)."""
    pgs = [pad_graph(_graph(n, s), capacity=CAP)
           for n, s in ((90, 0), (200, 1))]
    cfg = CFGS["gat" if case == "gat" else "gcn"]
    if case == "grasp":
        ops = [build_operands(pg, cfg, lean=True, grasp=True) for pg in pgs]
        ops = [dataclasses.replace(o, block_sparse=pad_block_sparse(
            o.block_sparse, grasp_max_nnz(CAP))) for o in ops]
    else:
        ops = [build_operands(pg, cfg, lean=True) for pg in pgs]
    tops = ([derive_tier_operands(o.norm_adj) for o in ops]
            if case == "tier" else None)
    return ops + ops[-1:], (tops + tops[-1:] if tops else None)


@pytest.mark.parametrize("case", ["gcn", "gat", "grasp", "tier"])
def test_compiled_stack_equals_eager_stack(case):
    ops, tops = _slot_sets(case)
    stacker = build_operand_stacker()
    got = stacker(ops, tops)
    want = (stack_operands(ops),
            None if tops is None else stack_tier_operands(tops))
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    field = "mask_mult" if case == "gat" else "norm_adj"
    assert getattr(got[0], field).shape == (3, CAP, CAP)
    assert (got[0].block_sparse is not None) == (case == "grasp")
    assert (got[1] is not None) == (case == "tier")
    # a second dispatch of the same shapes replays the compiled program
    stacker(ops, tops)
    assert stacker.trace_count == 1


def _engine(batch_slots=3):
    sc = GraphServeConfig(ladder=BucketLadder(buckets=(CAP,)),
                          batch_slots=batch_slots)
    eng = GraphServe(sc, seed=0)
    for name, cfg in CFGS.items():
        eng.register_model(name, cfg)
    eng.warmup()
    return eng


@pytest.mark.parametrize("bad", ["offline_quant", "mixed_grasp"])
def test_dispatch_refuses_unbatchable_operands(bad):
    eng = _engine()
    gids = [eng.attach(_graph(n, s), model="gcn")
            for n, s in ((90, 0), (200, 1))]
    for gid in gids:
        eng.query(gid)
    a, b = eng.queue
    if bad == "offline_quant":
        for r in (a, b):
            r.ops = dataclasses.replace(r.ops, quant={"l1": object()})
        match = "calibrate_quant"
    else:
        a.ops = dataclasses.replace(
            a.ops, block_sparse=build_operands(a.pg, CFGS["gcn"],
                                               grasp=True).block_sparse)
        match = "mix"
    traces = eng.summary()["operand_stack_traces"]
    with pytest.raises(ValueError, match=match):
        eng.run()
    # refused on the host, before the compiled call traced anything
    assert eng.summary()["operand_stack_traces"] == traces


@pytest.mark.parametrize("fill", ["full", "partial"])
@pytest.mark.parametrize("model", ["gcn", "gat"])
def test_warm_dispatch_adds_no_trace_or_compile(model, fill):
    eng = _engine()
    gids = [eng.attach(_graph(n, s), model=model)
            for n, s in ((90, 0), (200, 1), (150, 2))]
    warm = eng.summary()["operand_stack_traces"]
    assert warm > 0                 # warmup() stacked through it
    events = []

    def on_event(event, duration, **_kw):
        if event.startswith("/jax/core/compile/"):
            events.append(event)

    monitoring.register_event_duration_secs_listener(on_event)
    try:
        for gid in gids if fill == "full" else gids[:1]:
            eng.query(gid)
        done = eng.run()
    finally:
        monitoring.unregister_event_duration_listener(on_event)
    assert events == []
    assert len(done) == (3 if fill == "full" else 1)
    assert all(r.preds is not None for r in done)
    s = eng.summary()
    assert s["batches"] == 1
    assert s["batch_occupancy"] == (1.0 if fill == "full" else 1 / 3)
    assert s["operand_stack_traces"] == warm
    eng.assert_warm()
