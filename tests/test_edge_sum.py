"""The MXU edge-sum kernel (`kernels/edge_sum.py`) in interpret mode against
`jax.ops.segment_sum` over the plain edge list, and the destination-tiled
layout it reads (`core.graph.edge_arrays`, DESIGN.md §16)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import (EDGE_TILE_ROWS, BucketLadder, Graph,
                              edge_arrays)
from repro.kernels import ops as kops
from repro.kernels.edge_sum import edge_sum
from repro.runtime import gnn_server
from repro.runtime.gnn_server import GraphServe, GraphServeConfig

CAP = 512                      # four tiles and the spare one


def _edges(case, seed=0):
    """(num_nodes, (2, E) edge_index) of one layout case."""
    rng = np.random.default_rng(seed)
    n = 500
    src = rng.integers(0, n, 3000)
    dst = rng.integers(0, n, 3000)
    if case == "lonely_rows":          # every third row has no in-edges
        dst = dst - dst % 3 + 1
    elif case == "hub":                # 1,400 in-edges: at least 3 chunks
        dst[:1400] = 7
    elif case == "empty_tiles":        # tiles 1 and 2 have no edges at all
        dst = np.where((dst >= 128) & (dst < 384), dst % 128, dst)
    elif case == "no_edges":           # only padding: every tile still zero
        src, dst = src[:0], dst[:0]
    return n, np.stack([src, dst]).astype(np.int32)


def _operands(n, ei):
    return [jnp.asarray(a) for a in edge_arrays(ei, n, CAP)]


def _expected(h, ei):
    """The old reduction, segment_sum over the edge list into cap + 1 rows
    (the spare row empty: no edge lands there), and the sums of absolute
    values that bound its fp32 rounding."""
    return tuple(jax.ops.segment_sum(m, ei[1], num_segments=CAP + 1)
                 for m in (h[ei[0]], jnp.abs(h[ei[0]])))


def _close(got, want):
    """Equal up to fp32 rounding of the sums, whatever their order: within
    1e-5 of each element's sum of absolute values (a hub's 1,400 terms
    may round 1400 * 2**-24 = 8e-5 of it apart at worst)."""
    value, abs_sum = want
    np.testing.assert_array_less(np.abs(np.asarray(got - value)),
                                 1e-5 * np.asarray(abs_sum) + 1e-30)


@pytest.mark.parametrize("w", (40, 128, 256))
@pytest.mark.parametrize("case", ("lonely_rows", "hub", "empty_tiles",
                                  "no_edges"))
def test_kernel_matches_segment_sum(case, w):
    """The kernel's (cap + 1, w) sums equal the segment-sum row for row,
    the spare row included (0: padding matches no row)."""
    n, ei = _edges(case)
    src, rows, tiles, _ = _operands(n, ei)
    h = jax.random.normal(jax.random.PRNGKey(w), (CAP, w), jnp.float32)
    got = edge_sum(h[src], rows, tiles, out_rows=CAP + 1,
                   tile_rows=EDGE_TILE_ROWS, interpret=True)
    assert got.shape == (CAP + 1, w) and got.dtype == jnp.float32
    _close(got, _expected(h, ei))
    assert not np.asarray(got[CAP]).any()


@pytest.mark.parametrize("mode", ("interpret", "ref"))
def test_routes_agree_under_a_vmap_over_two_slots(mode, monkeypatch):
    """Two graphs of one layout stacked as a plan stacks its slots: each
    slot's sums are its own graph's, through the kernel and the ref path."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", mode)
    n, ei = _edges("hub", seed=1)
    # the same destinations from other sources: one layout, other sums
    other = np.stack([np.roll(ei[0], 17), ei[1]])
    ops = [_operands(n, e) for e in (ei, other)]
    assert ops[0][1].shape == ops[1][1].shape
    src, rows, tiles = (jnp.stack([o[i] for o in ops]) for i in range(3))
    h = jax.random.normal(jax.random.PRNGKey(3), (2, CAP, 128), jnp.float32)
    got = jax.vmap(lambda hh, s, r, t: kops.edge_sum(
        hh[s], r, t, out_rows=CAP + 1, tile_rows=EDGE_TILE_ROWS))(
        h, src, rows, tiles)
    for i, e in enumerate((ei, other)):
        _close(got[i], _expected(h[i], e))


@pytest.mark.parametrize("case", ("lonely_rows", "hub", "empty_tiles",
                                  "no_edges"))
def test_layout_invariants(case, monkeypatch):
    """Chunk tiles never decrease (a tile's chunks are contiguous), every
    tile of the cap + 1 rows owns at least one chunk, each real slot lies
    in its destination's tile, and the engine's `edge_chunk_fill` is real
    slots over slots."""
    n, ei = _edges(case)
    src, rows, tiles, _ = (np.asarray(a) for a in _operands(n, ei))
    k, _, c = rows.shape
    assert src.shape == (k * c,) and tiles.shape == (k,)
    assert (np.diff(tiles) >= 0).all()
    np.testing.assert_array_equal(np.unique(tiles),
                                  np.arange(CAP // EDGE_TILE_ROWS + 1))
    real = rows.reshape(-1) >= 0
    assert real.sum() == ei.shape[1]
    assert (rows[rows >= 0] < EDGE_TILE_ROWS).all()
    if case == "hub":
        assert (tiles == 0).sum() >= 3
    # 1 MiB of device memory: the bucket's dense operands do not fit
    monkeypatch.setattr(gnn_server, "device_memory_bytes", lambda: 1 << 20)
    eng = GraphServe(GraphServeConfig(ladder=BucketLadder(buckets=(CAP,))))
    eng.register_model("sage", _sage_cfg(), agg_backend="auto")
    eng.attach(Graph(edge_index=ei, num_nodes=n,
                     features=np.zeros((n, 4), np.float32)), model="sage")
    assert eng.summary()["edge_chunk_fill"] == pytest.approx(
        real.mean(), rel=1e-12)


def _sage_cfg():
    from repro.core.models import GNNConfig
    return GNNConfig(kind="sage", in_feats=4, hidden=8, num_classes=3,
                     num_layers=2, max_neighbors=None)


@pytest.mark.parametrize("mode", ("interpret", "ref"))
@pytest.mark.parametrize("groups", (1, 2, 8))
@pytest.mark.parametrize("case", ("hub", "empty_tiles"))
def test_edge_mean_in_groups_of_chunks(case, groups, mode, monkeypatch):
    """With the gather's block budget cut so the chunks go in `groups`
    groups (a hub's tile split between groups, tiles a group never names),
    `edge_mean` still gives every row its in-neighbours' mean."""
    from repro.core import models
    monkeypatch.setenv("REPRO_KERNEL_MODE", mode)
    n, ei = _edges(case)
    eo = models.EdgeOperands(*_operands(n, ei))
    k, c = eo.layout
    w = 40
    # one group holds k * c / groups slots of 128 lanes
    monkeypatch.setattr(models, "EDGE_GATHER_BLOCK_BYTES",
                        4 * k * c * 128 // groups)
    assert models.edge_gather_groups(k, c, w) == groups
    h = jax.random.normal(jax.random.PRNGKey(groups), (CAP, w), jnp.float32)
    got = models.edge_mean(h, eo)
    value, abs_sum = _expected(h, ei)
    scale = np.asarray(eo.inv_deg)[:, None]
    np.testing.assert_array_less(
        np.abs(np.asarray(got) - np.asarray(value[:CAP]) * scale),
        1e-5 * np.asarray(abs_sum[:CAP]) * scale + 1e-30)
    assert np.isfinite(np.asarray(got)).all()
