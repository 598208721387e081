"""The main-path Pallas kernels, and the edge-list plan at ogbn-arxiv
size, compile for a TPU v5e, with no chip attached.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: block shapes off the (8, 128) tiling, lane slices that are not
tile-aligned, primitives Mosaic does not lower. These cases hand the
`kernels/ops.py` entries shapes placed on a described `v5e:2x2` chip and
compile them at the chip smoke's widths: its top NodePad rung (3072) and
the Cora model widths (1433 features -> 64 hidden -> 7 classes, 8 GAT
heads). Nothing runs; a case passes when the chip's compiler accepts it.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and the test workers all import this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.sparsity import compact_block_sparse, grasp_max_nnz
from repro.kernels import ops as kops

N, FIN, HIDDEN, HEADS, CLASSES = 3072, 1433, 64, 8, 7
F = HIDDEN // HEADS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but can
    # never be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def _shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return _shape


def _block_sparse(shape):
    """Shapes of the GraSp structure serving derives at the top rung."""
    bsp, _ = jax.eval_shape(
        lambda a: compact_block_sparse(a, max_nnz=grasp_max_nnz(N)),
        jax.ShapeDtypeStruct((N, N), jnp.float32))
    leaves = [shape(leaf.shape, leaf.dtype)
              for leaf in jax.tree_util.tree_leaves(bsp)]
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(bsp),
                                        leaves)


def _quant(shape, fin, o):
    """(wq, w_scale, x_scale, h_scale, aq, a_scale) of the QuantGr tier."""
    return (shape((fin, o), jnp.int8), shape((o,)), shape(()), shape(()),
            shape((N, N), jnp.int8), shape((N, 1)))


# name -> (fn, argument shapes as a function of the `shape` fixture)
CASES = {
    "fused_gcn_dense": (
        lambda a, x, w, b: kops.fused_gcn_layer(x, w, b, norm_adj=a,
                                                activation="relu"),
        lambda s: (s((N, N)), s((N, FIN)), s((FIN, HIDDEN)), s((HIDDEN,)))),
    "fused_gcn_int8": (
        lambda x, w, b, q: kops.fused_gcn_layer(x, w, b, quant=q,
                                                activation="relu"),
        lambda s: (s((N, FIN)), s((FIN, HIDDEN)), s((HIDDEN,)),
                   _quant(s, FIN, HIDDEN))),
    "fused_gcn_grasp": (
        lambda bsp, x, w, b: kops.fused_gcn_layer(x, w, b, block_sparse=bsp,
                                                  activation="relu"),
        lambda s: (_block_sparse(s), s((N, FIN)), s((FIN, HIDDEN)),
                   s((HIDDEN,)))),
    "fused_gat_full_layer1": (
        lambda x, w, a1, a2, bias, b: kops.fused_gat_layer(
            x, w, a1, a2, bias, b, activation="elu"),
        lambda s: (s((N, FIN)), s((FIN, HEADS, F)), s((HEADS, F)),
                   s((HEADS, F)), s((N, N)), s((HEADS, F)))),
    "fused_gat_full_layer2": (
        lambda x, w, a1, a2, bias, b: kops.fused_gat_layer(
            x, w, a1, a2, bias, b),
        lambda s: (s((N, HIDDEN)), s((HIDDEN, 1, CLASSES)), s((1, CLASSES)),
                   s((1, CLASSES)), s((N, N)), s((1, CLASSES)))),
    "fused_gat_precombined": (
        lambda h, ad, as_, a1, a2, bias, b: kops.fused_gat_layer(
            None, None, a1, a2, bias, b, activation="elu",
            precombined=(h, ad, as_)),
        lambda s: (s((N, HEADS, F)), s((N, HEADS)), s((N, HEADS)),
                   s((HEADS, F)), s((HEADS, F)), s((N, N)), s((HEADS, F)))),
    "gat_attention": (
        kops.gat_attention,
        lambda s: (s((N, HEADS, F)), s((N, HEADS)), s((N, HEADS)),
                   s((N, N)))),
    "fused_sage_mean": (
        lambda m, x, ws, wn, b: kops.fused_sage_layer(
            x, ws, wn, b, mean_mask=m, activation="relu"),
        lambda s: (s((N, N)), s((N, FIN)), s((FIN, HIDDEN)),
                   s((FIN, HIDDEN)), s((HIDDEN,)))),
    "fused_sage_max": (
        lambda m, p, x, ws, wn, b: kops.fused_sage_layer(
            x, ws, wn, b, sample_mask=m, pooled=p, activation="relu"),
        lambda s: (s((N, N)), s((N, FIN)), s((N, FIN)), s((FIN, HIDDEN)),
                   s((FIN, HIDDEN)), s((HIDDEN,)))),
    "sage_max": (
        kops.sage_max,
        lambda s: (s((N, N)), s((N, FIN)))),
    "bitmap_spmm": (
        kops.bitmap_spmm,
        lambda s: (_block_sparse(s), s((N, HIDDEN)))),
    "int8_matmul": (
        kops.int8_matmul,
        lambda s: (s((N, FIN), jnp.int8), s((FIN, HIDDEN), jnp.int8), s(()),
                   s((HIDDEN,)))),
    "block_matmul": (
        kops.matmul,
        lambda s: (s((N, N)), s((N, HIDDEN)))),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, shape, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    fn, args = CASES[name]
    compiled = jax.jit(fn).lower(*args(shape)).compile()
    assert "tpu_custom_call" in compiled.as_text(), name



def _arxiv_edges_plan(shape, b):
    """The edges plan of OGB's ogbn-arxiv SAGE at its bucket and the chunk
    rung of its 2.33 M directed edges (4,992 chunks of 512 slots), at `b`
    batch slots and "highest", compiled for the described chip."""
    from repro.configs.gnn import sage
    from repro.core.models import EdgeOperands, build_plan, init_params
    from repro.runtime.gnn_server import tier_techniques
    cfg, cap, k, c = sage("arxiv"), 169984, 4992, 512
    plan = build_plan(cfg, cap, tier_techniques("sage")["fp32"],
                      batch_size=b, backend="edges")
    params = jax.tree_util.tree_map(
        lambda leaf: shape(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    eo = EdgeOperands(shape((b, k * c), jnp.int32),
                      shape((b, k, 1, c), jnp.int32), shape((b, k), jnp.int32),
                      shape((b, cap)))
    with jax.default_matmul_precision("highest"):
        compiled = plan.fn.lower(params, shape((b, cap, cfg.in_feats)), eo,
                                 None, None).compile()
    return cfg, k * c, compiled


def test_edges_plan_compiles_for_v5e_at_arxiv_size(shape, monkeypatch):
    """The edge-list plan (DESIGN.md §16) at ogbn-arxiv size and the
    engine's default 4 batch slots: the chip's compiler accepts it with
    the MXU edge-sum kernel in each layer, and the plan's own temporaries
    stay far below the chip's memory: ONE slot's gathered (K * C, 256) f32
    messages of the widest layer and little more, since the slots run one
    after another."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    cfg, slots, compiled = _arxiv_edges_plan(shape, 4)
    assert compiled.as_text().count("tpu_custom_call") >= cfg.num_layers
    assert compiled.memory_analysis().temp_size_in_bytes < (
        slots * cfg.hidden * 4 + (1 << 30))


def test_edges_plan_compiles_in_groups_of_chunks_for_v5e(shape, monkeypatch):
    """With the gather's block budget at 512 MiB the same plan gathers and
    sums each layer's chunks in groups (8 at width 256): the chip's
    compiler accepts the loop around the kernel, and the temporaries shrink
    to about one group's messages, well under the one-group plan's."""
    from repro.core import models
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    monkeypatch.setattr(models, "EDGE_GATHER_BLOCK_BYTES", 1 << 29)
    assert models.edge_gather_groups(4992, 512, 256) == 8
    cfg, slots, compiled = _arxiv_edges_plan(shape, 1)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < (
        slots * cfg.hidden * 4 // 8 + (1 << 30))
