"""Public kernel entry points with backend routing.

Routing policy (documented in DESIGN.md §6), in PRECEDENCE ORDER — the first
rule that applies wins:

  1. REPRO_KERNEL_MODE=pallas|interpret|ref — explicit per-process override,
     checked BEFORE backend autodetect so tests and benchmarks can force a
     mode (e.g. exercise the real grid in interpret mode on a CPU box, or
     time the ref path on a TPU). Any other value raises.
  2. backend == "tpu"            -> real Pallas kernels (MXU tiling).
  3. REPRO_PALLAS_INTERPRET=1    -> Pallas kernels in interpret mode (CPU
                                    correctness validation; the CI legs).
  4. otherwise (CPU dry-run)     -> pure-jnp reference path. Same math, same
                                    FLOPs in cost_analysis, no TPU-only
                                    lowering — the multi-pod dry-run and the
                                    CPU serving engine compile this.

The environment is read at TRACE time: changing either variable after a
function has been jit-compiled does not re-route the cached executable.

Every wrapper pads operands to kernel tile multiples when needed and strips
the padding from the result (NodePad makes this a no-op for graph operands).
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .bitmap_spmm import bitmap_spmm as _bitmap_spmm_kernel
from .block_matmul import block_matmul as _block_matmul
from .edge_sum import edge_sum as _edge_sum_kernel
from .flash_attention import flash_attention as _flash_kernel
from .fused_layers import fused_gat_full as _fused_gat_full_kernel
from .fused_layers import fused_gat_precombined as _fused_gat_pre_kernel
from .fused_layers import fused_gcn_dense as _fused_gcn_dense_kernel
from .fused_layers import fused_gcn_grasp as _fused_gcn_grasp_kernel
from .fused_layers import fused_gcn_int8 as _fused_gcn_int8_kernel
from .fused_layers import fused_sage as _fused_sage_kernel
from .gat_attention import gat_attention as _gat_kernel
from .int8_matmul import int8_matmul as _int8_kernel
from .sage_max import sage_max as _sage_max_kernel

_KERNEL_MODES = ("pallas", "interpret", "ref")


def _mode() -> str:
    forced = os.environ.get("REPRO_KERNEL_MODE", "")
    if forced:
        if forced not in _KERNEL_MODES:
            raise ValueError(
                f"REPRO_KERNEL_MODE={forced!r}: expected one of {_KERNEL_MODES}")
        return forced
    if jax.default_backend() == "tpu":
        return "pallas"
    if os.environ.get("REPRO_PALLAS_INTERPRET", "0") == "1":
        return "interpret"
    return "ref"


def _pad2(x: jnp.ndarray, m0: int, m1: int) -> jnp.ndarray:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 == 0 and p1 == 0:
        return x
    return jnp.pad(x, ((0, p0), (0, p1)))


def _head_major(h: jnp.ndarray, npad: int) -> jnp.ndarray:
    """(N, H, F) -> the GAT kernels' (H, N + npad, F), F padded to 128."""
    f = h.shape[2]
    return jnp.pad(jnp.transpose(h, (1, 0, 2)),
                   ((0, 0), (0, npad), (0, (-f) % 128)))


def _head_major_alphas(alpha_dst: jnp.ndarray, alpha_src: jnp.ndarray):
    """(N, H) alpha terms -> dst (H, N, 1) columns, src (H, 1, N) rows."""
    return alpha_dst.T[:, :, None], alpha_src.T[:, None, :]


def _node_major(out: jnp.ndarray, n: int, f: int) -> jnp.ndarray:
    """Kernel (H, N', F') output -> (n, H, f), padding stripped."""
    return jnp.transpose(out, (1, 0, 2))[:n, :, :f]


def matmul(a: jnp.ndarray, b: jnp.ndarray, *, out_dtype=None) -> jnp.ndarray:
    """StaGr aggregation backbone: C = A @ B (MXU-tiled on TPU)."""
    mode = _mode()
    if mode == "ref":
        return ref.matmul_ref(a, b, out_dtype=out_dtype)
    m, k = a.shape
    _, n = b.shape
    ap, bp = _pad2(a, 128, 128), _pad2(b, 128, 128)
    out = _block_matmul(ap, bp, interpret=(mode == "interpret"),
                        out_dtype=out_dtype or a.dtype)
    return out[:m, :n]


def int8_matmul(xq: jnp.ndarray, wq: jnp.ndarray, x_scale, w_scale) -> jnp.ndarray:
    """QuantGr INT8 datapath."""
    mode = _mode()
    if mode == "ref":
        return ref.int8_matmul_ref(xq, wq, x_scale, w_scale)
    m, k = xq.shape
    _, n = wq.shape
    xp, wp = _pad2(xq, 128, 128), _pad2(wq, 128, 128)
    sp = jnp.pad(jnp.asarray(w_scale), (0, (-n) % 128))
    out = _int8_kernel(xp, wp, x_scale, sp, interpret=(mode == "interpret"))
    return out[:m, :n]


def bitmap_spmm_mode() -> str:
    """Which execution form a GraSp dispatch takes right now: "pallas"
    (real skip grid), "interpret" (same grid, interpreter), or "ref" (plain
    XLA gather+einsum over the compacted form — the silent dense fallback
    GraphServe surfaces as `backend_fallbacks`, DESIGN.md §10)."""
    return _mode()


def bitmap_spmm(block_sparse, h: jnp.ndarray) -> jnp.ndarray:
    """GraSp block-sparse aggregation; `block_sparse` from `to_block_sparse`
    / `compact_block_sparse` (a registered pytree, so its leaves may be
    runtime tracers — serving plans pass the structure as a vmapped plan
    argument). The ref path computes on the compacted form with plain XLA
    ops (no skip grid, padded entries multiplied not skipped): same math,
    none of the win — observable via `bitmap_spmm_mode()`."""
    mode = _mode()
    bs = block_sparse.block_size
    n_out = block_sparse.shape[0]
    n, f = h.shape
    hp = _pad2(h, bs, 128)
    blocks = jnp.asarray(block_sparse.blocks)
    cols = jnp.asarray(block_sparse.block_cols)
    counts = jnp.asarray(block_sparse.counts)
    if mode == "ref":
        out = ref.bitmap_spmm_block_ref(blocks, cols, counts, hp,
                                        block_size=bs)
    else:
        out = _bitmap_spmm_kernel(blocks, cols, counts, hp, block_size=bs,
                                  interpret=(mode == "interpret"))
    return out[:n_out, :f]


def bitmap_spmm_batched(block_sparse, h: jnp.ndarray) -> jnp.ndarray:
    """Batched GraSp aggregation: `block_sparse` is a stacked structure
    (`stack_block_sparse`, every leaf carrying a leading B) and h is
    (B, N, F). One vmap over the single-graph entry — the same lowering a
    batched ExecutionPlan produces when the operands carry a block
    structure, exposed here for direct callers and benchmarks."""
    return jax.vmap(bitmap_spmm, in_axes=(0, 0))(block_sparse, h)


def edge_sum(msgs: jnp.ndarray, slot_rows: jnp.ndarray,
             chunk_tile: jnp.ndarray, *, out_rows: int,
             tile_rows: int) -> jnp.ndarray:
    """Sum each destination row's messages over the tiled edge layout
    (`core.graph.edge_arrays`) into (out_rows, w) f32: the MXU one-hot
    kernel on TPU; on the ref path the same sums as an XLA segment-sum,
    padding dropped (`edge_sum_mode()` says which)."""
    mode = _mode()
    if mode == "ref":
        return ref.edge_sum_ref(msgs, slot_rows, chunk_tile,
                                out_rows=out_rows, tile_rows=tile_rows)
    return _edge_sum_kernel(msgs, slot_rows, chunk_tile, out_rows=out_rows,
                            tile_rows=tile_rows,
                            interpret=(mode == "interpret"))


def edge_sum_mode() -> str:
    """Which form an edges plan's reduction takes right now: "pallas",
    "interpret" or "ref" (GraphServe counts a TPU dispatch that ran "ref"
    as `edge_agg_fallbacks`, DESIGN.md §16)."""
    return _mode()


def gat_attention(h: jnp.ndarray, alpha_dst: jnp.ndarray, alpha_src: jnp.ndarray,
                  bias_add: jnp.ndarray) -> jnp.ndarray:
    """Fused EffOp+GrAx1+GrAx2 GAT layer."""
    mode = _mode()
    if mode == "ref":
        return ref.gat_attention_ref(h, alpha_dst, alpha_src, bias_add)
    f = h.shape[2]
    out = _gat_kernel(_head_major(h, 0), *_head_major_alphas(alpha_dst,
                                                             alpha_src),
                      bias_add, interpret=(mode == "interpret"))
    return _node_major(out, h.shape[0], f)


def sage_max(mask01: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """GrAx3 masked max aggregation."""
    mode = _mode()
    if mode == "ref":
        return ref.sage_max_ref(mask01, h)
    n, f = h.shape
    hp = _pad2(h, 128, 128)
    out = _sage_max_kernel(mask01, hp, interpret=(mode == "interpret"))
    return out[:n, :f]


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    q_offset: int = 0) -> jnp.ndarray:
    """GQA attention: Pallas flash kernel on TPU, exact oracle elsewhere.

    NOTE: the LM substrate's *dry-run* path does not call this for long
    sequences — it uses `repro.nn.attention.chunked_attention` (pure-JAX
    online softmax) so 32k/500k prefill compiles without O(S^2) buffers on
    any backend. This wrapper is the TPU hot-spot entry.
    """
    mode = _mode()
    if mode == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       softcap=softcap, scale=scale,
                                       q_offset=q_offset)
    return _flash_kernel(q, k, v, causal=causal, window=window, softcap=softcap,
                         scale=scale, q_offset=q_offset,
                         interpret=(mode == "interpret"))


# ----------------------------------------------------- fused layer entries
#
# One entry per GNN kind; the (tier x backend) variant is selected by which
# operands are present — the same discriminators `core.layers` uses for the
# unfused path, so a fused plan traces the same structure per PlanKey.


def fused_gcn_layer(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
                    norm_adj: Optional[jnp.ndarray] = None,
                    block_sparse=None, quant=None,
                    activation: str = "none") -> jnp.ndarray:
    """Fused GCN layer: act(aggregate(combine(X)) + b) in one kernel pass.

    Exactly one of `norm_adj` (dense Â), `block_sparse` (GraSp form) must be
    given, or `quant` = (wq, w_scale, x_scale, h_scale, aq, a_scale) for the
    QuantGr tier (dense int8 Â). b: (O,) or (1, O).
    """
    mode = _mode()
    b2 = jnp.reshape(b, (1, -1))
    n = x.shape[0]
    o = w.shape[1] if quant is None else quant[0].shape[1]
    if mode == "ref":
        if block_sparse is not None:
            return ref.fused_gcn_grasp_layer_ref(
                jnp.asarray(block_sparse.blocks),
                jnp.asarray(block_sparse.block_cols),
                jnp.asarray(block_sparse.counts),
                _pad2(x, block_sparse.block_size, 1), w, b2,
                block_size=block_sparse.block_size,
                activation=activation)[:n]
        return ref.fused_gcn_layer_ref(x, w, b2, norm_adj=norm_adj,
                                       quant=quant, activation=activation)
    interp = mode == "interpret"
    if block_sparse is not None:
        bs = block_sparse.block_size
        out = _fused_gcn_grasp_kernel(
            jnp.asarray(block_sparse.blocks),
            jnp.asarray(block_sparse.block_cols),
            jnp.asarray(block_sparse.counts),
            _pad2(x, bs, 128), _pad2(w, 128, 128),
            _pad2(b2, 1, 128), block_size=bs, activation=activation,
            interpret=interp)
        return out[:n, :o]
    if quant is not None:
        wq, w_scale, x_scale, h_scale, aq, a_scale = quant
        sw = jnp.reshape(x_scale * w_scale, (1, -1))
        out = _fused_gcn_int8_kernel(
            _pad2(x, 128, 128), _pad2(wq, 128, 128), _pad2(sw, 1, 128),
            jnp.reshape(x_scale, (1, 1)), jnp.reshape(h_scale, (1, 1)),
            _pad2(aq, 128, 128), _pad2(jnp.reshape(a_scale, (-1, 1)), 128, 1),
            _pad2(b2, 1, 128), activation=activation, interpret=interp)
        return out[:n, :o]
    out = _fused_gcn_dense_kernel(
        _pad2(norm_adj, 128, 128), _pad2(x, 128, 128), _pad2(w, 128, 128),
        _pad2(b2, 1, 128), activation=activation, interpret=interp)
    return out[:n, :o]


def fused_gat_layer(x: Optional[jnp.ndarray], w: Optional[jnp.ndarray],
                    a_src: jnp.ndarray, a_dst: jnp.ndarray,
                    bias_add: jnp.ndarray, b: jnp.ndarray, *,
                    activation: str = "none",
                    precombined=None) -> jnp.ndarray:
    """Fused GAT layer -> (N, H, F).

    x: (N, Fin); w: (Fin, H, F); a_src/a_dst: (H, F); bias_add: (N, N);
    b: (H, F). `precombined` = (h, alpha_dst, alpha_src) for QuantGr tiers:
    the int8 combine runs outside, attention + epilogue stay fused.
    """
    mode = _mode()
    if mode == "ref":
        return ref.fused_gat_layer_ref(x, w, a_src, a_dst, bias_add, b,
                                       activation=activation,
                                       precombined=precombined)
    interp = mode == "interpret"
    n = bias_add.shape[0]
    f = b.shape[1]
    npad = (-n) % 128
    # Padded bias rows/cols are fully masked (-1e9): padded columns never
    # win the row softmax, padded rows produce garbage that is stripped.
    bias_p = jnp.pad(bias_add, ((0, npad), (0, npad)),
                     constant_values=ref.NEG_INF)
    bp = _pad2(b, 1, 128)[:, None, :]                  # (H, 1, F) head rows
    if precombined is not None:
        h, alpha_dst, alpha_src = precombined
        out = _fused_gat_pre_kernel(
            _head_major(h, npad),
            *_head_major_alphas(_pad2(alpha_dst, 128, 1),
                                _pad2(alpha_src, 128, 1)),
            bias_p, bp, activation=activation, interpret=interp)
        return _node_major(out, n, f)
    w_hm = jnp.transpose(w, (1, 0, 2))                 # (H, Fin, F)
    out = _fused_gat_full_kernel(
        _pad2(x, 128, 128),
        jnp.pad(w_hm, ((0, 0), (0, (-w.shape[0]) % 128), (0, (-f) % 128))),
        _pad2(a_src, 1, 128)[:, None, :], _pad2(a_dst, 1, 128)[:, None, :],
        bias_p, bp, activation=activation, interpret=interp)
    return _node_major(out, n, f)


def fused_sage_layer(x: jnp.ndarray, w_self: jnp.ndarray,
                     w_neigh: jnp.ndarray, b: jnp.ndarray, *,
                     mean_mask: Optional[jnp.ndarray] = None,
                     sample_mask: Optional[jnp.ndarray] = None,
                     pooled: Optional[jnp.ndarray] = None,
                     activation: str = "none") -> jnp.ndarray:
    """Fused SAGE layer: act(X @ Wself + AGG @ Wneigh + b).

    mean aggregation: pass `mean_mask`; GrAx3 max aggregation: pass the 0/1
    `sample_mask` plus the non-negative `pooled` features. b: (O,) or (1, O).
    """
    mode = _mode()
    b2 = jnp.reshape(b, (1, -1))
    aggregator = "mean" if mean_mask is not None else "max"
    mask = mean_mask if mean_mask is not None else sample_mask
    xk = x if mean_mask is not None else pooled
    if mode == "ref":
        return ref.fused_sage_layer_ref(mask, xk, x, w_self, w_neigh, b2,
                                        aggregator=aggregator,
                                        activation=activation)
    n = x.shape[0]
    o = w_self.shape[1]
    out = _fused_sage_kernel(
        _pad2(mask, 128, 128), _pad2(xk, 128, 128), _pad2(x, 128, 128),
        _pad2(w_self, 128, 128), _pad2(w_neigh, 128, 128), _pad2(b2, 1, 128),
        aggregator=aggregator, activation=activation,
        interpret=(mode == "interpret"))
    return out[:n, :o]
