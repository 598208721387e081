"""GCN / GAT / GraphSAGE layers — baseline (gather) and GraNNite paths.

Every layer has two executable forms:

  * baseline  — edge-list gather/scatter/segment ops, with graph
    preprocessing (degree, normalization) ON DEVICE. This mirrors the
    out-of-the-box NPU mapping the paper measures (Fig. 4/5: preprocessing +
    control ops land on the DSP); on TPU these lower to gather/scatter HLOs.
  * grannite — dense masked matmuls on statically padded operands (StaGr /
    PreG / EffOp / GrAx), optionally through the Pallas kernels.

The set of enabled techniques is explicit (`Techniques`) so the benchmark
harness can reproduce the paper's progressive Fig. 20 stacking.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from . import effop
from .quant import QuantizedLinear, apply_quantized_linear

NEG_INF = effop.NEG_INF


@dataclasses.dataclass(frozen=True)
class Techniques:
    """Which GraNNite techniques are active (paper Fig. 7 suite)."""
    stagr: bool = False        # dense precomputed-mask aggregation
    grad_dynamic: bool = False  # masks as runtime inputs (vs baked constants)
    graphsplit: bool = False   # host-side preprocessing (PreG on CPU)
    grasp: bool = False        # block-sparse bitmap aggregation kernel
    quantgr: bool = False      # INT8 combine matmuls
    effop: bool = False        # dense masked attention / max instead of gather
    grax1: bool = False        # additive attention mask
    grax2: bool = False        # fused broadcast-add ordering
    grax3: bool = False        # SAGE-max as mask-mul + maxpool
    use_pallas: bool = False   # route matmuls through Pallas kernels

    @staticmethod
    def baseline() -> "Techniques":
        return Techniques()

    @staticmethod
    def full_gcn() -> "Techniques":
        return Techniques(stagr=True, grad_dynamic=True, graphsplit=True,
                          grasp=True, quantgr=True)

    @staticmethod
    def full_gat() -> "Techniques":
        return Techniques(stagr=True, graphsplit=True, effop=True,
                          grax1=True, grax2=True)

    @staticmethod
    def full_sage() -> "Techniques":
        return Techniques(stagr=True, graphsplit=True, effop=True, grax3=True)


def glorot(key, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[0], shape[-1]
    lim = jnp.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


# =========================================================================
# GCN
# =========================================================================

def gcn_init(key, in_feats: int, out_feats: int) -> Dict:
    kw, = jax.random.split(key, 1)
    return {"w": glorot(kw, (in_feats, out_feats)), "b": jnp.zeros((out_feats,))}


def gcn_baseline(params: Dict, x: jnp.ndarray, edge_index: jnp.ndarray,
                 num_nodes: int) -> jnp.ndarray:
    """Edge-list GCN with ON-DEVICE preprocessing (the paper's slow path).

    degree -> rsqrt -> per-edge gather of norms -> scatter-add: four
    control-heavy stages that land on the DSP on the NPU and on serialized
    gather/scatter HLOs on TPU.
    """
    src, dst = edge_index[0], edge_index[1]
    h = x @ params["w"]
    ones = jnp.ones(src.shape[0], dtype=h.dtype)
    deg = jax.ops.segment_sum(ones, dst, num_segments=num_nodes)
    dis = jnp.where(deg > 0, jax.lax.rsqrt(jnp.maximum(deg, 1e-12)), 0.0)
    coef = dis[dst] * dis[src]                     # gather (DSP analogue)
    msgs = h[src] * coef[:, None]                  # gather + mul
    agg = jax.ops.segment_sum(msgs, dst, num_segments=num_nodes)  # scatter
    return agg + params["b"]


def gcn_grannite(params: Dict, x: jnp.ndarray, norm_adj: jnp.ndarray,
                 t: Techniques, *, quant: Optional[QuantizedLinear] = None,
                 quant_agg=None, agg_h_scale=None, tier_aq=None,
                 tier_a_scale=None, block_sparse=None) -> jnp.ndarray:
    """StaGr/PreG path: out = Â @ (X W) + b — two dense matmuls.

    Â arrives precomputed (PreG on host when t.graphsplit) and either baked
    (StaGr, static) or as a runtime arg (GrAd) — identical math here; the
    trace/caching difference is exercised by the caller. QuantGr covers the
    WHOLE datapath (combine + aggregation) as on the paper's NPU. The
    aggregation has three QuantGr forms, all bit-identical for the same Â:
    `quant_agg` (offline QuantizedAgg, one baked graph — the paper-table
    path); `agg_h_scale` + `tier_aq`/`tier_a_scale` (serving tiers: int8 Â
    derived ONCE per structure version and passed as a runtime arg, so the
    plan reads 1-byte Â rows instead of 4-byte — DESIGN.md §8); or
    `agg_h_scale` alone (in-trace derivation, `quantize_agg_dynamic`, for
    one-shot/eager calls where caching would never amortize).
    """
    if t.quantgr and quant is not None:
        h = apply_quantized_linear(x, quant, use_kernel=t.use_pallas)
    elif t.use_pallas:
        from repro.kernels import ops as kops
        h = kops.matmul(x, params["w"])
    else:
        h = x @ params["w"]

    if t.quantgr and quant_agg is not None:
        from .quant import apply_quantized_agg
        agg = apply_quantized_agg(quant_agg, h, use_kernel=t.use_pallas)
    elif t.quantgr and agg_h_scale is not None:
        from .quant import (QuantizedAgg, apply_quantized_agg,
                            quantize_agg_dynamic)
        if tier_aq is not None:
            qa = QuantizedAgg(aq=tier_aq, a_scale=tier_a_scale,
                              h_scale=agg_h_scale)
        else:
            qa = quantize_agg_dynamic(norm_adj, agg_h_scale)
        agg = apply_quantized_agg(qa, h, use_kernel=t.use_pallas)
    elif t.grasp and block_sparse is not None:
        from repro.kernels import ops as kops
        agg = kops.bitmap_spmm(block_sparse, h)
    elif t.use_pallas:
        from repro.kernels import ops as kops
        agg = kops.matmul(norm_adj, h)
    else:
        agg = norm_adj @ h
    return agg + params["b"]


# =========================================================================
# GAT (single layer, H heads)
# =========================================================================

def gat_init(key, in_feats: int, out_feats: int, heads: int) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w": glorot(k1, (in_feats, heads * out_feats)),
        "a_src": glorot(k2, (heads, out_feats)),
        "a_dst": glorot(k3, (heads, out_feats)),
        "b": jnp.zeros((heads * out_feats,)),
    }


def _gat_head_feats(params, x, heads, out_feats):
    h = x @ params["w"]
    return h.reshape(x.shape[0], heads, out_feats)


def gat_baseline(params: Dict, x: jnp.ndarray, edge_index: jnp.ndarray,
                 num_nodes: int, *, heads: int, out_feats: int,
                 concat: bool = True) -> jnp.ndarray:
    """Edge-list GAT: per-edge gathers, segment softmax, scatter-add.

    This is the Fig. 5 profile: Select/Greater/Softmax/Elu on the DSP.
    """
    src, dst = edge_index[0], edge_index[1]
    h = _gat_head_feats(params, x, heads, out_feats)          # (N, H, F)
    alpha_src = jnp.einsum("nhf,hf->nh", h, params["a_src"])  # (N, H)
    alpha_dst = jnp.einsum("nhf,hf->nh", h, params["a_dst"])
    e = alpha_dst[dst] + alpha_src[src]                       # gathers
    e = jax.nn.leaky_relu(e, negative_slope=0.2)
    # segment softmax over incoming edges of each dst (control-heavy)
    e_max = jax.ops.segment_max(e, dst, num_segments=num_nodes)
    e = jnp.exp(e - e_max[dst])
    e_sum = jax.ops.segment_sum(e, dst, num_segments=num_nodes)
    attn = e / jnp.maximum(e_sum[dst], 1e-12)
    msgs = h[src] * attn[:, :, None]
    out = jax.ops.segment_sum(msgs, dst, num_segments=num_nodes)  # (N, H, F)
    out = out.reshape(num_nodes, heads * out_feats) if concat else out.mean(axis=1)
    return out + (params["b"] if concat else 0.0)


def gat_grannite(params: Dict, x: jnp.ndarray, mask_mult: jnp.ndarray,
                 bias_add: jnp.ndarray, t: Techniques, *, heads: int,
                 out_feats: int, concat: bool = True,
                 quant: Optional[QuantizedLinear] = None) -> jnp.ndarray:
    """EffOp dense GAT: scores as broadcast-add, dense masked softmax,
    aggregation as matmul. GrAx1 picks additive masking, GrAx2 the fused
    broadcast ordering; the Pallas `gat_attention` kernel fuses the whole
    score->softmax->aggregate pipeline per head.

    QuantGr on GAT quantizes the combine matmul X @ W (the FLOPs-dominant
    term at Cora's F=1433); the per-head score einsums and the softmax stay
    fp32 — attention weights are exactly the small-magnitude tensors the
    paper keeps in float.
    """
    n = x.shape[0]
    if t.quantgr and quant is not None:
        h = apply_quantized_linear(x, quant, use_kernel=t.use_pallas)
        h = h.reshape(n, heads, out_feats)
    else:
        h = _gat_head_feats(params, x, heads, out_feats)      # (N, H, F)
    alpha_src = jnp.einsum("nhf,hf->nh", h, params["a_src"])  # (N, H)
    alpha_dst = jnp.einsum("nhf,hf->nh", h, params["a_dst"])

    if t.use_pallas:
        from repro.kernels import ops as kops
        out = kops.gat_attention(h, alpha_dst, alpha_src, bias_add)
    else:
        outs = []
        for hd in range(heads):  # heads unrolled; N x N per head
            e = effop.broadcast_add_scores(alpha_src[:, hd], alpha_dst[:, hd],
                                           grax2=t.grax2)
            e = jax.nn.leaky_relu(e, negative_slope=0.2)
            if t.grax1:
                attn = effop.segment_softmax_dense(e, bias_add)
            else:
                e = effop.masked_select_exact(e, mask_mult)
                attn = jax.nn.softmax(e, axis=-1)
            outs.append(attn @ h[:, hd, :])
        out = jnp.stack(outs, axis=1)                          # (N, H, F)
    out = out.reshape(n, heads * out_feats) if concat else out.mean(axis=1)
    return out + (params["b"] if concat else 0.0)


# =========================================================================
# GraphSAGE (mean / max aggregators)
# =========================================================================

def sage_init(key, in_feats: int, out_feats: int, *, aggregator: str) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w_self": glorot(k1, (in_feats, out_feats)),
        "w_neigh": glorot(k2, (in_feats, out_feats)),
        "b": jnp.zeros((out_feats,)),
    }
    if aggregator == "max":
        p["w_pool"] = glorot(k3, (in_feats, in_feats))
        p["b_pool"] = jnp.zeros((in_feats,))
    return p


# PyTorch's BatchNorm1d default, which OGB's ogbn-arxiv example keeps
BN_EPS = 1e-5


def batch_norm_init(width: int) -> Dict:
    """Eval-mode BatchNorm state: scale, shift and running statistics,
    at the identity."""
    return {"gamma": jnp.ones((width,)), "beta": jnp.zeros((width,)),
            "mean": jnp.zeros((width,)), "var": jnp.ones((width,))}


def batch_norm_eval(params: Dict, h: jnp.ndarray) -> jnp.ndarray:
    """BatchNorm1d in eval mode: the running statistics, never the batch's."""
    return ((h - params["mean"]) / jnp.sqrt(params["var"] + BN_EPS)
            * params["gamma"] + params["beta"])


def sage_baseline(params: Dict, x: jnp.ndarray, edge_index: jnp.ndarray,
                  num_nodes: int, *, aggregator: str) -> jnp.ndarray:
    """Edge-list SAGE. max: sequential per-neighborhood segment_max (DSP)."""
    src, dst = edge_index[0], edge_index[1]
    if aggregator == "mean":
        msgs = x[src]
        agg = jax.ops.segment_sum(msgs, dst, num_segments=num_nodes)
        cnt = jax.ops.segment_sum(jnp.ones_like(src, dtype=x.dtype), dst,
                                  num_segments=num_nodes)
        agg = agg / jnp.maximum(cnt, 1.0)[:, None]
    elif aggregator == "max":
        pooled = jax.nn.relu(x @ params["w_pool"] + params["b_pool"])
        agg = jax.ops.segment_max(pooled[src], dst, num_segments=num_nodes)
        agg = jnp.where(jnp.isfinite(agg), agg, 0.0)
    else:
        raise ValueError(aggregator)
    return x @ params["w_self"] + agg @ params["w_neigh"] + params["b"]


def sage_grannite(params: Dict, x: jnp.ndarray, sample_mask: jnp.ndarray,
                  mean_mask: jnp.ndarray, t: Techniques, *,
                  aggregator: str,
                  quant: Optional[Dict] = None) -> jnp.ndarray:
    """StaGr sampled-adjacency SAGE. mean: mask matmul; max: GrAx3.

    QuantGr quantizes the three combine matmuls (`self` / `neigh` / `pool`
    keys of `quant`, each a QuantizedLinear); the mean-mask aggregation stays
    fp32 — its rows are already 1/deg-scaled and contribute negligible FLOPs
    next to the F-wide combines.
    """
    q = quant if (t.quantgr and quant is not None) else {}

    def _lin(v, w, ql):
        if ql is not None:
            return apply_quantized_linear(v, ql, use_kernel=t.use_pallas)
        return v @ w

    if aggregator == "mean":
        if t.use_pallas:
            from repro.kernels import ops as kops
            agg = kops.matmul(mean_mask, x)
        else:
            agg = mean_mask @ x
    elif aggregator == "max":
        pooled = jax.nn.relu(_lin(x, params["w_pool"], q.get("pool"))
                             + params["b_pool"])
        if t.use_pallas and t.grax3:
            from repro.kernels import ops as kops
            agg = kops.sage_max(sample_mask, pooled)
        else:
            agg = effop.masked_max_aggregate(pooled, sample_mask, grax3=t.grax3)
    else:
        raise ValueError(aggregator)
    return (_lin(x, params["w_self"], q.get("self"))
            + _lin(agg, params["w_neigh"], q.get("neigh")) + params["b"])


# =========================================================================
# Fused per-layer dispatch (fusion="layer" plans — DESIGN.md §11)
# =========================================================================
#
# One call per layer into `kernels.ops.fused_*_layer`: aggregate + combine +
# bias + activation execute as a single kernel pass (EffOp resolves the tier
# scale selection / backend flag / mask application into the kernel epilogue
# at trace time). Branch ladders mirror the unfused functions above so a
# fused plan traces the same operand structure per PlanKey. Two combinations
# fuse PARTIALLY by design: QuantGr GAT (int8 combine outside, attention +
# epilogue fused) and QuantGr SAGE (nothing legally foldable — the unfused
# tier math runs with the activation folded here); see DESIGN.md §11.


def _apply_act(z: jnp.ndarray, activation: str) -> jnp.ndarray:
    if activation == "relu":
        return jax.nn.relu(z)
    if activation == "elu":
        return jax.nn.elu(z)
    if activation == "none":
        return z
    raise ValueError(f"unknown activation {activation!r}")


def gcn_grannite_fused(params: Dict, x: jnp.ndarray, norm_adj: jnp.ndarray,
                       t: Techniques, *, activation: str = "none",
                       quant: Optional[QuantizedLinear] = None,
                       quant_agg=None, agg_h_scale=None, tier_aq=None,
                       tier_a_scale=None, block_sparse=None) -> jnp.ndarray:
    """Fused twin of `gcn_grannite` (+ bias + activation in the kernel).

    Same QuantGr aggregation forms, same precedence; the GraSp form takes
    the block-skip fused kernel, everything else the dense one.
    """
    from repro.kernels import ops as kops
    if t.quantgr and quant is not None:
        if quant_agg is not None:
            qa = quant_agg
        elif agg_h_scale is not None:
            if tier_aq is not None:
                from .quant import QuantizedAgg
                qa = QuantizedAgg(aq=tier_aq, a_scale=tier_a_scale,
                                  h_scale=agg_h_scale)
            else:
                from .quant import quantize_agg_dynamic
                qa = quantize_agg_dynamic(norm_adj, agg_h_scale)
        else:
            # No aggregation scales: nothing past the combine can fuse —
            # degenerate to the unfused tier math with the act folded here.
            return _apply_act(gcn_grannite(params, x, norm_adj, t,
                                           quant=quant), activation)
        qt = (quant.wq, quant.w_scale, quant.x_scale, qa.h_scale, qa.aq,
              qa.a_scale)
        return kops.fused_gcn_layer(x, params["w"], params["b"], quant=qt,
                                    activation=activation)
    if t.grasp and block_sparse is not None:
        return kops.fused_gcn_layer(x, params["w"], params["b"],
                                    block_sparse=block_sparse,
                                    activation=activation)
    return kops.fused_gcn_layer(x, params["w"], params["b"],
                                norm_adj=norm_adj, activation=activation)


def gat_grannite_fused(params: Dict, x: jnp.ndarray, bias_add: jnp.ndarray,
                       t: Techniques, *, heads: int, out_feats: int,
                       activation: str = "none",
                       quant: Optional[QuantizedLinear] = None) -> jnp.ndarray:
    """Fused twin of `gat_grannite` (concat form): the whole layer for fp32
    tiers; QuantGr keeps the int8 combine outside and fuses attention +
    bias + activation (the precombined kernel)."""
    from repro.kernels import ops as kops
    n = x.shape[0]
    b = params["b"].reshape(heads, out_feats)
    if t.quantgr and quant is not None:
        h = apply_quantized_linear(x, quant, use_kernel=t.use_pallas)
        h = h.reshape(n, heads, out_feats)
        alpha_src = jnp.einsum("nhf,hf->nh", h, params["a_src"])
        alpha_dst = jnp.einsum("nhf,hf->nh", h, params["a_dst"])
        out = kops.fused_gat_layer(None, None, params["a_src"],
                                   params["a_dst"], bias_add, b,
                                   activation=activation,
                                   precombined=(h, alpha_dst, alpha_src))
    else:
        w3 = params["w"].reshape(x.shape[1], heads, out_feats)
        out = kops.fused_gat_layer(x, w3, params["a_src"], params["a_dst"],
                                   bias_add, b, activation=activation)
    return out.reshape(n, heads * out_feats)


def sage_grannite_fused(params: Dict, x: jnp.ndarray,
                        sample_mask: jnp.ndarray, mean_mask: jnp.ndarray,
                        t: Techniques, *, aggregator: str,
                        activation: str = "none",
                        quant: Optional[Dict] = None) -> jnp.ndarray:
    """Fused twin of `sage_grannite`: mean (M @ X) or GrAx3 masked-max plus
    both combines and the epilogue in one pass. QuantGr SAGE cannot fuse
    (the neighbor combine consumes the aggregation output and all three
    combines are int8): the unfused tier math runs with the act folded."""
    from repro.kernels import ops as kops
    if t.quantgr and quant is not None:
        return _apply_act(sage_grannite(params, x, sample_mask, mean_mask, t,
                                        aggregator=aggregator, quant=quant),
                          activation)
    if aggregator == "mean":
        return kops.fused_sage_layer(x, params["w_self"], params["w_neigh"],
                                     params["b"], mean_mask=mean_mask,
                                     activation=activation)
    pooled = jax.nn.relu(x @ params["w_pool"] + params["b_pool"])
    return kops.fused_sage_layer(x, params["w_self"], params["w_neigh"],
                                 params["b"], sample_mask=sample_mask,
                                 pooled=pooled, activation=activation)
