"""Pure-jnp oracles for every Pallas kernel in this package.

Each `*_ref` is the semantic ground truth: simple, obviously-correct jnp.
Kernel tests sweep shapes/dtypes and `assert_allclose(kernel, ref)`; `ops.py`
also uses these as the CPU fallback path (the dry-run compiles these — same
FLOPs, no TPU-only lowering).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e9


def matmul_ref(a: jnp.ndarray, b: jnp.ndarray, *, out_dtype=None) -> jnp.ndarray:
    out_dtype = out_dtype or a.dtype
    return jnp.matmul(a, b, preferred_element_type=jnp.float32).astype(out_dtype)


def int8_matmul_ref(xq: jnp.ndarray, wq: jnp.ndarray, x_scale: jnp.ndarray,
                    w_scale: jnp.ndarray) -> jnp.ndarray:
    """INT8 x INT8 -> INT32 accumulate -> FP32 rescale."""
    acc = jnp.matmul(xq.astype(jnp.int32), wq.astype(jnp.int32),
                     preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (x_scale * w_scale)


def bitmap_spmm_ref(dense_a: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """GraSp oracle: the block-compacted form must equal the dense matmul."""
    return (dense_a @ h).astype(h.dtype)


def bitmap_spmm_block_ref(blocks: jnp.ndarray, block_cols: jnp.ndarray,
                          counts: jnp.ndarray, h: jnp.ndarray, *,
                          block_size: int) -> jnp.ndarray:
    """GraSp ref path ON the compacted form — pure jnp, so it traces under
    jit/vmap with the block structure as a runtime argument (the serving
    plans need exactly that; the old ref densified on the HOST and could
    not see tracers). Same math as the kernel: gather the H row-blocks each
    bitmap entry names, MAC the real ones, mask the padded tail.

    This is still a dense-XLA fallback, not a skip win: every padded list
    entry is fetched and multiplied-by-zero rather than skipped — callers
    that must observe a GraSp dispatch running without the skip grid check
    `ops.bitmap_spmm_mode()` (GraphServe counts it as `backend_fallbacks`).
    """
    rb, max_nnz = block_cols.shape
    bs = block_size
    f = h.shape[1]
    hb = h.reshape(h.shape[0] // bs, bs, f)
    gathered = hb[block_cols]                           # (rb, max_nnz, bs, f)
    blk = blocks.reshape(rb, max_nnz, bs, bs)
    mask = (jnp.arange(max_nnz)[None, :] < counts[:, None]).astype(blocks.dtype)
    return jnp.einsum("rk,rkij,rkjf->rif", mask, blk, gathered
                      ).reshape(rb * bs, f).astype(h.dtype)


def edge_sum_ref(msgs: jnp.ndarray, slot_rows: jnp.ndarray,
                 chunk_tile: jnp.ndarray, *, out_rows: int,
                 tile_rows: int) -> jnp.ndarray:
    """Edge-sum oracle: the tiled layout's slots as global destination rows
    (tile * tile_rows + local row), padding past `out_rows` so the
    segment-sum drops it."""
    k, _, c = slot_rows.shape
    local = slot_rows.reshape(k, c)
    rows = jnp.where(local >= 0, chunk_tile[:, None] * tile_rows + local,
                     out_rows)
    return jax.ops.segment_sum(msgs.astype(jnp.float32), rows.reshape(-1),
                               num_segments=out_rows)


def gat_attention_ref(h: jnp.ndarray, alpha_dst: jnp.ndarray,
                      alpha_src: jnp.ndarray, bias_add: jnp.ndarray,
                      *, negative_slope: float = 0.2) -> jnp.ndarray:
    """Fused GAT oracle (EffOp + GrAx1 + GrAx2 dense formulation).

    h: (N, H, F); alpha_dst/alpha_src: (N, H); bias_add: (N, N) 0 / -1e9.
    out[i, hd] = sum_j softmax_j(leaky(ad[i,hd]+as[j,hd]) + bias[i,j]) h[j,hd].
    """
    e = alpha_dst[:, None, :] + alpha_src[None, :, :]            # (N, N, H)
    e = jax.nn.leaky_relu(e, negative_slope=negative_slope)
    e = e + bias_add[:, :, None]
    e = e - jnp.max(e, axis=1, keepdims=True)
    p = jnp.exp(e)
    attn = p / jnp.maximum(p.sum(axis=1, keepdims=True), 1e-12)  # (N, N, H)
    return jnp.einsum("ijh,jhf->ihf", attn, h)


def sage_max_ref(mask01: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """GrAx3 oracle: out[i,f] = max_j mask[i,j] * h[j,f] (h assumed >= 0;
    isolated rows -> 0, matching the paper's DPU max-pool semantics)."""
    prod = mask01[:, :, None] * h[None, :, :]
    return jnp.max(prod, axis=1)


# ------------------------------------------------- fused per-layer twins
#
# Exact jnp ground truth for `fused_layers.py` — one twin per fused kernel,
# composed from the per-op refs above plus the EffOp catalogue
# (`repro.core.effop`), which makes EffOp the semantic spec for the fused
# epilogues on every backend (the ref path IS what serves on CPU).
# `repro.core.effop` is imported lazily inside each twin: ref.py loads with
# the kernels package, before repro.core exists.


def _act_ref(z: jnp.ndarray, activation: str) -> jnp.ndarray:
    if activation == "relu":
        return jax.nn.relu(z)
    if activation == "elu":
        return jax.nn.elu(z)
    if activation == "none":
        return z
    raise ValueError(f"unknown activation {activation!r}")


def fused_gcn_layer_ref(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
                        norm_adj: Optional[jnp.ndarray] = None,
                        quant=None, activation: str = "none") -> jnp.ndarray:
    """act(Â @ (X @ W) + b) — dense GCN layer twin.

    quant: optional (wq, w_scale, x_scale, h_scale, aq, a_scale) for the
    QuantGr tier; then the combine is the int8 chain (quantize X, s8 dot,
    dequant, re-quantize H) and the aggregate is Âq @ Hq — the exact unfused
    `apply_quantized_linear` + `apply_quantized_agg` math, inlined so the
    twin has no dependency on repro.core.
    """
    if quant is not None:
        wq, w_scale, x_scale, h_scale, aq, a_scale = quant
        xq = jnp.clip(jnp.round(x / x_scale), -127.0, 127.0).astype(jnp.int8)
        h = int8_matmul_ref(xq, wq, x_scale, w_scale)
        hq = jnp.clip(jnp.round(h / h_scale), -127.0, 127.0).astype(jnp.int8)
        acc = jnp.matmul(aq.astype(jnp.int32), hq.astype(jnp.int32),
                         preferred_element_type=jnp.int32)
        z = acc.astype(jnp.float32) * (a_scale * h_scale) + b
        return _act_ref(z, activation)
    h = matmul_ref(x, w, out_dtype=jnp.float32)
    return _act_ref(norm_adj @ h + b, activation).astype(x.dtype)


def fused_gcn_grasp_layer_ref(blocks: jnp.ndarray, block_cols: jnp.ndarray,
                              counts: jnp.ndarray, x: jnp.ndarray,
                              w: jnp.ndarray, b: jnp.ndarray, *,
                              block_size: int,
                              activation: str = "none") -> jnp.ndarray:
    """GraSp GCN layer twin: combine then block-compacted aggregate."""
    h = matmul_ref(x, w, out_dtype=jnp.float32)
    agg = bitmap_spmm_block_ref(blocks, block_cols, counts, h,
                                block_size=block_size)
    return _act_ref(agg + b, activation).astype(x.dtype)


def fused_gat_layer_ref(x: Optional[jnp.ndarray], w: Optional[jnp.ndarray],
                        a_src: jnp.ndarray, a_dst: jnp.ndarray,
                        bias_add: jnp.ndarray, b: jnp.ndarray, *,
                        negative_slope: float = 0.2,
                        activation: str = "none",
                        precombined=None) -> jnp.ndarray:
    """Whole-GAT-layer twin via the EffOp catalogue (GrAx1 + GrAx2).

    x: (N, Fin); w: (Fin, H, F); a_src/a_dst: (H, F); b: (H, F) -> (N, H, F).
    precombined: optional (h, alpha_dst, alpha_src) — the QuantGr tiers
    compute the combine outside (int8) and only attention + epilogue fuse.
    """
    from repro.core import effop
    if precombined is not None:
        h, alpha_dst, alpha_src = precombined
    else:
        h = jnp.einsum("nf,fhd->nhd", x, w)
        alpha_src = jnp.einsum("nhf,hf->nh", h, a_src)
        alpha_dst = jnp.einsum("nhf,hf->nh", h, a_dst)
    outs = []
    for hd in range(h.shape[1]):
        e = effop.broadcast_add_scores(alpha_src[:, hd], alpha_dst[:, hd],
                                       grax2=True)
        e = jax.nn.leaky_relu(e, negative_slope=negative_slope)
        attn = effop.segment_softmax_dense(e, bias_add)       # GrAx1 mask
        outs.append(attn @ h[:, hd, :] + b[hd][None, :])
    return _act_ref(jnp.stack(outs, axis=1), activation)


def fused_sage_layer_ref(mask: jnp.ndarray, xk: jnp.ndarray, x: jnp.ndarray,
                         w_self: jnp.ndarray, w_neigh: jnp.ndarray,
                         b: jnp.ndarray, *, aggregator: str = "mean",
                         activation: str = "none") -> jnp.ndarray:
    """SAGE layer twin: mean (M @ X) or GrAx3 masked-max aggregation plus
    both combines and the epilogue. xk is X (mean) or pooled >= 0 (max)."""
    from repro.core import effop
    if aggregator == "mean":
        agg = mask @ xk
    else:
        agg = effop.masked_max_aggregate(xk, mask, grax3=True)
    return _act_ref(x @ w_self + agg @ w_neigh + b, activation)


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        scale: Optional[float] = None,
                        q_offset: int = 0) -> jnp.ndarray:
    """Exact GQA attention oracle.

    q: (B, Sq, H, D); k, v: (B, Skv, KV, D) with H % KV == 0.
    `q_offset`: absolute position of q[0] (decode: Skv-1 typically).
    `window`: sliding-window size (attend to keys within `window` positions).
    `softcap`: gemma2-style tanh logit soft capping.
    """
    b, sq, hh, d = q.shape
    _, skv, kv, _ = k.shape
    group = hh // kv
    scale = scale if scale is not None else d ** -0.5
    kr = jnp.repeat(k, group, axis=2)
    vr = jnp.repeat(v, group, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kr).astype(jnp.float32) * scale
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(skv)
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    attn = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", attn.astype(vr.dtype), vr)
    return out.astype(q.dtype)
