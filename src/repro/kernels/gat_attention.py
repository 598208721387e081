"""Fused GAT attention — the paper's EffOp + GrAx1 + GrAx2 pipeline, one pass.

Per (head, row-block): scores = leaky_relu(alpha_dst ⊕ alpha_src) + bias
(GrAx2 fused broadcast-add; GrAx1 additive mask — no Select, no multiply),
row softmax, then attn @ H aggregation on the MXU. The entire score matrix
row-strip (bm, N) stays in VMEM — it is produced, normalized, and consumed
without ever round-tripping to HBM, which is the Pallas analogue of keeping
the intermediate attention map out of DRAM (the paper's DSP<->DRAM traffic).

Layout is head-major so every block's last two dims are (8, 128)-tiled or
full, as the TPU lowering requires: h and out are (H, N, F) with the head
dim squeezed out of the block, alpha_dst is a (H, N, 1) column per head
and alpha_src a (H, 1, N) row per head — exactly the two broadcast shapes
the GrAx2 add consumes, so the kernel never relayouts a vector.

Grid: (H, N/bm). NodePad guarantees N % 128 == 0; F (per-head feature dim)
is zero-padded to the lane width by `ops.gat_attention` when needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 128


def attend(ad: jnp.ndarray, a_src: jnp.ndarray, bias: jnp.ndarray,
           h: jnp.ndarray, negative_slope: float) -> jnp.ndarray:
    """One head's attention for one row-block, as fp32 (bm, F).

    ad: (bm, 1) dst terms; a_src: (1, N) src terms; bias: (bm, N);
    h: (N, F). Shared by every GAT kernel (the fused ones add an epilogue).
    """
    e = ad + a_src                        # GrAx2: single fused broadcast-add
    e = jnp.where(e >= 0, e, negative_slope * e)          # leaky_relu
    e = e + bias                          # GrAx1: additive mask, no Select
    e = e - jnp.max(e, axis=1, keepdims=True)
    p = jnp.exp(e)
    attn = p / jnp.maximum(p.sum(axis=1, keepdims=True), 1e-12)
    return jnp.dot(attn.astype(h.dtype), h, preferred_element_type=jnp.float32)


def _gat_kernel(ad_ref, as_ref, bias_ref, h_ref, o_ref, *, negative_slope: float):
    o_ref[...] = attend(ad_ref[...], as_ref[...], bias_ref[...], h_ref[...],
                        negative_slope).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "negative_slope", "interpret"))
def gat_attention(h: jnp.ndarray, alpha_dst: jnp.ndarray, alpha_src: jnp.ndarray,
                  bias_add: jnp.ndarray, *, bm: int = DEFAULT_BM,
                  negative_slope: float = 0.2,
                  interpret: bool = False) -> jnp.ndarray:
    """Head-major: h (H, N, F), alpha_dst (H, N, 1), alpha_src (H, 1, N),
    bias_add (N, N) -> out (H, N, F)."""
    heads, n, f = h.shape
    assert alpha_dst.shape == (heads, n, 1), alpha_dst.shape
    assert alpha_src.shape == (heads, 1, n) and bias_add.shape == (n, n)
    bm = min(bm, n)
    assert n % bm == 0, (n, bm)
    return pl.pallas_call(
        functools.partial(_gat_kernel, negative_slope=negative_slope),
        grid=(heads, n // bm),
        in_specs=[
            pl.BlockSpec((None, bm, 1), lambda hd, i: (hd, i, 0)),  # alpha_dst
            pl.BlockSpec((None, 1, n), lambda hd, i: (hd, 0, 0)),   # alpha_src
            pl.BlockSpec((bm, n), lambda hd, i: (i, 0)),            # bias strip
            pl.BlockSpec((None, n, f), lambda hd, i: (hd, 0, 0)),   # h, this head
        ],
        out_specs=pl.BlockSpec((None, bm, f), lambda hd, i: (hd, i, 0)),
        out_shape=jax.ShapeDtypeStruct((heads, n, f), h.dtype),
        interpret=interpret,
    )(alpha_dst, alpha_src, bias_add, h)
