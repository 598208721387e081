"""Fused per-layer GNN kernels: aggregate + combine (+ bias + act), ONE grid.

GraNNite's Step-2 claim is that the win comes from keeping the whole layer on
the data-parallel engine: EffOp rewrites the per-request control flow as
masked arithmetic and the GrAx variants fold attention / broadcast-add / max
into the same pass. These kernels are the TPU-native form of that claim — a
single `pl.pallas_call` per layer whose grid produces the combine result
H = X @ W *into VMEM scratch* and consumes it from there for aggregation,
bias and activation, so the (N, hidden) intermediate never round-trips to HBM
(the paper's DSP<->DRAM traffic, our HBM bytes in `benchmarks/tpu_model.py`).

One kernel per (kind x tier x backend) hot combination:

  * `fused_gcn_dense`  — act(Â @ (X @ W) + b), fp32. Grid (O/bn, N/bm, N/bk);
    at i == 0 each k-step writes one row-block of the H strip into VMEM
    (zero extra FLOPs: the strip is computed exactly once per output strip),
    every step MACs Â's row-block against the resident strip.
  * `fused_gcn_int8`   — the QuantGr tier: the combine phase quantizes X,
    runs the s8xs8->s32 MXU dot (the `int8_matmul` epilogue), re-quantizes H
    to int8 in VMEM, and the aggregate phase is Âq @ Hq with the per-row
    dequant + bias + act folded into the store. Bit-identical to the unfused
    `apply_quantized_linear` + `apply_quantized_agg` chain.
  * `fused_gcn_grasp`  — the GraSp backend: same combine phase, then the
    block-skip walk of `bitmap_spmm` (scalar-prefetched block-column bitmap
    steering VMEM reads) against the resident H strip.
  * `fused_gat_full`   — combine + GrAx2 broadcast-add + GrAx1 additive mask
    + row softmax + attn@H + bias + act per head, one grid. The alpha terms
    are reduced from the VMEM H blocks as they are produced.
  * `fused_gat_precombined` — QuantGr GAT: H comes from the int8 combine
    outside; attention + bias + act stay fused (the `gat_attention` grid with
    the epilogue folded in).
  * `fused_sage`       — mean (M @ X) or GrAx3 masked-max aggregation
    accumulated in VMEM, with BOTH combines (self + neigh) and bias + act in
    the store step.

Activation is a *static* kernel parameter ("none" | "relu" | "elu") — EffOp
dispatch means the per-layer control flow is resolved at trace time into the
epilogue arithmetic, never into per-request host branching.

All shapes must divide the 128 tiles; `ops.py` wrappers pad and strip
(NodePad makes that a no-op for serving operands).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gat_attention import attend

DEFAULT_BLOCK = (128, 128, 128)  # (bm, bn, bk)
_INT8_MAX = 127.0
_ROW_SLAB = 8                    # GrAx3 slab: one sublane tile of neighbours


def _act(z: jnp.ndarray, activation: str) -> jnp.ndarray:
    if activation == "relu":
        return jnp.maximum(z, 0.0)
    if activation == "elu":             # exp - 1: Mosaic lowers no expm1
        return jnp.where(z > 0, z, jnp.exp(jnp.minimum(z, 0.0)) - 1.0)
    if activation == "none":
        return z
    raise ValueError(f"unknown activation {activation!r}")


# ------------------------------------------------------------- GCN (dense)


def _gcn_dense_kernel(a_ref, x_ref, w_ref, b_ref, o_ref, hbuf_ref, acc_ref, *,
                      k_steps: int, bk: int, activation: str):
    i = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Combine phase: the H = X @ W[:, strip] row-block is produced straight
    # into VMEM, once per output strip (i == 0), never written to HBM.
    @pl.when(i == 0)
    def _combine():
        hbuf_ref[pl.ds(k * bk, bk), :] = jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.float32)

    # Aggregate phase: Â row-block x the VMEM-resident H strip.
    acc_ref[...] += jnp.dot(a_ref[...], hbuf_ref[pl.ds(k * bk, bk), :],
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _store():
        o_ref[...] = _act(acc_ref[...] + b_ref[...],
                          activation).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "activation", "interpret"))
def fused_gcn_dense(norm_adj: jnp.ndarray, x: jnp.ndarray, w: jnp.ndarray,
                    b: jnp.ndarray, *, block: tuple = DEFAULT_BLOCK,
                    activation: str = "none",
                    interpret: bool = False) -> jnp.ndarray:
    """out = act(Â @ (X @ W) + b).

    norm_adj: (N, N); x: (N, Fin); w: (Fin, O); b: (1, O).
    N and O must divide the 128 tiles (callers pad via `ops.fused_gcn_layer`).
    """
    n, fin = x.shape
    _, o = w.shape
    assert norm_adj.shape == (n, n) and b.shape == (1, o)
    bm, bn, bk = block
    bm, bn, bk = min(bm, n), min(bn, o), min(bk, n)
    assert n % bm == 0 and n % bk == 0 and o % bn == 0, (x.shape, w.shape)
    k_steps = n // bk
    return pl.pallas_call(
        functools.partial(_gcn_dense_kernel, k_steps=k_steps, bk=bk,
                          activation=activation),
        grid=(o // bn, n // bm, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, i, k: (i, k)),     # Â
            pl.BlockSpec((bk, fin), lambda j, i, k: (k, 0)),    # X
            pl.BlockSpec((fin, bn), lambda j, i, k: (0, j)),    # W strip
            pl.BlockSpec((1, bn), lambda j, i, k: (0, j)),      # bias strip
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, o), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(norm_adj, x, w, b)


# -------------------------------------------------------- GCN (QuantGr int8)


def _gcn_int8_kernel(x_ref, wq_ref, sw_ref, sx_ref, sh_ref, aq_ref, asc_ref,
                     b_ref, o_ref, hqbuf_ref, acc_ref, *, k_steps: int,
                     bk: int, activation: str):
    i = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Combine phase (QuantGr): quantize X, s8xs8->s32 dot, dequant by the
    # folded x_scale*w_scale strip, re-quantize H to int8 — all in VMEM.
    @pl.when(i == 0)
    def _combine():
        xq = jnp.clip(jnp.round(x_ref[...] / sx_ref[0, 0]),
                      -_INT8_MAX, _INT8_MAX).astype(jnp.int8)
        hf = jax.lax.dot_general(
            xq, wq_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32) * sw_ref[...]
        hqbuf_ref[pl.ds(k * bk, bk), :] = jnp.clip(
            jnp.round(hf / sh_ref[0, 0]), -_INT8_MAX, _INT8_MAX
        ).astype(jnp.int8)

    # Aggregate phase: Âq @ Hq in int32.
    acc_ref[...] += jax.lax.dot_general(
        aq_ref[...], hqbuf_ref[pl.ds(k * bk, bk), :],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)

    @pl.when(k == k_steps - 1)
    def _store():
        z = acc_ref[...].astype(jnp.float32) * (asc_ref[...] * sh_ref[0, 0]) \
            + b_ref[...]
        o_ref[...] = _act(z, activation).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "activation", "interpret"))
def fused_gcn_int8(x: jnp.ndarray, wq: jnp.ndarray, sw: jnp.ndarray,
                   x_scale: jnp.ndarray, h_scale: jnp.ndarray,
                   aq: jnp.ndarray, a_scale: jnp.ndarray, b: jnp.ndarray, *,
                   block: tuple = DEFAULT_BLOCK, activation: str = "none",
                   interpret: bool = False) -> jnp.ndarray:
    """QuantGr fused layer, bit-identical to the unfused int8 chain.

    x: (N, Fin) fp32; wq: (Fin, O) int8; sw: (1, O) = x_scale * w_scale;
    x_scale, h_scale: (1, 1); aq: (N, N) int8; a_scale: (N, 1); b: (1, O).
    """
    n, fin = x.shape
    _, o = wq.shape
    assert aq.shape == (n, n) and a_scale.shape == (n, 1)
    bm, bn, bk = block
    bm, bn, bk = min(bm, n), min(bn, o), min(bk, n)
    assert n % bm == 0 and n % bk == 0 and o % bn == 0, (x.shape, wq.shape)
    k_steps = n // bk
    return pl.pallas_call(
        functools.partial(_gcn_int8_kernel, k_steps=k_steps, bk=bk,
                          activation=activation),
        grid=(o // bn, n // bm, k_steps),
        in_specs=[
            pl.BlockSpec((bk, fin), lambda j, i, k: (k, 0)),    # X
            pl.BlockSpec((fin, bn), lambda j, i, k: (0, j)),    # Wq strip
            pl.BlockSpec((1, bn), lambda j, i, k: (0, j)),      # sw strip
            pl.BlockSpec((1, 1), lambda j, i, k: (0, 0)),       # x_scale
            pl.BlockSpec((1, 1), lambda j, i, k: (0, 0)),       # h_scale
            pl.BlockSpec((bm, bk), lambda j, i, k: (i, k)),     # Âq
            pl.BlockSpec((bm, 1), lambda j, i, k: (i, 0)),      # a_scale rows
            pl.BlockSpec((1, bn), lambda j, i, k: (0, j)),      # bias strip
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, o), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, bn), jnp.int8),
                        pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x, wq, sw, x_scale, h_scale, aq, a_scale, b)


# -------------------------------------------------------- GCN (GraSp blocks)


def _gcn_grasp_kernel(counts_ref, cols_ref, x_ref, w_ref, blocks_ref, b_ref,
                      o_ref, hbuf_ref, acc_ref, *, cb: int, max_nnz: int,
                      bs: int, activation: str):
    i = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Combine phase: the first cb steps of each output strip build the full
    # H strip in VMEM (i == 0 only — it is shared by every block-row).
    @pl.when((i == 0) & (t < cb))
    def _combine():
        hbuf_ref[pl.ds(t * bs, bs), :] = jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.float32)

    # Skip walk: the remaining max_nnz steps visit ONLY the bitmap's blocks;
    # the block-column index steers a VMEM read instead of an HBM fetch.
    @pl.when((t >= cb) & (t - cb < counts_ref[i]))
    def _mac():
        col = cols_ref[i, jnp.clip(t - cb, 0, max_nnz - 1)]
        acc_ref[...] += jnp.dot(blocks_ref[0],
                                hbuf_ref[pl.ds(col * bs, bs), :],
                                preferred_element_type=jnp.float32)

    @pl.when(t == cb + max_nnz - 1)
    def _store():
        o_ref[...] = _act(acc_ref[...] + b_ref[...],
                          activation).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_size", "bn", "activation",
                                             "interpret"))
def fused_gcn_grasp(blocks: jnp.ndarray, block_cols: jnp.ndarray,
                    counts: jnp.ndarray, x: jnp.ndarray, w: jnp.ndarray,
                    b: jnp.ndarray, *, block_size: int = 128, bn: int = 128,
                    activation: str = "none",
                    interpret: bool = False) -> jnp.ndarray:
    """GraSp fused layer: combine + block-skip aggregate + bias + act.

    blocks/block_cols/counts: the compacted form of `bitmap_spmm`;
    x: (N, Fin) with N = rb * bs; w: (Fin, O); b: (1, O).
    """
    bs = block_size
    rb, max_nnz = block_cols.shape
    n, fin = x.shape
    _, o = w.shape
    assert blocks.shape == (rb * max_nnz, bs, bs), (blocks.shape, rb, max_nnz)
    assert n == rb * bs and o % bn == 0, (x.shape, w.shape, bs)
    cb = n // bs
    kernel = functools.partial(_gcn_grasp_kernel, cb=cb, max_nnz=max_nnz,
                               bs=bs, activation=activation)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # counts, block_cols -> SMEM
            grid=(o // bn, rb, cb + max_nnz),
            in_specs=[
                # X block: walks rows during the combine phase, parks on the
                # last block during the skip walk (clamped index).
                pl.BlockSpec((bs, fin),
                             lambda j, i, t, counts, cols:
                             (jnp.minimum(t, cb - 1), 0)),
                pl.BlockSpec((fin, bn), lambda j, i, t, counts, cols: (0, j)),
                # Compacted block list entry (i * max_nnz + (t - cb)).
                pl.BlockSpec((1, bs, bs),
                             lambda j, i, t, counts, cols:
                             (i * max_nnz + jnp.clip(t - cb, 0, max_nnz - 1),
                              0, 0)),
                pl.BlockSpec((1, bn), lambda j, i, t, counts, cols: (0, j)),
            ],
            out_specs=pl.BlockSpec((bs, bn),
                                   lambda j, i, t, counts, cols: (i, j)),
            scratch_shapes=[pltpu.VMEM((n, bn), jnp.float32),
                            pltpu.VMEM((bs, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n, o), x.dtype),
        interpret=interpret,
    )(counts, block_cols, x, w, blocks, b)


# --------------------------------------------------------------- GAT (full)


def _gat_full_kernel(x_ref, w_ref, asv_ref, adv_ref, bias_ref, b_ref, o_ref,
                     hbuf_ref, asb_ref, adb_ref, *, k_steps: int, bm: int,
                     bk: int, negative_slope: float, activation: str):
    i = pl.program_id(1)
    k = pl.program_id(2)

    # Combine phase: produce this head's H blocks into VMEM and reduce the
    # alpha terms from them as they appear (GrAx2's operands): dst terms as
    # a column, src terms as a row — the two shapes the broadcast-add takes.
    @pl.when(i == 0)
    def _combine():
        hblk = jnp.dot(x_ref[...], w_ref[...],
                       preferred_element_type=jnp.float32)      # (bk, F)
        start = pl.multiple_of(k * bk, bk)
        hbuf_ref[pl.ds(start, bk), :] = hblk
        asb_ref[:, pl.ds(start, bk)] = jnp.sum(
            (hblk * asv_ref[...]).T, axis=0, keepdims=True)    # (1, bk)
        adb_ref[pl.ds(start, bk), :] = jnp.sum(
            hblk * adv_ref[...], axis=1, keepdims=True)         # (bk, 1)

    # Attention phase: GrAx2 broadcast-add, leaky, GrAx1 additive mask, row
    # softmax, attn @ H, bias + act — the (bm, N) score strip never leaves
    # VMEM.
    @pl.when(k == k_steps - 1)
    def _attend():
        ad = adb_ref[pl.ds(pl.multiple_of(i * bm, bm), bm), :]  # (bm, 1)
        z = attend(ad, asb_ref[...], bias_ref[...], hbuf_ref[...],
                   negative_slope) + b_ref[...]
        o_ref[...] = _act(z, activation).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "negative_slope",
                                             "activation", "interpret"))
def fused_gat_full(x: jnp.ndarray, w: jnp.ndarray, a_src: jnp.ndarray,
                   a_dst: jnp.ndarray, bias_add: jnp.ndarray, b: jnp.ndarray,
                   *, block: tuple = DEFAULT_BLOCK,
                   negative_slope: float = 0.2, activation: str = "none",
                   interpret: bool = False) -> jnp.ndarray:
    """Whole fp32 GAT layer in one grid, per head. Head-major throughout
    (see `gat_attention`): x: (N, Fin); w: (H, Fin, F); a_src/a_dst/b:
    (H, 1, F) per-head rows; bias_add: (N, N) -> out (H, N, F).
    """
    n, fin = x.shape
    heads, _, f = w.shape
    assert a_src.shape == (heads, 1, f) and a_dst.shape == (heads, 1, f)
    assert b.shape == (heads, 1, f) and bias_add.shape == (n, n)
    bm, _, bk = block
    bm, bk = min(bm, n), min(bk, n)
    assert n % bm == 0 and n % bk == 0, (n, block)
    k_steps = n // bk
    head_row = pl.BlockSpec((None, 1, f), lambda hd, i, k: (hd, 0, 0))
    return pl.pallas_call(
        functools.partial(_gat_full_kernel, k_steps=k_steps, bm=bm, bk=bk,
                          negative_slope=negative_slope, activation=activation),
        grid=(heads, n // bm, k_steps),
        in_specs=[
            pl.BlockSpec((bk, fin), lambda hd, i, k: (k, 0)),        # X
            pl.BlockSpec((None, fin, f), lambda hd, i, k: (hd, 0, 0)),  # W
            head_row,                                                # a_src
            head_row,                                                # a_dst
            pl.BlockSpec((bm, n), lambda hd, i, k: (i, 0)),          # bias
            head_row,                                                # b
        ],
        out_specs=pl.BlockSpec((None, bm, f), lambda hd, i, k: (hd, i, 0)),
        out_shape=jax.ShapeDtypeStruct((heads, n, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, f), jnp.float32),
                        pltpu.VMEM((1, n), jnp.float32),
                        pltpu.VMEM((n, 1), jnp.float32)],
        interpret=interpret,
    )(x, w, a_src, a_dst, bias_add, b)


# -------------------------------------------------- GAT (precombined tiers)


def _gat_pre_kernel(ad_ref, as_ref, bias_ref, h_ref, b_ref, o_ref, *,
                    negative_slope: float, activation: str):
    z = attend(ad_ref[...], as_ref[...], bias_ref[...], h_ref[...],
               negative_slope) + b_ref[...]
    o_ref[...] = _act(z, activation).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "negative_slope",
                                             "activation", "interpret"))
def fused_gat_precombined(h: jnp.ndarray, alpha_dst: jnp.ndarray,
                          alpha_src: jnp.ndarray, bias_add: jnp.ndarray,
                          b: jnp.ndarray, *, bm: int = 128,
                          negative_slope: float = 0.2,
                          activation: str = "none",
                          interpret: bool = False) -> jnp.ndarray:
    """QuantGr GAT: H from the int8 combine outside; attention + bias + act
    fused. Head-major like `gat_attention`: h (H, N, F), alpha_dst
    (H, N, 1), alpha_src (H, 1, N), bias_add (N, N), b (H, 1, F) -> out
    (H, N, F)."""
    heads, n, f = h.shape
    assert alpha_dst.shape == (heads, n, 1), alpha_dst.shape
    assert alpha_src.shape == (heads, 1, n) and bias_add.shape == (n, n)
    assert b.shape == (heads, 1, f)
    bm = min(bm, n)
    assert n % bm == 0, (n, bm)
    return pl.pallas_call(
        functools.partial(_gat_pre_kernel, negative_slope=negative_slope,
                          activation=activation),
        grid=(heads, n // bm),
        in_specs=[
            pl.BlockSpec((None, bm, 1), lambda hd, i: (hd, i, 0)),  # alpha_dst
            pl.BlockSpec((None, 1, n), lambda hd, i: (hd, 0, 0)),   # alpha_src
            pl.BlockSpec((bm, n), lambda hd, i: (i, 0)),            # bias strip
            pl.BlockSpec((None, n, f), lambda hd, i: (hd, 0, 0)),   # h, this head
            pl.BlockSpec((None, 1, f), lambda hd, i: (hd, 0, 0)),   # b head
        ],
        out_specs=pl.BlockSpec((None, bm, f), lambda hd, i: (hd, i, 0)),
        out_shape=jax.ShapeDtypeStruct((heads, n, f), h.dtype),
        interpret=interpret,
    )(alpha_dst, alpha_src, bias_add, h, b)


# -------------------------------------------------------------------- SAGE


def _sage_kernel(mm_ref, xk_ref, xs_ref, ws_ref, wn_ref, b_ref, o_ref,
                 aggbuf_ref, *, k_steps: int, aggregator: str, slab: int,
                 n_slabs: int, activation: str):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((j == 0) & (k == 0))
    def _init():
        aggbuf_ref[...] = jnp.zeros_like(aggbuf_ref)

    # Aggregate phase (j == 0 only: the buffer is shared by every output
    # strip of this row-block): mean is M @ X on the MXU; max is the GrAx3
    # masked multiply + max-pool streamed in slabs of neighbours. For max
    # the mask block arrives TRANSPOSED, (bk, bm): a slab of neighbours is
    # then an 8-row sublane slice of both operands — a dynamic lane slice
    # of the (bm, bk) block is not tile-aligned and the TPU refuses it.
    @pl.when(j == 0)
    def _agg():
        if aggregator == "mean":
            aggbuf_ref[...] += jnp.dot(mm_ref[...], xk_ref[...],
                                       preferred_element_type=jnp.float32)
        else:
            def body(r, _):
                sl = pl.ds(pl.multiple_of(r * slab, slab), slab)
                msk = mm_ref[sl, :]                       # (slab, bm)
                pkk = xk_ref[sl, :]                       # (slab, Fin)
                prod = msk[:, :, None] * pkk[:, None, :]  # GrAx3
                aggbuf_ref[...] = jnp.maximum(aggbuf_ref[...],
                                              jnp.max(prod, axis=0))
                return 0

            jax.lax.fori_loop(0, n_slabs, body, 0)

    # Store: both combines (self + neigh) + bias + act in one epilogue.
    @pl.when(k == k_steps - 1)
    def _store():
        z = (jnp.dot(xs_ref[...], ws_ref[...],
                     preferred_element_type=jnp.float32)
             + jnp.dot(aggbuf_ref[...], wn_ref[...],
                       preferred_element_type=jnp.float32)
             + b_ref[...])
        o_ref[...] = _act(z, activation).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("aggregator", "block",
                                             "activation", "interpret"))
def fused_sage(mask: jnp.ndarray, xk: jnp.ndarray, x: jnp.ndarray,
               w_self: jnp.ndarray, w_neigh: jnp.ndarray, b: jnp.ndarray, *,
               aggregator: str = "mean", block: tuple = DEFAULT_BLOCK,
               activation: str = "none", interpret: bool = False) -> jnp.ndarray:
    """out = act(X @ Wself + AGG(mask, xk) @ Wneigh + b).

    mask: (N, N) — mean_mask (mean) or 0/1 sample_mask (max);
    xk: (N, Fin) — X itself (mean) or the non-negative pooled features (max);
    x: (N, Fin); w_self/w_neigh: (Fin, O); b: (1, O).
    """
    n, fin = x.shape
    _, o = w_self.shape
    assert mask.shape == (n, n) and xk.shape == (n, fin)
    assert w_neigh.shape == (fin, o) and b.shape == (1, o)
    bm, bn, bk = DEFAULT_BLOCK if block is None else block
    bm, bn, bk = min(bm, n), min(bn, o), min(bk, n)
    assert n % bm == 0 and n % bk == 0 and o % bn == 0, (x.shape, w_self.shape)
    slab = min(bk, _ROW_SLAB)
    k_steps = n // bk
    if aggregator == "mean":
        mask_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    else:                                # GrAx3 slabs index mask^T rows
        mask, mask_spec = mask.T, pl.BlockSpec((bk, bm),
                                               lambda i, j, k: (k, i))
    return pl.pallas_call(
        functools.partial(_sage_kernel, k_steps=k_steps, aggregator=aggregator,
                          slab=slab, n_slabs=bk // slab, activation=activation),
        grid=(n // bm, o // bn, k_steps),
        in_specs=[
            mask_spec,                                          # mask
            pl.BlockSpec((bk, fin), lambda i, j, k: (k, 0)),    # xk
            pl.BlockSpec((bm, fin), lambda i, j, k: (i, 0)),    # X row strip
            pl.BlockSpec((fin, bn), lambda i, j, k: (0, j)),    # Wself strip
            pl.BlockSpec((fin, bn), lambda i, j, k: (0, j)),    # Wneigh strip
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),      # bias strip
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, o), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, fin), jnp.float32)],
        interpret=interpret,
    )(mask, xk, x, w_self, w_neigh, b)
