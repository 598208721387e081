"""Edge-list SUM on the MXU: each destination row's messages, summed over a
destination-tiled edge layout (`repro.core.graph.edge_arrays`).

The edges are sorted by destination and cut into tiles of `tile_rows`
destination rows; each tile's in-edges are padded to whole chunks of C
slots, and every tile has at least one chunk. A chunk's slots carry their
message (gathered by the caller) and their destination row local to the
tile, -1 on padding. The grid runs over chunks; the output block of chunk k
is its tile, read from `chunk_tile` in SMEM (scalar prefetch), so a tile's
consecutive chunks revisit one (tile_rows, w) f32 accumulator, initialised
on the tile's first chunk: the grouped-matmul pattern.

One step builds the (tile_rows, C) one-hot `iota == local_row` in VMEM and
adds `one_hot @ msgs_chunk`. The f32 messages go to the MXU as three bf16
parts (hi, mid, lo) whose sum is the message exactly, against the exact
0/1 one-hot, accumulated in f32: the products are fp32-exact, so the sums
round like an f32 scatter-add, never like one bf16 pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _split3(x: jnp.ndarray):
    """x (f32) as three bf16 parts whose f32 sum is x."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _edge_sum_kernel(tile_ref, rows_ref, msgs_ref, o_ref):
    k = pl.program_id(0)

    @pl.when((k == 0) | (tile_ref[k] != tile_ref[jnp.maximum(k - 1, 0)]))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    r, c = o_ref.shape[0], rows_ref.shape[-1]
    hit = jax.lax.broadcasted_iota(jnp.int32, (r, c), 0) == rows_ref[...]
    one_hot = jnp.where(hit, 1.0, 0.0).astype(jnp.bfloat16)
    acc = o_ref[...]
    for part in _split3(msgs_ref[...]):
        # bf16 x bf16 products are exact at one pass: pinned, so a
        # process-wide "highest" (which Mosaic refuses for bf16) cannot
        # reach it
        acc += jnp.dot(one_hot, part, precision=jax.lax.Precision.DEFAULT,
                       preferred_element_type=jnp.float32)
    o_ref[...] = acc


@functools.partial(jax.jit,
                   static_argnames=("out_rows", "tile_rows", "interpret"))
def edge_sum(msgs: jnp.ndarray, slot_rows: jnp.ndarray,
             chunk_tile: jnp.ndarray, *, out_rows: int, tile_rows: int,
             interpret: bool = False) -> jnp.ndarray:
    """(out_rows, w) f32 sums of `msgs` by destination.

    msgs:       (K * C, w) f32, slot j of chunk k at row k * C + j.
    slot_rows:  (K, 1, C) int32 destination row within the chunk's tile,
                -1 (matching no row) on padding.
    chunk_tile: (K,) int32 tile of each chunk, non-decreasing, every tile
                of the ceil(out_rows / tile_rows) named at least once.
    """
    k, _, c = slot_rows.shape
    w = msgs.shape[1]
    assert msgs.shape[0] == k * c and chunk_tile.shape == (k,), (
        msgs.shape, slot_rows.shape, chunk_tile.shape)
    return pl.pallas_call(
        _edge_sum_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,      # chunk_tile -> SMEM, the out index map
            grid=(k,),
            in_specs=[pl.BlockSpec((None, 1, c), lambda i, t: (i, 0, 0)),
                      pl.BlockSpec((c, w), lambda i, t: (i, 0))],
            out_specs=pl.BlockSpec((tile_rows, w), lambda i, t: (t[i], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((out_rows, w), jnp.float32),
        interpret=interpret,
    )(chunk_tile, slot_rows, msgs)
