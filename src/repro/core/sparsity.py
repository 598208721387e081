"""GraSp: sparsity exploitation — ZVC packing, block bitmaps, agg backends.

Two granularities, mirroring the paper's Fig. 13:

  * element ZVC (Zero Value Compression): store only non-zeros + a bitmap.
    On TPU this is a *storage/transfer* format (checkpoint, host->device);
    dense compute unpacks it. Matches DESIGN.md's SymG discussion.
  * 128x128 block bitmap: the compute-side form. Real graph adjacencies are
    >99% zero; after NodePad alignment most 128x128 blocks of Â are entirely
    zero. The host compacts the non-zero block coordinates per block-row and
    the `bitmap_spmm` Pallas kernel loops only over those — the TPU-native
    realization of "the bitmap directs the NPU to skip zero entries".

Serving contract (DESIGN.md §10): `BlockSparse` is a registered pytree, so
a compacted structure rides `GranniteOperands` across jit/vmap boundaries
as a runtime argument. To make that SHAPE-STABLE per NodePad bucket, every
serving-path structure is padded to the bucket's `grasp_max_nnz` budget —
derived on the device from the realized fp32 Â by `compact_block_sparse`
(pure jnp, jitted per bucket; `to_block_sparse` + `pad_block_sparse` are
its host reference) — and same-bucket structures stack into one batched
operand (`stack_block_sparse`).
`select_agg_backend` is the density/cost rule (same modelled-latency style
as `partition.py` / `benchmarks/tpu_model.py`) that decides, per graph and
bucket, whether the batched `bitmap_spmm` dispatch beats the dense matmul.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import MXU_TILE


# ----------------------------- element-level ZVC ---------------------------

def zvc_pack(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray, Tuple[int, ...]]:
    """Pack: (nonzero values, packed bitmap bytes, original shape)."""
    flat = x.reshape(-1)
    mask = flat != 0
    values = flat[mask]
    bitmap = np.packbits(mask.astype(np.uint8))
    return values.astype(x.dtype), bitmap, x.shape


def zvc_unpack(values: np.ndarray, bitmap: np.ndarray, shape: Tuple[int, ...],
               dtype=np.float32) -> np.ndarray:
    total = int(np.prod(shape))
    mask = np.unpackbits(bitmap)[:total].astype(bool)
    out = np.zeros(total, dtype=dtype)
    out[mask] = values
    return out.reshape(shape)


def zvc_compressed_bytes(x: np.ndarray) -> int:
    """Bytes after ZVC: non-zeros * itemsize + bitmap (1 bit/elem)."""
    nnz = int(np.count_nonzero(x))
    return nnz * x.dtype.itemsize + (x.size + 7) // 8


# ----------------------------- block-level bitmap --------------------------

@dataclasses.dataclass
class BlockSparse:
    """Block-compacted matrix for the bitmap_spmm kernel.

    blocks:     (n_blocks, bs, bs) gathered non-zero blocks (row-major order
                within each block-row).
    block_cols: (n_row_blocks, max_nnz) int32 column-block index of each
                non-zero block; padded entries hold an arbitrary VALID block
                index (the kernel masks them via counts but still prefetches
                them, so they must stay in range).
    counts:     (n_row_blocks,) int32 non-zero blocks in each block-row.
    bitmap:     (n_row_blocks, n_col_blocks) uint8 — diagnostic / GraSp stats.

    Registered as a jax pytree: (blocks, block_cols, counts, bitmap) are
    runtime leaves, (block_size, shape) static structure — so a compacted Â
    crosses jit/vmap boundaries as a plan ARGUMENT (GrAd discipline), and a
    batched form is simply the same pytree with a leading B on every leaf
    (`stack_block_sparse`).
    """

    blocks: np.ndarray
    block_cols: np.ndarray
    counts: np.ndarray
    bitmap: np.ndarray
    block_size: int
    shape: Tuple[int, int]

    @property
    def density(self) -> float:
        return float(np.asarray(self.bitmap).mean())

    @property
    def max_nnz(self) -> int:
        """The per-block-row list budget this structure is padded to."""
        return int(self.block_cols.shape[-1])

    @property
    def nbytes(self) -> int:
        """Bytes the compacted form occupies / moves (blocks + indices)."""
        return int(self.blocks.nbytes + self.block_cols.nbytes
                   + self.counts.nbytes)


jax.tree_util.register_pytree_node(
    BlockSparse,
    lambda s: ((s.blocks, s.block_cols, s.counts, s.bitmap),
               (s.block_size, s.shape)),
    lambda aux, ch: BlockSparse(*ch, *aux))


def to_block_sparse(a: np.ndarray, *, block_size: int = MXU_TILE
                    ) -> BlockSparse:
    """Host-side block compaction: the reference the device-side
    `compact_block_sparse` is tested against."""
    n, m = a.shape
    bs = block_size
    if n % bs or m % bs:
        raise ValueError(f"shape {a.shape} not a multiple of block {bs} (NodePad first)")
    rb, cb = n // bs, m // bs
    view = a.reshape(rb, bs, cb, bs).transpose(0, 2, 1, 3)  # (rb, cb, bs, bs)
    bitmap = (np.abs(view).sum(axis=(2, 3)) > 0).astype(np.uint8)
    counts = bitmap.sum(axis=1).astype(np.int32)
    max_nnz = max(int(counts.max()), 1)
    # Pad each block-row's list to max_nnz; gather the blocks densely so the
    # kernel indexes them with a flat dynamic slice.
    block_cols = np.zeros((rb, max_nnz), dtype=np.int32)
    blocks = np.zeros((rb * max_nnz, bs, bs), dtype=a.dtype)
    for i in range(rb):
        cols = np.nonzero(bitmap[i])[0]
        block_cols[i, : len(cols)] = cols
        for k, c in enumerate(cols):
            blocks[i * max_nnz + k] = view[i, c]
    return BlockSparse(blocks=blocks, block_cols=block_cols, counts=counts,
                       bitmap=bitmap, block_size=bs, shape=(n, m))


def from_block_sparse(sp: BlockSparse) -> np.ndarray:
    n, m = sp.shape
    bs = sp.block_size
    rb = n // bs
    max_nnz = sp.block_cols.shape[1]
    out = np.zeros((n, m), dtype=sp.blocks.dtype)
    for i in range(rb):
        for k in range(int(sp.counts[i])):
            c = int(sp.block_cols[i, k])
            out[i * bs:(i + 1) * bs, c * bs:(c + 1) * bs] = sp.blocks[i * max_nnz + k]
    return out


# ------------------------- batched serving form (DESIGN.md §10) ------------

# Per-bucket block-list budget: every serving-path BlockSparse at capacity
# `cap` pads its per-block-row lists to grasp_max_nnz(cap), so one compiled
# (bucket, backend) plan serves every admitted structure. A quarter of the
# column blocks (floor 2, ceiling cb) keeps the budget well under the dense
# fetch while admitting community/banded structure; graphs whose densest
# block-row exceeds it are ineligible and serve dense (select_agg_backend).

def grasp_max_nnz(capacity: int, *, block_size: int = MXU_TILE) -> int:
    """Block-list budget for one NodePad bucket (monotone in capacity)."""
    cb = max(capacity // block_size, 1)
    return min(cb, max(2, -(-cb // 4)))          # clamp(ceil(cb/4), 2, cb)


def pad_block_sparse(sp: BlockSparse, max_nnz: int) -> BlockSparse:
    """Pad a host-compacted structure's block lists to a bucket budget.

    Serving plans are shape-stable per bucket, so every graph's data-driven
    `to_block_sparse` width must grow to the shared `grasp_max_nnz` budget
    before it can enter a batch. Raises when the structure is too dense for
    the budget — callers run `select_agg_backend` first, which rejects
    those to the dense backend instead.
    """
    rb, mx = sp.block_cols.shape
    if mx > max_nnz:
        raise ValueError(
            f"block structure needs max_nnz={mx} > budget {max_nnz}; "
            "select_agg_backend should have routed this graph dense")
    if mx == max_nnz:
        return sp
    bs = sp.block_size
    cols = np.zeros((rb, max_nnz), np.int32)
    cols[:, :mx] = sp.block_cols
    blocks = np.zeros((rb, max_nnz, bs, bs), np.asarray(sp.blocks).dtype)
    blocks[:, :mx] = np.asarray(sp.blocks).reshape(rb, mx, bs, bs)
    return dataclasses.replace(sp, blocks=blocks.reshape(rb * max_nnz, bs, bs),
                               block_cols=cols)


def stack_block_sparse(sps: Sequence[BlockSparse]) -> BlockSparse:
    """Stack same-bucket structures into one batched (B, ...) operand.

    Requires identical (block_size, shape, max_nnz) — which every structure
    padded to one bucket's budget has. The result is the same pytree with a
    leading batch dim on every leaf; vmapped plans strip it back off, so
    `bitmap_spmm` always sees the single-graph form.
    """
    if not sps:
        raise ValueError("cannot stack an empty block-sparse batch")
    head = sps[0]
    for sp in sps[1:]:
        if (sp.block_size, sp.shape, sp.max_nnz) != (
                head.block_size, head.shape, head.max_nnz):
            raise ValueError(
                "mixed block-sparse structures in one batch: "
                f"{(sp.block_size, sp.shape, sp.max_nnz)} vs "
                f"{(head.block_size, head.shape, head.max_nnz)} "
                "(pad to one bucket budget first)")
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *sps)


def block_counts(a: jnp.ndarray, *, block_size: int = MXU_TILE
                 ) -> jnp.ndarray:
    """Per-block-row non-zero block counts of one dense operand — the
    cheap device-side reduction feeding the backend rule (pure jnp). A
    graph the rule routes dense never needs the full `compact_block_sparse`
    gather; deriving counts alone keeps the dense-routed decision at one
    bitmap reduction per structure version."""
    n, m = a.shape
    bs = block_size
    rb, cb = n // bs, m // bs
    nz = jnp.abs(a.reshape(rb, bs, cb, bs)).sum(axis=(1, 3)) > 0
    return nz.sum(axis=1).astype(jnp.int32)


def compact_block_sparse(a: jnp.ndarray, *, max_nnz: int,
                         block_size: int = MXU_TILE
                         ) -> Tuple[BlockSparse, jnp.ndarray]:
    """Device-side `to_block_sparse`: derive the budgeted block structure
    from an (already device-resident) dense Â with pure jnp ops.

    This is the CacheG-derived sparse operand (DESIGN.md §10): when the
    fp32 Â was materialized on device (§7), re-deriving its block structure
    there moves ZERO extra host→device bytes — the engine jits this per
    bucket (`core.models.build_block_compactor`) and caches the result per
    (graph_id, structure_version). Padded list entries gather genuine
    all-zero blocks at valid in-range column indices (argsort puts zero
    blocks last), so the kernel's count mask is belt-and-braces.

    Returns (structure, true_counts): `true_counts` is the UNCLAMPED
    per-block-row non-zero count — a row exceeding `max_nnz` means the
    structure is truncated and MUST NOT serve (the caller's eligibility
    check; `counts` inside the structure is clamped to the budget).
    """
    n, m = a.shape
    bs = block_size
    rb, cb = n // bs, m // bs
    view = a.reshape(rb, bs, cb, bs).transpose(0, 2, 1, 3)   # (rb, cb, bs, bs)
    nz = jnp.abs(view).sum(axis=(2, 3)) > 0                  # (rb, cb)
    counts_true = nz.sum(axis=1).astype(jnp.int32)
    # non-zero column indices first (ascending), zero blocks pushed to cb
    keys = jnp.where(nz, jnp.arange(cb, dtype=jnp.int32), cb)
    order = jnp.argsort(keys, axis=1)[:, :max_nnz].astype(jnp.int32)
    blocks = jnp.take_along_axis(view, order[:, :, None, None], axis=1)
    return BlockSparse(blocks=blocks.reshape(rb * max_nnz, bs, bs),
                       block_cols=order,
                       counts=jnp.minimum(counts_true, max_nnz),
                       bitmap=nz.astype(jnp.uint8),
                       block_size=bs, shape=(n, m)), counts_true


def block_stats(a: np.ndarray, *, block_size: int = MXU_TILE) -> Dict:
    """Host-side block-bitmap statistics of one dense operand (numpy; the
    cheap O(cap²) pass the serving host stage runs to feed the backend
    rule when the graph is not yet device-resident)."""
    a = np.asarray(a)
    n, m = a.shape
    rb, cb = n // block_size, m // block_size
    nz = np.abs(a.reshape(rb, block_size, cb, block_size)).sum(axis=(1, 3)) > 0
    counts = nz.sum(axis=1)
    return {"nnz_blocks": int(counts.sum()),
            "max_row_nnz": int(counts.max()) if counts.size else 0,
            "n_row_blocks": rb, "n_col_blocks": cb,
            "block_density": float(nz.mean()) if nz.size else 0.0,
            # the bitmap itself, so a follow-up to_block_sparse on the
            # same matrix can skip its own reduction pass
            "bitmap": nz.astype(np.uint8)}


# --------------------- backend dispatch rule (DESIGN.md §10) ----------------

# Same modelled-latency style as partition.default_gnn_stages and
# benchmarks/tpu_model.py — structurally the same numbers now: every
# consumer reads `core.costs` (re-exported here for existing importers).
from .costs import HBM_BW, MXU_RATE  # noqa: F401  (re-export)
# Per-grid-step cost of the sparse kernel (scalar-prefetch read, index-map
# evaluation, small-dot underutilization) — what keeps tiny buckets dense.
GRASP_STEP_OVERHEAD_S = 5e-8


def agg_cost_model(capacity: int, feats: int, *, nnz_blocks: int,
                   max_nnz: int, block_size: int = MXU_TILE
                   ) -> Tuple[float, float]:
    """Modelled aggregation latency (dense_s, grasp_s) for one Â @ H.

    Dense: one (cap, cap) @ (cap, F) matmul — roofline max of MXU FLOPs and
    HBM bytes. GraSp: the kernel MACs only the `nnz_blocks` real blocks but
    FETCHES the full padded budget (`rb * max_nnz` block + H-tile DMAs —
    masked grid steps skip compute, not the prefetch) and pays a per-step
    overhead. The crossover this produces is the technique's win condition:
    large buckets with block-sparse structure go grasp, tiny buckets and
    scattered graphs stay dense.
    """
    bs = block_size
    rb = max(capacity // bs, 1)
    dense_flops = 2.0 * capacity * capacity * feats
    dense_bytes = 4.0 * (capacity * capacity + 2 * capacity * feats)
    dense_s = max(dense_flops / MXU_RATE, dense_bytes / HBM_BW)
    steps = rb * max_nnz * max(feats // 128, 1)
    grasp_flops = 2.0 * nnz_blocks * bs * bs * feats
    grasp_bytes = 4.0 * (rb * max_nnz * (bs * bs + bs * feats)
                         + capacity * feats)
    grasp_s = (max(grasp_flops / MXU_RATE, grasp_bytes / HBM_BW)
               + steps * GRASP_STEP_OVERHEAD_S)
    return dense_s, grasp_s


def select_agg_backend(capacity: int, feats: int, *, nnz_blocks: int,
                       max_row_nnz: int, mode: str = "auto",
                       block_size: int = MXU_TILE,
                       measured: Optional[Tuple[Optional[float],
                                                Optional[float]]] = None
                       ) -> Tuple[str, float, float]:
    """The per-(graph, bucket) AggBackend decision: "dense" | "grasp".

    Eligibility first — a block-row denser than the bucket's budget cannot
    be represented (truncation would drop real blocks), so it serves dense
    regardless of `mode`; its reported grasp cost is priced at the list
    width it WOULD need (`max_row_nnz`), so the returned costs stay
    meaningful either way. Within eligibility, `mode="grasp"` forces the
    sparse path and `mode="auto"` takes the cost winner.

    `measured=(dense_s, grasp_s)` is the hardware-in-the-loop override
    (DESIGN.md §14): when BOTH backends carry a real measured latency
    (from the serving `LatencyBank`), auto mode ranks on those instead of
    the analytic model — measurement corrects the roofline where they
    disagree (BENCH_gnn.json's grasp rows on CPU). A partial pair (either
    side None) falls back to the model: an unmeasured path is never
    condemned by the measured one. Eligibility is never overridden —
    measurement can't make an unrepresentable row representable. Returns
    (backend, dense_s, grasp_s) modelled costs so callers can surface the
    decision.
    """
    if mode not in ("auto", "grasp"):
        raise ValueError(f"mode must be 'auto' or 'grasp', got {mode!r}")
    budget = grasp_max_nnz(capacity, block_size=block_size)
    width = max(budget, max_row_nnz)
    dense_s, grasp_s = agg_cost_model(capacity, feats, nnz_blocks=nnz_blocks,
                                      max_nnz=width, block_size=block_size)
    if max_row_nnz > budget:
        return "dense", dense_s, grasp_s
    if mode == "grasp":
        return "grasp", dense_s, grasp_s
    rank_dense, rank_grasp = dense_s, grasp_s
    if measured is not None and measured[0] is not None \
            and measured[1] is not None:
        rank_dense, rank_grasp = float(measured[0]), float(measured[1])
    return ("grasp" if rank_grasp < rank_dense else "dense"), dense_s, grasp_s


def bfs_reorder(adj: np.ndarray, num_nodes: int) -> np.ndarray:
    """BFS (Cuthill–McKee-like) node permutation to densify blocks.

    Beyond-paper GraSp enhancement (DESIGN.md §6): element-level ZVC is the
    paper's NPU mechanism, but the TPU's skip granularity is the 128×128 MXU
    block. Uniformly-scattered edges leave almost every block non-zero even
    at 99% element sparsity; ordering nodes by BFS over the graph clusters
    neighborhoods near the diagonal, concentrating edges into far fewer
    blocks (the block-skip fraction becomes meaningful). Returns a
    permutation `perm` such that A' = A[perm][:, perm].
    """
    n = num_nodes
    deg = (adj[:n, :n] > 0).sum(axis=1)
    visited = np.zeros(n, dtype=bool)
    order = []
    # start from lowest-degree nodes (classic CM heuristic)
    for seed in np.argsort(deg):
        if visited[seed]:
            continue
        queue = [int(seed)]
        visited[seed] = True
        while queue:
            v = queue.pop(0)
            order.append(v)
            nbrs = np.nonzero(adj[v, :n])[0]
            nbrs = nbrs[~visited[nbrs]]
            nbrs = nbrs[np.argsort(deg[nbrs])]
            visited[nbrs] = True
            queue.extend(int(x) for x in nbrs)
    perm = np.asarray(order + list(range(n, adj.shape[0])), dtype=np.int64)
    return perm


def apply_reorder(a: np.ndarray, perm: np.ndarray) -> np.ndarray:
    return a[perm][:, perm]


def sparsity_report(a: np.ndarray, *, block_size: int = MXU_TILE) -> dict:
    sp = to_block_sparse(a, block_size=block_size)
    return {
        "element_density": float(np.count_nonzero(a) / a.size),
        "block_density": sp.density,
        "dense_bytes": int(a.nbytes),
        "zvc_bytes": zvc_compressed_bytes(a),
        "block_compacted_bytes": int(sp.blocks.nbytes + sp.block_cols.nbytes
                                     + sp.counts.nbytes),
        "flop_skip_fraction": 1.0 - sp.density,
    }
