"""Graph containers and structure preprocessing (StaGr / PreG / SymG / NodePad).

The paper's Step-1 enablement: graphs are preprocessed on the *host*
(GraphSplit assigns control-heavy structure work to the CPU) into dense,
statically-shaped operands that the device consumes as plain matmuls.

NodePad: every graph is padded to a fixed *bucket* capacity (a multiple of
the MXU tile, 128) so the compiled program is reused across graph sizes —
the JAX analogue of the paper's "one precompiled blob" (jit cache hit).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import numpy as np

MXU_TILE = 128  # TPU systolic tile; NodePad buckets align to this.


@dataclasses.dataclass
class Graph:
    """A static graph snapshot. Host-side (numpy) until padded/uploaded."""

    edge_index: np.ndarray  # (2, E) int32, row 0 = src, row 1 = dst
    num_nodes: int
    features: np.ndarray  # (N, F) float32
    labels: Optional[np.ndarray] = None  # (N,) int32
    train_mask: Optional[np.ndarray] = None  # (N,) bool
    test_mask: Optional[np.ndarray] = None  # (N,) bool

    @property
    def num_edges(self) -> int:
        return int(self.edge_index.shape[1])


def required_capacity(num_nodes: int, slack: float = 0.0) -> int:
    """Single owner of the NodePad admission rule: nodes * (1 + slack).

    `slack` reserves headroom for dynamic node insertion (GrAd) without a
    recompile — the paper pads Cora 2708 -> 3000. Both the free-form
    `node_bucket` and the ladder's `bucket_for` round THIS number up, so the
    slack policy cannot drift between the two call sites.
    """
    return int(np.ceil(num_nodes * (1.0 + slack)))


def node_bucket(num_nodes: int, *, tile: int = MXU_TILE, slack: float = 0.0) -> int:
    """NodePad bucket: smallest tile multiple >= required_capacity.

    We pad to tile multiples so the same capacity also satisfies the Pallas
    kernel grids.
    """
    want = required_capacity(num_nodes, slack)
    return int(-(-want // tile) * tile)


def add_self_loops(edge_index: np.ndarray, num_nodes: int) -> np.ndarray:
    loops = np.arange(num_nodes, dtype=edge_index.dtype)
    return np.concatenate([edge_index, np.stack([loops, loops])], axis=1)


def dense_adjacency(edge_index: np.ndarray, capacity: int, *, self_loops: bool = True,
                    num_nodes: Optional[int] = None) -> np.ndarray:
    """(capacity, capacity) float32 0/1 adjacency; A[dst, src] = 1.

    Padded rows/cols stay zero — the paper's convention '0 = no edge' makes
    NodePad padding semantically inert.
    """
    a = np.zeros((capacity, capacity), dtype=np.float32)
    src, dst = edge_index
    a[dst, src] = 1.0
    if self_loops:
        n = capacity if num_nodes is None else num_nodes
        idx = np.arange(n)
        a[idx, idx] = 1.0
    return a


def gcn_norm_adjacency(edge_index: np.ndarray, num_nodes: int, capacity: int) -> np.ndarray:
    """PreG: Â = D^-1/2 (A + I) D^-1/2 precomputed on the host.

    The sqrt/recip ops (the NPU's slow-DSP work, TPU's non-MXU scalar work)
    happen exactly once, offline; the device only ever sees one dense matmul
    operand. Padded nodes have degree 0 -> their norm rows/cols are 0.
    """
    a = dense_adjacency(edge_index, capacity, self_loops=True, num_nodes=num_nodes)
    deg = a.sum(axis=1)
    with np.errstate(divide="ignore"):
        d_inv_sqrt = np.where(deg > 0, 1.0 / np.sqrt(np.maximum(deg, 1e-12)), 0.0)
    return (d_inv_sqrt[:, None] * a * d_inv_sqrt[None, :]).astype(np.float32)


def mean_adjacency(edge_index: np.ndarray, num_nodes: int, capacity: int,
                   *, self_loops: bool = True) -> np.ndarray:
    """Row-normalized adjacency (mean aggregation): D^-1 (A [+ I])."""
    a = dense_adjacency(edge_index, capacity, self_loops=self_loops, num_nodes=num_nodes)
    deg = a.sum(axis=1, keepdims=True)
    return (a / np.maximum(deg, 1.0)).astype(np.float32)


# ---------------------------------------------------------------------------
# SymG — triangular packing of the symmetric normalized adjacency.
# On TPU this is a storage/transfer optimization (checkpoint + host->device
# bytes ~halved); compute reassembles the dense matrix (see DESIGN.md §2).
# ---------------------------------------------------------------------------

def symg_pack(sym: np.ndarray) -> Tuple[np.ndarray, int]:
    """Pack a symmetric (N, N) matrix into its upper triangle (incl. diag)."""
    n = sym.shape[0]
    if not np.allclose(sym, sym.T, atol=1e-6):
        raise ValueError("symg_pack requires a symmetric matrix")
    iu = np.triu_indices(n)
    return sym[iu].astype(sym.dtype), n


def symg_unpack(packed: np.ndarray, n: int) -> np.ndarray:
    out = np.zeros((n, n), dtype=packed.dtype)
    iu = np.triu_indices(n)
    out[iu] = packed
    out = out + np.triu(out, k=1).T
    return out


# ---------------------------------------------------------------------------
# CacheG compact transfer format (DESIGN.md §7): a 0/1 adjacency crosses the
# host→device link as PACKED BITS, not float32 — 32× fewer bytes, 64× when
# the graph is undirected and SymG keeps only the upper triangle. The dense
# operands are re-derived on device (core.models.materialize_operands).
# ---------------------------------------------------------------------------


def triangular_nbits(n: int) -> int:
    """Bits in the upper triangle (incl. diagonal) of an (n, n) matrix."""
    return n * (n + 1) // 2


def is_symmetric_adjacency(adj: np.ndarray) -> bool:
    """True when the 0/1 adjacency is undirected (SymG-packable)."""
    return bool(np.array_equal(adj, adj.T))


def pack_adjacency_bits(adj: np.ndarray) -> np.ndarray:
    """Bit-pack a full 0/1 (cap, cap) adjacency row-major -> (cap²/8,) uint8."""
    return np.packbits((adj > 0).reshape(-1))


def symg_pack_adjacency_bits(adj: np.ndarray, *, check: bool = True
                             ) -> np.ndarray:
    """SymG + bit-pack: upper triangle (incl. diag) of an undirected 0/1
    adjacency -> (cap(cap+1)/2 / 8,) uint8. Raises on a directed matrix —
    callers fall back to `pack_adjacency_bits` (or the eager dense path).
    `check=False` skips the O(cap²) validation when the caller already ran
    `is_symmetric_adjacency` on this matrix.
    """
    if check and not is_symmetric_adjacency(adj):
        raise ValueError("symg_pack_adjacency_bits requires an undirected "
                         "(symmetric) adjacency")
    iu = np.triu_indices(adj.shape[0])
    return np.packbits((adj[iu] > 0))


def pad_features(x: np.ndarray, capacity: int) -> np.ndarray:
    """NodePad: zero-pad node features to the bucket capacity."""
    n, f = x.shape
    if n > capacity:
        raise ValueError(f"graph ({n} nodes) exceeds NodePad capacity {capacity}")
    if n == capacity:
        return x.astype(np.float32)
    out = np.zeros((capacity, f), dtype=np.float32)
    out[:n] = x
    return out


def pad_labels(y: np.ndarray, capacity: int, *, fill: int = -1) -> np.ndarray:
    out = np.full((capacity,), fill, dtype=np.int32)
    out[: y.shape[0]] = y
    return out


@dataclasses.dataclass
class PaddedGraph:
    """Device-ready NodePad'ded graph: every array statically (cap, ·)-shaped.

    `norm_adj` is the GrAd *input* form — passed as an argument, never baked
    into the trace — so edge updates re-run only host preprocessing, never
    XLA compilation (the paper's recompile-free dynamic-graph path).
    """

    capacity: int
    num_nodes: int
    features: np.ndarray      # (cap, F)
    norm_adj: Optional[np.ndarray]  # (cap, cap)  Â (PreG-normalized)
    adj: Optional[np.ndarray]       # (cap, cap)  raw 0/1 (no self loops)
    node_mask: np.ndarray     # (cap,) 1.0 for real nodes
    labels: Optional[np.ndarray] = None
    train_mask: Optional[np.ndarray] = None
    test_mask: Optional[np.ndarray] = None
    # the edge-list form (`pad_graph(dense=False)`): the (2, E) edges in
    # place of the two dense matrices, which are then None
    edge_index: Optional[np.ndarray] = None

    @property
    def dense(self) -> bool:
        return self.edge_index is None

    @functools.cached_property
    def layout(self) -> Tuple[int, int]:
        """(chunks, slots per chunk) of the edge-list form's tiled layout
        (`edge_layout`), worked out once per padded graph."""
        return edge_layout(self.edge_index[1], self.capacity)


def pad_graph(g: Graph, *, capacity: Optional[int] = None, slack: float = 0.0,
              norm: str = "gcn", dense: bool = True) -> PaddedGraph:
    """NodePad one graph. `dense=False` keeps the edge list and builds no
    (cap, cap) array: the form of a graph whose dense operands the device
    cannot hold (`edge_arrays` turns it into operands)."""
    cap = capacity if capacity is not None else node_bucket(g.num_nodes, slack=slack)
    if not dense:
        na = adj = None
    elif norm == "gcn":
        na = gcn_norm_adjacency(g.edge_index, g.num_nodes, cap)
    elif norm == "mean":
        na = mean_adjacency(g.edge_index, g.num_nodes, cap)
    else:
        raise ValueError(f"unknown norm {norm!r}")
    if dense:
        adj = dense_adjacency(g.edge_index, cap, self_loops=False)
    mask = np.zeros((cap,), dtype=np.float32)
    mask[: g.num_nodes] = 1.0

    def _pad_bool(m):
        if m is None:
            return None
        out = np.zeros((cap,), dtype=bool)
        out[: g.num_nodes] = m
        return out

    return PaddedGraph(
        capacity=cap,
        num_nodes=g.num_nodes,
        features=pad_features(g.features, cap),
        norm_adj=na,
        adj=adj,
        node_mask=mask,
        labels=None if g.labels is None else pad_labels(g.labels, cap),
        train_mask=_pad_bool(g.train_mask),
        test_mask=_pad_bool(g.test_mask),
        edge_index=None if dense else np.asarray(g.edge_index, np.int32),
    )


# ---------------------------------------------------------------------------
# Edge-list operands: a graph whose dense (cap, cap) operands the device
# cannot hold aggregates from its edge list, laid out by destination tile
# (DESIGN.md §16) and padded to a chunk rung, so that graphs of one bucket
# and similar edge counts replay one compiled plan.
# ---------------------------------------------------------------------------

EDGE_TILE_ROWS = MXU_TILE  # destination rows summed by one output tile


def edge_layout(dst: np.ndarray, capacity: int) -> Tuple[int, int]:
    """(chunks, slots per chunk) of the tiled edge layout of the edges
    whose destinations are `dst`: the shapes its operands take."""
    _, per_tile, c = _chunks_per_tile(dst, capacity)
    return int(per_tile.sum()), c


def _chunks_per_tile(dst: np.ndarray, capacity: int
                     ) -> Tuple[np.ndarray, np.ndarray, int]:
    """(in-edges per tile, chunks per tile, chunk size C) of the tiled
    edge layout; the last tile holds the spare row `capacity`, one past
    the bucket.

    C is the largest of 512, 256 and 128 slots that is at most half a
    tile's mean in-edge count, so a tile wastes well under half its slots
    to padding. Every tile takes at least one chunk, so a tile without
    in-edges is still written; the spare tile takes the padding chunks up
    to the rung: a multiple of 1/32 of the power of two at or below the
    chunk count (at least 8), so padding wastes at most 1/32."""
    counts = np.bincount(np.asarray(dst) // EDGE_TILE_ROWS,
                         minlength=capacity // EDGE_TILE_ROWS + 1)
    half_mean = dst.shape[0] / counts.shape[0] / 2
    c = max([s for s in (512, 256) if s <= half_mean], default=MXU_TILE)
    per_tile = np.maximum(1, -(-counts // c))
    k = int(per_tile.sum())
    step = max(1 << max(k.bit_length() - 6, 0), 8)
    per_tile[-1] += -(-k // step) * step - k
    return counts, per_tile, c


def edge_arrays(edge_index: np.ndarray, num_nodes: int, capacity: int
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(src, slot_rows, chunk_tile, inv_deg) of one graph's edge-list
    operands, in the tiled layout of `edge_layout` (K chunks of C slots).

    The edges, sorted by (dst, src), fill their destination tile's chunks
    in order. `src` (K * C,) int32 is each slot's source row, 0 on
    padding; `slot_rows` (K, 1, C) int32 its destination row within the
    tile, -1 on padding; `chunk_tile` (K,) int32 each chunk's tile,
    non-decreasing. `inv_deg` (cap,) float32 is 1 / in-degree, 0 for nodes
    with no in-edges and for padding rows.
    """
    ei = np.asarray(edge_index)
    if ei.size and (ei.min() < 0 or ei.max() >= num_nodes):
        raise ValueError(f"edge_index references a node outside "
                         f"[0, {num_nodes})")
    if num_nodes > capacity:
        raise ValueError(f"{num_nodes} nodes exceed capacity {capacity}")
    src, dst = ei[0].astype(np.int32), ei[1].astype(np.int32)
    order = np.lexsort((src, dst))
    src, dst = src[order], dst[order]
    counts, per_tile, c = _chunks_per_tile(dst, capacity)
    k = int(per_tile.sum())
    chunk_tile = np.repeat(np.arange(per_tile.shape[0], dtype=np.int32),
                           per_tile)
    tile = dst // EDGE_TILE_ROWS
    first_slot = (np.cumsum(per_tile) - per_tile) * c
    first_edge = np.cumsum(counts) - counts
    slot = first_slot[tile] + np.arange(dst.shape[0]) - first_edge[tile]
    out_src = np.zeros((k * c,), np.int32)
    out_rows = np.full((k * c,), -1, np.int32)
    out_src[slot] = src
    out_rows[slot] = dst - tile * EDGE_TILE_ROWS
    deg = np.bincount(dst, minlength=capacity).astype(np.float32)
    inv_deg = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0
                       ).astype(np.float32)
    return out_src, out_rows.reshape(k, 1, c), chunk_tile, inv_deg


# ---------------------------------------------------------------------------
# BucketLadder — the multi-graph NodePad policy (DESIGN.md §3).
# One compiled blob per (model, bucket); a graph joins the smallest bucket
# that holds it, and a growing graph re-buckets (the one legitimate
# recompile) only when it outgrows its current capacity.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BucketLadder:
    """A sorted set of NodePad capacities shared by many graphs.

    `slack` reserves growth headroom at admission: a graph is placed in the
    smallest bucket >= num_nodes * (1 + slack), so GrAd updates have room
    before the re-bucket policy has to move it up the ladder.
    """

    buckets: Tuple[int, ...] = (256, 512, 1024, 2048)
    slack: float = 0.0

    def __post_init__(self):
        bs = tuple(sorted(int(b) for b in self.buckets))
        if not bs:
            raise ValueError("BucketLadder needs at least one bucket")
        for b in bs:
            if b <= 0 or b % MXU_TILE:
                raise ValueError(
                    f"bucket {b} is not a positive multiple of the MXU tile "
                    f"{MXU_TILE} (NodePad buckets must tile-align)")
        object.__setattr__(self, "buckets", bs)

    def bucket_for(self, num_nodes: int) -> int:
        """Smallest bucket holding num_nodes (+ admission slack)."""
        want = required_capacity(num_nodes, self.slack)
        for b in self.buckets:
            if want <= b:
                return b
        # slack is headroom, not a hard requirement: a graph that fits the
        # top bucket without slack is still admissible there.
        if num_nodes <= self.buckets[-1]:
            return self.buckets[-1]
        raise ValueError(
            f"graph with {num_nodes} nodes exceeds the largest bucket "
            f"{self.buckets[-1]}")

    def pad(self, g: Graph, *, norm: str = "gcn") -> PaddedGraph:
        return pad_graph(g, capacity=self.bucket_for(g.num_nodes), norm=norm)

    def grow(self, pg: PaddedGraph, edge_index: np.ndarray, num_nodes: int,
             features: np.ndarray, *, norm: str = "gcn"
             ) -> Tuple[PaddedGraph, bool]:
        """GrAd update with re-bucket policy.

        Returns (updated graph, rebucketed). While the graph fits its
        current capacity this is a pure value update (zero recompiles); once
        it outgrows the bucket, the graph is re-padded into the next rung —
        the caller pays exactly one new (model, bucket) compile, which the
        serving engine counts as a rebucket event.
        """
        if num_nodes <= pg.capacity:
            upd = update_edges(pg, edge_index, num_nodes, norm=norm)
            upd = dataclasses.replace(
                upd, features=pad_features(features, pg.capacity))
            return upd, False
        # Re-bucket: carry the supervision arrays across the move. Nodes
        # beyond the old capacity are new and unlabeled (fill -1 / False) —
        # silently dropping labels/train_mask/test_mask here would strand an
        # attached graph's evaluation state the first time it climbs.
        old = pg.capacity

        def _grown(arr, fill, dtype):
            if arr is None:
                return None
            out = np.full((num_nodes,), fill, dtype=dtype)
            out[:old] = arr[:old]
            return out

        fresh = Graph(edge_index=edge_index, num_nodes=num_nodes,
                      features=features,
                      labels=_grown(pg.labels, -1, np.int32),
                      train_mask=_grown(pg.train_mask, False, bool),
                      test_mask=_grown(pg.test_mask, False, bool))
        cap = self.bucket_for(num_nodes)
        return pad_graph(fresh, capacity=cap, norm=norm), True


@dataclasses.dataclass
class EdgeDelta:
    """Host product of one EFFECTIVE GrAd edge delta (DESIGN.md §13).

    `apply_edge_delta` patches the raw adjacency and Â in O(|touched|·cap)
    instead of the O(cap²) full rebuild, with the renormalized rows/cols
    computed by the exact expression (and association order) of
    `gcn_norm_adjacency` — so `norm_adj` here is bit-identical to a full
    rebuild of the patched structure, and the flip/touched/dis arrays are
    everything the device-side patcher (`core.models.patch_operands`)
    needs to bring a cached operand entry to the same bits.
    """
    adj: np.ndarray                # (cap, cap) patched raw 0/1 adjacency
    norm_adj: np.ndarray           # (cap, cap) patched Â, rebuild-exact
    dis: np.ndarray                # (cap,) patched D^-1/2 (float32)
    flip_i: np.ndarray             # (P,) int32 canonical flip endpoints
    flip_j: np.ndarray             # (P,) int32   (i < j; device scatters
    flip_v: np.ndarray             # (P,) float32  both orientations)
    touched: np.ndarray            # (T,) int32 sorted nodes with changed
    #                                rows/cols (the flip endpoints)

    def boundary_rows(self, assignment: np.ndarray,
                      num_nodes: int) -> np.ndarray:
        """Touched nodes whose rows cross a shard boundary (DESIGN.md §15).

        Against a shard `assignment` (GraphShards.assignment, original
        node ids), returns the sorted subset of `touched` that has at
        least one neighbor on ANOTHER shard in the PATCHED adjacency —
        the only rows whose remote copies a sharded halo re-exchange must
        refresh. A delta confined to one shard's interior returns an
        empty set: nothing crosses the wire.
        """
        t = self.touched[self.touched < num_nodes]
        if t.size == 0:
            return t.astype(np.int32)
        sub = self.adj[t][:, :num_nodes] != 0
        diff = assignment[None, :num_nodes] != assignment[t][:, None]
        return t[(sub & diff).any(axis=1)].astype(np.int32)


def apply_edge_delta(adj: np.ndarray, norm_adj: np.ndarray, num_nodes: int,
                     add_edges, remove_edges) -> Optional[EdgeDelta]:
    """GrAd incremental structure update on the host (DESIGN.md §13).

    `add_edges` / `remove_edges` are (k, 2) node-pair arrays (any order,
    both orientations equivalent — the graph is undirected). Ineffective
    flips (adding a present edge, removing an absent one) and self-loop
    pairs (the GCN/GAT diagonal is forced, so they cannot change any
    operand) are skipped; returns None when NOTHING effective remains, so
    the caller can skip the version bump entirely. Out-of-range nodes and
    a pair listed on both sides raise — those are caller bugs, not deltas.
    """
    def _pairs(edges) -> np.ndarray:
        e = np.asarray(edges if edges is not None else [],
                       dtype=np.int64).reshape(-1, 2)
        if e.size and (e.min() < 0 or e.max() >= num_nodes):
            raise ValueError(
                f"edge delta references node outside [0, {num_nodes}) — "
                "node-set changes take the full update() path")
        e = e[e[:, 0] != e[:, 1]]
        if not len(e):
            return e.reshape(0, 2)
        lo = np.minimum(e[:, 0], e[:, 1])
        hi = np.maximum(e[:, 0], e[:, 1])
        return np.unique(np.stack([lo, hi], axis=1), axis=0)

    adds, removes = _pairs(add_edges), _pairs(remove_edges)
    if len(adds) and len(removes):
        both = (set(map(tuple, adds.tolist()))
                & set(map(tuple, removes.tolist())))
        if both:
            raise ValueError(f"edge pair(s) {sorted(both)} listed as both "
                             "add and remove")
    if len(adds):
        adds = adds[adj[adds[:, 0], adds[:, 1]] == 0]
    if len(removes):
        removes = removes[adj[removes[:, 0], removes[:, 1]] != 0]
    if not len(adds) and not len(removes):
        return None
    flips = np.concatenate([adds, removes], axis=0)
    vals = np.concatenate([np.ones(len(adds), np.float32),
                           np.zeros(len(removes), np.float32)])
    new_adj = adj.copy()
    new_adj[flips[:, 0], flips[:, 1]] = vals
    new_adj[flips[:, 1], flips[:, 0]] = vals
    touched = np.unique(flips)

    # renorm the touched rows/cols with gcn_norm_adjacency's EXACT
    # expression — same forced diagonal, same 1e-12 clamp, same
    # left-associated products — so patched entries match a rebuild's bits
    awl = new_adj.copy()
    idx = np.arange(num_nodes)
    awl[idx, idx] = 1.0
    deg = awl.sum(axis=1)
    with np.errstate(divide="ignore"):
        dis = np.where(deg > 0, 1.0 / np.sqrt(np.maximum(deg, 1e-12)), 0.0)
    na = norm_adj.copy()
    na[touched, :] = dis[touched][:, None] * awl[touched, :] * dis[None, :]
    na[:, touched] = dis[:, None] * awl[:, touched] * dis[touched][None, :]
    return EdgeDelta(adj=new_adj, norm_adj=na, dis=dis.astype(np.float32),
                     flip_i=flips[:, 0].astype(np.int32),
                     flip_j=flips[:, 1].astype(np.int32),
                     flip_v=vals,
                     touched=touched.astype(np.int32))


def edge_list_delta(edge_index: np.ndarray, num_nodes: int, add_edges,
                    remove_edges) -> Optional[np.ndarray]:
    """An undirected edge delta applied to an edge list (the edge-list
    form's counterpart of `apply_edge_delta`): each (k, 2) pair is added or
    removed in both directions, self-loop pairs skipped. Returns the new
    (2, E) edges, or None when nothing effective changed."""
    def _both(edges) -> np.ndarray:
        e = np.asarray(edges if edges is not None else [],
                       dtype=np.int64).reshape(-1, 2)
        if e.size and (e.min() < 0 or e.max() >= num_nodes):
            raise ValueError(
                f"edge delta references node outside [0, {num_nodes}) — "
                "node-set changes take the full update() path")
        e = e[e[:, 0] != e[:, 1]]
        return np.concatenate([e, e[:, ::-1]]) * [num_nodes, 1]

    cur = np.asarray(edge_index, np.int64)
    keys = cur[0] * num_nodes + cur[1]
    add = np.setdiff1d(_both(add_edges).sum(axis=1), keys)
    keep = ~np.isin(keys, _both(remove_edges).sum(axis=1))
    if not add.size and keep.all():
        return None
    new = np.concatenate([keys[keep], add])
    return np.stack([new // num_nodes, new % num_nodes]).astype(np.int32)


def edge_index_from_adjacency(adj: np.ndarray, num_nodes: int) -> np.ndarray:
    """Recover the (2, E) edge list from a dense adjacency (A[dst, src]=1)
    — the full-rebuild fallback's input when only the patched adjacency is
    on hand."""
    dst, src = np.nonzero(adj[:num_nodes, :num_nodes])
    return np.stack([src, dst]).astype(np.int32)


def update_edges(pg: PaddedGraph, edge_index: np.ndarray, num_nodes: int,
                 *, norm: str = "gcn") -> PaddedGraph:
    """GrAd: rebuild only the runtime mask inputs for an evolved graph.

    No recompilation: shapes are unchanged (same capacity), only array
    *values* change. Raises if the graph outgrew its bucket (the caller then
    re-buckets — the one legitimate recompile).
    """
    if num_nodes > pg.capacity:
        raise ValueError(
            f"graph grew to {num_nodes} nodes > capacity {pg.capacity}; re-bucket")
    if norm == "gcn":
        na = gcn_norm_adjacency(edge_index, num_nodes, pg.capacity)
    else:
        na = mean_adjacency(edge_index, num_nodes, pg.capacity)
    mask = np.zeros((pg.capacity,), dtype=np.float32)
    mask[:num_nodes] = 1.0
    return dataclasses.replace(
        pg, num_nodes=num_nodes, norm_adj=na,
        adj=dense_adjacency(edge_index, pg.capacity, self_loops=False),
        node_mask=mask)
