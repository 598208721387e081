"""Paper models: 2-layer GCN / GAT / GraphSAGE for node classification.

Matches the paper's Section V setup: hidden width 64 (GAT: 8 heads x 8),
trained with Adam-style optimization, evaluated top-1 on a held-out mask.
Both execution paths (baseline edge-list vs GraNNite dense) share the SAME
parameters, so the benchmark harness compares *implementations*, never
different models.

Module contracts (what the serving layer relies on):

  * Pytree registration — `GranniteOperands` and `CompactOperands` are
    registered pytrees: runtime leaves cross jit/vmap boundaries as
    arguments, and `CompactOperands`' aux data (capacity, fields,
    triangular) is the ONLY static structure, so one jitted materializer
    specializes exactly once per (bucket, operand-fieldset).
  * Zero-recompile accounting — `ExecutionPlan.trace_count` and
    `OperandMaterializer.trace_count` increment on actual jit traces (a
    python side effect inside the traced fn), never on cache-key inserts.
    `GraphServe.compiled_blobs` sums them; `assert_warm()` is therefore a
    claim about the COMPILER's behavior, not our bookkeeping.
  * Plan identity — `PlanKey = (cfg, capacity, batch, techniques, backend)`.
    Params and QuantGr calibrations are runtime arguments, never closed
    over, so models sharing a key legitimately share one compiled blob; a
    quality tier (DESIGN.md §8) is fully identified by its `Techniques`,
    and the aggregation backend (DESIGN.md §10: `dense` matmul vs `grasp`
    block-sparse `bitmap_spmm`) is the key's orthogonal last dimension — a
    grasp plan's operands always carry a block structure, a dense plan's
    never do, so the trace structure per key is fixed.
  * Calibration shape invariance — `calibrate_tier` output contains only
    model-shaped arrays (per-layer int8 weights + scalar scales); its
    pytree structure is a function of `GNNConfig` alone, never of the
    calibration graph, so a plan warmed against a placeholder calibration
    replays warm against every real one.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import effop, layers, masks
from .graph import PaddedGraph
from .layers import Techniques
from .quant import QuantizedLinear, quantize_linear
from .sparsity import (BlockSparse, block_counts, compact_block_sparse,
                       stack_block_sparse, to_block_sparse)


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    kind: str                  # "gcn" | "gat" | "sage"
    in_feats: int
    hidden: int = 64
    num_classes: int = 7
    heads: int = 8             # GAT only (hidden per-head = hidden // heads)
    aggregator: str = "mean"   # SAGE only: "mean" | "max"
    # SAGE: an int samples up to that many in-neighbours and adds the node
    # itself (the paper's 10); None takes every in-neighbour and no self
    # loop, as OGB's full-batch SAGEConv inference does
    max_neighbors: Optional[int] = 10
    num_layers: int = 2        # SAGE only may differ from 2
    batch_norm: bool = False   # SAGE only: eval BatchNorm before each ReLU

    def __post_init__(self):
        if self.num_layers < 1:
            raise ValueError(f"num_layers must be >= 1, got {self.num_layers}")
        if self.kind != "sage" and (self.num_layers != 2 or self.batch_norm):
            raise ValueError(f"{self.kind} models are two layers without "
                             "BatchNorm; num_layers and batch_norm are "
                             "SAGE's")


def layer_widths(cfg: GNNConfig) -> Tuple[int, ...]:
    """Input width of each layer, then the output width of the last one."""
    return (cfg.in_feats,) + (cfg.hidden,) * (cfg.num_layers - 1) + (
        cfg.num_classes,)


def has_edge_form(cfg: GNNConfig) -> bool:
    """Whether the model can aggregate from the edge-list operands
    (`EdgeOperands`): SAGE-mean over every in-neighbour."""
    return (cfg.kind == "sage" and cfg.aggregator == "mean"
            and cfg.max_neighbors is None)


def init_params(key, cfg: GNNConfig) -> Dict:
    keys = jax.random.split(key, cfg.num_layers)
    if cfg.kind == "gcn":
        return {"l1": layers.gcn_init(keys[0], cfg.in_feats, cfg.hidden),
                "l2": layers.gcn_init(keys[1], cfg.hidden, cfg.num_classes)}
    if cfg.kind == "gat":
        per_head = cfg.hidden // cfg.heads
        return {"l1": layers.gat_init(keys[0], cfg.in_feats, per_head,
                                      cfg.heads),
                "l2": layers.gat_init(keys[1], cfg.heads * per_head,
                                      cfg.num_classes, 1)}
    if cfg.kind == "sage":
        w = layer_widths(cfg)
        params = {f"l{i + 1}": layers.sage_init(k, w[i], w[i + 1],
                                                aggregator=cfg.aggregator)
                  for i, k in enumerate(keys)}
        if cfg.batch_norm:
            params.update({f"bn{i}": layers.batch_norm_init(w[i])
                           for i in range(1, cfg.num_layers)})
        return params
    raise ValueError(cfg.kind)


def _sage_between(params: Dict, cfg: GNNConfig, i: int, h: jnp.ndarray
                  ) -> jnp.ndarray:
    """What follows SAGE layer `i` (1-based) when another comes after it:
    eval BatchNorm if the model has it, then ReLU."""
    if cfg.batch_norm:
        h = layers.batch_norm_eval(params[f"bn{i}"], h)
    return jax.nn.relu(h)


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def forward_baseline(params: Dict, cfg: GNNConfig, x: jnp.ndarray,
                     edge_index: jnp.ndarray, num_nodes: int) -> jnp.ndarray:
    if cfg.kind == "gcn":
        h = jax.nn.relu(layers.gcn_baseline(params["l1"], x, edge_index, num_nodes))
        return layers.gcn_baseline(params["l2"], h, edge_index, num_nodes)
    if cfg.kind == "gat":
        per_head = cfg.hidden // cfg.heads
        h = jax.nn.elu(layers.gat_baseline(params["l1"], x, edge_index, num_nodes,
                                           heads=cfg.heads, out_feats=per_head))
        return layers.gat_baseline(params["l2"], h, edge_index, num_nodes,
                                   heads=1, out_feats=cfg.num_classes)
    if cfg.kind == "sage":
        # the plain reference of every SAGE config: mean over the edge
        # list's in-neighbours, L layers, BatchNorm as its own step
        h = x
        for i in range(1, cfg.num_layers + 1):
            h = layers.sage_baseline(params[f"l{i}"], h, edge_index,
                                     num_nodes, aggregator=cfg.aggregator)
            if i < cfg.num_layers:
                h = _sage_between(params, cfg, i, h)
        return h
    raise ValueError(cfg.kind)


@dataclasses.dataclass
class GranniteOperands:
    """Host-precomputed (GraphSplit/PreG/StaGr) dense operands.

    For GrAd these are *arguments*; for StaGr-static callers may close over
    them. Building this object is the 'CPU side' of GraphSplit. Registered as
    a jax pytree so a whole operand set crosses jit/vmap boundaries as one
    runtime input (the plan/executor split, DESIGN.md §2).
    """
    norm_adj: jnp.ndarray                 # (cap, cap) PreG-normalized
    mask_mult: jnp.ndarray                # GAT exact multiplicative mask
    bias_add: jnp.ndarray                 # GrAx1 additive mask
    sample_mask: jnp.ndarray              # SAGE sampled 0/1 adjacency
    mean_mask: jnp.ndarray                # row-normalized sample mask
    block_sparse: Optional[BlockSparse] = None  # GraSp compacted Â
    quant: Optional[Dict[str, QuantizedLinear]] = None  # QuantGr layers


jax.tree_util.register_pytree_node(
    GranniteOperands,
    lambda o: ((o.norm_adj, o.mask_mult, o.bias_add, o.sample_mask,
                o.mean_mask, o.block_sparse, o.quant), None),
    lambda _, c: GranniteOperands(*c))


# Which operand fields each model kind actually reads; the rest may be
# placeholder zeros when operands are built per-request (lean=True).
OPERAND_FIELDS = {
    "gcn": ("norm_adj",),
    "gat": ("mask_mult", "bias_add"),
    "sage": ("sample_mask", "mean_mask"),
}


def build_operands(pg: PaddedGraph, cfg: GNNConfig, *, grasp: bool = False,
                   rng: Optional[np.random.Generator] = None,
                   lean: bool = False) -> GranniteOperands:
    """Host side of GraphSplit: all dense operands for one padded graph.

    lean=True builds only the fields `cfg.kind` consumes (OPERAND_FIELDS) and
    fills the rest with (1, 1) placeholders — the serving engine builds
    operands per request, where the unused (cap, cap) masks would dominate
    host time and memory. Placeholders are safe through jit/vmap because the
    forward for that kind never touches them.
    """
    fields = OPERAND_FIELDS[cfg.kind] if lean else (
        "norm_adj", "mask_mult", "bias_add", "sample_mask", "mean_mask")
    hole = jnp.zeros((1, 1), jnp.float32)
    vals = {k: hole for k in ("norm_adj", "mask_mult", "bias_add",
                              "sample_mask", "mean_mask")}
    if "norm_adj" in fields:
        vals["norm_adj"] = jnp.asarray(pg.norm_adj)
    if "mask_mult" in fields or "bias_add" in fields:
        awl = masks.adj_with_self_loops(pg.adj, pg.num_nodes)
        vals["mask_mult"] = jnp.asarray(masks.attention_bias_multiplicative(awl))
        vals["bias_add"] = jnp.asarray(masks.attention_bias_additive(awl))
    if "sample_mask" in fields or "mean_mask" in fields:
        sample = masks.sage_sample_adjacency(
            pg.adj, pg.num_nodes, max_neighbors=cfg.max_neighbors, rng=rng)
        vals["sample_mask"] = jnp.asarray(sample)
        vals["mean_mask"] = jnp.asarray(masks.mean_from_mask(sample))
    return GranniteOperands(
        block_sparse=to_block_sparse(pg.norm_adj) if grasp else None, **vals)


def check_batchable(ops: Sequence[GranniteOperands]) -> bool:
    """Refuse operand sets that cannot share one vmapped dispatch; return
    whether they carry GraSp block structures (all of them, or none)."""
    if any(o.quant is not None for o in ops):
        raise ValueError(
            "per-graph offline QuantGr operands (ops.quant, built by "
            "calibrate_quant) cannot be batched — their QuantizedAgg bakes "
            "one graph's Â; serve quantized tiers through the model-level "
            "calibrate_tier path instead (DESIGN.md §8)")
    with_blocks = [o.block_sparse is not None for o in ops]
    if any(with_blocks) and not all(with_blocks):
        raise ValueError(
            "cannot batch a mix of GraSp and dense operand sets — resolve "
            "one aggregation backend per batch (DESIGN.md §10)")
    return all(with_blocks)


def stack_operands(ops: Sequence[GranniteOperands]) -> GranniteOperands:
    """Stack per-graph operands into one batched (B, ...) operand set.

    Batched plans execute vmapped, so every field gains a leading batch dim
    — including GraSp block structures: same-bucket structures padded to
    one `grasp_max_nnz` budget stack via `stack_block_sparse` (all-or-none
    per batch; a grasp plan's operands always carry one, a dense plan's
    never do — DESIGN.md §10). Only the per-graph OFFLINE QuantGr form
    (`ops.quant`, from `calibrate_quant`) has no batched shape — it bakes
    ONE graph's Â into its QuantizedAgg, so the engine runs it
    single-graph. Serving-tier QuantGr does not hit this limit: its
    calibration is model-level (`calibrate_tier`) and rides the plan's
    broadcast `quant` argument, never the operands (DESIGN.md §8).
    """
    with_blocks = check_batchable(ops)
    return GranniteOperands(
        norm_adj=jnp.stack([o.norm_adj for o in ops]),
        mask_mult=jnp.stack([o.mask_mult for o in ops]),
        bias_add=jnp.stack([o.bias_add for o in ops]),
        sample_mask=jnp.stack([o.sample_mask for o in ops]),
        mean_mask=jnp.stack([o.mean_mask for o in ops]),
        block_sparse=(stack_block_sparse([o.block_sparse for o in ops])
                      if with_blocks else None),
    )


# ---------------------------------------------------------------------------
# CacheG operand pipeline (DESIGN.md §7)
#
# The eager path above builds the O(cap²) float32 operands on the HOST and
# ships them over the host→device link on every request. CacheG replaces
# that with (1) a compact transfer form — one bit-packed 0/1 adjacency plus
# a degree vector (`CompactOperands`), SymG-triangular when the graph is
# undirected — and (2) a jitted device-side materializer that re-derives the
# dense operands with VPU ops, so the big arrays are *created* in device
# memory and never cross the link. GraphServe then caches the materialized
# result per (graph_id, structure_version).
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CompactOperands:
    """Compact host→device transfer form of one graph's operand structure.

    `packed` is the bit-packed 0/1 adjacency: SymG upper triangle for
    undirected GCN/GAT graphs (`triangular=True`), the full row-major matrix
    otherwise — for SAGE it packs the host-*sampled* adjacency (sampling
    stays on the host for seeded determinism; it is O(cap²) bit work, not
    float32 mask construction). `degree` carries the row sums the
    materializer divides by (deg(A+I) for GCN, sample row sums for SAGE), so
    host and device paths normalize with bit-identical denominators.

    Registered as a pytree: (packed, degree, num_nodes) are runtime leaves;
    (capacity, fields, triangular) are static structure, so one jitted
    materializer specializes exactly once per (bucket, operand-fieldset).
    """
    packed: jnp.ndarray      # (nbits/8,) uint8
    degree: jnp.ndarray      # (cap,) float32
    num_nodes: jnp.ndarray   # () int32
    capacity: int
    fields: Tuple[str, ...]  # which GranniteOperands fields to materialize
    triangular: bool         # SymG triangular packing vs full row-major

    @property
    def nbytes(self) -> int:
        """Bytes this form moves host→device (the operand_bytes_h2d unit)."""
        return int(self.packed.nbytes + self.degree.nbytes
                   + self.num_nodes.nbytes)


jax.tree_util.register_pytree_node(
    CompactOperands,
    lambda c: ((c.packed, c.degree, c.num_nodes),
               (c.capacity, c.fields, c.triangular)),
    lambda aux, ch: CompactOperands(*ch, *aux))


def compact_operands(pg: PaddedGraph, cfg: GNNConfig, *,
                     rng: Optional[np.random.Generator] = None,
                     check_symmetry: bool = True) -> CompactOperands:
    """Host side of CacheG: pack one graph's structure into transfer form.

    GCN/GAT pack the raw adjacency (SymG triangular — requires an undirected
    graph; callers check `is_symmetric_adjacency` and fall back to the eager
    dense path for directed ones, see GraphServe). SAGE samples on the host
    (same seeded rng as `build_operands`) and packs the sampled mask, which
    is direction-biased, hence always full row-major.

    `check_symmetry=False` skips the O(cap²) symmetry re-validation for
    callers that already ran `is_symmetric_adjacency` on this adjacency
    (the serving hot path checks once to pick compact-vs-fallback).
    """
    from .graph import pack_adjacency_bits, symg_pack_adjacency_bits
    fields = OPERAND_FIELDS[cfg.kind]
    cap = pg.capacity
    if cfg.kind == "sage":
        sample = masks.sage_sample_adjacency(
            pg.adj, pg.num_nodes, max_neighbors=cfg.max_neighbors, rng=rng)
        packed = pack_adjacency_bits(sample)
        degree = sample.sum(axis=1).astype(np.float32)
        triangular = False
    else:
        packed = symg_pack_adjacency_bits(pg.adj, check=check_symmetry)
        if "norm_adj" in fields:
            # degree of A+I via the idempotent self-loop set (NOT adj.sum+1,
            # which would double-count an explicit (i, i) edge in edge_index)
            degree = masks.adj_with_self_loops(pg.adj, pg.num_nodes).sum(
                axis=1).astype(np.float32)
        else:
            # GAT reads only the masks; the degree leaf must still exist
            # (stable pytree structure) but need not be computed
            degree = np.zeros((cap,), np.float32)
        triangular = True
    return CompactOperands(
        packed=jnp.asarray(packed),
        degree=jnp.asarray(degree),
        num_nodes=jnp.asarray(pg.num_nodes, jnp.int32),
        capacity=cap, fields=fields, triangular=triangular)


def _unpack_adjacency(co: CompactOperands) -> jnp.ndarray:
    """Device-side unpack: packed bits -> dense (cap, cap) float32 0/1.

    The triangular path gathers each (i, j >= i) entry from its linear
    upper-triangle offset computed with iota arithmetic (no O(cap²) index
    constants baked into the trace) and symmetrizes with a max against the
    transpose — exact for 0/1 matrices.
    """
    from .graph import triangular_nbits
    cap = co.capacity
    if co.triangular:
        nbits = triangular_nbits(cap)
        bits = jnp.unpackbits(co.packed, count=nbits)
        i = jnp.arange(cap, dtype=jnp.int32)[:, None]
        j = jnp.arange(cap, dtype=jnp.int32)[None, :]
        # row i's triangle starts at i*cap - i(i-1)/2; entry (i, j) sits j-i in
        lin = i * (2 * cap - i + 1) // 2 + (j - i)
        upper = jnp.where(j >= i, bits[jnp.clip(lin, 0, nbits - 1)], 0)
        return jnp.maximum(upper, upper.T).astype(jnp.float32)
    bits = jnp.unpackbits(co.packed, count=cap * cap)
    return bits.reshape(cap, cap).astype(jnp.float32)


def materialize_operands(co: CompactOperands) -> GranniteOperands:
    """Device side of CacheG: expand the compact form into the dense operand
    set `co.fields` names, leaving the rest as (1, 1) placeholders exactly
    like `build_operands(lean=True)`. Pure jnp — jit it once per bucket
    (GraphServe warms it in `warmup()`), after which every structure miss is
    one tiny upload plus O(cap²) VPU work entirely in device memory.
    """
    cap = co.capacity
    adj = _unpack_adjacency(co)
    hole = jnp.zeros((1, 1), jnp.float32)
    vals = {k: hole for k in ("norm_adj", "mask_mult", "bias_add",
                              "sample_mask", "mean_mask")}
    if "sample_mask" in co.fields or "mean_mask" in co.fields:
        # packed IS the sampled adjacency (self loops already included)
        vals["sample_mask"] = adj
        vals["mean_mask"] = adj / jnp.maximum(co.degree[:, None], 1.0)
    else:
        i = jnp.arange(cap, dtype=jnp.int32)
        real = (i < co.num_nodes)
        awl = jnp.where((i[:, None] == i[None, :]) & real[:, None], 1.0, adj)
        if "norm_adj" in co.fields:
            dis = jnp.where(co.degree > 0,
                            1.0 / jnp.sqrt(jnp.maximum(co.degree, 1e-12)), 0.0)
            vals["norm_adj"] = dis[:, None] * awl * dis[None, :]
        if "mask_mult" in co.fields or "bias_add" in co.fields:
            vals["mask_mult"] = (awl > 0).astype(jnp.float32)
            vals["bias_add"] = jnp.where(awl > 0, 0.0, masks.NEG_INF
                                         ).astype(jnp.float32)
    return GranniteOperands(**vals)


@dataclasses.dataclass
class OperandMaterializer:
    """The jitted CacheG expander, with the same trace accounting as
    ExecutionPlan: jit specializes on the CompactOperands *structure*
    (capacity, fields, triangular), so `trace_count` is the number of
    (bucket, fieldset) combinations compiled — GraphServe warms them all in
    `warmup()` and folds the count into the zero-recompile contract.
    """
    fn: Callable = dataclasses.field(default=None, repr=False)
    trace_count: int = 0

    def __call__(self, co: CompactOperands) -> GranniteOperands:
        return self.fn(co)


def build_materializer() -> OperandMaterializer:
    mat = OperandMaterializer()

    def _materialize(co):
        mat.trace_count += 1              # python side effect: traces only
        return materialize_operands(co)

    mat.fn = jax.jit(_materialize)
    return mat


@dataclasses.dataclass
class HostOperands:
    """Product of the pipeline's HOST stage for one graph (DESIGN.md §9).

    The GraphSplit host work — padding aside (the caller pads), this is
    CompactOperands bit-packing or the eager dense build — is separable
    from the DEVICE work (materialization + the plan dispatch) so a
    scheduler can run the two on different threads: `prepare_host_operands`
    is pure numpy/bit work a host worker executes, `realize_operands` turns
    the result into the device-resident `GranniteOperands` the plan
    consumes. Exactly one of `compact` / `eager` is set; `nbytes` is the
    host→device operand traffic this form moves (the `operand_bytes_h2d`
    unit), and `fallback` marks a directed GCN/GAT graph that could not
    take the SymG compact path (counted as `cacheg_fallbacks`). A GraSp
    block structure is never part of it: the engine derives that on the
    device from the realized Â (`BlockCompactor`, DESIGN.md §10).
    """
    compact: Optional[CompactOperands] = None
    eager: Optional[GranniteOperands] = None
    nbytes: int = 0
    fallback: bool = False


def prepare_host_operands(pg: PaddedGraph, cfg: GNNConfig, *,
                          rng: Optional[np.random.Generator] = None
                          ) -> HostOperands:
    """HOST stage of the operand pipeline: pack (CacheG) or build (eager).

    SAGE and undirected GCN/GAT graphs take the CacheG compact transfer
    form; directed GCN/GAT graphs (SymG needs symmetry) fall back to the
    eager dense host build. No device work happens here — a scheduler host
    worker can call this from any thread.
    """
    from .graph import is_symmetric_adjacency
    if cfg.kind == "sage" or is_symmetric_adjacency(pg.adj):
        co = compact_operands(pg, cfg, rng=rng, check_symmetry=False)
        return HostOperands(compact=co, nbytes=co.nbytes)
    ops = build_operands(pg, cfg, lean=True, rng=rng)
    return HostOperands(eager=ops, nbytes=operand_nbytes(ops), fallback=True)


def realize_operands(ho: HostOperands,
                     materializer: OperandMaterializer) -> GranniteOperands:
    """DEVICE stage counterpart: expand the host product into the dense
    operand set (a jitted materializer call for the compact form, identity
    for the eager fallback). Dispatch is async under jax, so a host worker
    calling this merely *enqueues* device work — the dense arrays are
    created in device memory either way."""
    return materializer(ho.compact) if ho.compact is not None else ho.eager


@dataclasses.dataclass
class EdgeOperands:
    """Device-resident edge-list operands of one graph (`core.graph.
    edge_arrays`): int32 `src`/`dst` padded to the graph's edge rung and
    sorted by destination, spare edges landing in row `capacity` (one past
    the bucket), and (cap,) float32 `inv_deg`. Memory grows with the
    edges, never with the bucket squared. A registered pytree, so a stack
    of them crosses the batched plan's vmap like `GranniteOperands`."""
    src: jnp.ndarray
    dst: jnp.ndarray
    inv_deg: jnp.ndarray


jax.tree_util.register_pytree_node(
    EdgeOperands, lambda o: ((o.src, o.dst, o.inv_deg), None),
    lambda _, c: EdgeOperands(*c))


def edge_operands(pg: PaddedGraph) -> EdgeOperands:
    """Host side of the edge-list form: sort, pad and upload one graph's
    edges (`pg` from `pad_graph(dense=False)`)."""
    from .graph import edge_arrays
    return EdgeOperands(*(jnp.asarray(a) for a in edge_arrays(
        pg.edge_index, pg.num_nodes, pg.capacity)))


def edge_mean(h: jnp.ndarray, eo: EdgeOperands) -> jnp.ndarray:
    """Mean over each row's in-neighbours: gather the source rows, sum them
    by destination (the edges are sorted by it) into cap + 1 rows, drop the
    spare edges' row, scale by 1 / in-degree. A row with no in-edges gets
    0."""
    cap = h.shape[0]
    msgs = h.at[eo.src].get(mode="promise_in_bounds")
    summed = jax.ops.segment_sum(msgs, eo.dst, num_segments=cap + 1,
                                 indices_are_sorted=True)
    return summed[:cap] * eo.inv_deg[:, None]


def forward_edges(params: Dict, cfg: GNNConfig, x: jnp.ndarray,
                  eo: EdgeOperands) -> jnp.ndarray:
    """SAGE-mean over every in-neighbour from edge-list operands, L layers,
    eval BatchNorm and ReLU between them (PyG's SAGEConv: `w_neigh` maps
    the mean and carries the bias, `w_self` maps the node itself). Each
    layer aggregates on the narrower side of its neighbour map, which is
    exact (mean(h) W = mean(h W)): the last layer's 40 classes, say, in
    place of its 256 inputs. The aggregation of layer i runs under
    `graphserve.agg.l<i>` (the ops' metadata); a TPU trace names its ops
    by their output instead: the only f32 results of cap + 1 rows."""
    h = x
    for i in range(1, cfg.num_layers + 1):
        p = params[f"l{i}"]
        narrower = p["w_neigh"].shape[1] < p["w_neigh"].shape[0]
        m = h @ p["w_neigh"] if narrower else h
        with jax.named_scope(f"graphserve.agg.l{i}"):
            agg = edge_mean(m, eo)
        if not narrower:
            agg = agg @ p["w_neigh"]
        h = h @ p["w_self"] + agg + p["b"]
        if i < cfg.num_layers:
            h = _sage_between(params, cfg, i, h)
    return h


def operand_nbytes(ops: GranniteOperands) -> int:
    """Host→device bytes of one eagerly built operand set: the five dense
    fields (a GraSp structure is derived on the device, and offline QuantGr
    never takes the batched serve path).
    Reads `.nbytes` (both jnp and np expose it) — no device→host copy."""
    return int(sum(f.nbytes for f in (
        ops.norm_adj, ops.mask_mult, ops.bias_add, ops.sample_mask,
        ops.mean_mask)))


def calibrate_quant(params: Dict, cfg: GNNConfig, x: jnp.ndarray,
                    ops_: GranniteOperands) -> Dict:
    """QuantGr static calibration — whole GCN datapath (combine matmuls AND
    the aggregation Â@H, which dominates FLOPs at 2·N²·H)."""
    from .quant import quantize_agg
    if cfg.kind != "gcn":
        raise NotImplementedError("QuantGr calibration wired for GCN (paper Fig. 20)")
    pre1 = x @ params["l1"]["w"]
    h1 = jax.nn.relu(layers.gcn_grannite(params["l1"], x, ops_.norm_adj,
                                         Techniques(stagr=True)))
    pre2 = h1 @ params["l2"]["w"]
    return {"l1": quantize_linear(params["l1"]["w"], x),
            "l2": quantize_linear(params["l2"]["w"], h1),
            "agg1": quantize_agg(ops_.norm_adj, pre1),
            "agg2": quantize_agg(ops_.norm_adj, pre2)}


@dataclasses.dataclass
class TierOperands:
    """Per-(graph, tier) DERIVED operands (DESIGN.md §8).

    Today this is GCN's int8 aggregation form: Â quantized per-row ONCE per
    structure version, then cached device-resident next to the fp32 operand
    set it was derived from. The point is byte traffic, not math: the int8
    plan reads 1-byte Â rows instead of re-reading (and re-quantizing) the
    4-byte fp32 Â every query — on the NPU this is exactly the state CacheG
    keeps SRAM-resident. GAT/SAGE tiers need no per-graph derivation (their
    QuantGr state is model-level weights), so they pass None.
    """
    agg_aq: jnp.ndarray        # (cap, cap) int8 row-quantized Â
    agg_a_scale: jnp.ndarray   # (cap, 1) float32 per-row scales


jax.tree_util.register_pytree_node(
    TierOperands,
    lambda o: ((o.agg_aq, o.agg_a_scale), None),
    lambda _, c: TierOperands(*c))


def derive_tier_operands(norm_adj: jnp.ndarray) -> TierOperands:
    """Device side of the tier-operand derivation: row-quantize one fp32 Â
    (`quantize_rowwise` — the same rounding rule as every other QuantGr agg
    path). Pure jnp; the serving engine jits it per bucket
    (`build_agg_quantizer`) and caches the result per structure version."""
    from .quant import quantize_rowwise
    aq, a_scale = quantize_rowwise(norm_adj)
    return TierOperands(agg_aq=aq, agg_a_scale=a_scale)


def stack_tier_operands(tos: Sequence[TierOperands]) -> TierOperands:
    """Stack per-graph tier operands for one vmapped batched dispatch."""
    return TierOperands(agg_aq=jnp.stack([t.agg_aq for t in tos]),
                        agg_a_scale=jnp.stack([t.agg_a_scale for t in tos]))


@dataclasses.dataclass
class OperandStacker:
    """The dispatch's jitted operand stack: ONE compiled program copies the
    slots' resident `GranniteOperands` (block structures included) and,
    when the tier carries them, their `TierOperands` into the batched sets
    `stack_operands` and `stack_tier_operands` return — the same exact
    copies, where the eager forms launch one program per op per field.
    `check_batchable` runs on the host before the compiled call. Same
    trace accounting as ExecutionPlan: jit specializes on the slot count,
    the operand shapes and which optional parts are present, so
    `trace_count` is the number of such combinations compiled — GraphServe
    warms them in `warmup()` and reports the count in `summary()`."""
    fn: Callable = dataclasses.field(default=None, repr=False)
    trace_count: int = 0

    def __call__(self, ops: Sequence[GranniteOperands],
                 tier_ops: Optional[Sequence[TierOperands]] = None
                 ) -> Tuple[GranniteOperands, Optional[TierOperands]]:
        check_batchable(ops)
        return self.fn(ops, tier_ops)


def build_operand_stacker() -> OperandStacker:
    st = OperandStacker()

    def _stack_operands(ops, tier_ops):
        st.trace_count += 1               # python side effect: traces only
        return (stack_operands(ops),
                None if tier_ops is None else stack_tier_operands(tier_ops))

    st.fn = jax.jit(_stack_operands)
    return st


@dataclasses.dataclass
class AggQuantizer:
    """The jitted tier-operand deriver, with the same trace accounting as
    ExecutionPlan / OperandMaterializer: jit specializes on Â's shape, so
    `trace_count` is the number of buckets compiled — GraphServe warms them
    in `warmup()` and folds the count into the zero-recompile contract."""
    fn: Callable = dataclasses.field(default=None, repr=False)
    trace_count: int = 0

    def __call__(self, norm_adj: jnp.ndarray) -> TierOperands:
        return self.fn(norm_adj)


def build_agg_quantizer() -> AggQuantizer:
    q = AggQuantizer()

    def _derive(norm_adj):
        q.trace_count += 1                # python side effect: traces only
        return derive_tier_operands(norm_adj)

    q.fn = jax.jit(_derive)
    return q


# ---------------------------------------------------------------------------
# GrAd edge-delta patching (DESIGN.md §13): device-side incremental update
# of the cached operand forms — scatter the flipped awl entries, renorm the
# touched rows/cols of Â with the host-recomputed D^-1/2, re-quantize only
# the rows whose fp32 values changed. Every arithmetic expression below
# copies `materialize_operands` / `quantize_rowwise` operand-for-operand, so
# a patched entry is BIT-IDENTICAL to a fresh rebuild of the new structure
# version — the differential property suite holds it to that.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DeltaSpec:
    """Device-side description of one symmetric edge delta.

    Static padding keeps the trace count bounded: `flip_*` and `touched`
    are padded to engine-configured widths by REPEATING their first entry —
    the scatters then write identical values at duplicate indices, which is
    deterministic, and the row renorms recompute a row to the same bits
    twice. `dis` is the FULL patched D^-1/2 vector, computed host-side with
    the exact `gcn_norm_adjacency` expression (a few cap·4 bytes on the
    wire — the dense Â itself never crosses).
    """
    flip_i: jnp.ndarray            # (K_e,) int32 flip endpoints (symmetric:
    flip_j: jnp.ndarray            # (K_e,) int32  both (i,j) and (j,i) write)
    flip_v: jnp.ndarray            # (K_e,) float32 new awl value (1=add 0=rm)
    touched: jnp.ndarray           # (K_t,) int32 nodes with changed rows/cols
    dirty: jnp.ndarray             # (K_t,) int32 boundary-dirty subset of
    #                                `touched` (§15): rows whose remote
    #                                copies a sharded halo-delta exchange
    #                                must refresh — padded like `touched`;
    #                                unused by the local patch math (an
    #                                unsharded delta pads it inertly)
    dis: jnp.ndarray               # (cap,) float32 patched D^-1/2
    fields: Tuple[str, ...] = ()   # static: which operand fields to patch


jax.tree_util.register_pytree_node(
    DeltaSpec,
    lambda d: ((d.flip_i, d.flip_j, d.flip_v, d.touched, d.dirty, d.dis),
               d.fields),
    lambda fields, c: DeltaSpec(*c, fields=fields))


def patch_operands(ops: GranniteOperands, d: DeltaSpec) -> GranniteOperands:
    """Patch one graph's cached dense operands in place of a rebuild.

    GCN: awl is recovered exactly from the cached Â (an entry is non-zero
    iff awl=1 — real rows have dis>0, padded rows are all zero), the flips
    scattered in, and only the touched rows/cols renormalized with the same
    left-associated `dis[:, None] * awl * dis[None, :]` products the
    materializer uses — untouched entries keep their bits, touched ones
    get the bits a full rebuild would produce. GAT: the mask IS awl, so the
    flips scatter straight in and the bias re-derives from it. Pure jnp.
    """
    if "norm_adj" in d.fields:
        na = ops.norm_adj
        awl = (na != 0).astype(jnp.float32)
        awl = awl.at[d.flip_i, d.flip_j].set(d.flip_v)
        awl = awl.at[d.flip_j, d.flip_i].set(d.flip_v)
        rows = d.dis[d.touched][:, None] * awl[d.touched, :] * d.dis[None, :]
        na = na.at[d.touched, :].set(rows)
        cols = d.dis[:, None] * awl[:, d.touched] * d.dis[d.touched][None, :]
        na = na.at[:, d.touched].set(cols)
        ops = dataclasses.replace(ops, norm_adj=na)
    if "mask_mult" in d.fields:
        m = ops.mask_mult
        m = m.at[d.flip_i, d.flip_j].set(d.flip_v)
        m = m.at[d.flip_j, d.flip_i].set(d.flip_v)
        bias = jnp.where(m > 0, 0.0, masks.NEG_INF).astype(jnp.float32)
        ops = dataclasses.replace(ops, mask_mult=m, bias_add=bias)
    return ops


def patch_tier_operands(tops: TierOperands, norm_adj: jnp.ndarray,
                        touched: jnp.ndarray) -> TierOperands:
    """Re-quantize ONLY the touched rows of the cached int8 Â from the
    patched fp32 Â. `quantize_rowwise` is row-local (per-row absmax), so
    quantizing a gathered row block is bit-identical to the same rows of a
    full `derive_tier_operands` — the whole-matrix requant stays the
    fallback when the changed-row set exceeds the pad width."""
    from .quant import quantize_rowwise
    aq, a_scale = quantize_rowwise(norm_adj[touched, :])
    return TierOperands(
        agg_aq=tops.agg_aq.at[touched].set(aq),
        agg_a_scale=tops.agg_a_scale.at[touched].set(a_scale))


@dataclasses.dataclass
class DeltaPatcher:
    """The jitted GrAd delta patchers, with the same trace accounting as
    ExecutionPlan / OperandMaterializer / AggQuantizer: `fn` specializes
    per (capacity, fieldset, pad widths), `tier_fn` per (capacity, requant
    width) — GraphServe warms both per bucket in `warmup()` and folds the
    count into the zero-recompile contract."""
    fn: Callable = dataclasses.field(default=None, repr=False)
    tier_fn: Callable = dataclasses.field(default=None, repr=False)
    trace_count: int = 0

    def __call__(self, ops: GranniteOperands, d: DeltaSpec
                 ) -> GranniteOperands:
        return self.fn(ops, d)

    def patch_tier(self, tops: TierOperands, norm_adj: jnp.ndarray,
                   touched: jnp.ndarray) -> TierOperands:
        return self.tier_fn(tops, norm_adj, touched)


def build_delta_patcher() -> DeltaPatcher:
    p = DeltaPatcher()

    def _patch(ops, d):
        p.trace_count += 1                # python side effect: traces only
        return patch_operands(ops, d)

    def _tier(tops, norm_adj, touched):
        p.trace_count += 1                # python side effect: traces only
        return patch_tier_operands(tops, norm_adj, touched)

    p.fn = jax.jit(_patch)
    p.tier_fn = jax.jit(_tier)
    return p


@dataclasses.dataclass
class BlockCompactor:
    """The jitted GraSp structure deriver (DESIGN.md §10), with the same
    trace accounting as ExecutionPlan / OperandMaterializer / AggQuantizer:
    jit specializes on Â's shape and the static `max_nnz` budget, so
    `trace_count` is the number of buckets compiled — GraphServe warms them
    in `warmup()` and folds the count into the zero-recompile contract.

    Like the int8 Â (`AggQuantizer`), the block structure is DERIVED state:
    computed device-side from the cached fp32 `norm_adj` once per
    (graph_id, structure_version), so repeat grasp queries move zero
    sparse-structure bytes over the host→device link. `counts` is the
    cheap half of that derivation (one bitmap reduction, no block
    gather) — enough for the backend rule, so a graph the rule routes
    dense never pays the full compaction.
    """
    fn: Callable = dataclasses.field(default=None, repr=False)
    counts_fn: Callable = dataclasses.field(default=None, repr=False)
    trace_count: int = 0

    def __call__(self, norm_adj: jnp.ndarray, *,
                 max_nnz: int) -> Tuple[BlockSparse, jnp.ndarray]:
        return self.fn(norm_adj, max_nnz)

    def counts(self, norm_adj: jnp.ndarray) -> jnp.ndarray:
        return self.counts_fn(norm_adj)


def build_block_compactor() -> BlockCompactor:
    c = BlockCompactor()

    def _compact(norm_adj, max_nnz):
        c.trace_count += 1                # python side effect: traces only
        return compact_block_sparse(norm_adj, max_nnz=max_nnz)

    def _counts(norm_adj):
        c.trace_count += 1                # python side effect: traces only
        return block_counts(norm_adj)

    c.fn = jax.jit(_compact, static_argnames=("max_nnz",))
    c.counts_fn = jax.jit(_counts)
    return c


def calibrate_tier(params: Dict, cfg: GNNConfig, x: jnp.ndarray,
                   ops_: GranniteOperands) -> Dict:
    """Model-level QuantGr calibration for one serving tier (all kinds).

    Unlike `calibrate_quant` (whose QuantizedAgg bakes ONE graph's Â into
    int8 — the right thing for a paper table, useless to a multi-graph
    plan), the returned pytree carries only model-shaped state: per-layer
    QuantizedLinear weights plus, for GCN, the static aggregation
    activation scales. One calibration therefore serves every graph of the
    model — the per-graph int8 Â is a separate DERIVED operand the engine
    quantizes once per structure version (`derive_tier_operands`, cached
    device-resident) and feeds to the plan as `tier_ops`; in-trace
    derivation (`quantize_agg_dynamic`) remains only as the fallback for
    one-shot/eager calls. Runs one fp32 forward over the calibration
    features to record absmax ranges (static scales, never re-derived at
    query time).
    """
    if cfg.kind == "gcn":
        # same math as calibrate_quant minus its QuantizedAgg construction
        # (which row-quantizes the full (cap, cap) Â twice only for the
        # scalar h_scales to survive — serving derives the int8 Â per
        # structure version instead, derive_tier_operands)
        from .quant import calibrate_absmax
        pre1 = x @ params["l1"]["w"]
        h1 = jax.nn.relu(layers.gcn_grannite(params["l1"], x, ops_.norm_adj,
                                             Techniques(stagr=True)))
        pre2 = h1 @ params["l2"]["w"]
        return {"l1": quantize_linear(params["l1"]["w"], x),
                "l2": quantize_linear(params["l2"]["w"], h1),
                "agg1_h": calibrate_absmax(pre1).scale,
                "agg2_h": calibrate_absmax(pre2).scale}
    if cfg.kind == "gat":
        per_head = cfg.hidden // cfg.heads
        h1 = jax.nn.elu(layers.gat_grannite(
            params["l1"], x, ops_.mask_mult, ops_.bias_add,
            Techniques(effop=True), heads=cfg.heads, out_feats=per_head))
        return {"l1": quantize_linear(params["l1"]["w"], x),
                "l2": quantize_linear(params["l2"]["w"], h1)}
    if cfg.kind == "sage":
        t0 = Techniques(effop=True)

        def _layer(p, xin):
            if cfg.aggregator == "max":
                pooled = jax.nn.relu(xin @ p["w_pool"] + p["b_pool"])
                agg = effop.masked_max_aggregate(pooled, ops_.sample_mask,
                                                 grax3=False)
            else:
                agg = ops_.mean_mask @ xin
            ql = {"self": quantize_linear(p["w_self"], xin),
                  "neigh": quantize_linear(p["w_neigh"], agg)}
            if "w_pool" in p:
                ql["pool"] = quantize_linear(p["w_pool"], xin)
            return ql

        out, h = {}, x
        for i in range(1, cfg.num_layers + 1):
            out[f"l{i}"] = _layer(params[f"l{i}"], h)
            if i < cfg.num_layers:
                h = _sage_between(params, cfg, i, layers.sage_grannite(
                    params[f"l{i}"], h, ops_.sample_mask, ops_.mean_mask, t0,
                    aggregator=cfg.aggregator))
        return out
    raise ValueError(cfg.kind)


# Fusion modes (DESIGN.md §11): how a plan executes each LAYER.
#   none  — aggregate and combine as separate XLA dots (+ host-side act).
#   layer — one fused kernel pass per layer (aggregate + combine + bias +
#           act in a single grid; EffOp epilogue dispatch). A plan
#           dimension, not a tier: fused and unfused plans compute the same
#           tier math, only the execution schedule differs.
FUSION_MODES = ("none", "layer")


def forward_grannite(params: Dict, cfg: GNNConfig, x: jnp.ndarray,
                     ops_: GranniteOperands, t: Techniques,
                     quant: Optional[Dict] = None,
                     tier_ops: Optional[TierOperands] = None,
                     fusion: str = "none") -> jnp.ndarray:
    """One dense GraNNite forward. `quant` is the model-level tier
    calibration from `calibrate_tier` (serving tiers); `ops_.quant` is the
    per-graph offline form from `calibrate_quant` (paper tables). When both
    are present the per-graph form wins — it is the more faithful one.
    `tier_ops` carries the per-graph DERIVED tier operands (GCN's cached
    int8 Â); without it a QuantGr GCN forward derives the int8 Â in-trace.
    `fusion="layer"` executes each layer as one fused kernel pass
    (`layers.*_grannite_fused`) with the inter-layer activation folded into
    the kernel epilogue — same math, one grid per layer (DESIGN.md §11).
    """
    if fusion not in FUSION_MODES:
        raise ValueError(f"unknown fusion mode {fusion!r}; pick from "
                         f"{FUSION_MODES}")
    fused = fusion == "layer"
    tq = (quant or {}) if t.quantgr else {}
    if cfg.kind == "gcn":
        q = ops_.quant or {}
        taq = tier_ops.agg_aq if tier_ops is not None else None
        tas = tier_ops.agg_a_scale if tier_ops is not None else None
        l1_kw = dict(quant=q.get("l1") or tq.get("l1"),
                     quant_agg=q.get("agg1"), agg_h_scale=tq.get("agg1_h"),
                     tier_aq=taq, tier_a_scale=tas,
                     block_sparse=ops_.block_sparse)
        l2_kw = dict(quant=q.get("l2") or tq.get("l2"),
                     quant_agg=q.get("agg2"), agg_h_scale=tq.get("agg2_h"),
                     tier_aq=taq, tier_a_scale=tas,
                     block_sparse=ops_.block_sparse)
        if fused:
            h = layers.gcn_grannite_fused(params["l1"], x, ops_.norm_adj, t,
                                          activation="relu", **l1_kw)
            return layers.gcn_grannite_fused(params["l2"], h, ops_.norm_adj,
                                             t, activation="none", **l2_kw)
        h = jax.nn.relu(layers.gcn_grannite(params["l1"], x, ops_.norm_adj,
                                            t, **l1_kw))
        return layers.gcn_grannite(params["l2"], h, ops_.norm_adj, t, **l2_kw)
    if cfg.kind == "gat":
        per_head = cfg.hidden // cfg.heads
        if fused:
            h = layers.gat_grannite_fused(params["l1"], x, ops_.bias_add, t,
                                          heads=cfg.heads, out_feats=per_head,
                                          activation="elu", quant=tq.get("l1"))
            return layers.gat_grannite_fused(params["l2"], h, ops_.bias_add,
                                             t, heads=1,
                                             out_feats=cfg.num_classes,
                                             activation="none",
                                             quant=tq.get("l2"))
        h = jax.nn.elu(layers.gat_grannite(
            params["l1"], x, ops_.mask_mult, ops_.bias_add, t,
            heads=cfg.heads, out_feats=per_head, quant=tq.get("l1")))
        return layers.gat_grannite(params["l2"], h, ops_.mask_mult, ops_.bias_add,
                                   t, heads=1, out_feats=cfg.num_classes,
                                   quant=tq.get("l2"))
    if cfg.kind == "sage":
        h = x
        for i in range(1, cfg.num_layers + 1):
            last = i == cfg.num_layers
            kw = dict(aggregator=cfg.aggregator, quant=tq.get(f"l{i}"))
            if fused:
                # the epilogue applies ReLU only where no BatchNorm
                # stands between the layer and it
                act = "none" if last or cfg.batch_norm else "relu"
                h = layers.sage_grannite_fused(
                    params[f"l{i}"], h, ops_.sample_mask, ops_.mean_mask, t,
                    activation=act, **kw)
                if not last and cfg.batch_norm:
                    h = _sage_between(params, cfg, i, h)
            else:
                h = layers.sage_grannite(params[f"l{i}"], h, ops_.sample_mask,
                                         ops_.mean_mask, t, **kw)
                if not last:
                    h = _sage_between(params, cfg, i, h)
        return h
    raise ValueError(cfg.kind)


# ---------------------------------------------------------------------------
# Plan / executor split (DESIGN.md §2)
# ---------------------------------------------------------------------------

# Aggregation backends (DESIGN.md §10): how a plan executes Â @ H.
#   dense — one dense matmul over the full (cap, cap) operand.
#   grasp — the block-sparse bitmap_spmm kernel over a compacted structure
#           (the operands MUST carry `block_sparse`, padded to the bucket's
#           grasp_max_nnz budget; dense plans must carry None).
#   edges — gather and segment-sum over edge-list operands (`EdgeOperands`,
#           `forward_edges`): SAGE-mean over every in-neighbour, for buckets
#           whose dense operands the device cannot hold.
AGG_BACKENDS = ("dense", "grasp", "edges")

# (cfg, capacity, batch, techniques, backend, fusion, shards)
PlanKey = Tuple[GNNConfig, int, int, Techniques, str, str, int]


@dataclasses.dataclass
class ExecutionPlan:
    """One compiled execution recipe: (model kind, NodePad bucket,
    Techniques, aggregation backend).

    The plan owns the jitted callable; operands are *runtime arguments*
    (GrAd discipline), so every graph that lands in the same bucket reuses
    the same compiled blob — callers never rebuild traces ad hoc. With
    batch_size > 0 the forward is vmapped over a leading batch dim of both
    features and operands (params broadcast), which is how GraphServe turns
    many small irregular graphs into one dense statically-shaped dispatch.

    `trace_count` counts actual jit traces (not cache-key entries), so the
    zero-recompile contract is asserted against the compiler, not our own
    bookkeeping. Params are runtime arguments (never closed over), so `key`
    is the full identity of the compiled blob: models sharing (cfg,
    capacity, batch, techniques, backend) can legitimately share one plan.
    A quality tier (DESIGN.md §8) is a Techniques variant, so tiers get
    their own plans through the same key — and tiers that alias the same
    Techniques (GCN's int8 vs int8+grax) share one blob. `backend` is the
    orthogonal aggregation dimension (DESIGN.md §10): "grasp" plans run the
    block-sparse `bitmap_spmm` aggregation and expect operands carrying a
    budget-padded block structure; "dense" plans expect None there.
    `fusion` is the orthogonal execution-schedule dimension (DESIGN.md §11):
    "layer" plans run each layer as ONE fused kernel pass — same tier math,
    different compiled blob, hence part of the key.
    `shards` > 0 marks a SHARDED plan (DESIGN.md §12): `capacity` is then
    the per-shard row bucket, the leading dim of x/operands is the shard
    axis (not a batch), and the trace includes the halo-exchange
    collectives — a different blob per shard count, hence part of the key
    (0 = the ordinary unsharded plan).
    """
    cfg: GNNConfig
    techniques: Techniques
    capacity: int
    batch_size: int = 0                       # 0 = single-graph plan
    backend: str = "dense"
    fusion: str = "none"
    shards: int = 0                           # 0 = unsharded plan
    fn: Callable = dataclasses.field(default=None, repr=False)
    trace_count: int = 0
    # Sharded plans only: how the shard axis is placed — "shard_map" over
    # shards x replicas devices, or "vmap" simulating it on one device.
    placement: str = ""
    # Captured AT TRACE TIME for grasp plans: True when the kernel routing
    # lowered the aggregation through the dense `ref` path (no skip grid).
    # The compiled blob keeps whatever lowering it was traced with, so
    # fallback accounting must read this — not the env at dispatch time.
    grasp_ref_fallback: bool = False

    @property
    def key(self) -> PlanKey:
        return (self.cfg, self.capacity, self.batch_size, self.techniques,
                self.backend, self.fusion, self.shards)

    def __call__(self, params: Dict, x: jnp.ndarray, ops_: GranniteOperands,
                 quant: Optional[Dict] = None,
                 tier_ops: Optional[TierOperands] = None,
                 node_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        if self.shards:
            return self.fn(params, x, ops_, node_mask, quant)
        return self.fn(params, x, ops_, quant, tier_ops)


def build_plan(cfg: GNNConfig, capacity: int, t: Techniques, *,
               batch_size: int = 0, backend: str = "dense",
               fusion: str = "none") -> ExecutionPlan:
    """Compile-on-first-call plan for (cfg.kind, capacity, t, backend,
    fusion).

    batch_size > 0 builds the batched executor: x is (B, cap, F) and every
    operand field carries a leading B dim (see stack_operands); the
    model-level `quant` calibration broadcasts (in_axes=None), exactly like
    params, while the per-graph `tier_ops` are batched like the operands
    (stack_tier_operands). Call discipline for warmth: a plan whose
    Techniques enable QuantGr must ALWAYS be called with a calibration
    pytree (placeholder or real — same structure either way, see
    `calibrate_tier`) and, for GCN, with TierOperands; a non-QuantGr plan
    with None for both. Flipping between None and a pytree changes the
    trace structure and would recompile — the same discipline covers the
    backend dimension: a "grasp" plan's operands must always carry a
    block structure padded to ONE budget, a "dense" plan's never any.

    `backend="grasp"` (DESIGN.md §10) executes the aggregation through the
    block-sparse `bitmap_spmm` path: the tier's Techniques identity is
    unchanged (tiers are serving policy, the backend is a dispatch
    decision), the executed techniques just gain the grasp flag.
    `fusion="layer"` (DESIGN.md §11) executes each layer as one fused
    kernel pass — like the backend, a dispatch decision orthogonal to the
    tier, carried in the key because it changes the compiled blob.
    `backend="edges"` (DESIGN.md §16) aggregates from `EdgeOperands`
    (`forward_edges`); its operands' shapes follow the edge rung, and each
    rung is one more trace of the same plan.
    """
    if backend not in AGG_BACKENDS:
        raise ValueError(f"unknown aggregation backend {backend!r}; pick "
                         f"from {AGG_BACKENDS}")
    if backend == "edges" and not (has_edge_form(cfg) and fusion == "none"
                                   and not t.quantgr):
        raise ValueError("the edges backend runs unfused, unquantized "
                         "SAGE-mean over every in-neighbour")
    if fusion not in FUSION_MODES:
        raise ValueError(f"unknown fusion mode {fusion!r}; pick from "
                         f"{FUSION_MODES}")
    exec_t = dataclasses.replace(t, grasp=True) if backend == "grasp" else t
    plan = ExecutionPlan(cfg=cfg, techniques=t, capacity=capacity,
                         batch_size=batch_size, backend=backend,
                         fusion=fusion)

    def _forward(params, x, ops_, quant, tier_ops):
        plan.trace_count += 1                 # python side effect: traces only
        if backend == "grasp":
            from repro.kernels.ops import bitmap_spmm_mode
            plan.grasp_ref_fallback = bitmap_spmm_mode() == "ref"
        if backend == "edges":
            return forward_edges(params, cfg, x, ops_)
        return forward_grannite(params, cfg, x, ops_, exec_t, quant=quant,
                                tier_ops=tier_ops, fusion=fusion)

    if batch_size > 0:
        plan.fn = jax.jit(jax.vmap(_forward, in_axes=(None, 0, 0, None, 0)))
    else:
        plan.fn = jax.jit(_forward)
    return plan


# ---------------------------------------------------------------------------
# Sharded execution (DESIGN.md §12) — GraphSplit across N devices.
#
# A graph too large for the ladder's top bucket is row-partitioned
# (core.partition.partition_graph): shard s owns slot rows
# [s*shard_cap, (s+1)*shard_cap) of a permuted full-capacity layout. Each
# layer runs as: project OWN rows -> halo-exchange the projected rows into
# the full row space (one int8-compressed psum of disjoint zero-padded
# blocks, dist.compress) -> aggregate OWN rows against the FULL space
# through a rectangular (shard_cap, full_rows) operand row block. Row
# blocks keep complete Â rows, so per-row quantization scales — and hence
# the int8 tier numerics — match the single-device path exactly; the only
# sharding-induced error is the wire compression (<= scale/2 per element,
# zero when halo_compress is off).
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardSlice:
    """One shard's device-resident operand slice.

    The serving CacheG unit for sharded graphs: cached per
    (graph_id, structure_version, shard) and stacked along a leading shard
    axis at dispatch (`stack_shard_slices`). A registered pytree, so a
    cached slice tuple's device bytes are its leaves'.
    """
    x: jnp.ndarray              # (shard_cap, F) this shard's feature rows
    ops: GranniteOperands       # kind fields (shard_cap, full_rows); holes (1,1)
    node_mask: jnp.ndarray      # (shard_cap,) 1.0 real / 0.0 padding


jax.tree_util.register_pytree_node(
    ShardSlice, lambda s: ((s.x, s.ops, s.node_mask), None),
    lambda _, c: ShardSlice(*c))


def build_sharded_operands(g, part, cfg: GNNConfig, *,
                           rng: Optional[np.random.Generator] = None
                           ) -> Tuple[ShardSlice, ...]:
    """Host side of N-way GraphSplit: per-shard operand row blocks.

    Builds the ordinary full-capacity operands once (identical math to the
    unsharded path — including SAGE's seeded neighbor sampling, so the
    sharded forward is differentially testable against it), permutes rows
    AND columns into the slot layout, and slices shard row blocks. Padding
    is interleaved per shard; padded rows/cols are zero, hence inert.
    """
    from .graph import pad_graph
    pg = pad_graph(g, capacity=part.full_rows)
    ops = build_operands(pg, cfg, lean=True, rng=rng)
    perm = part.perm
    fields = OPERAND_FIELDS[cfg.kind]
    mats = {f: np.asarray(getattr(ops, f))[perm][:, perm] for f in fields}
    feats = pg.features[perm]
    mask = (perm < pg.num_nodes).astype(np.float32)
    hole = jnp.zeros((1, 1), jnp.float32)
    c = part.shard_cap
    out = []
    for s in range(part.shards):
        rows = slice(s * c, (s + 1) * c)
        vals = {k: hole for k in ("norm_adj", "mask_mult", "bias_add",
                                  "sample_mask", "mean_mask")}
        for f in fields:
            vals[f] = jnp.asarray(mats[f][rows])
        out.append(ShardSlice(x=jnp.asarray(feats[rows]),
                              ops=GranniteOperands(**vals),
                              node_mask=jnp.asarray(mask[rows])))
    return tuple(out)


def stack_shard_slices(slices: Sequence[ShardSlice]
                       ) -> Tuple[jnp.ndarray, GranniteOperands, jnp.ndarray]:
    """Stack per-shard slices -> (x, ops, node_mask) with a leading shard
    axis, the sharded plan's calling convention."""
    return (jnp.stack([s.x for s in slices]),
            stack_operands([s.ops for s in slices]),
            jnp.stack([s.node_mask for s in slices]))


def unshard_logits(stacked: np.ndarray, part) -> np.ndarray:
    """(shards, shard_cap, classes) slot-ordered logits -> (num_nodes,
    classes) in the original node order (inverse of `part.perm`)."""
    flat = np.asarray(stacked).reshape(part.full_rows, -1)
    out = np.empty_like(flat)
    out[part.perm] = flat
    return out[: part.num_nodes]


def halo_exchange(h_own: jnp.ndarray, node_mask: jnp.ndarray, *,
                  shard_cap: int, full_rows: int, axis_name: str = "shard",
                  compress: bool = True) -> jnp.ndarray:
    """Assemble the full (full_rows, width) matrix from per-shard row blocks.

    Each shard writes its (masked) rows into its slot range of a zeroed
    full-height buffer and the buffers are summed across the shard axis —
    with `compress` the sum is the int8-on-the-wire psum of
    `dist.compress.compressed_psum` (QuantGr applied to the halo traffic).
    Because the blocks are disjoint and zeros quantize exactly, every
    element of the result carries at most scale/2 absolute error, where
    scale = (global absmax)/127 — the bound the dist unit tests assert.
    Padded rows are zeroed BEFORE the exchange so softmax garbage in pad
    rows (GAT) can never inflate the shared compression scale.
    """
    from repro.dist.compress import compressed_psum
    h_own = h_own * node_mask[:, None]
    idx = jax.lax.axis_index(axis_name)
    buf = jnp.zeros((full_rows, h_own.shape[1]), h_own.dtype)
    buf = jax.lax.dynamic_update_slice(buf, h_own, (idx * shard_cap, 0))
    if compress:
        full, _ = compressed_psum(buf, axis_name)
        return full
    return jax.lax.psum(buf, axis_name)


def forward_grannite_sharded(params: Dict, cfg: GNNConfig, x: jnp.ndarray,
                             ops_: GranniteOperands, node_mask: jnp.ndarray,
                             t: Techniques, quant: Optional[Dict] = None, *,
                             shard_cap: int, full_rows: int,
                             axis_name: str = "shard",
                             compress: bool = True) -> jnp.ndarray:
    """One shard's slice of a sharded GraNNite forward (DESIGN.md §12).

    Runs under an SPMD shard axis (`shard_map` or a vmap-simulated axis):
    `x` is this shard's (shard_cap, F) feature rows, `ops_` carries
    rectangular (shard_cap, full_rows) operand row blocks, and the return
    value is this shard's (shard_cap, num_classes) logit rows in slot
    order. Exchange schedule per kind: GCN exchanges the projected hidden
    rows (widths hidden then classes); GAT the per-head projections; SAGE
    the aggregation INPUTS (raw features then layer-1 activations). QuantGr
    GCN derives the int8 Â from the row block in-trace — complete rows
    quantize to exactly the single-device scales, so no sharded tier-operand
    cache is needed.
    """
    from .quant import (QuantizedAgg, apply_quantized_agg,
                        apply_quantized_linear, quantize_rowwise)
    tq = (quant or {}) if t.quantgr else {}

    def _exchange(h_own):
        return halo_exchange(h_own, node_mask, shard_cap=shard_cap,
                             full_rows=full_rows, axis_name=axis_name,
                             compress=compress)

    if cfg.kind == "gcn":
        def _layer(p, v_own, ql, h_scale):
            h_own = (apply_quantized_linear(v_own, ql)
                     if ql is not None else v_own @ p["w"])
            h_full = _exchange(h_own)
            if h_scale is not None:
                aq, a_scale = quantize_rowwise(ops_.norm_adj)
                agg = apply_quantized_agg(
                    QuantizedAgg(aq=aq, a_scale=a_scale, h_scale=h_scale),
                    h_full)
            else:
                agg = ops_.norm_adj @ h_full
            return agg + p["b"]

        h = jax.nn.relu(_layer(params["l1"], x, tq.get("l1"),
                               tq.get("agg1_h")))
        return _layer(params["l2"], h, tq.get("l2"), tq.get("agg2_h"))

    if cfg.kind == "gat":
        def _layer(p, v_own, heads, f_out, ql):
            h_own = (apply_quantized_linear(v_own, ql)
                     if ql is not None else v_own @ p["w"])
            h_full = _exchange(h_own).reshape(full_rows, heads, f_out)
            h_mine = h_own.reshape(shard_cap, heads, f_out)
            a_src = jnp.einsum("nhf,hf->nh", h_full, p["a_src"])  # (full, H)
            a_dst = jnp.einsum("nhf,hf->nh", h_mine, p["a_dst"])  # (C, H)
            outs = []
            for hd in range(heads):
                e = effop.broadcast_add_scores(a_src[:, hd], a_dst[:, hd],
                                               grax2=t.grax2)   # (C, full)
                e = jax.nn.leaky_relu(e, negative_slope=0.2)
                if t.grax1:
                    attn = effop.segment_softmax_dense(e, ops_.bias_add)
                else:
                    e = effop.masked_select_exact(e, ops_.mask_mult)
                    attn = jax.nn.softmax(e, axis=-1)
                outs.append(attn @ h_full[:, hd, :])
            out = jnp.stack(outs, axis=1).reshape(shard_cap, heads * f_out)
            return out + p["b"]

        per_head = cfg.hidden // cfg.heads
        h = jax.nn.elu(_layer(params["l1"], x, cfg.heads, per_head,
                              tq.get("l1")))
        return _layer(params["l2"], h, 1, cfg.num_classes, tq.get("l2"))

    if cfg.kind == "sage":
        def _lin(v, w, ql):
            return apply_quantized_linear(v, ql) if ql is not None else v @ w

        def _layer(p, v_own, q):
            q = q or {}
            v_full = _exchange(v_own)
            if cfg.aggregator == "mean":
                agg = ops_.mean_mask @ v_full
            else:
                pooled = jax.nn.relu(_lin(v_full, p["w_pool"], q.get("pool"))
                                     + p["b_pool"])
                agg = effop.masked_max_aggregate(pooled, ops_.sample_mask,
                                                 grax3=t.grax3)
            return (_lin(v_own, p["w_self"], q.get("self"))
                    + _lin(agg, p["w_neigh"], q.get("neigh")) + p["b"])

        h = jax.nn.relu(_layer(params["l1"], x, tq.get("l1")))
        return _layer(params["l2"], h, tq.get("l2"))
    raise ValueError(cfg.kind)


def build_sharded_plan(cfg: GNNConfig, shard_cap: int, shards: int,
                       t: Techniques, *, compress: bool = True,
                       replicas: int = 1) -> ExecutionPlan:
    """Sharded ExecutionPlan: per-shard aggregate+combine under a shard
    axis, halo exchange as a compressed psum (DESIGN.md §12).

    Placement: with >= `shards` devices the plan runs under `shard_map` on
    a 1-D shard mesh (`launch.mesh.make_shard_mesh`), in/out specs derived
    through the `dist.sharding` rules ("graph_shard" -> "shard", everything
    else replicated). With fewer devices — the common 1-CPU test box — the
    shard axis is vmap-simulated (`axis_name` collectives are identical),
    so the plan's math and trace structure never depend on device count.
    Which of the two ran is recorded in `plan.placement` ("shard_map" or
    "vmap"), so a fallback to one device is never silent.
    Sharded plans are dense, fusion="none", single-graph (the shard axis
    occupies the leading dim a batched plan would use); call with
    `plan(params, x, ops, quant, node_mask=mask)`.

    `replicas=R > 1` adds a replica axis (DESIGN.md §15): every array
    operand gains a LEADING R dim and the plan runs R independent sharded
    batches concurrently — on the ("replica", "shard") R x S mesh when the
    host has R*S devices, else under an outer anonymous vmap. The replica
    axis carries NO collectives (halo psums name only "shard", so each
    replica row exchanges within itself); replica rows are bit-identical
    to R separate single-replica dispatches, which the property tests
    assert. `replicas=1` is the historical calling convention exactly —
    no leading dim, same jaxpr.
    """
    plan = ExecutionPlan(cfg=cfg, techniques=t, capacity=shard_cap,
                         batch_size=0, backend="dense", fusion="none",
                         shards=shards)
    full_rows = shards * shard_cap

    def _forward(params, x, ops_, mask, quant):
        plan.trace_count += 1                 # python side effect: traces only
        return forward_grannite_sharded(
            params, cfg, x, ops_, mask, t, quant=quant, shard_cap=shard_cap,
            full_rows=full_rows, axis_name="shard", compress=compress)

    lead = 1 if replicas == 1 else 2          # dims ahead of (cap, ...)
    if shards > 1 and len(jax.devices()) >= shards * replicas:
        from jax.sharding import PartitionSpec as P

        from repro.dist.sharding import spec_for_axes
        from repro.launch.mesh import make_shard_mesh
        if replicas == 1:
            mesh = make_shard_mesh(shards)
            row = spec_for_axes(("graph_shard",), (shards,), mesh)
        else:
            mesh = make_shard_mesh(shards, replicas)
            row = spec_for_axes(("graph_replica", "graph_shard"),
                                (replicas, shards), mesh)
        x_spec = P(*row, None, None)
        mask_spec = P(*row, None)

        def _spmd(params, x, ops_, mask, quant):
            # shard_map leaves keep leading block dims of 1 per mesh axis
            sq = lambda l: l.reshape(l.shape[lead:])
            out = _forward(params, sq(x), jax.tree_util.tree_map(sq, ops_),
                           sq(mask), quant)
            return out.reshape((1,) * lead + out.shape)

        plan.placement = "shard_map"
        plan.fn = jax.jit(jax.shard_map(
            _spmd, mesh=mesh,
            in_specs=(P(), x_spec, P(*row), mask_spec, P()),
            out_specs=x_spec, check_vma=False))
    else:
        plan.placement = "vmap"
        fn = jax.vmap(_forward, in_axes=(None, 0, 0, 0, None),
                      axis_name="shard")
        if replicas > 1:
            fn = jax.vmap(fn, in_axes=(None, 0, 0, 0, None))
        plan.fn = jax.jit(fn)
    return plan


def sharded_exchange_widths(cfg: GNNConfig) -> Tuple[int, ...]:
    """Per-layer halo widths `forward_grannite_sharded` exchanges (§12).

    GCN moves the projected hidden rows then the class rows; GAT the
    concatenated per-head layer-1 projections then the single-head class
    rows; SAGE the aggregation INPUTS (raw features, then the layer-1
    activations). One source of truth for the serving engine's collective
    byte accounting and the benchmark's modelled latency — if the exchange
    schedule changes, both move with it.
    """
    if cfg.kind == "gcn":
        return (cfg.hidden, cfg.num_classes)
    if cfg.kind == "gat":
        return (cfg.heads * (cfg.hidden // cfg.heads), cfg.num_classes)
    return (cfg.in_feats, cfg.hidden)


# ---------------------------------------------------------------------------
# Training / evaluation (to reproduce the paper's accuracy table)
# ---------------------------------------------------------------------------

def masked_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                         mask: jnp.ndarray) -> jnp.ndarray:
    logp = jax.nn.log_softmax(logits, axis=-1)
    safe = jnp.maximum(labels, 0)
    nll = -jnp.take_along_axis(logp, safe[:, None], axis=-1)[:, 0]
    m = mask.astype(logits.dtype)
    return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)


def accuracy(logits: jnp.ndarray, labels: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    pred = jnp.argmax(logits, axis=-1)
    ok = (pred == labels) & mask
    return ok.sum() / jnp.maximum(mask.sum(), 1)


def train_node_classifier(key, cfg: GNNConfig, pg: PaddedGraph,
                          forward: Callable[[Dict, jnp.ndarray], jnp.ndarray],
                          params: Optional[Dict] = None, *, lr: float = 0.01,
                          weight_decay: float = 5e-4, epochs: int = 100) -> Dict:
    """Full-batch Adam training (paper: lr 0.01, wd 5e-4, 100 epochs)."""
    from repro.optim.adamw import adamw_init, adamw_update

    x = jnp.asarray(pg.features)
    y = jnp.asarray(pg.labels)
    tm = jnp.asarray(pg.train_mask)
    params = params if params is not None else init_params(key, cfg)
    opt = adamw_init(params)

    def loss_fn(p):
        return masked_cross_entropy(forward(p, x), y, tm)

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(loss_fn)(p)
        p, o = adamw_update(p, g, o, lr=lr, weight_decay=weight_decay)
        return p, o, loss

    for _ in range(epochs):
        params, opt, _ = step(params, opt)
    return params


def evaluate(cfg: GNNConfig, params: Dict, pg: PaddedGraph,
             forward: Callable[[Dict, jnp.ndarray], jnp.ndarray]) -> float:
    logits = forward(params, jnp.asarray(pg.features))
    return float(accuracy(logits, jnp.asarray(pg.labels), jnp.asarray(pg.test_mask)))
