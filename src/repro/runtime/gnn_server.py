"""GraphServe: a multi-graph, multi-bucket GNN inference engine.

The LM server (`runtime/server.py`) turns the paper's Step-1 techniques into
a serving discipline for token streams; GraphServe does the same for streams
of *graphs* — the paper's actual workload:

  * NodePad / BucketLadder — every request's graph is padded into one rung
    of a shared bucket ladder (tile-aligned capacities, e.g. 256/512/1024/
    2048), so the engine holds exactly one compiled blob per
    (model kind, bucket) after warmup, independent of request shapes.
  * GrAd — adjacency operands are runtime *arguments* of an ExecutionPlan
    (`core.models.build_plan`), never baked constants: evolving graphs
    re-run host preprocessing only. A graph that outgrows its bucket moves
    up the ladder (`BucketLadder.grow`) — the one legitimate recompile,
    surfaced as a `rebucket_events` metric.
  * GraphSplit — padding, PreG normalization, and mask construction happen
    on the host at submit/update time; the device executes one dense,
    statically-shaped, vmapped forward per batch. With `shard_counts`
    configured the split also goes multi-device (DESIGN.md §12, §15): a
    graph too large for the TOP ladder bucket no longer errors out of
    `attach()` — the engine partitions it N-way
    (`core.partition.partition_for_ladder`, multilevel coarsen + KL/FM
    refinement under a per-shard bucket cap, `partition_method`) and serves
    it through a sharded plan: per-shard aggregate+combine under a shard
    axis, the halo exchanged per layer as an int8-compressed psum
    (`dist.compress` — QuantGr applied to the wire). A sharded dispatch
    carries up to `replica_groups` requests, one per replica row of the
    mesh (the shard axis occupies the leading dim a batch would use), the
    shard count joins the batch key so a dispatch never mixes sharded and
    unsharded plans, and `warmup()` pre-traces every configured
    (shard count, bucket, tier) — mixed traffic replays warm.
  * Batching — same-bucket requests are stacked with a leading batch dim,
    on the device, by one compiled program for the features and one for
    the operands (`core.models.OperandStacker`: the whole operand set,
    block structures and tier operands included, in one launch) and
    executed through the plan's vmapped
    callable at a FIXED batch width; partial batches repeat a real request
    into the junk slots (dropped on output) so batch width never changes
    shape — the same trick as the LM server's empty decode slots. Batch
    selection is best-fill (`best_fill_key`): the fullest (model, bucket,
    tier, backend) key dispatches first, with per-model fairness on ties,
    so a lone odd request at the head of the queue cannot force a 1-of-N
    batch.
  * Pipeline (DESIGN.md §9) — the sync path (`submit`/`query` + `run()`)
    executes host and device stages serially; `scheduler()` attaches the
    async two-stage pipeline (`runtime/scheduler.py`): host worker threads
    run `prepare_submit`/`prepare_query` (padding, operand build/packing,
    CacheG lookups) while the dispatcher thread drives `_execute_batch`,
    so host preprocessing for request N+1 overlaps device execution of
    request N. Every engine contract below holds under both drivers.
  * CacheG (DESIGN.md §7) — operands cross the host→device link as a
    bit-packed compact form (SymG triangular for undirected graphs) and are
    expanded to the dense float32 set ON DEVICE by a jitted materializer;
    attached graphs cache the materialized result and their padded
    features per (graph_id, structure_version), so repeated queries of an
    unchanged graph move ZERO operand or feature bytes and `_run_batch`
    stacks device-resident buffers. `update()` bumps the version and
    re-materializes once.
    Directed GCN/GAT graphs fall back to the eager dense upload (counted as
    `cacheg_fallbacks`), cached per version the same way — same plans, no
    extra traces. One routine (`_operand_set`) decides every unsharded
    request's operands, tier operands and agg backend: through the cache
    for an attached graph, uncached for a one-shot submit.
  * GraSp agg backends (DESIGN.md §10) — every request's aggregation
    dispatches through one of two backends: `dense` (one matmul over the
    full padded Â) or `grasp` (the block-sparse `bitmap_spmm` kernel over
    a compacted structure padded to the bucket's `grasp_max_nnz` budget).
    A model registered with `agg_backend="auto"` routes each graph by the
    modelled density/cost rule (`core.sparsity.select_agg_backend`);
    `"grasp"` forces the sparse path where eligible. The block structure
    is DERIVED state like the int8 Â: computed device-side from the
    realized fp32 Â (`BlockCompactor`) — once per (graph_id,
    structure_version) for an attached graph, once per request for a
    one-shot one — and never crosses the link. The backend joins the batch
    key, so a dispatch never mixes backends, and
    warmup pre-traces BOTH backends' plans — mixed dense/grasp traffic
    replays warm. Every REQUEST whose grasp intent could not run the skip
    grid (forced-but-ineligible structure, or the kernel routing's dense
    `ref` fallback) is counted in `backend_fallbacks` — the same
    per-request unit as `tier_fallbacks`, never silent.
  * Quality tiers (DESIGN.md §8) — every registered model carries a tier
    registry mapping tier names to `Techniques` variants (standard ladder:
    `fp32` exact / `int8` QuantGr / `int8+grax` QuantGr + the kind's GrAx
    approximations). A tier is just another ExecutionPlan: requests pick
    one per call (`query(gid, tier="int8")`), QuantGr tiers carry a
    model-level calibration (`calibrate_tier`) as the plan's broadcast
    runtime argument, and an uncalibrated quant tier serves through fp32
    (counted as `tier_fallbacks`) rather than erroring. Calibration runs
    once per (model, tier) — on the first `attach()` or an explicit
    `calibrate()` — and also measures `accuracy_delta_vs_fp32` on the
    held-out part of the calibration graph.

Engine contracts (what tests and operators may rely on):

  * Zero-recompile — after `warmup()`, `assert_warm()` holds however many
    mixed-size, mixed-TIER, mixed-BACKEND requests arrive, as long as no
    graph climbs the ladder. Warmup compiles every (model, bucket, tier,
    backend) plan — quant-tier plans against a placeholder calibration
    whose pytree structure equals any real one (calibration shapes are
    model-level, see core.models), grasp plans against a placeholder
    block structure at the bucket budget — plus one CacheG materializer
    trace per (bucket, operand-fieldset) and two block-compactor traces
    (counts reduction + full gather) per grasp-capable bucket.
  * Cache keys — all four operand caches (fp32 operands, sharded slices,
    int8 Â, grasp structure) are keyed by (graph_id, structure_version)
    and NOTHING else. The primary caches hold the tier- and
    backend-agnostic forms every request shares; the tier and grasp
    caches hold forms DERIVED from that same version (GCN's int8 Â,
    quantized once per version so the int8 plan reads 1-byte rows instead
    of re-quantizing 4-byte fp32 every query; the budget-padded block
    structure plus the backend decision, compacted once per version).
    `update()`/`update_delta()` bumping the version (or `detach()`) is
    the only INVALIDATION path for all four — and capacity eviction
    (below) is not invalidation: an evicted entry's key is still live and
    the next query rebuilds or re-materializes the identical value.
  * Bounded residency (DESIGN.md §13) — with `device_cache_budget_bytes`
    set, all four caches live under one byte-budgeted manager
    (`runtime/cache.py`): every entry carries its measured device cost,
    cost-aware LRU eviction keeps `cache_resident_bytes <= budget` at
    every step (derived forms evict before the primary they hang off),
    evicted primaries spill to a host-RAM compact form re-materialized on
    fault (`cache_spill_hits` — compact bytes cross the link again, zero
    host packing), and `attach()` becomes the admission gate
    (`CacheAdmissionError`, policy `admission="evict"|"reject"`).
    Eviction, spill, and re-materialization never trace: the materializer
    and patcher blobs are bucket-shaped and warm.
  * GrAd deltas (§13) — `update_delta(gid, add_edges, remove_edges)`
    patches the packed adjacency host-side and every device-resident
    cached form IN PLACE of a rebuild (touched-row Â renorm, GAT
    mask/bias rescatter, touched-row int8 re-quantization, grasp
    re-derivation, sharded row blocks with the partition kept), bit-exact
    against a full rebuild; deltas past the warmed pad widths (or SAGE)
    fall back to `update()` — `delta_updates` vs `delta_fallbacks`.
  * Plan identity — plans are keyed by (cfg, bucket, batch, Techniques,
    backend, fusion): tenants sharing a config share blobs, and tier names
    that alias the same Techniques (GCN int8 vs int8+grax) share too. Tier
    names are a serving-policy concept; the compiler only ever sees
    Techniques plus the aggregation backend and the fusion mode.
  * Fused layers (DESIGN.md §11) — `fusion="layer"` routes each GNN layer
    through one fused Pallas kernel (aggregate + combine + bias + act in a
    single grid, `kernels/fused_layers.py`) with per-request control flow
    expressed as EffOp masked arithmetic in the kernel epilogue instead of
    host-side branching. Fusion is a PLAN dimension, not a tier: it never
    changes numerics beyond kernel-vs-XLA float ordering, so it joins the
    batch key (a dispatch never mixes fused and unfused plans) and warmup
    pre-traces BOTH fusion modes per (tier, backend) — mixed fused/unfused
    traffic replays warm.
  * Edge-list form (DESIGN.md §16) — where a bucket's dense (cap, cap)
    operands cannot be held on the device (`_dense_fits`, from the
    device's own memory), a model registered with `agg_backend="auto"`
    whose kind has an edge form (SAGE-mean over every in-neighbour) keeps
    each graph as its edge list: `pad_graph(dense=False)` builds no dense
    array, `attach` uploads `EdgeOperands` padded to the graph's edge rung
    once per version, and the `edges` plan gathers and segment-sums them.
    Such graphs serve unfused fp32; a model that cannot take the form is
    refused at `attach` instead of allocating the dense operands.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import (BucketLadder, Graph, PaddedGraph,
                              apply_edge_delta, edge_index_from_adjacency,
                              edge_list_delta, edge_rung,
                              is_symmetric_adjacency, pad_graph)
from repro.core.layers import Techniques
from repro.core.models import (FUSION_MODES, OPERAND_FIELDS, DeltaSpec,
                               ExecutionPlan, GNNConfig, GranniteOperands,
                               PlanKey, ShardSlice, TierOperands,
                               build_agg_quantizer, build_block_compactor,
                               build_delta_patcher, build_materializer,
                               build_operand_stacker, build_operands,
                               build_plan, build_sharded_operands,
                               build_sharded_plan, calibrate_tier,
                               compact_operands, edge_operands,
                               EdgeOperands, forward_grannite,
                               has_edge_form, init_params, layer_widths,
                               prepare_host_operands,
                               realize_operands, sharded_exchange_widths,
                               stack_operands, stack_shard_slices,
                               unshard_logits)
from repro.core.partition import (GraphShards, partition_for_ladder,
                                  patch_halo, transfer_cost)
from repro.core.sparsity import (HBM_BW, MXU_RATE, grasp_max_nnz,
                                 select_agg_backend)
from repro.dist.compress import ring_psum_nbytes
from repro.runtime.cache import (CacheAdmissionError, DeviceCacheManager,
                                 estimate_dense_entry_bytes,
                                 estimate_shard_entry_bytes, pytree_nbytes)
from repro.runtime.clock import WALL, Clock
from repro.runtime.ewma import LatencyBank
from repro.runtime.slo import SLOConfig, SLOGovernor
from repro.runtime.tracing import Tracer, default_tracer

# Per-kind serving techniques for models registered WITHOUT a tier ladder.
# GraSp is deliberately NOT a technique flag here: block-sparse aggregation
# is an execution *backend* the engine dispatches per (graph, bucket)
# (`agg_backend=` on register_model, DESIGN.md §10), not part of a tier's
# quality identity; QuantGr is tier-servable via the model-level
# calibration path (DESIGN.md §8).
DEFAULT_TECHNIQUES: Dict[str, Techniques] = {
    "gcn": Techniques(stagr=True, grad_dynamic=True, graphsplit=True),
    "gat": Techniques.full_gat(),
    "sage": Techniques.full_sage(),
}

STANDARD_TIERS = ("fp32", "int8", "int8+grax")

# Aggregation-backend serving modes (register_model(agg_backend=...)):
# "dense" never dispatches GraSp, "auto" routes per graph by the modelled
# density/cost rule, "grasp" forces the sparse path where eligible.
AGG_BACKEND_MODES = ("dense", "auto", "grasp")

# (model, bucket, tier, agg backend, fusion mode, shard count — 0 unsharded;
# for a sharded request the bucket element is the PER-SHARD capacity)
BatchKey = Tuple[str, int, str, str, str, int]


def device_memory_bytes() -> Optional[int]:
    """Memory of the device the plans run on, as its backend reports it
    (`bytes_limit`); None where it reports none, as the CPU does, and then
    every dense bucket counts as held."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return int(limit) if limit else None


def best_fill_key(stats: Dict[BatchKey, Tuple[int, int]], batch_slots: int,
                  last_dispatch: Optional[Dict[str, int]] = None,
                  *, replica_slots: int = 1) -> BatchKey:
    """Pick the batch key to dispatch next (DESIGN.md §9).

    `stats` maps each pending (model, bucket, tier, backend, fusion) key to
    `(count, head_order)` — how many requests wait under it and the arrival
    order of its oldest one. Selection order:

      1. best fill — most waiting requests, capped at `batch_slots` (a key
         with 9 waiting fills a 4-slot batch no better than one with 4);
      2. per-model fairness — among equal fills, the model dispatched
         LONGEST ago (its serial in `last_dispatch`) goes first, so one
         chatty tenant cannot starve another at equal batch efficiency;
      3. FIFO — oldest head request breaks remaining ties.

    This replaces the old head-of-line rule (`queue[0]`'s key, whatever it
    was), under which a lone odd request at the head forced a 1-of-N batch
    while fully-fillable keys waited behind it.

    Dispatch width differs per key: an unsharded key fills up to
    `batch_slots` requests, a sharded key (`key[5] > 0`) fills up to
    `replica_slots` replica rows of the mesh (DESIGN.md §15) — so "fill"
    is the FRACTION of the key's own width it can occupy, making a
    2-of-2-replicas sharded dispatch exactly as good as a 4-of-4-slot
    dense one. With every width equal the fraction orders identically to
    the historical absolute count.
    """
    last_dispatch = last_dispatch or {}

    def width(k: BatchKey) -> int:
        return replica_slots if k[5] else batch_slots

    return min(stats.items(),
               key=lambda kv: (-min(kv[1][0], width(kv[0])) / width(kv[0]),
                               last_dispatch.get(kv[0][0], -1),
                               kv[1][1]))[0]


def edf_best_fill_key(stats: Dict[BatchKey, Tuple[int, int, float]],
                      batch_slots: int,
                      last_dispatch: Optional[Dict[str, int]] = None,
                      *, replica_slots: int = 1) -> BatchKey:
    """Slack-aware EDF variant of `best_fill_key` (DESIGN.md §14).

    `stats` values are `(count, head_order, min_slack)` where `min_slack`
    is the tightest `deadline - now` (seconds) among the key's pending
    requests, `+inf` when none carries a deadline. Selection order:

      1. best fill — identical to `best_fill_key`: batching efficiency is
         still the primary axis (a tight deadline never justifies a 1-of-N
         dispatch while a full batch waits — that would miss MORE
         deadlines under load);
      2. tightest slack — among equal fills, the key whose most urgent
         request expires soonest dispatches first (earliest-deadline-first
         as the tie-break, which is where a deadline actually changes the
         outcome);
      3. per-model fairness, then FIFO — unchanged from `best_fill_key`,
         so deadline-free traffic batches exactly as before (every slack
         is +inf and rules 3-4 decide).

    Fill is the per-key width fraction exactly as in `best_fill_key`
    (sharded keys fill `replica_slots` replica rows, unsharded keys fill
    `batch_slots`).
    """
    last_dispatch = last_dispatch or {}

    def width(k: BatchKey) -> int:
        return replica_slots if k[5] else batch_slots

    return min(stats.items(),
               key=lambda kv: (-min(kv[1][0], width(kv[0])) / width(kv[0]),
                               kv[1][2],
                               last_dispatch.get(kv[0][0], -1),
                               kv[1][1]))[0]


def pending_stats(reqs: Sequence["GNNRequest"]
                  ) -> Dict[BatchKey, Tuple[int, int]]:
    """Fold a pending-request sequence into `best_fill_key` stats."""
    stats: Dict[BatchKey, Tuple[int, int]] = {}
    for i, r in enumerate(reqs):
        k = r.batch_key
        c = stats.get(k)
        stats[k] = (1, i) if c is None else (c[0] + 1, c[1])
    return stats


def edf_pending_stats(reqs: Sequence["GNNRequest"], now: float
                      ) -> Dict[BatchKey, Tuple[int, int, float]]:
    """Fold pending requests into `edf_best_fill_key` stats at time `now`."""
    stats: Dict[BatchKey, Tuple[int, int, float]] = {}
    for i, r in enumerate(reqs):
        k = r.batch_key
        slack = (r.deadline_s - now if r.deadline_s is not None
                 else float("inf"))
        c = stats.get(k)
        stats[k] = ((1, i, slack) if c is None
                    else (c[0] + 1, c[1], min(c[2], slack)))
    return stats


def tier_techniques(kind: str) -> Dict[str, Techniques]:
    """The standard quality-tier registry for one model kind (DESIGN.md §8).

    `fp32` is the exact dense serving path — no approximation, the accuracy
    reference every other tier's delta is measured against. `int8` switches
    the combine matmuls (and, for GCN, the Â aggregation) to QuantGr.
    `int8+grax` adds the kind's GrAx approximations: GrAx1+GrAx2 for GAT
    attention, GrAx3 for SAGE-max; GCN has no GrAx variant, so its
    `int8+grax` aliases the int8 Techniques and shares its compiled plans.
    """
    fp32 = {"gcn": Techniques(stagr=True, grad_dynamic=True, graphsplit=True),
            "gat": Techniques(stagr=True, graphsplit=True, effop=True),
            "sage": Techniques(stagr=True, graphsplit=True, effop=True)}[kind]
    int8 = dataclasses.replace(fp32, quantgr=True)
    grax = {"gcn": int8,
            "gat": dataclasses.replace(int8, grax1=True, grax2=True),
            "sage": dataclasses.replace(int8, grax3=True)}[kind]
    return {"fp32": fp32, "int8": int8, "int8+grax": grax}


def _delta_points(base_logits, tier_logits, pg: PaddedGraph) -> float:
    """`accuracy_delta_vs_fp32` in percentage points, on the held-out batch.

    Labeled calibration graphs score top-1 accuracy on `test_mask` (the
    held-out split; falls back to all labeled nodes when no mask exists);
    unlabeled ones fall back to argmax agreement with the fp32 tier, shifted
    so 0.0 still reads "identical predictions" and negative "divergence".
    """
    n = pg.num_nodes
    bp = np.asarray(base_logits)[:n].argmax(-1)
    tp = np.asarray(tier_logits)[:n].argmax(-1)
    if pg.labels is not None:
        labels = np.asarray(pg.labels)[:n]
        mask = labels >= 0
        if pg.test_mask is not None and np.asarray(pg.test_mask)[:n].any():
            mask = mask & np.asarray(pg.test_mask)[:n]
        if mask.any():
            acc_b = float((bp[mask] == labels[mask]).mean())
            acc_t = float((tp[mask] == labels[mask]).mean())
            return (acc_t - acc_b) * 100.0
    return (float((tp == bp).mean()) - 1.0) * 100.0


@dataclasses.dataclass
class GNNRequest:
    uid: int
    model: str
    pg: PaddedGraph
    ops: GranniteOperands                  # EdgeOperands when backend is
    # "edges" (§16)
    bucket: int
    submitted_s: float
    x: Optional[jnp.ndarray] = None        # (cap, F) device features of an
    # unsharded request; dropped once its dispatch has run
    tier: str = "fp32"                     # resolved tier (post-fallback)
    backend: str = "dense"                 # resolved agg backend (§10)
    fusion: str = "none"                   # resolved fusion mode (§11)
    tier_ops: Optional[TierOperands] = None  # derived (e.g. GCN int8 Â)
    deadline_s: Optional[float] = None     # absolute clock deadline (§14);
    # None = no SLO — the request can never expire or be flagged late
    tolerance: Optional[float] = None      # max |accuracy_delta| (points)
    # the tier router may trade away (§14); None = no tolerance routing
    deadline_missed: bool = False          # §14: expired unserved (preds is
    # None) or finished past its deadline (preds still delivered)
    shards: int = 0                        # >0: sharded dispatch (§12);
    # then `ops` holds the STACKED per-shard operand row blocks and the
    # three fields below carry the rest of the sharded calling convention
    part: Optional[GraphShards] = None     # the partition (unshard map)
    shard_x: Optional[jnp.ndarray] = None  # (S, C, F) stacked features
    shard_mask: Optional[jnp.ndarray] = None  # (S, C) real-row masks
    finished_s: float = 0.0
    done: bool = False
    preds: Optional[np.ndarray] = None     # (num_nodes,) argmax classes
    logits: Optional[np.ndarray] = None    # (num_nodes, C) if return_logits

    @property
    def batch_key(self) -> BatchKey:
        """What the requests of one dispatch share. An edges request's
        backend element also names its edge rung (§16): operands of two
        rungs do not stack."""
        backend = (f"edges/{self.ops.src.shape[0]}" if self.backend == "edges"
                   else self.backend)
        return (self.model, self.bucket, self.tier, backend, self.fusion,
                self.shards)


@dataclasses.dataclass
class GraphServeConfig:
    ladder: BucketLadder = dataclasses.field(default_factory=BucketLadder)
    batch_slots: int = 4                   # fixed batch width per dispatch
    return_logits: bool = False
    shard_counts: Tuple[int, ...] = ()     # §12: shard counts attach() may
    # auto-shard an over-ladder graph across; () keeps sharding disabled
    # (oversized graphs raise, exactly the pre-§12 behavior)
    halo_compress: bool = True             # int8 QuantGr on the halo wire;
    # False exchanges exact fp32 (4x the collective bytes)
    device_cache_budget_bytes: Optional[int] = None   # §13: byte budget the
    # four operand caches share; None keeps them unbounded (pre-§13)
    spill_to_host: bool = True             # §13: evicted primaries keep a
    # host-RAM compact form, re-materialized on fault; False drops them
    admission: str = "evict"               # §13 attach() policy when a new
    # graph's projected operands overflow the budget: "evict" admits and
    # lets insert-time eviction make room, "reject" raises
    delta_pad_rows: int = 64               # §13 GrAd delta threshold: max
    # touched nodes update_delta() patches device-side (flip scatters pad
    # to 2x this); bigger deltas — and 0, disabling the path — take the
    # full update() rebuild
    replica_groups: int = 1                # §15: sharded dispatch width —
    # R concurrent sharded batches per plan call on an R x S mesh (falls
    # back to a vmap-simulated replica axis below R*S devices); 1 keeps
    # the pre-§15 single-replica convention exactly
    partition_method: str = "multilevel"   # §15 partitioner for attach()'s
    # auto-sharding: "multilevel" (coarsen + KL/FM refine) or "greedy"
    # (the §12 streaming baseline the benchmark compares against)


@dataclasses.dataclass
class _ModelEntry:
    cfg: GNNConfig
    params: Dict
    tiers: Dict[str, Techniques]           # tier name -> execution variant
    default_tier: str
    agg_backend: str = "dense"             # "dense" | "auto" | "grasp" (§10)
    default_fusion: str = "none"           # "none" | "layer" (§11)
    name: str = ""                         # registry name (bank/routing key)
    # once per (model, tier): calibrate_tier pytrees for QuantGr tiers, and
    # the measured accuracy_delta_vs_fp32 for every non-fp32 tier
    calibrations: Dict[str, Dict] = dataclasses.field(default_factory=dict)
    accuracy_delta: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def techniques(self) -> Techniques:
        """The default tier's Techniques (back-compat accessor)."""
        return self.tiers[self.default_tier]


class GraphServe:
    def __init__(self, sc: Optional[GraphServeConfig] = None, *, seed: int = 0,
                 clock: Optional[Clock] = None,
                 slo: Optional[SLOConfig] = None,
                 tracer: Optional[Tracer] = None):
        self.sc = sc or GraphServeConfig()
        self.seed = seed
        # §14: every timestamp, deadline comparison, and latency sample in
        # the serving path reads THIS clock — tests inject a fake one and
        # drive the whole SLO loop without a single real sleep
        self.clock = clock if clock is not None else WALL
        # spans of the request and dispatch paths (runtime/tracing.py), on
        # this engine's clock; the process's default ring unless injected
        self.tracer = tracer if tracer is not None else default_tracer()
        # §14: measured-latency oracle per BatchKey, roofline-seeded; the
        # single cost source behind backend routing and the tier router
        self.bank = LatencyBank()
        # §14: optional SLO governor — None keeps serving exactly pre-§14
        self.governor = SLOGovernor(slo) if slo is not None else None
        self.models: Dict[str, _ModelEntry] = {}
        self.queue: List[GNNRequest] = []
        self.finished: List[GNNRequest] = []
        self.graphs: Dict[int, Tuple[str, PaddedGraph]] = {}
        self._plans: Dict[PlanKey, ExecutionPlan] = {}
        self._materializer = build_materializer()
        self._agg_quantizer = build_agg_quantizer()
        self._block_compactor = build_block_compactor()
        self._delta_patcher = build_delta_patcher()
        # the dispatch's device-side stack of its slots' resident features:
        # one compiled program per (batch_slots, bucket, in_feats); of
        # dense slots' operands (and tier operands), one per (batch_slots,
        # bucket, fieldset, block structure, tier operands); and of
        # edge-form slots' operands, one per (batch_slots, bucket, rung)
        self._stack_x = jax.jit(jnp.stack)
        self._stack_operands = build_operand_stacker()
        self._stack_edges = jax.jit(lambda ops: jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *ops))
        if self.sc.admission not in ("evict", "reject"):
            raise ValueError(f"unknown admission policy "
                             f"{self.sc.admission!r}; pick evict|reject")
        # CacheG device-resident operand hierarchy, keyed by (graph_id,
        # structure_version) and NOTHING else: the primary fp32 operands
        # ("operand"), the graph's padded fp32 features ("features"), the
        # DERIVED forms of the same version — GCN's int8 Â ("tier") and the
        # resolved agg backend plus budget-padded block structure ("grasp",
        # DESIGN.md §10) — and the sharded slice tuple ("shard", §12).
        # Since §13 all five live under one byte-budgeted manager
        # (`runtime/cache.py`): cost-aware LRU eviction against
        # `device_cache_budget_bytes`, evicted primaries spilling to a
        # host-RAM compact form, update()/detach() invalidating by key.
        self._cache = DeviceCacheManager(
            budget_bytes=self.sc.device_cache_budget_bytes,
            spill_to_host=self.sc.spill_to_host)
        # sharded registry (§12): graph_id -> (partition, source Graph) for
        # graphs attach() auto-sharded past the top ladder bucket
        self._sharded: Dict[int, Tuple[GraphShards, Graph]] = {}
        # shard count -> where its last dispatch actually ran: the plan's
        # placement ("shard_map" | "vmap") and the output's device count
        self._sharded_placement: Dict[int, Dict[str, object]] = {}
        self._graph_version: Dict[int, int] = {}
        self._warm_blobs: Optional[int] = None
        self._uid = 0
        self._gid = 0
        # one lock guards uid/gid counters, metrics, the operand caches, and
        # the graphs registry: the pipeline scheduler (runtime/scheduler.py)
        # runs prepare_submit/prepare_query on host worker threads while
        # update()/detach() arrive from the caller's thread. Never call a
        # _lock-taking helper while holding _lock.
        self._lock = threading.Lock()
        self._dispatch_serial = 0
        self._last_dispatch: Dict[str, int] = {}   # model -> dispatch serial
        self.metrics = {"batches": 0, "slots_filled": 0, "slots_total": 0,
                        "rebucket_events": 0, "latency_s": [],
                        "first_submit_s": None, "last_finish_s": None,
                        # host clock from the start of each dispatch span to
                        # the end of its d2h child: the whole device stage
                        # as the host sees it, not time the chip was busy
                        "device_busy_s": 0.0,
                        "operand_bytes_h2d": 0, "feature_bytes_h2d": 0,
                        "operand_cache_hits": 0,
                        "operand_cache_misses": 0, "cacheg_fallbacks": 0,
                        "tier_fallbacks": 0, "backend_fallbacks": 0,
                        "grasp_batches": 0, "sharded_batches": 0,
                        "halo_bytes_exchanged": 0,
                        "collective_bytes_compressed": 0,
                        "collective_bytes_exact": 0,
                        "cache_spill_hits": 0, "cache_admission_rejects": 0,
                        "delta_updates": 0, "delta_fallbacks": 0,
                        # §15 halo-delta wire accounting: what the
                        # dirty-boundary-row exchange moved vs what a full
                        # halo re-exchange would have, per sharded delta
                        "delta_halo_bytes_exchanged": 0,
                        "delta_halo_bytes_full": 0,
                        "delta_dirty_rows": 0,
                        # §16: real directed edges the edges plans
                        # aggregated, once per layer
                        "edges_dispatched": 0,
                        "deadline_misses": 0, "shed_requests": 0}

    def _count(self, name: str, delta=1) -> None:
        with self._lock:
            self.metrics[name] += delta

    # ------------------------------------------------------- cache cost model
    def _projected_primary_bytes(self, model: str, pg: PaddedGraph,
                                 part: Optional[GraphShards]) -> int:
        """Projected device cost of the entries this graph pins on first
        query — what attach() admission control (§13) sizes against: the
        PRIMARY and the (cap, F) fp32 features (a sharded slice tuple holds
        its features itself). Derived forms (int8 Â, grasp structure) are
        not counted: they rank below the primary in eviction order and
        never exceed it."""
        cfg = self.models[model].cfg
        nf = len(OPERAND_FIELDS[cfg.kind])
        if not pg.dense:
            return 4 * (2 * edge_rung(pg.edge_index.shape[1]) + pg.capacity
                        + pg.capacity * cfg.in_feats)
        if part is not None:
            return estimate_shard_entry_bytes(part.shards, part.shard_cap,
                                              part.full_rows, nf,
                                              cfg.in_feats)
        return (estimate_dense_entry_bytes(nf, pg.capacity)
                + pg.capacity * cfg.in_feats * 4)

    def _operand_spill_fn(self, key: Tuple[int, int]):
        """Eviction-time producer of the §13 host-RAM spill form: re-packs
        the CacheG compact `HostOperands` from the graph's CURRENT host
        snapshot (SymG bit-packed, ~64x smaller than the dense fp32 entry;
        SAGE re-samples under the same seeded default rng, so the packed
        mask reproduces the evicted operands bit-for-bit). Called by the
        manager under the engine `_lock`. Declines — the entry is dropped
        and the next miss runs the full build — when the version moved on,
        the graph detached or went sharded, or the pack fell back to the
        eager dense form (directed structure: nothing compact to keep)."""
        graph_id, ver = key

        def _spill():
            if (self._graph_version.get(graph_id) != ver
                    or graph_id in self._sharded):
                return None
            model, pg = self.graphs[graph_id]
            ho = prepare_host_operands(pg, self.models[model].cfg)
            return None if ho.fallback else ho
        return _spill

    # ------------------------------------------------------------------ setup
    def register_model(self, name: str, cfg: GNNConfig, params: Optional[Dict] = None,
                       *, techniques: Optional[Techniques] = None,
                       tiers=None, default_tier: str = "fp32",
                       agg_backend: str = "dense",
                       fusion: str = "none") -> None:
        """Register a model with its quality-tier registry.

        `tiers` may be: None (single-tier registry {"fp32": techniques or
        DEFAULT_TECHNIQUES}); a sequence of STANDARD_TIERS names (resolved
        through `tier_techniques(cfg.kind)`); or a full {name: Techniques}
        dict. The registry must always contain "fp32" — it is the accuracy
        reference and the calibration-fallback target, not just a tier.

        `agg_backend` picks the model's GraSp dispatch mode (DESIGN.md
        §10): "dense" (default — never block-sparse), "auto" (per-graph
        density/cost rule), or "grasp" (forced where the structure fits the
        bucket budget; ineligible graphs serve dense, counted in
        `backend_fallbacks`). Only GCN aggregation has a block-sparse form
        today — other kinds (and QuantGr tiers, whose aggregation is the
        cached int8 Â) always resolve dense, so a non-"dense" mode on them
        is a no-op, not an error.

        `fusion` picks the model's DEFAULT fused-layer mode (DESIGN.md
        §11): "none" (per-op dispatch) or "layer" (one fused Pallas kernel
        per GNN layer). Requests may override it per call
        (`query(gid, fusion=...)`); warmup pre-traces both modes either
        way, so the default is a routing preference, not a compile
        commitment.
        """
        if params is None:
            params = init_params(jax.random.PRNGKey(self.seed), cfg)
        if tiers is None:
            registry = {"fp32": techniques if techniques is not None
                        else DEFAULT_TECHNIQUES[cfg.kind]}
        else:
            if techniques is not None:
                raise ValueError(
                    "pass per-tier Techniques inside `tiers`, not both "
                    "`techniques` and `tiers`")
            if isinstance(tiers, dict):
                registry = dict(tiers)
            else:
                std = tier_techniques(cfg.kind)
                unknown = [tn for tn in tiers if tn not in std]
                if unknown:
                    raise ValueError(
                        f"unknown standard tier name(s) {unknown}; pick "
                        f"from {sorted(std)} or pass a "
                        f"{{name: Techniques}} dict")
                registry = {tn: std[tn] for tn in tiers}
        if "fp32" not in registry:
            raise ValueError("tier registry must include 'fp32' (the "
                             "accuracy reference / calibration fallback)")
        if registry["fp32"].quantgr:
            # the fallback tier must be servable UNCALIBRATED: a QuantGr
            # fp32 tier would fall back to itself and execute its plan with
            # quant=None, flipping the trace structure warmup compiled
            raise ValueError("the 'fp32' tier cannot enable QuantGr — it "
                             "is the uncalibrated-fallback path; register "
                             "quantized variants under another tier name")
        if default_tier not in registry:
            raise ValueError(f"default tier {default_tier!r} not in "
                             f"{sorted(registry)}")
        if agg_backend not in AGG_BACKEND_MODES:
            raise ValueError(f"unknown agg_backend mode {agg_backend!r}; "
                             f"pick from {AGG_BACKEND_MODES}")
        if fusion not in FUSION_MODES:
            raise ValueError(f"unknown fusion mode {fusion!r}; "
                             f"pick from {FUSION_MODES}")
        self.models[name] = _ModelEntry(cfg=cfg, params=params,
                                        tiers=registry,
                                        default_tier=default_tier,
                                        agg_backend=agg_backend,
                                        default_fusion=fusion,
                                        name=name)

    def _modelled_batch_s(self, model: str, bucket: int, tier: str,
                          backend: str, shards: int) -> float:
        """Roofline seed for the latency bank (§14): modelled seconds for
        ONE dispatch under this key. Two-layer GNN forward priced with the
        same MXU/HBM constants as `agg_cost_model`: per layer one dense
        (cap, cap) @ (cap, w) aggregation plus the (cap, w_in) @ (w_in,
        w_out) combine, times the batch width. Backend/tier scaling is
        deliberately coarse (grasp halves the aggregation term, int8 runs
        combines at the 2x rate over quarter bytes): the seed only has to
        ORDER cold keys — the first measured sample replaces it outright,
        and `ewma_vs_model` in `summary()` tracks how wrong it was."""
        cfg = self.models[model].cfg
        widths = layer_widths(cfg)
        b = self.sc.replica_groups if shards else self.sc.batch_slots
        cap = bucket
        quant = self.models[model].tiers[tier].quantgr
        total = 0.0
        for w_in, w_out in zip(widths[:-1], widths[1:]):
            agg_flops = 2.0 * cap * cap * w_in
            agg_bytes = 4.0 * (cap * cap + 2 * cap * w_in)
            agg = max(agg_flops / MXU_RATE, agg_bytes / HBM_BW)
            if backend == "grasp":
                agg *= 0.5
            comb_flops = 2.0 * cap * w_in * w_out
            comb_bytes = 4.0 * cap * (w_in + w_out) + 4.0 * w_in * w_out
            rate, byte_scale = ((2.0 * MXU_RATE, 0.25) if quant
                                else (MXU_RATE, 1.0))
            comb = max(comb_flops / rate, comb_bytes * byte_scale / HBM_BW)
            total += agg + comb
        return total * b

    def _bank_key(self, model: str, bucket: int, tier: str, backend: str,
                  fusion: str, shards: int) -> BatchKey:
        return (model, bucket, tier, backend, fusion, shards)

    def _seed_bank(self, model: str, bucket: int, tier: str, backend: str,
                   fusion: str, shards: int) -> None:
        key = self._bank_key(model, bucket, tier, backend, fusion, shards)
        self.bank.seed(key, self._modelled_batch_s(model, bucket, tier,
                                                   backend, shards))

    def plan_for(self, model: str, bucket: int, tier: Optional[str] = None,
                 backend: str = "dense", fusion: str = "none",
                 shards: int = 0) -> ExecutionPlan:
        # keyed by the plan's full identity, not the (model, tier) names:
        # params and calibrations are runtime args, so models/tiers with
        # identical (cfg, techniques, backend, fusion, shards) share one
        # compiled blob per bucket
        e = self.models[model]
        tier_name = tier if tier is not None else e.default_tier
        t = e.tiers[tier_name]
        # §14: every plan resolution (warmup included) seeds the latency
        # bank's modelled figure for its batch key, so routing has a cost
        # ordering before the first measured sample lands
        self._seed_bank(model, bucket, tier_name,
                        "dense" if shards else backend,
                        "none" if shards else fusion, shards)
        if shards:
            # sharded plans (§12) are dense/unfused single-graph dispatches
            # — the shard axis occupies the leading dim, so batch is 0 and
            # `bucket` is the PER-SHARD capacity
            key: PlanKey = (e.cfg, bucket, 0, t, "dense", "none", shards)
            if key not in self._plans:
                self._plans[key] = build_sharded_plan(
                    e.cfg, bucket, shards, t,
                    compress=self.sc.halo_compress,
                    replicas=self.sc.replica_groups)
            return self._plans[key]
        key = (e.cfg, bucket, self.sc.batch_slots, t, backend, fusion, 0)
        if key not in self._plans:
            self._plans[key] = build_plan(e.cfg, bucket, t,
                                          batch_size=self.sc.batch_slots,
                                          backend=backend, fusion=fusion)
        return self._plans[key]

    @property
    def compiled_blobs(self) -> int:
        """Actual jit traces: all plans + the CacheG materializer (one trace
        per bucket × operand-fieldset) + the tier-operand deriver (one per
        bucket with a QuantGr GCN tier) + the GraSp block compactor (two
        per bucket with a grasp-capable model — the counts reduction and
        the full gather) + the GrAd delta patcher (one per bucket ×
        GCN/GAT fieldset, plus one row-requant trace per bucket with a
        QuantGr GCN tier, when `delta_pad_rows > 0`), all compiled during
        warmup."""
        return (sum(p.trace_count for p in self._plans.values())
                + self._materializer.trace_count
                + self._agg_quantizer.trace_count
                + self._block_compactor.trace_count
                + self._delta_patcher.trace_count)

    def warmup(self, *, buckets: Optional[Tuple[int, ...]] = None) -> int:
        """Compile every (model, bucket, tier, backend, fusion) plan — and
        every (bucket, fieldset) CacheG materializer — once
        with placeholder inputs. BOTH fusion modes warm per (tier,
        backend): fusion is a per-request plan dimension (DESIGN.md §11),
        so mixed fused/unfused traffic must replay warm exactly like mixed
        tiers and backends do.

        QuantGr tiers not yet calibrated warm against a THROWAWAY
        calibration built from the placeholder graph: `calibrate_tier`'s
        pytree structure depends only on the model config, so the trace
        compiled here replays warm when the real calibration arrives — the
        placeholder is never stored, and an uncalibrated tier still falls
        back to fp32 at query time.

        Models with a non-"dense" `agg_backend` additionally warm the
        GraSp side per (bucket, non-quant tier): the per-bucket block
        compactor plus the grasp-backend plan, called with a placeholder
        block structure at the bucket's `grasp_max_nnz` budget — so mixed
        dense/grasp traffic after warmup replays entirely warm however the
        per-graph rule routes it (DESIGN.md §10).

        With `shard_counts` configured, a final leg warms every sharded
        plan (shard count x bucket x tier, DESIGN.md §12) against
        placeholder shard slices, so a giant graph attaching AFTER warmup
        serves with zero new traces — the zero-recompile contract covers
        mixed sharded/unsharded traffic too.

        At a bucket whose dense operands the device cannot hold (§16) no
        dense or fused plan is compiled: the edges plan is, at the edge
        rung of each edge-form graph attached there so far.
        """
        buckets = buckets if buckets is not None else self.sc.ladder.buckets
        b = self.sc.batch_slots
        warm_cal: Dict[Tuple[str, str], Dict] = {}
        warmed: set = set()
        for bucket in buckets:
            empty = None
            for name, e in self.models.items():
                if not self._dense_fits(e.cfg, bucket):
                    # no dense plan here (§16): the edge plans at the rungs
                    # of the graphs attached so far
                    self._warm_edges(name, bucket, warmed)
                    continue
                if empty is None:
                    empty = pad_graph(Graph(
                        edge_index=np.zeros((2, 0), np.int32), num_nodes=1,
                        features=np.zeros((1, 1), np.float32)),
                        capacity=bucket)
                pg = dataclasses.replace(
                    empty, features=np.zeros((bucket, e.cfg.in_feats),
                                             np.float32))
                single = self._materializer(compact_operands(pg, e.cfg))
                x = self._stack_x([jnp.zeros((bucket, e.cfg.in_feats),
                                             jnp.float32)] * b)
                single_grasp = None
                if self._grasp_capable(e):
                    # placeholder block structure at the bucket budget —
                    # these calls also warm the per-bucket block compactor
                    # (both halves: the counts reduction the backend rule
                    # reads, and the full gather grasp graphs pay)
                    self._block_compactor.counts(single.norm_adj)
                    bsp, _ = self._block_compactor(
                        single.norm_adj, max_nnz=grasp_max_nnz(bucket))
                    single_grasp = dataclasses.replace(single,
                                                       block_sparse=bsp)
                for tier, t in e.tiers.items():
                    backends = ("dense",) if (single_grasp is None
                                              or t.quantgr
                                              ) else ("dense", "grasp")
                    for backend in backends:
                        for fusion in FUSION_MODES:
                            # alias tiers (e.g. GCN int8+grax == int8)
                            # share a plan AND a calibration structure —
                            # exercising them again would just recompute
                            # placeholders for zero new traces
                            plan = self.plan_for(name, bucket, tier,
                                                 backend, fusion)
                            if (name, plan.key) in warmed:
                                continue
                            warmed.add((name, plan.key))
                            quant = e.calibrations.get(tier)
                            if quant is None and t.quantgr:
                                if (name, tier) not in warm_cal:
                                    x1 = jnp.zeros((bucket, e.cfg.in_feats),
                                                   jnp.float32)
                                    warm_cal[(name, tier)] = calibrate_tier(
                                        e.params, e.cfg, x1, single)
                                quant = warm_cal[(name, tier)]
                            tops = None
                            if self._needs_tier_ops(e, tier):
                                # also warms the per-bucket tier-operand
                                # deriver
                                tops = [self._agg_quantizer(single.norm_adj)
                                        ] * b
                            # the dispatch's own operand stack, so each
                            # shape it meets is traced here
                            ops, tops = self._stack_operands(
                                [single_grasp if backend == "grasp"
                                 else single] * b, tops)
                            out = plan(e.params, x, ops, quant, tops)
                            out.block_until_ready()
                self._warm_delta(e, bucket, single, warmed)
        for shards in sorted({int(s) for s in self.sc.shard_counts
                              if int(s) >= 2}):
            for bucket in buckets:
                for name, e in self.models.items():
                    # placeholder sharded calling convention: (S, C, F)
                    # features, (S, C, S*C) rectangular operand row blocks
                    # for the kind's fields, (1, 1) holes for the rest,
                    # all-pad node masks — shape identity is all a trace
                    # needs. With replica groups (§15) every shape gains
                    # the leading R dim the R-wide plan expects.
                    full = shards * bucket
                    lead = (() if self.sc.replica_groups == 1
                            else (self.sc.replica_groups,))
                    x = jnp.zeros((*lead, shards, bucket, e.cfg.in_feats),
                                  jnp.float32)
                    mask = jnp.zeros((*lead, shards, bucket), jnp.float32)
                    hole = jnp.zeros((*lead, shards, 1, 1), jnp.float32)
                    blk = jnp.zeros((*lead, shards, bucket, full),
                                    jnp.float32)
                    kind_fields = set(OPERAND_FIELDS[e.cfg.kind])
                    ops = GranniteOperands(**{
                        f: (blk if f in kind_fields else hole)
                        for f in ("norm_adj", "mask_mult", "bias_add",
                                  "sample_mask", "mean_mask")})
                    for tier, t in e.tiers.items():
                        plan = self.plan_for(name, bucket, tier,
                                             shards=shards)
                        if (name, plan.key) in warmed:
                            continue
                        warmed.add((name, plan.key))
                        quant = e.calibrations.get(tier)
                        if quant is None and t.quantgr:
                            # the dense leg above already built this
                            # placeholder calibration for every
                            # uncalibrated QuantGr tier
                            quant = warm_cal[(name, tier)]
                        out = plan(e.params, x, ops, quant,
                                   node_mask=mask)
                        out.block_until_ready()
                    if (self.sc.delta_pad_rows > 0
                            and e.cfg.kind in ("gcn", "gat")):
                        # sharded delta patch runs over the CONCATENATED
                        # (full, full) permuted operand matrices (§13) —
                        # one extra patcher trace per (full rows, fieldset)
                        fields = OPERAND_FIELDS[e.cfg.kind]
                        if ("delta", full, fields) not in warmed:
                            warmed.add(("delta", full, fields))
                            hole1 = jnp.zeros((1, 1), jnp.float32)
                            fmat = jnp.zeros((full, full), jnp.float32)
                            ph = GranniteOperands(**{
                                f: (fmat if f in kind_fields else hole1)
                                for f in ("norm_adj", "mask_mult",
                                          "bias_add", "sample_mask",
                                          "mean_mask")})
                            self._delta_patcher(
                                ph, self._placeholder_delta(full, fields))
        self._warm_blobs = self.compiled_blobs
        return self._warm_blobs

    def _warm_edges(self, name: str, bucket: int, warmed: set) -> None:
        """Compile the edges plan of one (model, bucket) at the edge rung
        of every edge-form graph of that model attached there: a rung is
        known only once a graph brings its edges."""
        e = self.models[name]
        b = self.sc.batch_slots
        with self._lock:
            rungs = {edge_rung(pg.edge_index.shape[1])
                     for m, pg in self.graphs.values()
                     if m == name and not pg.dense and pg.capacity == bucket}
        if not rungs:
            return
        plan = self.plan_for(name, bucket, "fp32", "edges", "none")
        x = self._stack_x([jnp.zeros((bucket, e.cfg.in_feats), jnp.float32)]
                          * b)
        for rung in sorted(rungs):
            if (name, plan.key, rung) in warmed:
                continue
            warmed.add((name, plan.key, rung))
            idx = jnp.zeros((rung,), jnp.int32)
            eo = EdgeOperands(src=idx, dst=idx,
                              inv_deg=jnp.zeros((bucket,), jnp.float32))
            plan(e.params, x, self._stack_edges([eo] * b)
                 ).block_until_ready()

    def _delta_pads(self, cap: int) -> Tuple[int, int]:
        """(touched, flip) static pad widths of the delta patcher at one
        capacity — the §13 delta-vs-rebuild threshold in shape form."""
        kt = min(self.sc.delta_pad_rows, cap)
        return kt, 2 * kt

    def _placeholder_delta(self, cap: int, fields: Tuple[str, ...]
                           ) -> DeltaSpec:
        kt, ke = self._delta_pads(cap)
        return DeltaSpec(flip_i=jnp.zeros((ke,), jnp.int32),
                         flip_j=jnp.zeros((ke,), jnp.int32),
                         flip_v=jnp.zeros((ke,), jnp.float32),
                         touched=jnp.zeros((kt,), jnp.int32),
                         dirty=jnp.zeros((kt,), jnp.int32),
                         dis=jnp.zeros((cap,), jnp.float32), fields=fields)

    def _warm_delta(self, e: _ModelEntry, bucket: int,
                    single: GranniteOperands, warmed: set) -> None:
        """Warm the GrAd delta patcher for one (bucket, model): the operand
        patch trace per fieldset, plus the tier row-requant trace when a
        QuantGr GCN tier will keep a derived int8 Â to patch."""
        if self.sc.delta_pad_rows <= 0 or e.cfg.kind not in ("gcn", "gat"):
            return
        fields = OPERAND_FIELDS[e.cfg.kind]
        if ("delta", bucket, fields) not in warmed:
            warmed.add(("delta", bucket, fields))
            self._delta_patcher(single,
                                self._placeholder_delta(bucket, fields))
        if (any(self._needs_tier_ops(e, tn) for tn in e.tiers)
                and ("delta_tier", bucket) not in warmed):
            warmed.add(("delta_tier", bucket))
            self._delta_patcher.patch_tier(
                self._agg_quantizer(single.norm_adj), single.norm_adj,
                jnp.zeros((min(2 * self.sc.delta_pad_rows, bucket),),
                          jnp.int32))

    def assert_warm(self) -> None:
        """The zero-recompile contract (mirrors the LM server's assertion)."""
        assert self._warm_blobs is not None, "call warmup() first"
        assert self.compiled_blobs == self._warm_blobs, (
            f"recompile after warmup: {self.compiled_blobs} traces vs "
            f"{self._warm_blobs} at warmup")

    # ------------------------------------------------------------- calibration
    def calibrate(self, model: str, g: Graph, *,
                  force: bool = False) -> Dict[str, float]:
        """Per-(model, tier) QuantGr calibration + quality audit.

        Runs one fp32 forward over `g` to record each QuantGr tier's static
        activation scales (`core.models.calibrate_tier`) — once per (model,
        tier); re-calling with another graph is a true no-op unless
        `force=True` (scales AND the audited deltas both keep their first
        graph), because swapping scales mid-traffic would silently change
        every tenant's numerics and re-auditing on a different graph would
        silently change the advertised quality numbers. Every non-fp32
        tier gets its `accuracy_delta_vs_fp32` measured against the fp32
        tier on the held-out part of `g` (test_mask when labeled, argmax
        agreement otherwise), in percentage points. Pure value work: no
        new traces, `assert_warm()` still holds afterwards.
        """
        return self._calibrate(model, self._pad(model, g), force=force)

    def _calibrate(self, model: str, pg: PaddedGraph, *,
                   force: bool = False) -> Dict[str, float]:
        e = self.models[model]
        x = jnp.asarray(pg.features)
        ops = base = None
        # alias tiers (equal Techniques, e.g. GCN int8+grax == int8) share
        # one calibration pytree and one audit forward, like they share a plan
        done_cal: Dict[Techniques, Dict] = {}
        done_delta: Dict[Techniques, float] = {}
        for tier, t in e.tiers.items():
            if tier == "fp32" or (not force and tier in e.accuracy_delta
                                  and (not t.quantgr
                                       or tier in e.calibrations)):
                continue
            if t in done_delta:
                if t.quantgr:
                    e.calibrations[tier] = done_cal[t]
                e.accuracy_delta[tier] = done_delta[t]
                continue
            if ops is None:
                ops = build_operands(pg, e.cfg, lean=True)
                base = forward_grannite(e.params, e.cfg, x, ops,
                                        e.tiers["fp32"])
            if t.quantgr:
                if force or tier not in e.calibrations:
                    e.calibrations[tier] = calibrate_tier(e.params, e.cfg,
                                                          x, ops)
                done_cal[t] = e.calibrations[tier]
            out = forward_grannite(e.params, e.cfg, x, ops, t,
                                   quant=e.calibrations.get(tier))
            done_delta[t] = _delta_points(base, out, pg)
            e.accuracy_delta[tier] = done_delta[t]
        return dict(e.accuracy_delta)

    def _resolve_tier(self, model: str, tier: Optional[str]) -> str:
        """Requested tier -> served tier: model default when unspecified,
        fp32 fallback (counted, never an error) for an uncalibrated QuantGr
        tier — a tenant asking for int8 before anyone calibrated should get
        correct-but-slower answers, not a 500."""
        e = self.models[model]
        tier = tier if tier is not None else e.default_tier
        if tier not in e.tiers:
            raise KeyError(f"model {model!r} has no tier {tier!r} "
                           f"(registered: {sorted(e.tiers)})")
        if e.tiers[tier].quantgr and tier not in e.calibrations:
            self._count("tier_fallbacks")
            return "fp32"
        return tier

    def _tier_for_tolerance(self, model: str, tolerance: float,
                            bucket: int) -> str:
        """Tolerance tier router (§14): the cheapest SERVABLE tier whose
        measured accuracy delta fits the request's tolerance (percentage
        points vs fp32). Candidates: fp32 always (delta 0 by definition),
        plus every tier with a MEASURED delta within tolerance that is
        also servable right now (QuantGr ⇒ calibrated — the router never
        selects a tier `_resolve_tier` would bounce, so the fallback
        contract is preserved by construction, not by luck). Cost is the
        latency bank's prediction at this bucket — measured EWMA when
        samples exist, roofline seed otherwise; an unpredictable tier
        ranks last. fp32 leads the candidate list, so a cost tie (e.g.
        totally cold bank) degrades to the exact path."""
        e = self.models[model]
        cands = ["fp32"]
        for tn in e.tiers:
            if tn == "fp32":
                continue
            delta = e.accuracy_delta.get(tn)
            if delta is None or abs(delta) > tolerance:
                continue
            if e.tiers[tn].quantgr and tn not in e.calibrations:
                continue
            cands.append(tn)

        def cost(tn: str) -> float:
            # MEASURED latencies trump seeds within a tier: once any of
            # the tier's execution variants has real samples, an
            # optimistic roofline seed on a sibling variant cannot mask a
            # measured slowdown. Across tiers the comparison may still mix
            # measured vs seed — that is the cold-start contract.
            m_best, s_best = None, None
            for key in self.bank.keys():
                if key[0] != model or key[1] != bucket or key[2] != tn:
                    continue
                m = self.bank.measured(key)
                if m is not None:
                    m_best = m if m_best is None else min(m_best, m)
                else:
                    p = self.bank.predict(key)
                    if p is not None:
                        s_best = p if s_best is None else min(s_best, p)
            if m_best is not None:
                return m_best
            return s_best if s_best is not None else float("inf")

        return min(cands, key=lambda tn: (cost(tn), cands.index(tn)))

    def _route_tier(self, model: str, tier: Optional[str],
                    tolerance: Optional[float], bucket: int) -> str:
        """Requested (tier, tolerance) -> served tier (§14). An explicit
        tier is a contract: it resolves exactly as before (fallback
        included) and tolerance/governor never override it. A tolerance
        with no tier runs the tolerance router. Neither -> the governor
        (when configured) may downgrade the model default; its pick still
        flows through `_resolve_tier`, so an uncalibrated downgrade target
        falls back to fp32, counted, instead of erroring."""
        if tier is None and tolerance is not None:
            tier = self._tier_for_tolerance(model, tolerance, bucket)
        elif tier is None and self.governor is not None:
            e = self.models[model]
            tier = self.governor.tier_override(e.default_tier, list(e.tiers))
        return self._resolve_tier(model, tier)

    def _resolve_fusion(self, model: str, fusion: Optional[str]) -> str:
        """Requested fusion mode -> served mode: model default when
        unspecified; an unknown name is a caller error (unlike tier
        fallback, there is no quality ladder to degrade along)."""
        fusion = (fusion if fusion is not None
                  else self.models[model].default_fusion)
        if fusion not in FUSION_MODES:
            raise ValueError(f"unknown fusion mode {fusion!r}; "
                             f"pick from {FUSION_MODES}")
        return fusion

    @staticmethod
    def _needs_tier_ops(e: _ModelEntry, tier: str) -> bool:
        """GCN QuantGr tiers consume a per-graph derived operand (the int8
        Â); every other (kind, tier) passes None — consistently per plan,
        so the trace structure never flips."""
        return e.cfg.kind == "gcn" and e.tiers[tier].quantgr

    @staticmethod
    def _grasp_capable(e: _ModelEntry) -> bool:
        """Whether this model can ever dispatch the GraSp backend: a
        non-"dense" mode AND a kind whose aggregation has a block-sparse
        form (GCN's Â @ H today)."""
        return e.agg_backend != "dense" and e.cfg.kind == "gcn"

    def _measured_agg_pair(self, model: str, capacity: int
                           ) -> Tuple[Optional[float], Optional[float]]:
        """Best MEASURED batch latency per agg backend at (model, bucket),
        from the §14 latency bank — the hardware-in-the-loop input to
        `select_agg_backend`. None on either side until that backend has
        served a real dispatch here, which keeps the override inert (the
        roofline decides) for cold paths."""
        best = self.bank.measured_pair(
            match=lambda k: k[0] == model and k[1] == capacity,
            backend_of=lambda k: k[3])
        return best.get("dense"), best.get("grasp")

    def _backend_from_stats(self, e: _ModelEntry, capacity: int,
                            stats: Dict) -> str:
        """Run the density/cost rule (DESIGN.md §10) for one graph at one
        bucket, preferring MEASURED costs (§14) where both backends have
        served here before. Pure decision — `backend_fallbacks`
        accounting happens per REQUEST at the resolution sites (mirroring
        how `tier_fallbacks` counts), never here, so cached decisions and
        fresh ones count identically."""
        mode = "grasp" if e.agg_backend == "grasp" else "auto"
        choice, _, _ = select_agg_backend(
            capacity, e.cfg.hidden, nnz_blocks=stats["nnz_blocks"],
            max_row_nnz=stats["max_row_nnz"], mode=mode,
            measured=self._measured_agg_pair(e.name, capacity))
        return choice

    def _count_forced_fallback(self, e: _ModelEntry, backend: str) -> None:
        """One REQUEST under a forced-grasp model resolved dense (its
        structure exceeds the bucket budget): count it, per request —
        asked for sparse, quietly ran dense, so it must be observable
        (`backend_fallbacks`, same unit as `tier_fallbacks`)."""
        if e.agg_backend == "grasp" and backend == "dense":
            self._count("backend_fallbacks")

    def _derive_grasp(self, e: _ModelEntry, capacity: int, norm_adj
                      ) -> Tuple[str, object]:
        """Counts-first device-side derivation of `_operand_set`, for
        attached and one-shot graphs alike: one cheap jitted bitmap
        reduction feeds the backend rule, and ONLY a grasp-routed graph
        pays the full block gather — so eligibility is always judged
        against the exact (materialized) Â the gather would read, and a
        dense-routed decision costs a reduction, not a structure."""
        ct = np.asarray(self._block_compactor.counts(norm_adj))
        stats = {"nnz_blocks": int(ct.sum()),
                 "max_row_nnz": int(ct.max()) if ct.size else 0}
        backend = self._backend_from_stats(e, capacity, stats)
        bsp = None
        if backend == "grasp":
            bsp, _ = self._block_compactor(norm_adj,
                                           max_nnz=grasp_max_nnz(capacity))
        return backend, bsp

    def _dense_fits(self, cfg: GNNConfig, capacity: int) -> bool:
        """Whether dense operands at this bucket can be held (§16): one
        tenant's cached entry plus a dispatch's stacked copy of
        `batch_slots` of them in half the device's memory, the other half
        left to features, activations and the other tenants."""
        mem = device_memory_bytes()
        if mem is None:
            return True
        entry = estimate_dense_entry_bytes(len(OPERAND_FIELDS[cfg.kind]),
                                           capacity)
        return (self.sc.batch_slots + 1) * entry <= mem // 2

    def _pad(self, model: str, g: Graph,
             capacity: Optional[int] = None) -> PaddedGraph:
        """NodePad `g` into its ladder bucket (or `capacity`), dense where
        the bucket's dense operands fit the device and as its edge list
        where they do not (§16): for a model registered "auto" whose kind
        has an edge form; any other model is refused here, before a dense
        array is built."""
        e = self.models[model]
        cap = (capacity if capacity is not None
               else self.sc.ladder.bucket_for(g.num_nodes))
        if self._dense_fits(e.cfg, cap):
            return pad_graph(g, capacity=cap)
        nbytes = estimate_dense_entry_bytes(len(OPERAND_FIELDS[e.cfg.kind]),
                                            cap)
        if not (e.agg_backend == "auto" and has_edge_form(e.cfg)):
            raise ValueError(
                f"model {model!r}: dense operands at bucket {cap} "
                f"({nbytes} bytes a graph) do not fit the device's "
                f"{device_memory_bytes()} bytes; only SAGE-mean over every "
                f"in-neighbour registered with agg_backend='auto' serves "
                f"such a bucket, from its edge list")
        if e.default_fusion != "none" or any(t.quantgr
                                             for t in e.tiers.values()):
            raise ValueError(
                f"model {model!r}: edge-list graphs serve unfused fp32; "
                f"register it with fusion='none' and no quantized tier")
        return pad_graph(g, capacity=cap, dense=False)

    def _partition(self, model: str, g: Graph) -> GraphShards:
        """Partition a graph past the top bucket (§12); sharded plans run
        two-layer models without BatchNorm only."""
        cfg = self.models[model].cfg
        if cfg.num_layers != 2 or cfg.batch_norm:
            raise ValueError(f"model {model!r}: sharded plans run two-layer "
                             f"models without BatchNorm")
        return partition_for_ladder(g.edge_index, g.num_nodes,
                                    self.sc.ladder, self.sc.shard_counts,
                                    method=self.sc.partition_method)

    def _edge_operands(self, graph_id: Optional[int], pg: PaddedGraph
                       ) -> EdgeOperands:
        """Build and upload one edge-form graph's operands, under an
        `operands.edges` span (id: the graph, None for a one-shot
        request), their bytes counted in `operand_bytes_h2d`."""
        with self.tracer.span("operands.edges", self.clock, graph_id):
            eo = edge_operands(pg)
        self._count("operand_bytes_h2d", pytree_nbytes(eo))
        return eo

    # ------------------------------------------------------------------ intake
    def _resident(self, kind: str, key: Optional[Tuple[int, int]], build,
                  *, count: bool = False):
        """One cached form of an attached graph (§7, §13): the entry of
        `kind` at `key` = (graph_id, structure_version), or `build()` run
        OUTSIDE the engine lock and inserted only if that version is still
        current — a request racing an `update()` serves the snapshot it
        read, and a stale build never pins device memory under a dead key.
        Two workers missing one key may both build; the values are
        identical. An evicted operand entry faults back from its host-RAM
        spill form (`cache_spill_hits`, compact bytes over the link again,
        no host packing), which is not a miss; only the operand kind spills
        — a slice tuple or an edge list re-derives from the engine's own
        registry. `count` feeds a primary's hits and misses to
        `operand_cache_hits`/`_misses`. With `key=None` (a one-shot
        request) it builds and caches nothing."""
        if key is None:
            return build()
        with self._lock:
            value = self._cache.get(kind, key)
            if value is not None:
                if count:
                    self.metrics["operand_cache_hits"] += 1
                return value
            spilled = self._cache.spill_get(kind, key)
        if spilled is not None:
            self._count("cache_spill_hits")
            self._count("operand_bytes_h2d", spilled.nbytes)
            value = realize_operands(spilled, self._materializer)
        else:
            if count:
                self._count("operand_cache_misses")
            value = build()
        nb = pytree_nbytes(value)
        with self._lock:
            if self._graph_version.get(key[0]) == key[1]:
                # a derived form re-derives on the device from its primary
                # (which its insert never evicts: the manager protects the
                # inserted key); the rest cross the link again
                self._cache.put(
                    kind, key, value, nbytes=nb,
                    remat_s=(0.0 if kind in ("tier", "grasp")
                             else transfer_cost(nb)),
                    spill_fn=(self._operand_spill_fn(key)
                              if kind == "operand" else None))
        return value

    def _operand_set(self, e: _ModelEntry, pg: PaddedGraph, tier: str,
                     key: Optional[Tuple[int, int]] = None
                     ) -> Tuple[str, object, Optional[TierOperands]]:
        """The one place an unsharded request's operands are decided:
        `(backend, ops, tier_ops)` for a request served at `tier`. An
        edge-form graph (§16) takes its `EdgeOperands`. A dense graph takes
        its fp32 operands (`_device_operands`), the int8 Â a QuantGr GCN
        tier reads (§8), and its agg backend with the block structure a
        grasp-routed graph reads (§10), both derived on the device from
        the fp32 Â. With `key`, an attached graph's (graph_id, version),
        every form is cached once per version (`_resident`); a one-shot
        request (`key=None`) builds them for itself alone."""
        if not pg.dense:
            gid = key[0] if key is not None else None
            return "edges", self._resident(
                "edges", key, lambda: self._edge_operands(gid, pg),
                count=True), None
        ops = self._resident("operand", key,
                             lambda: self._device_operands(e.cfg, pg),
                             count=True)
        tops = None
        if self._needs_tier_ops(e, tier):
            tops = self._resident("tier", key,
                                  lambda: self._agg_quantizer(ops.norm_adj))
        backend = "dense"
        if self._grasp_capable(e) and not e.tiers[tier].quantgr:
            backend, bsp = self._resident(
                "grasp", key,
                lambda: self._derive_grasp(e, pg.capacity, ops.norm_adj))
            self._count_forced_fallback(e, backend)   # per request, cached
            if backend == "grasp":                    # decision or not
                ops = dataclasses.replace(ops, block_sparse=bsp)
        return backend, ops, tops

    def _device_operands(self, cfg: GNNConfig, pg: PaddedGraph
                         ) -> GranniteOperands:
        """Build one graph's device-resident operands: the HOST stage
        (`prepare_host_operands` — CacheG compact packing, or the eager
        dense build for directed GCN/GAT graphs, counted as
        `cacheg_fallbacks`) followed immediately by the DEVICE stage
        (`realize_operands`). The pipeline scheduler runs the same two
        calls, just on a host worker thread."""
        ho = prepare_host_operands(pg, cfg)
        self._count("operand_bytes_h2d", ho.nbytes)
        if ho.fallback:
            self._count("cacheg_fallbacks")
        return realize_operands(ho, self._materializer)

    def _device_features(self, pg: PaddedGraph) -> jnp.ndarray:
        """Put one graph's padded (cap, F) features on the device, counted
        in `feature_bytes_h2d`: once per CacheG miss of an attached graph,
        once per one-shot request, always in the host stage."""
        x = jnp.asarray(pg.features)
        self._count("feature_bytes_h2d", x.nbytes)
        return x

    def _prepare(self, model: str, pg: PaddedGraph, tier: str, backend: str,
                 ops, tier_ops: Optional[TierOperands],
                 x: Optional[jnp.ndarray], *,
                 fusion: Optional[str] = None,
                 submitted_s: Optional[float] = None,
                 deadline_ms: Optional[float] = None,
                 tolerance: Optional[float] = None,
                 bucket: Optional[int] = None, **sharded) -> GNNRequest:
        """Host-stage tail shared by every intake path, once the served
        tier, backend, operands and features are resolved: resolve the
        fusion mode and assign the uid. Returns the ready-to-dispatch
        request WITHOUT touching the engine queue — the sync path pushes it
        (`_push`), the pipeline scheduler hands it to its own ready stage.
        `submitted_s` lets the scheduler pin latency accounting to intake
        time (queue wait included) rather than to host-stage completion;
        `deadline_ms` is RELATIVE to that same submit instant, so queue
        wait spends the budget. A sharded request passes its shard
        `bucket` and the rest of its calling convention (`sharded`)."""
        now = self.clock.now()
        submitted_s = submitted_s if submitted_s is not None else now
        fusion = self._resolve_fusion(model, fusion)
        if not pg.dense and fusion != "none":
            raise ValueError("edge-list graphs serve fusion='none' only "
                             "(DESIGN.md §16)")
        with self._lock:
            uid = self._uid
            self._uid += 1
            if self.metrics["first_submit_s"] is None:
                self.metrics["first_submit_s"] = submitted_s
        deadline_s = (submitted_s + deadline_ms * 1e-3
                      if deadline_ms is not None else None)
        return GNNRequest(uid=uid, model=model, pg=pg, ops=ops,
                          bucket=bucket or pg.capacity,
                          submitted_s=submitted_s, x=x, tier=tier,
                          backend=backend, fusion=fusion, tier_ops=tier_ops,
                          deadline_s=deadline_s, tolerance=tolerance,
                          **sharded)

    def _push(self, req: GNNRequest) -> int:
        self.queue.append(req)
        return req.uid

    def prepare_submit(self, g: Graph, *, model: str,
                       tier: Optional[str] = None,
                       fusion: Optional[str] = None,
                       submitted_s: Optional[float] = None,
                       deadline_ms: Optional[float] = None,
                       tolerance: Optional[float] = None) -> GNNRequest:
        """HOST stage of a one-shot request: NodePad padding + operand
        build/packing, nothing cached. Scheduler-callable from any worker
        thread."""
        if submitted_s is None:         # the host stage counts as latency
            submitted_s = self.clock.now()
        pg = self._pad(model, g)
        tier = self._route_tier(model, tier, tolerance, pg.capacity)
        return self._prepare(model, pg, tier,
                             *self._operand_set(self.models[model], pg, tier),
                             self._device_features(pg), fusion=fusion,
                             submitted_s=submitted_s, deadline_ms=deadline_ms,
                             tolerance=tolerance)

    def submit(self, g: Graph, *, model: str,
               tier: Optional[str] = None,
               fusion: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               tolerance: Optional[float] = None) -> int:
        """One-shot inference request over a static graph. `deadline_ms`
        (relative to now) and `tolerance` (max accuracy points traded by
        the tier router) opt the request into the §14 SLO machinery."""
        return self._push(self.prepare_submit(g, model=model, tier=tier,
                                              fusion=fusion,
                                              deadline_ms=deadline_ms,
                                              tolerance=tolerance))

    def attach(self, g: Graph, *, model: str, calibrate: bool = True) -> int:
        """Register an evolving graph; returns a graph_id for update/query.

        Operands materialize lazily on the first `query()` and stay cached
        on device until `update()` changes the structure. The first attach
        to a model with uncalibrated non-fp32 tiers also runs the (model,
        tier) calibration on this graph (`calibrate=False` to defer to an
        explicit `calibrate()` call).

        A graph exceeding the TOP ladder bucket auto-shards (§12) when
        `shard_counts` is configured: `partition_for_ladder` picks the
        smallest configured shard count whose balanced per-shard load
        admits into the ladder, and every query over this graph_id
        dispatches through the sharded plan. Without `shard_counts` the
        oversized graph raises, exactly as before.

        Where the bucket's dense operands do not fit the device, the graph
        keeps its edge list (§16, `_pad`), and its edge operands are built
        and made resident here (after an `update()`, by the next query).

        With `device_cache_budget_bytes` set, attach() is the admission
        gate (§13): a graph whose projected primary operand entry can
        NEVER fit the budget raises `CacheAdmissionError` outright; under
        `admission="reject"` one that would overflow the CURRENT residency
        raises too, while the default `admission="evict"` admits it and
        lets insert-time eviction make room on first query."""
        part = None
        try:
            cap = self.sc.ladder.bucket_for(g.num_nodes)
        except ValueError:
            if not self.sc.shard_counts:
                raise
            part = self._partition(model, g)
            pg = pad_graph(g, capacity=part.full_rows)
        else:
            pg = self._pad(model, g, cap)
        if self.sc.device_cache_budget_bytes is not None:
            projected = self._projected_primary_bytes(model, pg, part)
            with self._lock:
                reject = (not self._cache.fits(projected)
                          or (self.sc.admission == "reject"
                              and self._cache.would_overflow(projected)))
                if reject:
                    self.metrics["cache_admission_rejects"] += 1
            if reject:
                raise CacheAdmissionError(
                    f"graph with projected primary operand entry of "
                    f"{projected} bytes cannot be admitted under "
                    f"device_cache_budget_bytes="
                    f"{self.sc.device_cache_budget_bytes} "
                    f"(policy {self.sc.admission!r}, "
                    f"{self._cache.resident_bytes} resident)")
        if calibrate:
            self._calibrate(model, pg)      # no-op once (model, tier) is done
        with self._lock:
            gid = self._gid
            self._gid += 1
            self.graphs[gid] = (model, pg)
            self._graph_version[gid] = 0
            if part is not None:
                self._sharded[gid] = (part, g)
        if not pg.dense:
            # the host work of the edge form happens here, not in the
            # first query's host stage
            self._resident("edges", (gid, 0),
                           lambda: self._edge_operands(gid, pg))
        return gid

    def detach(self, graph_id: int) -> None:
        """Release an attached graph, its device-resident operands and its
        device features.

        The cache pins O(cap²) float32 per attached graph in device memory
        (~32 MB for GAT at cap=2048), plus O(cap²) int8 per graph that took
        a QuantGr GCN tier. Without a `device_cache_budget_bytes` the
        manager never evicts, so long-running unbudgeted multi-tenant
        servers must detach graphs they stop serving; WITH a budget (§13)
        cost-aware LRU eviction bounds residency instead, and detach is
        how a tenant's spilled host-RAM form is released too. Lifecycle
        removal is not an eviction: detaching touches no eviction/spill
        counter.
        """
        with self._lock:
            key = (graph_id, self._graph_version.pop(graph_id, -1))
            self._cache.invalidate(key)
            self._sharded.pop(graph_id, None)
            self.graphs.pop(graph_id, None)

    def update(self, graph_id: int, edge_index: np.ndarray, num_nodes: int,
               features: np.ndarray) -> bool:
        """GrAd update of an attached graph; True if it climbed the ladder.

        Bumps the structure version, which invalidates the CacheG operand
        cache — the next `query()` re-materializes exactly once.

        Sharded graphs (§12) re-partition on every structure update (the
        edge-cut depends on the edges): an unchanged (shard count, shard
        bucket) pair is a pure value update like the unsharded case, a
        changed one counts as a rebucket. A graph that shrinks back into
        the ladder leaves the sharded path; an unsharded graph that grows
        past the top bucket enters it (rebucket either way). An edge-form
        graph (§16) is padded anew from the new edge list."""
        with self._lock:
            model, pg = self.graphs[graph_id]
            sharded = self._sharded.get(graph_id)
        new_sharded = None
        if sharded is None and pg.dense and num_nodes <= pg.capacity:
            # GrAd inside the bucket: a pure value update, no recompile
            pg, rebucketed = self.sc.ladder.grow(pg, edge_index, num_nodes,
                                                 features)
        else:
            held = sharded[1] if sharded is not None else pg

            # carry supervision arrays across the size change: new nodes
            # are unlabeled, shrinks truncate — a stale (old-length)
            # labels array would break padding at the first size change
            def _resized(arr, fill, dtype):
                if arr is None:
                    return None
                out = np.full((num_nodes,), fill, dtype=dtype)
                m = min(num_nodes, len(arr))
                out[:m] = arr[:m]
                return out

            g2 = Graph(edge_index=edge_index, num_nodes=num_nodes,
                       features=features,
                       labels=_resized(held.labels, -1, np.int32),
                       train_mask=_resized(held.train_mask, False, bool),
                       test_mask=_resized(held.test_mask, False, bool))
            try:
                cap = self.sc.ladder.bucket_for(num_nodes)
            except ValueError:
                if not self.sc.shard_counts:
                    raise
                # past the top of the ladder: the sharded path
                part2 = self._partition(model, g2)
                pg = pad_graph(g2, capacity=part2.full_rows)
                new_sharded = (part2, g2)
                rebucketed = sharded is None or (
                    (part2.shards, part2.shard_cap)
                    != (sharded[0].shards, sharded[0].shard_cap))
            else:
                # a new bucket, back from the sharded path, or an edge-form
                # graph, which keeps no dense form to update in place
                rebucketed = sharded is not None or cap != pg.capacity
                pg = self._pad(model, g2, cap)
        with self._lock:
            self.graphs[graph_id] = (model, pg)
            ver = self._graph_version[graph_id]
            # lifecycle invalidation, not eviction: a no-op on keys the
            # graph never populated (attach-then-update before any query),
            # and never counted in the §13 eviction/spill metrics
            self._cache.invalidate((graph_id, ver))
            if new_sharded is not None:
                self._sharded[graph_id] = new_sharded
            else:
                self._sharded.pop(graph_id, None)
            self._graph_version[graph_id] = ver + 1
            if rebucketed:
                self.metrics["rebucket_events"] += 1
        return rebucketed

    # ---------------------------------------------------- GrAd delta updates
    def _delta_spec(self, cap: int, fields: Tuple[str, ...], flip_i, flip_j,
                    flip_v, touched, dis, dirty=None) -> DeltaSpec:
        """Pad one host-computed edge delta to the engine's static patcher
        widths (§13): flips to K_e, touched rows to K_t, both by REPEATING
        the first entry — duplicate-index scatters write identical values
        and duplicate row renorms recompute the same bits, so the pads are
        numerically inert and the trace count stays bounded. `dirty` (§15)
        is the boundary-dirty subset a sharded halo-delta exchange must
        move; it pads to K_t repeating a touched row (the patch math never
        reads it, and a duplicate dirty row re-sends the same bits), and
        an unsharded delta — or one confined to shard interiors — carries
        the inert all-touched[0] pad."""
        kt, ke = self._delta_pads(cap)

        def _pad(a, k, dtype):
            out = np.full((k,), a[0], dtype=dtype)
            out[:len(a)] = a
            return jnp.asarray(out)

        d = np.asarray(dirty if dirty is not None and len(dirty)
                       else touched[:1])
        return DeltaSpec(flip_i=_pad(flip_i, ke, np.int32),
                         flip_j=_pad(flip_j, ke, np.int32),
                         flip_v=_pad(flip_v, ke, np.float32),
                         touched=_pad(touched, kt, np.int32),
                         dirty=_pad(d, kt, np.int32),
                         dis=jnp.asarray(dis.astype(np.float32)),
                         fields=fields)

    def _requant_rows(self, delta, cap: int):
        """Rows of the int8 Â a delta forces through re-quantization:
        touched rows themselves plus every row adjacent (new structure) to
        a touched node — their entries rescale with the touched dis even
        though their own degree is unchanged. Returns the padded row index
        vector, or None when the set exceeds the warmed width K_r (the
        caller re-quantizes the full matrix through the per-bucket
        `_agg_quantizer` instead — also warm)."""
        kr = min(2 * self.sc.delta_pad_rows, cap)
        neigh = np.flatnonzero(delta.adj[:, delta.touched].any(axis=1))
        rows = np.union1d(delta.touched, neigh).astype(np.int64)
        if len(rows) > kr:
            return None
        out = np.full((kr,), rows[0], np.int32)
        out[:len(rows)] = rows
        return jnp.asarray(out)

    def _patch_shard_slices(self, e: _ModelEntry, part: GraphShards,
                            slices: Tuple[ShardSlice, ...], delta,
                            dirty: Optional[np.ndarray] = None
                            ) -> Tuple[ShardSlice, ...]:
        """Device-patch a sharded slice tuple (§13): concatenate the shard
        row blocks back into the (full, full) permuted operand matrices,
        run the SAME warm patch trace in SLOT coordinates (flip/touched
        indices through the inverse permutation, dis permuted), re-slice.
        Features and node masks are untouched — an edge delta moves no
        nodes and the partition is deliberately KEPT (a fresh partition
        would reshuffle slots and force a full rebuild, defeating the
        patch). `dirty` (§15) is the boundary-dirty row set in ORIGINAL
        node ids; it rides the spec in slot coordinates — the set a
        distributed deployment would push through
        `dist.compress.compressed_psum_delta` instead of re-exchanging
        full halos, and what the engine's `delta_halo_bytes_*` counters
        price."""
        full, c = part.full_rows, part.shard_cap
        invperm = np.empty((full,), np.int64)
        invperm[part.perm] = np.arange(full)
        fields = OPERAND_FIELDS[e.cfg.kind]
        spec = self._delta_spec(full, fields,
                                invperm[delta.flip_i].astype(np.int64),
                                invperm[delta.flip_j].astype(np.int64),
                                delta.flip_v,
                                np.sort(invperm[delta.touched]),
                                delta.dis[part.perm],
                                dirty=(np.sort(invperm[dirty])
                                       if dirty is not None and len(dirty)
                                       else None))
        hole = jnp.zeros((1, 1), jnp.float32)
        cat = {f: jnp.concatenate([getattr(s.ops, f) for s in slices],
                                  axis=0) for f in fields}
        full_ops = GranniteOperands(**{
            f: cat.get(f, hole) for f in ("norm_adj", "mask_mult",
                                          "bias_add", "sample_mask",
                                          "mean_mask")})
        patched = self._delta_patcher(full_ops, spec)
        out = []
        for idx, s in enumerate(slices):
            blk = {f: getattr(patched, f)[idx * c:(idx + 1) * c]
                   for f in fields}
            out.append(dataclasses.replace(
                s, ops=dataclasses.replace(s.ops, **blk)))
        return tuple(out)

    def update_delta(self, graph_id: int, add_edges=None,
                     remove_edges=None) -> bool:
        """GrAd INCREMENTAL structure update (§13): patch, don't rebuild.

        `add_edges` / `remove_edges` are (k, 2) arrays of UNDIRECTED node
        pairs (directed graphs raise — take the full `update()` path).
        The host patches the packed adjacency and renormalizes only the
        touched rows/cols of Â (`core.graph.apply_edge_delta`); every
        device-resident cached form of the graph is then patched IN PLACE
        of a rebuild through the warm `DeltaPatcher` traces — fp32 Â
        row/col renorm, GAT mask/bias rescatter, int8 Â re-quantization of
        exactly the rows whose fp32 values changed, grasp block-list
        re-derivation from the patched Â, the unchanged device features
        carried over as they are, and on sharded graphs the
        concatenated permuted row blocks with the partition (and halo
        observability via `core.partition.patch_halo`) carried forward.
        The patched entries land under the NEW (graph_id, version+1) key —
        cached arrays are never mutated, so a request racing this update
        serves its snapshot unharmed, and the per-key lifecycle contract
        holds unchanged.

        Falls back to the full `update()` rebuild — counted in
        `delta_fallbacks` — when the delta exceeds the warmed patch widths
        (more than `delta_pad_rows` touched nodes or 2x that many edge
        flips), the kind is SAGE (its sampled mask is not incrementally
        patchable), or `delta_pad_rows=0` disabled patching. Ineffective
        deltas (all edges already present/absent) return True without
        bumping the version: every cache entry is still exact.

        Returns True when the structure was patched incrementally (or the
        delta was a no-op), False when it fell back to `update()`.
        """
        with self._lock:
            model, pg = self.graphs[graph_id]
            ver = self._graph_version[graph_id]
            sharded = self._sharded.get(graph_id)
        e = self.models[model]
        if not pg.dense:
            # §16: no dense form to patch; the edge list changes and the
            # graph rebuilds, as SAGE's sampled masks do
            edge_index = edge_list_delta(pg.edge_index, pg.num_nodes,
                                         add_edges, remove_edges)
            if edge_index is None:
                return True
            self._count("delta_fallbacks")
            self.update(graph_id, edge_index, pg.num_nodes,
                        pg.features[:pg.num_nodes])
            return False
        if not is_symmetric_adjacency(pg.adj):
            raise ValueError(
                "update_delta edits undirected edge pairs; directed "
                "graphs must take the full update() path")
        delta = apply_edge_delta(pg.adj, pg.norm_adj, pg.num_nodes,
                                 add_edges, remove_edges)
        if delta is None:
            return True          # nothing effective changed: caches stand
        kt, ke = self._delta_pads(pg.capacity)
        patchable = (self.sc.delta_pad_rows > 0
                     and e.cfg.kind in ("gcn", "gat")
                     and len(delta.touched) <= kt
                     and len(delta.flip_i) <= ke)
        if not patchable:
            # §13 delta-vs-rebuild threshold: past the warmed patch widths
            # (or for SAGE's sampled mask) a rebuild is both simpler and
            # cheaper than a cascade of patches — reuse update() verbatim
            self._count("delta_fallbacks")
            edge_index = edge_index_from_adjacency(delta.adj, pg.num_nodes)
            feats = (sharded[1].features if sharded is not None
                     else pg.features[:pg.num_nodes])
            self.update(graph_id, edge_index, pg.num_nodes, feats)
            return False
        pg2 = dataclasses.replace(pg, adj=delta.adj, norm_adj=delta.norm_adj)
        old_key, new_key = (graph_id, ver), (graph_id, ver + 1)
        if sharded is not None:
            part, g = sharded
            edge_index = edge_index_from_adjacency(delta.adj, pg.num_nodes)
            g2 = dataclasses.replace(g, edge_index=edge_index)
            part2 = patch_halo(part, edge_index)
            # §15 halo-delta: only the touched rows with a cross-shard
            # neighbor in the patched structure have remote copies to
            # refresh — that set (not the full halo) is what crosses the
            # wire, priced at the exact fp32 rate the operand patch
            # requires (a compressed dirty exchange would break the
            # patched-equals-rebuilt bit contract)
            dirty = delta.boundary_rows(part.assignment, pg.num_nodes)
            fields = OPERAND_FIELDS[e.cfg.kind]
            full = part.full_rows
            # each dirty row ships its operand rows plus its D^-1/2 entry;
            # an interior delta (no dirty rows) is wire-FREE — no remote
            # shard holds a copy of a non-boundary row
            delta_elems = len(dirty) * (full * len(fields) + 1)
            full_elems = len(fields) * full * full + full
            delta_bytes = int(ring_psum_nbytes(part.shards, delta_elems,
                                               bytes_per_elt=4))
            full_bytes = int(ring_psum_nbytes(part.shards, full_elems,
                                              bytes_per_elt=4))
            with self._lock:
                slices = self._cache.get("shard", old_key)
            new_slices = None
            if slices is not None:
                new_slices = self._patch_shard_slices(e, part, slices,
                                                      delta, dirty=dirty)
            with self._lock:
                if self._graph_version.get(graph_id) != ver:
                    return False          # a racing update/detach won
                self.graphs[graph_id] = (model, pg2)
                self._sharded[graph_id] = (part2, g2)
                self._cache.invalidate(old_key)
                self._graph_version[graph_id] = ver + 1
                if new_slices is not None:
                    nb = pytree_nbytes(new_slices)
                    self._cache.put("shard", new_key, new_slices, nbytes=nb,
                                    remat_s=transfer_cost(nb))
                self.metrics["delta_updates"] += 1
                self.metrics["delta_halo_bytes_exchanged"] += delta_bytes
                self.metrics["delta_halo_bytes_full"] += full_bytes
                self.metrics["delta_dirty_rows"] += len(dirty)
            return True
        with self._lock:
            ops_old = self._cache.get("operand", old_key)
            tops_old = self._cache.get("tier", old_key)
            had_grasp = self._cache.get("grasp", old_key) is not None
            # an edge delta leaves the features as they are: the same
            # buffer moves to the new key
            x_old = self._cache.get("features", old_key)
        new_ops = new_tops = new_grasp = None
        if ops_old is not None:
            fields = OPERAND_FIELDS[e.cfg.kind]
            spec = self._delta_spec(pg.capacity, fields, delta.flip_i,
                                    delta.flip_j, delta.flip_v,
                                    delta.touched, delta.dis)
            new_ops = self._delta_patcher(ops_old, spec)
            if tops_old is not None:
                rows = self._requant_rows(delta, pg.capacity)
                if rows is None:
                    new_tops = self._agg_quantizer(new_ops.norm_adj)
                else:
                    new_tops = self._delta_patcher.patch_tier(
                        tops_old, new_ops.norm_adj, rows)
            if had_grasp and self._grasp_capable(e):
                # the block structure cannot be patched sparsely (a flip
                # moves rows between blocks) but re-deriving from the
                # PATCHED device Â is still zero host bytes and warm
                new_grasp = self._derive_grasp(e, pg.capacity,
                                               new_ops.norm_adj)
        with self._lock:
            if self._graph_version.get(graph_id) != ver:
                return False              # a racing update/detach won
            self.graphs[graph_id] = (model, pg2)
            self._cache.invalidate(old_key)
            self._graph_version[graph_id] = ver + 1
            if new_ops is not None:
                nb = pytree_nbytes(new_ops)
                self._cache.put(
                    "operand", new_key, new_ops, nbytes=nb,
                    remat_s=transfer_cost(nb),
                    spill_fn=self._operand_spill_fn(new_key))
            if new_tops is not None:
                self._cache.put("tier", new_key, new_tops,
                                nbytes=pytree_nbytes(new_tops))
            if new_grasp is not None:
                self._cache.put("grasp", new_key, new_grasp,
                                nbytes=pytree_nbytes(new_grasp))
            if x_old is not None:
                self._cache.put("features", new_key, x_old,
                                nbytes=x_old.nbytes,
                                remat_s=transfer_cost(x_old.nbytes))
            self.metrics["delta_updates"] += 1
        return True

    def prepare_query(self, graph_id: int, *, tier: Optional[str] = None,
                      fusion: Optional[str] = None,
                      submitted_s: Optional[float] = None,
                      deadline_ms: Optional[float] = None,
                      tolerance: Optional[float] = None) -> GNNRequest:
        """HOST stage of a query over an attached graph's current snapshot,
        optionally pinning a quality tier and/or fusion mode (model
        defaults otherwise).

        CacheG hit path: an unchanged structure serves straight from the
        device-resident cache — zero host-side operand construction, zero
        operand or feature bytes over the link (the padded features are
        cached beside the operands, put on the device once per version;
        `update()` is what changes them). The operand set is
        `_operand_set`'s, the same routine a one-shot submit runs, here
        keyed by (graph_id, version): the fp32 operands, and the forms
        DERIVED from them once per version — the int8 Â QuantGr GCN tiers
        read and the GraSp decision with its block structure (DESIGN.md
        §10). The keys carry NO tier, so mixed-tier traffic over one graph
        shares one entry of each; `update()` invalidates them all, and
        `detach()` releases them.

        Thread discipline (the scheduler calls this from host workers while
        `update()` may arrive concurrently): the (model, pg, version)
        triple is snapshotted under the engine lock, and every form is
        built outside it and inserted only if the version is still current
        (`_resident`).
        """
        with self._lock:
            model, pg = self.graphs[graph_id]
            ver = self._graph_version[graph_id]
            sharded = self._sharded.get(graph_id)
        if sharded is not None:
            if fusion not in (None, "none"):
                raise ValueError(
                    "sharded graphs serve fusion='none' only — the shard "
                    "axis occupies the plan dimension fused layers batch "
                    "over (DESIGN.md §12)")
            return self._prepare_sharded(graph_id, model, pg, sharded,
                                         ver, tier=tier,
                                         submitted_s=submitted_s,
                                         deadline_ms=deadline_ms,
                                         tolerance=tolerance)
        key = (graph_id, ver)
        tier = self._route_tier(model, tier, tolerance, pg.capacity)
        backend, ops, tops = self._operand_set(self.models[model], pg, tier,
                                               key)
        x = self._resident("features", key, lambda: self._device_features(pg))
        return self._prepare(model, pg, tier, backend, ops, tops, x,
                             fusion=fusion, submitted_s=submitted_s,
                             deadline_ms=deadline_ms, tolerance=tolerance)

    def _prepare_sharded(self, graph_id: int, model: str, pg: PaddedGraph,
                         sharded: Tuple[GraphShards, Graph], ver: int, *,
                         tier: Optional[str],
                         submitted_s: Optional[float],
                         deadline_ms: Optional[float] = None,
                         tolerance: Optional[float] = None) -> GNNRequest:
        """HOST stage of a query over an auto-sharded graph (§12).

        The CacheG unit here is the tuple of per-shard `ShardSlice`s —
        built once per (graph_id, structure_version) by
        `build_sharded_operands` (full-capacity operands permuted into
        slot layout and sliced into rectangular row blocks), cached and
        invalidated exactly like the dense operand cache (same
        hit/miss accounting, same version-checked insert against racing
        updates). Tier resolution is unchanged — QuantGr tiers serve
        through the model calibration, uncalibrated ones fall back to
        fp32; the sharded GCN int8 path re-derives the int8 Â in-trace
        from its complete row block, so no sharded tier-operand cache
        exists. Backend is always dense and fusion always "none": the
        batch key's shard element is what keeps these dispatches from
        mixing with unsharded ones."""
        part, g = sharded
        e = self.models[model]
        resolved = self._route_tier(model, tier, tolerance, part.shard_cap)
        slices = self._resident(
            "shard", (graph_id, ver),
            lambda: build_sharded_operands(g, part, e.cfg), count=True)
        x, ops, mask = stack_shard_slices(slices)
        return self._prepare(model, pg, resolved, "dense", ops, None, None,
                             fusion="none", submitted_s=submitted_s,
                             deadline_ms=deadline_ms, tolerance=tolerance,
                             bucket=part.shard_cap, shards=part.shards,
                             part=part, shard_x=x, shard_mask=mask)

    def query(self, graph_id: int, *, tier: Optional[str] = None,
              fusion: Optional[str] = None,
              deadline_ms: Optional[float] = None,
              tolerance: Optional[float] = None) -> int:
        """Enqueue inference over an attached graph (see `prepare_query`)."""
        return self._push(self.prepare_query(graph_id, tier=tier,
                                             fusion=fusion,
                                             deadline_ms=deadline_ms,
                                             tolerance=tolerance))

    # --------------------------------------------------------------- execution
    def run(self) -> List[GNNRequest]:
        while self.queue:
            self._run_batch()
        return self.finished

    def _complete_expired(self, expired: List[GNNRequest],
                          now: float) -> None:
        """Finish requests whose deadline passed BEFORE dispatch (§14):
        they complete immediately with `deadline_missed=True` and no
        predictions — an answer the caller can no longer use must not
        occupy batch slots ahead of ones that still can. Counted per
        request in `deadline_misses`; their (submit → expiry) latency
        still feeds the metrics and the governor, because an expired
        request IS the overload signal the governor exists to see."""
        for r in expired:
            r.done = True
            r.deadline_missed = True
            r.finished_s = now
            r.x = None          # a finished request pins no device features
        with self._lock:
            for r in expired:
                self.metrics["latency_s"].append(now - r.submitted_s)
                self.metrics["deadline_misses"] += 1
                self.finished.append(r)
                if self.governor is not None:
                    self.governor.observe(now - r.submitted_s)
            self.metrics["last_finish_s"] = now

    def _run_batch(self) -> None:
        # expiry sweep first (§14): requests already past their deadline
        # complete flagged instead of wasting a dispatch
        now = self.clock.now()
        expired = [r for r in self.queue
                   if r.deadline_s is not None and r.deadline_s <= now]
        if expired:
            gone = {r.uid for r in expired}
            self.queue = [r for r in self.queue if r.uid not in gone]
            self._complete_expired(expired, now)
            if not self.queue:
                return
        # best-filling key first (not queue[0]'s — see best_fill_key), with
        # slack as the fill tie-break (edf_best_fill_key): a lone odd
        # request at the head no longer forces a 1-of-N dispatch
        # while fully-fillable keys wait behind it. Tier, agg backend AND
        # fusion mode are part of the batch key: all three select
        # different compiled plans, so a slot can never mix execution
        # variants.
        key = edf_best_fill_key(edf_pending_stats(self.queue, now),
                                self.sc.batch_slots, self._last_dispatch,
                                replica_slots=self.sc.replica_groups)
        # sharded: one request per replica row (§15; width-1 when R == 1)
        take = self.sc.replica_groups if key[5] else self.sc.batch_slots
        batch = [r for r in self.queue if r.batch_key == key][:take]
        taken = {r.uid for r in batch}
        self.queue = [r for r in self.queue if r.uid not in taken]
        self._execute_batch(batch)

    def _execute_batch(self, batch: List[GNNRequest]) -> None:
        """DEVICE stage: one fixed-width dispatch of same-key requests.

        Called with 1..batch_slots requests sharing one (model, bucket,
        tier, backend, fusion) key, from exactly ONE thread at a time (the sync
        `run()` loop, or the pipeline scheduler's dispatcher). Junk slots
        repeat a real request so batch width never changes shape; their
        outputs are dropped. Every request arrives with its features on the
        device (cached per version, or put there in its host stage), so
        nothing crosses the host→device link here. The slots' features
        and their operands are each stacked there by ONE compiled program
        (`_stack_x`; `_stack_operands`, which also stacks the tier
        operands, or `_stack_edges` for edge-form slots), so a dispatch
        launches three programs: two stacks and the plan. The dispatch is a
        `dispatch` span tiled by four children (`dispatch.stack`,
        `.operands`, `.device`, `.d2h`; runtime/tracing.py), all under one
        dispatch serial.
        `device_busy_s` accumulates host clock from the dispatch span's
        start to the end of its d2h child — the whole stage as the host
        sees it, which `summary()["dispatch_idle_fraction"]` is measured
        against. A grasp dispatch whose plan was TRACED through the
        `ref` kernel routing ran the aggregation dense (plain XLA over the
        block form, no skip grid) — every request in it is counted as
        `backend_fallbacks` so the degradation is observable, never
        invisible.

        A SHARDED request (shards > 0) routes to `_execute_sharded`
        instead: its dispatch width is `replica_groups` (§15; the shard
        axis occupies the dim a batched plan would use, and the replica
        axis — when configured — is the sharded batch dim), and both
        drivers — the sync `run()` loop and the pipeline scheduler, whose
        `_take_locked` takes the same width for a sharded key — arrive
        here with 1..replica_groups same-key requests.
        """
        head = batch[0]
        if head.shards:
            self._execute_sharded(batch)
            return
        b = self.sc.batch_slots
        bkey = head.batch_key
        serial = self._next_serial()
        span = self.tracer.span
        with span("dispatch", self.clock, serial) as disp:
            # fixed batch width: junk slots repeat a real request, outputs
            # dropped
            slots = batch + [batch[-1]] * (b - len(batch))
            e = self.models[head.model]
            # CacheG: r.x and r.ops are device-resident (cached, or built in
            # the host stage), so both stacks are device-side concats, one
            # compiled program each (DESIGN.md §7)
            with span("dispatch.stack", self.clock, serial):
                x = self._stack_x([r.x for r in slots])
            with span("dispatch.operands", self.clock, serial):
                if head.backend == "edges":
                    ops, tops = self._stack_edges([r.ops for r in slots]), None
                else:
                    ops, tops = self._stack_operands(
                        [r.ops for r in slots],
                        [r.tier_ops for r in slots]
                        if head.tier_ops is not None else None)
            # an edges dispatch names the real nodes and edges it aggregates
            real = ({"nodes": sum(r.pg.num_nodes for r in batch),
                     "edges": sum(r.pg.edge_index.shape[1] for r in batch)}
                    if head.backend == "edges" else {})
            with span("dispatch.device", self.clock, serial, **real):
                plan = self.plan_for(head.model, head.bucket, head.tier,
                                     head.backend, head.fusion)
                logits = plan(e.params, x, ops, e.calibrations.get(head.tier),
                              tops)
                logits.block_until_ready()
                # §14: fake clocks advance scripted per-key latency here —
                # between the dispatch timestamps — so batch cost is a test
                # input
                self.clock.on_batch(bkey)
            # trace-time capture, not a dispatch-time env read: the compiled
            # blob keeps whatever lowering it was traced with
            ran_dense_fallback = plan.grasp_ref_fallback
            with span("dispatch.d2h", self.clock, serial) as d2h:
                host_logits = np.asarray(logits)
                for i, r in enumerate(batch):
                    lg = host_logits[i, : r.pg.num_nodes]
                    r.preds = lg.argmax(axis=-1).astype(np.int32)
                    if self.sc.return_logits:
                        r.logits = lg
            counts = {}
            if real:
                counts["edges_dispatched"] = e.cfg.num_layers * real["edges"]
            if head.backend == "grasp":
                counts["grasp_batches"] = 1
                if ran_dense_fallback:
                    # per REQUEST (same unit as tier_fallbacks and the
                    # forced-but-ineligible count): every request in this
                    # dispatch ran its aggregation dense under ref routing
                    counts["backend_fallbacks"] = len(batch)
            self._finish_dispatch(batch, bkey, serial, b, disp.start,
                                  d2h.end, counts)

    def _next_serial(self) -> int:
        """A fresh dispatch serial: the id of a dispatch's spans, the parent
        of its requests' spans, and the fairness stamp of its model."""
        with self._lock:
            serial = self._dispatch_serial
            self._dispatch_serial += 1
        return serial

    def _finish_dispatch(self, batch: List[GNNRequest], bkey: BatchKey,
                         serial: int, width: int, t0: float, now: float,
                         counts: Dict[str, int]) -> None:
        """Close one dispatch whose answers are unpacked: stamp every
        request finished at `now` (the end of the dispatch's d2h span),
        record its `request.queue` span (submission to `t0`, the
        dispatch's start), and account the dispatch — the latency bank,
        latencies, slots, `device_busy_s` and the path's own `counts` —
        under the lock."""
        for r in batch:
            r.finished_s = now
            r.done = True
            # a finished request pins no device features (a cached buffer
            # lives on in the cache)
            r.x = None
            if r.deadline_s is not None and now > r.deadline_s:
                # executed but late (§14): the answer is delivered, the
                # breach is flagged — distinct from pre-dispatch expiry,
                # where preds stay None
                r.deadline_missed = True
            self.tracer.record("request.queue", r.submitted_s, t0, r.uid,
                               serial)
        with self._lock:
            self.bank.observe(bkey, now - t0)
            for r in batch:
                lat = now - r.submitted_s
                self.metrics["latency_s"].append(lat)
                self.finished.append(r)
                if r.deadline_missed:
                    self.metrics["deadline_misses"] += 1
                if self.governor is not None:
                    self.governor.observe(lat)
            self.metrics["batches"] += 1
            self.metrics["slots_filled"] += len(batch)
            self.metrics["slots_total"] += width
            for name, n in counts.items():
                self.metrics[name] += n
            self.metrics["device_busy_s"] += now - t0
            self.metrics["last_finish_s"] = now
            self._last_dispatch[bkey[0]] = serial

    def _halo_bytes(self, cfg: GNNConfig, part: GraphShards
                    ) -> Tuple[int, int]:
        """(compressed, exact) collective bytes one sharded forward moves:
        ring-psum traffic is priced through the single owner of the ring
        factor (`dist.compress.ring_psum_nbytes` — also what
        `core.partition.modelled_sharded_latency` uses, so metric and
        model cannot drift), int8 (1 B/elt) on the compressed wire vs fp32
        (4 B/elt) exact, over the kind's actual exchange schedule
        (`sharded_exchange_widths`)."""
        elems = sum(part.full_rows * w for w in sharded_exchange_widths(cfg))
        comp = ring_psum_nbytes(part.shards, elems, bytes_per_elt=1)
        return int(comp), int(4 * comp)

    def _execute_sharded(self, batch: List[GNNRequest]) -> None:
        """DEVICE stage of one sharded dispatch (§12, §15): the plan runs
        every shard's aggregate+combine under the shard axis (shard_map
        when the host exposes enough devices, vmap-simulated otherwise —
        identical collective math), the halo crossing as a compressed
        psum; the slot-ordered logits are unpermuted back to node order on
        the host (`unshard_logits`). With `replica_groups > 1` the batch
        carries up to R same-key requests, one per replica row of the
        R x S mesh — junk rows repeat a real request exactly like junk
        batch slots, outputs dropped. Each replica row exchanges halos
        within itself (the psum names only the shard axis), so collective
        bytes are accounted per REAL request — both what the compressed
        wire moved and what exact fp32 would have, so the compression win
        is a metric, not a claim. The spans are `_execute_batch`'s but for
        `dispatch.stack`: the replica stack of the device-resident
        `shard_x` (R > 1) is a device-side concat under
        `dispatch.operands`."""
        head = batch[0]
        R = self.sc.replica_groups
        bkey = (head.model, head.bucket, head.tier, "dense", "none",
                head.shards)
        serial = self._next_serial()
        span = self.tracer.span
        with span("dispatch", self.clock, serial) as disp:
            e = self.models[head.model]
            slots = batch + [batch[-1]] * (R - len(batch))
            with span("dispatch.operands", self.clock, serial):
                x, mask, ops = head.shard_x, head.shard_mask, head.ops
                if R > 1:
                    x = jnp.stack([r.shard_x for r in slots])
                    mask = jnp.stack([r.shard_mask for r in slots])
                    ops = stack_operands([r.ops for r in slots])
            with span("dispatch.device", self.clock, serial):
                plan = self.plan_for(head.model, head.bucket, head.tier,
                                     shards=head.shards)
                logits = plan(e.params, x, ops, e.calibrations.get(head.tier),
                              node_mask=mask)
                logits.block_until_ready()
                placed = {"placement": plan.placement,
                          "devices": len(logits.sharding.device_set)}
                self.clock.on_batch(bkey)
            with span("dispatch.d2h", self.clock, serial) as d2h:
                host_logits = np.asarray(logits)
                comp_total = exact_total = 0
                for i, r in enumerate(batch):
                    lg = unshard_logits(host_logits[i] if R > 1
                                        else host_logits, r.part)
                    r.preds = lg.argmax(axis=-1).astype(np.int32)
                    if self.sc.return_logits:
                        r.logits = lg
                    comp, exact = self._halo_bytes(e.cfg, r.part)
                    comp_total += comp
                    exact_total += exact
            with self._lock:
                self._sharded_placement[head.shards] = placed
            self._finish_dispatch(
                batch, bkey, serial, R, disp.start, d2h.end,
                {"sharded_batches": 1,
                 "halo_bytes_exchanged": (comp_total if self.sc.halo_compress
                                          else exact_total),
                 "collective_bytes_compressed": comp_total,
                 "collective_bytes_exact": exact_total})

    # -------------------------------------------------------------- pipeline
    def scheduler(self, pc=None):
        """Attach an async two-stage pipeline scheduler (DESIGN.md §9).

        Returns a `runtime.scheduler.PipelineScheduler` whose host workers
        run this engine's `prepare_submit`/`prepare_query` stages while its
        dispatcher drives `_execute_batch` — host preprocessing for request
        N+1 overlaps device execution of request N. Use as a context
        manager; the sync `submit`/`query` + `run()` path stays available
        on the bare engine."""
        from .scheduler import PipelineScheduler
        return PipelineScheduler(self, pc)

    # ---------------------------------------------------------------- metrics
    def tier_summary(self) -> Dict[str, Dict[str, float]]:
        """Per-tier serving stats, derived from the finished requests (each
        carries its RESOLVED tier, so fp32 fallbacks count as fp32 here and
        as `tier_fallbacks` in the top-level metrics)."""
        by_tier: Dict[str, List[GNNRequest]] = {}
        for r in self.finished:
            by_tier.setdefault(r.tier, []).append(r)
        out: Dict[str, Dict[str, float]] = {}
        for tn, reqs in sorted(by_tier.items()):
            lat = np.asarray([r.finished_s - r.submitted_s for r in reqs])
            span = (max(r.finished_s for r in reqs)
                    - min(r.submitted_s for r in reqs))
            out[tn] = {
                "requests": len(reqs),
                "p50_latency_ms": float(np.percentile(lat, 50) * 1e3),
                "p99_latency_ms": float(np.percentile(lat, 99) * 1e3),
                "throughput_rps": (len(reqs) / span) if span > 0 else 0.0,
            }
        return out

    def summary(self) -> Dict[str, object]:
        lat = np.asarray(self.metrics["latency_s"], np.float64)
        t0, t1 = self.metrics["first_submit_s"], self.metrics["last_finish_s"]
        span = (t1 - t0) if (t0 is not None and t1 is not None) else 0.0
        return {
            "requests": len(self.finished),
            "compiled_blobs": self.compiled_blobs,
            # traces of the dispatch's operand stack; flat after warmup()
            "operand_stack_traces": self._stack_operands.trace_count,
            "batches": self.metrics["batches"],
            "batch_occupancy": (self.metrics["slots_filled"]
                                / max(self.metrics["slots_total"], 1)),
            "device_busy_s": self.metrics["device_busy_s"],
            # fraction of the serving span no dispatch was in progress —
            # the pipeline scheduler's overlap claim is judged on this
            # (DESIGN.md §9); 1 - busy/span, 0 when nothing ran. Host
            # clock around whole dispatches: the chip itself can sit idle
            # through most of a dispatch, which only a profile shows
            "dispatch_idle_fraction": (
                max(0.0, 1.0 - self.metrics["device_busy_s"] / span)
                if span > 0 else 0.0),
            "rebucket_events": self.metrics["rebucket_events"],
            "operand_bytes_h2d": self.metrics["operand_bytes_h2d"],
            "feature_bytes_h2d": self.metrics["feature_bytes_h2d"],
            "operand_cache_hits": self.metrics["operand_cache_hits"],
            "operand_cache_misses": self.metrics["operand_cache_misses"],
            "cacheg_fallbacks": self.metrics["cacheg_fallbacks"],
            "tier_fallbacks": self.metrics["tier_fallbacks"],
            # GraSp backend dispatch (DESIGN.md §10): per-model serving
            # mode, how many batches took the sparse path, and how many
            # REQUESTS with grasp intent quietly ran dense — forced-mode
            # ineligible structure or ref-routing dispatch (same
            # per-request unit as tier_fallbacks)
            "agg_backends": {name: e.agg_backend
                             for name, e in self.models.items()},
            "grasp_batches": self.metrics["grasp_batches"],
            "backend_fallbacks": self.metrics["backend_fallbacks"],
            # sharded serving (DESIGN.md §12): which attached graphs run
            # partitioned (and across how many shards), how many width-1
            # sharded dispatches ran, where each shard count was placed
            # (shard_map over N devices, or the one-device vmap), and the
            # collective traffic — actual
            # bytes on the halo wire plus both counterfactual framings
            # (compressed vs exact), so the int8-wire win is inspectable
            "shard_counts": {gid: p.shards
                             for gid, (p, _) in self._sharded.items()},
            "sharded_batches": self.metrics["sharded_batches"],
            "sharded_placement": dict(self._sharded_placement),
            "halo_bytes_exchanged": self.metrics["halo_bytes_exchanged"],
            "collective_bytes_compressed":
                self.metrics["collective_bytes_compressed"],
            "collective_bytes_exact":
                self.metrics["collective_bytes_exact"],
            # §13 bounded cache hierarchy: residency vs budget, capacity
            # evictions split by outcome (spilled to host-RAM compact form
            # vs dropped — conservation: evictions == spilled + dropped),
            # second-level hits served from the spill store, admission
            # rejections, and the GrAd incremental-update counters
            "cache_resident_bytes": self._cache.resident_bytes,
            "cache_budget_bytes": self.sc.device_cache_budget_bytes,
            "cache_evictions": self._cache.evictions,
            "cache_spilled": self._cache.spilled,
            "cache_dropped": self._cache.dropped,
            "cache_spill_entries": self._cache.spill_entries,
            "cache_spill_hits": self.metrics["cache_spill_hits"],
            "cache_admission_rejects":
                self.metrics["cache_admission_rejects"],
            "delta_updates": self.metrics["delta_updates"],
            "delta_fallbacks": self.metrics["delta_fallbacks"],
            # §15 halo-delta exchange: exact wire bytes the dirty-
            # boundary-row exchange moved for sharded deltas vs what
            # re-exchanging the full halos would have — the wire
            # reduction is a counter, not a claim
            "delta_halo_bytes_exchanged":
                self.metrics["delta_halo_bytes_exchanged"],
            "delta_halo_bytes_full": self.metrics["delta_halo_bytes_full"],
            "delta_dirty_rows": self.metrics["delta_dirty_rows"],
            # §16 edge-list form: resident edge-operand bytes, and the real
            # edges its plans aggregated (once per layer)
            "edge_operand_bytes": self._cache.kind_bytes("edges"),
            "edges_dispatched": self.metrics["edges_dispatched"],
            # §14 SLO loop: deadline outcomes, governor decisions, and the
            # measured-vs-modelled drift of the latency bank (mean
            # EWMA/seed ratio over keys with both — the signal that the
            # roofline mispriced a path, e.g. the BENCH grasp inversion)
            "deadline_misses": self.metrics["deadline_misses"],
            "shed_requests": self.metrics["shed_requests"],
            "slo_downgrades": (self.governor.downgrades
                               if self.governor is not None else 0),
            "slo_upgrades": (self.governor.upgrades
                             if self.governor is not None else 0),
            "slo_level": (self.governor.level
                          if self.governor is not None else 0),
            "ewma_vs_model": self.bank.ewma_vs_model(),
            "tiers": self.tier_summary(),
            "accuracy_delta_vs_fp32": {
                name: dict(e.accuracy_delta)
                for name, e in self.models.items() if e.accuracy_delta},
            "throughput_rps": (len(self.finished) / span if span > 0 else 0.0),
            "p50_latency_ms": float(np.percentile(lat, 50) * 1e3) if lat.size else 0.0,
            "p99_latency_ms": float(np.percentile(lat, 99) * 1e3) if lat.size else 0.0,
            "mean_latency_s": float(lat.mean()) if lat.size else 0.0,
        }
