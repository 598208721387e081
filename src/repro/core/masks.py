"""StaGr / GrAd mask builders.

StaGr bakes masks as compile-time constants (closure captures) for truly
static graphs; GrAd passes the *same* masks as runtime arguments so dynamic
graphs never recompile. Both paths share these builders.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

NEG_INF = -1e9  # GrAx1 additive masking constant (paper: "large negative number")


def attention_bias_multiplicative(adj_with_loops: np.ndarray) -> np.ndarray:
    """Exact GAT masking operand: 1 where edge, 0 elsewhere.

    The exact path computes `where(mask, scores, -inf)` — a Select, the
    control-heavy op the paper measures on the DSP.
    """
    return (adj_with_loops > 0).astype(np.float32)


def attention_bias_additive(adj_with_loops: np.ndarray) -> np.ndarray:
    """GrAx1: additive bias. scores + bias ≈ masked scores, no Select/mul.

    bias = 0 on edges, NEG_INF off edges. After softmax the off-edge weights
    are ~exp(-1e9)=0; quality loss is negligible (paper Fig. 16).
    """
    return np.where(adj_with_loops > 0, 0.0, NEG_INF).astype(np.float32)


def adj_with_self_loops(adj: np.ndarray, num_nodes: int) -> np.ndarray:
    out = adj.copy()
    idx = np.arange(num_nodes)
    out[idx, idx] = 1.0
    return out


def sage_sample_adjacency(adj: np.ndarray, num_nodes: int, *,
                          max_neighbors: Optional[int],
                          rng: Optional[np.random.Generator] = None,
                          include_self: bool = True) -> np.ndarray:
    """StaGr for GraphSAGE: precomputed *sampled* adjacency, reused at inference.

    Uniformly samples up to `max_neighbors` in-neighbors per node (paper
    uses 10 on Cora). Returns a 0/1 (cap, cap) mask. `max_neighbors=None`
    keeps every in-neighbour of the real rows and adds no self loop
    (`GNNConfig.max_neighbors`: OGB's full-batch inference).

    Vectorized: every edge draws one uniform key and each row keeps its
    `max_neighbors` smallest-keyed neighbors (a per-row random permutation
    prefix == uniform sampling without replacement), so the whole sample is
    one argpartition over the matrix instead of an O(N) Python loop — this
    runs on the serving hot path at every structure miss. Deterministic for
    a seeded rng (default seed 0, matching the historical behavior).
    """
    out = np.zeros_like(adj)
    if max_neighbors is None:
        out[:num_nodes] = adj[:num_nodes] > 0
        return out
    rng = rng or np.random.default_rng(0)
    cap = adj.shape[0]
    if num_nodes > 0 and max_neighbors > 0:
        live = adj[:num_nodes] > 0
        keys = np.where(live, rng.random((num_nodes, cap)), np.inf)
        kth = min(max_neighbors, cap - 1)
        kept = np.argpartition(keys, kth, axis=1)[:, :max_neighbors]
        rows = np.repeat(np.arange(num_nodes), kept.shape[1])
        cols = kept.reshape(-1)
        picked = live[rows, cols]          # rows with < k neighbors pad w/ inf
        out[rows[picked], cols[picked]] = 1.0
    if include_self:
        idx = np.arange(num_nodes)
        out[idx, idx] = 1.0
    return out


def mean_from_mask(mask: np.ndarray) -> np.ndarray:
    """Row-normalize a 0/1 sampled mask -> mean-aggregation operand."""
    deg = mask.sum(axis=1, keepdims=True)
    return (mask / np.maximum(deg, 1.0)).astype(np.float32)


def max_bias_from_mask(mask: np.ndarray) -> np.ndarray:
    """Additive bias for exact masked-max: 0 on edges, -inf off-edge."""
    return np.where(mask > 0, 0.0, NEG_INF).astype(np.float32)
