"""Planetoid-shaped graphs from a seed (Yang et al. 2016 statistics).

A copy of the arithmetic of the program's `data/graphs.planetoid_like`, so
that the benchmark's inputs stay fixed whatever later PRs do to the program:
a homophilous stochastic block model drawn without replacement and
symmetrised, sparse class-conditioned bag-of-words features, row-normalised.
Returns plain numpy arrays; the caller wraps them in the program's `Graph`.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def planetoid_like(*, num_nodes: int, num_edges: int, num_feats: int,
                   num_classes: int, seed: int, homophily: float = 0.9,
                   feat_sparsity: float = 0.98, train_per_class: int = 20,
                   test_frac: float = 0.35) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, num_classes, size=num_nodes).astype(np.int32)

    src = rng.integers(0, num_nodes, size=num_edges * 3)
    same = rng.random(num_edges * 3) < homophily
    dst = np.where(same, _random_same_class(rng, labels, src, num_classes),
                   rng.integers(0, num_nodes, size=src.shape[0]))
    keep = src != dst
    edges = np.unique(np.stack([src[keep], dst[keep]]), axis=1)[:, :num_edges]
    edge_index = np.unique(np.concatenate([edges, edges[::-1]], axis=1),
                           axis=1).astype(np.int32)

    feats = np.zeros((num_nodes, num_feats), dtype=np.float32)
    words_per_class = num_feats // num_classes
    nnz_per_node = max(int(num_feats * (1.0 - feat_sparsity)), 4)
    for i in range(num_nodes):
        lo = labels[i] * words_per_class
        own = rng.integers(lo, lo + words_per_class, size=nnz_per_node * 3 // 4)
        noise = rng.integers(0, num_feats, size=nnz_per_node // 4)
        feats[i, np.concatenate([own, noise])] = 1.0
    feats /= np.maximum(feats.sum(axis=1, keepdims=True), 1.0)

    train_mask = np.zeros(num_nodes, dtype=bool)
    for c in range(num_classes):
        idx = np.nonzero(labels == c)[0]
        train_mask[rng.choice(idx, size=min(train_per_class, len(idx)),
                              replace=False)] = True
    rest = np.nonzero(~train_mask)[0]
    test_idx = rng.choice(rest, size=int(num_nodes * test_frac), replace=False)
    test_mask = np.zeros(num_nodes, dtype=bool)
    test_mask[test_idx] = True
    return {"edge_index": edge_index, "num_nodes": num_nodes,
            "features": feats, "labels": labels, "train_mask": train_mask,
            "test_mask": test_mask}


def _random_same_class(rng, labels, src, num_classes):
    order = np.argsort(labels, kind="stable")
    sorted_labels = labels[order]
    starts = np.searchsorted(sorted_labels, np.arange(num_classes))
    ends = np.searchsorted(sorted_labels, np.arange(num_classes), side="right")
    c = labels[src]
    span = np.maximum(ends[c] - starts[c], 1)
    pick = starts[c] + (rng.integers(0, 1 << 30, size=src.shape[0]) % span)
    return order[pick].astype(src.dtype)
