"""Decide `correct`: the served logits against the plain reference.

Every answered request of the window is compared with the reference's
logits for its tenant graph (a tenant's answer does not depend on which
batch or slot it rode in). The number compared is the worst, over those
requests, of max |served - reference| / max |reference|: the error as a
share of the largest logit. The reference is the model file's edge-list
forward in fp32 with its products at "highest" precision, run once per
tenant after the window, with the engine gone. Its edge lists are padded
to one length with edges into a spare node, so every tenant and seed
replays one compiled program.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

_EDGE_PAD = 4096


def padded_edges(edge_index: np.ndarray, num_nodes: int, length: int
                 ) -> np.ndarray:
    """Edges to `length` columns; the extra ones join spare node
    `num_nodes` to itself, whose row the caller drops."""
    extra = length - edge_index.shape[1]
    if extra < 0:
        raise ValueError(f"{edge_index.shape[1]} edges exceed pad {length}")
    fill = np.full((2, extra), num_nodes, np.int32)
    return np.concatenate([edge_index.astype(np.int32), fill], axis=1)


def reference_logits(model, config: Dict, params, graphs: Sequence[Dict], *,
                     precision: str = "highest") -> List[np.ndarray]:
    """The model's plain reference over each tenant graph, on the default
    device; the control asks for `precision="high"`."""
    import jax
    most = max(g["edge_index"].shape[1] for g in graphs)
    length = -(-most // _EDGE_PAD) * _EDGE_PAD
    sizes = {g["num_nodes"] for g in graphs}
    fns = {n: jax.jit(lambda p, x, ei, n=n: model.reference(
        p, config, x, ei, n + 1, precision=precision)[:n]) for n in sizes}
    out = []
    for g in graphs:
        n = g["num_nodes"]
        x = np.concatenate([g["features"],
                            np.zeros((1, g["features"].shape[1]), np.float32)])
        ei = padded_edges(g["edge_index"], n, length)
        out.append(np.asarray(fns[n](params, x, ei), np.float32))
    return out


def err_share(got: np.ndarray, ref: np.ndarray) -> float:
    """max |got - ref| / max |ref|; inf where the shapes differ or the
    answer holds a non-finite number."""
    if got.shape != ref.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def compare(served, refs: Sequence[np.ndarray]) -> Dict[str, float]:
    """Worst error share over the answered requests, and how many were
    compared."""
    worst, n = 0.0, 0
    for s in served:
        if s.logits is None:
            continue
        worst = max(worst, err_share(s.logits, refs[s.tenant]))
        n += 1
    return {"max_err_share": worst, "compared": n}
