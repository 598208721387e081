"""Two-layer GCN (Kipf & Welling 2017) as GraNNite serves it (arXiv
2502.06921 §V): H1 = relu(Â X W1 + b1), logits = Â H1 W2 + b2, with
Â = D^-1/2 (A + I) D^-1/2 over the undirected graph plus self loops.

Three things the benchmark needs of the model, kept apart from the program:

  * `init_params`  weights from a key, in one jitted call on the device, in
                   the program's parameter layout ({"l1": {"w", "b"}, ...});
  * `reference`    a plain edge-list forward in `jax.numpy`: gathers and
                   segment sums in fp32, its products at "highest"
                   precision (the control asks for "high", three bf16
                   passes: `benchlib/matmul.py`);
  * `work`         the least operations and bytes one request needs, over
                   the graph's real nodes and edges: no padding, no junk
                   slots, no dense N x N aggregation.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from benchlib import matmul


def program_config(cfg: Dict) -> Dict:
    """Keyword arguments of the program's `GNNConfig` for this model."""
    return {"kind": "gcn", "in_feats": cfg["in_feats"],
            "hidden": cfg["hidden"], "num_classes": cfg["num_classes"]}


def _shapes(cfg: Dict) -> Dict:
    f, h, c = cfg["in_feats"], cfg["hidden"], cfg["num_classes"]
    return {"l1": {"w": (f, h), "b": (h,)}, "l2": {"w": (h, c), "b": (c,)}}


def init_params(key, cfg: Dict) -> Dict:
    """Glorot-uniform weights and small uniform biases (not zero, so that
    the bias path is compared too), all float32, made on the device."""
    shapes = _shapes(cfg)

    def _init(k):
        out = {}
        for layer, leaves in shapes.items():
            k, kw, kb = jax.random.split(k, 3)
            f_in, f_out = leaves["w"]
            lim = (6.0 / (f_in + f_out)) ** 0.5
            out[layer] = {
                "w": jax.random.uniform(kw, leaves["w"], jnp.float32, -lim, lim),
                "b": jax.random.uniform(kb, leaves["b"], jnp.float32, -0.1, 0.1)}
        return out

    return jax.jit(_init)(key)


def reference(params: Dict, cfg: Dict, x, edge_index, num_nodes: int, *,
              precision: str = "highest"):
    """Edge-list GCN over `edge_index` (2, E) plus self loops."""
    n = num_nodes
    loops = jnp.arange(n, dtype=edge_index.dtype)
    src = jnp.concatenate([edge_index[0], loops])
    dst = jnp.concatenate([edge_index[1], loops])
    deg = jax.ops.segment_sum(jnp.ones(src.shape[0], jnp.float32), dst,
                              num_segments=n)
    dis = jax.lax.rsqrt(deg)
    coef = (dis[dst] * dis[src])[:, None]

    def layer(p, h):
        h = matmul.dot(h, p["w"], precision)
        agg = jax.ops.segment_sum(h[src] * coef, dst, num_segments=n)
        return agg + p["b"]

    h = jax.nn.relu(layer(params["l1"], x))
    return layer(params["l2"], h)


def work(cfg: Dict, num_nodes: int, num_edges: int) -> Tuple[float, float]:
    """(operations, bytes) one request needs at least.

    `num_edges` counts directed edges without self loops; the self loops
    are added in the operations and left implicit in the bytes. Operations:
    both combines (2 N F H, 2 N H C), both aggregations over E + N edges
    (a multiply and an add per edge and feature), bias and relu. Bytes:
    fp32 features, weights and logits, and the structure as int32 CSR
    (one column per edge, N + 1 row offsets).
    """
    n, e = num_nodes, num_edges
    el = e + n
    f, h, c = cfg["in_feats"], cfg["hidden"], cfg["num_classes"]
    flops = (2 * n * f * h + 2 * el * h + 2 * n * h
             + 2 * n * h * c + 2 * el * c + n * c)
    nbytes = 4 * (n * f + f * h + h + h * c + c + e + n + 1 + n * c)
    return float(flops), float(nbytes)
