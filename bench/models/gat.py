"""Two-layer GAT (Veličković et al. 2018, arXiv 1710.10903, the Cora setup):
layer 1 has `heads` attention heads of `head_dim` features each,
concatenated, then ELU; layer 2 has one head of `num_classes` outputs.
Each head scores an edge j -> i as LeakyReLU_0.2(a_dst . h_i + a_src . h_j),
normalises the scores by a softmax over i's incoming edges and self loop,
and sums the neighbours' projected features with those weights.

As in `gcn.py`: `init_params` (one jitted call, the program's layout
{"l1": {"w", "a_src", "a_dst", "b"}, ...}), `reference` (edge-list
gathers, segment max/sum softmax, fp32, its products at "highest"
precision or, for the control, "high") and `work`
(the least operations and bytes of one request over its real edges).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from benchlib import matmul


def program_config(cfg: Dict) -> Dict:
    return {"kind": "gat", "in_feats": cfg["in_feats"],
            "hidden": cfg["heads"] * cfg["head_dim"],
            "num_classes": cfg["num_classes"], "heads": cfg["heads"]}


def _layers(cfg: Dict):
    """(in width, heads, per-head width) of each layer."""
    d = cfg["heads"] * cfg["head_dim"]
    return (("l1", cfg["in_feats"], cfg["heads"], cfg["head_dim"]),
            ("l2", d, 1, cfg["num_classes"]))


def init_params(key, cfg: Dict) -> Dict:
    def _init(k):
        out = {}
        for name, f_in, heads, f_out in _layers(cfg):
            k, kw, ks, kd, kb = jax.random.split(k, 5)
            lim_w = (6.0 / (f_in + heads * f_out)) ** 0.5
            lim_a = (6.0 / (heads + f_out)) ** 0.5
            u = jax.random.uniform
            out[name] = {
                "w": u(kw, (f_in, heads * f_out), jnp.float32, -lim_w, lim_w),
                "a_src": u(ks, (heads, f_out), jnp.float32, -lim_a, lim_a),
                "a_dst": u(kd, (heads, f_out), jnp.float32, -lim_a, lim_a),
                "b": u(kb, (heads * f_out,), jnp.float32, -0.1, 0.1)}
        return out

    return jax.jit(_init)(key)


def reference(params: Dict, cfg: Dict, x, edge_index, num_nodes: int, *,
              precision: str = "highest"):
    n = num_nodes
    loops = jnp.arange(n, dtype=edge_index.dtype)
    src = jnp.concatenate([edge_index[0], loops])
    dst = jnp.concatenate([edge_index[1], loops])

    def layer(p, h_in, heads, f_out):
        h = matmul.dot(h_in, p["w"], precision).reshape(n, heads, f_out)
        a_src = matmul.einsum("nhf,hf->nh", h, p["a_src"], precision)
        a_dst = matmul.einsum("nhf,hf->nh", h, p["a_dst"], precision)
        e = jax.nn.leaky_relu(a_dst[dst] + a_src[src], negative_slope=0.2)
        e_max = jax.ops.segment_max(e, dst, num_segments=n)
        e = jnp.exp(e - e_max[dst])
        att = e / jax.ops.segment_sum(e, dst, num_segments=n)[dst]
        out = jax.ops.segment_sum(h[src] * att[:, :, None], dst,
                                  num_segments=n)
        return out.reshape(n, heads * f_out) + p["b"]

    (l1, _, h1, d1), (l2, _, h2, d2) = _layers(cfg)
    h = jax.nn.elu(layer(params[l1], x, h1, d1))
    return layer(params[l2], h, h2, d2)


def work(cfg: Dict, num_nodes: int, num_edges: int) -> Tuple[float, float]:
    """(operations, bytes) one request needs at least.

    Per layer of width f_in -> heads x f_out (D = heads * f_out) over
    E' = E + N edges: the combine 2 N f_in D; the two score projections
    4 N D; per edge and head 7 operations (add, LeakyReLU, the segment
    max, subtract, exp, the segment sum, divide); the weighted sum 2 E' D;
    the bias N D; and ELU N D after layer 1. Bytes: fp32 features,
    weights and logits, and int32 CSR structure with implicit self loops.
    """
    n, e = num_nodes, num_edges
    el = e + n
    flops = 0
    nbytes = n * cfg["in_feats"] + e + n + 1 + n * cfg["num_classes"]
    for name, f_in, heads, f_out in _layers(cfg):
        d = heads * f_out
        flops += (2 * n * f_in * d + 4 * n * d + 7 * el * heads
                  + 2 * el * d + n * d)
        nbytes += f_in * d + 2 * heads * f_out + d
        if name == "l1":
            flops += n * d
    return float(flops), float(4 * nbytes)
