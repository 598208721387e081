"""GraphSAGE as OGB's ogbn-arxiv example runs it (Hu et al., arXiv
2005.00687; `examples/nodeproppred/arxiv/gnn.py --use_sage`), with PyG's
`SAGEConv` and mean aggregation (Hamilton et al., arXiv 1706.02216):

    h' = lin_l(mean_{j in N(i)} h_j) + lin_r(h_i)

over the symmetrised graph without self loops; `lin_l` has the bias and
`lin_r` none; a node with no neighbours gets a mean of 0. `num_layers`
layers (128 -> 256 -> 256 -> 40 in OGB's), each but the last followed by
BatchNorm1d in eval mode (running statistics) and ReLU; dropout does
nothing at inference. The example ends in `log_softmax`; the benchmark
compares the logits before it.

As in `gcn.py`: `init_params` (the program's layout: {"l<i>": {"w_self",
"w_neigh", "b"}, "bn<i>": {"gamma", "beta", "mean", "var"}}), `reference`
(edge-list gathers and segment sums, fp32, its products at "highest" or,
for the control, "high"), `work` (the least operations and bytes of one
request over its real nodes and edges), and `agg_work` (the same for each
layer's aggregation alone).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchlib import matmul


def program_config(cfg: Dict) -> Dict:
    return {"kind": "sage", "in_feats": cfg["in_feats"],
            "hidden": cfg["hidden"], "num_classes": cfg["num_classes"],
            "aggregator": cfg["aggregator"], "max_neighbors": None,
            "num_layers": cfg["num_layers"], "batch_norm": True}


def _widths(cfg: Dict) -> List[Tuple[int, int]]:
    """(in, out) width of each layer."""
    w = ([cfg["in_feats"]] + [cfg["hidden"]] * (cfg["num_layers"] - 1)
         + [cfg["num_classes"]])
    return list(zip(w[:-1], w[1:]))


def init_params(key, cfg: Dict) -> Dict:
    """Glorot-uniform weights; biases, BatchNorm shifts and running means
    uniform in [-0.1, 0.1]; scales in [0.5, 1.5] and running variances in
    [0.5, 2]: nothing at its identity, so every term is compared."""
    widths = _widths(cfg)

    def _init(k):
        out = {}
        u = jax.random.uniform
        for i, (f_in, f_out) in enumerate(widths, start=1):
            k, ks, kn, kb = jax.random.split(k, 4)
            lim = (6.0 / (f_in + f_out)) ** 0.5
            out[f"l{i}"] = {
                "w_self": u(ks, (f_in, f_out), jnp.float32, -lim, lim),
                "w_neigh": u(kn, (f_in, f_out), jnp.float32, -lim, lim),
                "b": u(kb, (f_out,), jnp.float32, -0.1, 0.1)}
            if i < len(widths):
                k, kg, kt, km, kv = jax.random.split(k, 5)
                out[f"bn{i}"] = {
                    "gamma": u(kg, (f_out,), jnp.float32, 0.5, 1.5),
                    "beta": u(kt, (f_out,), jnp.float32, -0.1, 0.1),
                    "mean": u(km, (f_out,), jnp.float32, -0.1, 0.1),
                    "var": u(kv, (f_out,), jnp.float32, 0.5, 2.0)}
        return out

    return jax.jit(_init)(key)


def reference(params: Dict, cfg: Dict, x, edge_index, num_nodes: int, *,
              precision: str = "highest"):
    """Edge-list SAGE over `edge_index` (2, E), mean over in-neighbours."""
    n = num_nodes
    src, dst = edge_index[0], edge_index[1]
    count = jax.ops.segment_sum(jnp.ones(src.shape[0], jnp.float32), dst,
                                num_segments=n)
    eps = cfg["batch_norm_eps"]
    h = x
    last = cfg["num_layers"]
    for i in range(1, last + 1):
        p = params[f"l{i}"]
        mean = (jax.ops.segment_sum(h[src], dst, num_segments=n)
                / jnp.maximum(count, 1.0)[:, None])
        h = (matmul.dot(mean, p["w_neigh"], precision) + p["b"]
             + matmul.dot(h, p["w_self"], precision))
        if i < last:
            bn = params[f"bn{i}"]
            h = (h - bn["mean"]) / jnp.sqrt(bn["var"] + eps) * bn["gamma"] \
                + bn["beta"]
            h = jax.nn.relu(h)
    return h


def agg_work(cfg: Dict, num_nodes: int, num_edges: int
             ) -> List[Tuple[float, float]]:
    """(operations, bytes) each layer's aggregation needs at least, over
    the real nodes and directed edges, on the narrower side of the layer's
    neighbour map (w = min(in, out): mean(h) W = mean(h W)). Operations:
    an add per edge and feature, a scale by 1 / degree per node and
    feature. Bytes: the structure as int32 CSR (E columns, N + 1 offsets)
    and the fp32 (N, w) input read once and mean written once."""
    n, e = num_nodes, num_edges
    out = []
    for f_in, f_out in _widths(cfg):
        w = min(f_in, f_out)
        out.append((float((e + n) * w),
                    float(4 * (e + n + 1) + 8 * n * w)))
    return out


def work(cfg: Dict, num_nodes: int, num_edges: int) -> Tuple[float, float]:
    """(operations, bytes) one request needs at least.

    Per layer (in, out): both combines 2 N in out each, the aggregation
    (`agg_work`), the bias N out; after every layer but the last, eval
    BatchNorm (subtract, divide, scale, shift: 4 N out) and ReLU N out.
    Bytes: fp32 features, weights, BatchNorm state and logits, and the
    structure as int32 CSR."""
    n, e = num_nodes, num_edges
    widths = _widths(cfg)
    flops = sum(ops for ops, _ in agg_work(cfg, n, e))
    params = 0
    for i, (f_in, f_out) in enumerate(widths, start=1):
        flops += 4 * n * f_in * f_out + n * f_out
        params += 2 * f_in * f_out + f_out
        if i < len(widths):
            flops += 5 * n * f_out
            params += 4 * f_out
    nbytes = 4 * (n * cfg["in_feats"] + params + e + n + 1
                  + n * cfg["num_classes"])
    return float(flops), float(nbytes)
