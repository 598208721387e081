"""The paper's own model configs (Section V): 2-layer GCN / GAT / GraphSAGE
on Cora/Citeseer-shaped graphs, hidden width 64, GAT 8 heads, SAGE fan-out 10;
and OGB's ogbn-arxiv GraphSAGE (`sage("arxiv")`).
"""
from __future__ import annotations

from repro.core.models import GNNConfig

CORA_FEATS, CORA_CLASSES = 1433, 7
CITESEER_FEATS, CITESEER_CLASSES = 3703, 6
ARXIV_FEATS, ARXIV_CLASSES = 128, 40


def gcn(dataset: str = "cora") -> GNNConfig:
    f, c = ((CORA_FEATS, CORA_CLASSES) if dataset == "cora"
            else (CITESEER_FEATS, CITESEER_CLASSES))
    return GNNConfig(kind="gcn", in_feats=f, hidden=64, num_classes=c)


def gat(dataset: str = "cora") -> GNNConfig:
    f, c = ((CORA_FEATS, CORA_CLASSES) if dataset == "cora"
            else (CITESEER_FEATS, CITESEER_CLASSES))
    return GNNConfig(kind="gat", in_feats=f, hidden=64, num_classes=c, heads=8)


def sage(dataset: str = "cora", aggregator: str = "mean") -> GNNConfig:
    if dataset == "arxiv":
        # OGB's example (arXiv:2005.00687, examples/nodeproppred/arxiv/
        # gnn.py --use_sage): 3 layers of 256, BatchNorm, every neighbour
        return GNNConfig(kind="sage", in_feats=ARXIV_FEATS, hidden=256,
                         num_classes=ARXIV_CLASSES, aggregator=aggregator,
                         max_neighbors=None, num_layers=3, batch_norm=True)
    f, c = ((CORA_FEATS, CORA_CLASSES) if dataset == "cora"
            else (CITESEER_FEATS, CITESEER_CLASSES))
    return GNNConfig(kind="sage", in_feats=f, hidden=64, num_classes=c,
                     aggregator=aggregator, max_neighbors=10)


GNN_MODELS = {
    "gcn": gcn, "gat": gat,
    "sage-mean": lambda d="cora": sage(d, "mean"),
    "sage-max": lambda d="cora": sage(d, "max"),
}
