"""Operations and least bytes of a FedAIS round, from shapes.

Only real nodes and edges count: padding rows, padded neighbour slots and
the neighbours the fanout drops are work an implementation may skip. The
forward count per node is the program's own arithmetic
(``repro.models.gcn.gcn_flops_per_node``), copied so that a later change to
the program cannot change the yardstick.
"""
from __future__ import annotations

import numpy as np


def forward_flops_per_node(n_features: int, hidden, n_classes: int,
                           avg_deg: float) -> float:
    """Self and neighbour matmuls plus the mean aggregation of each layer,
    then the classifier."""
    dims = (n_features, *hidden)
    fl = 0.0
    for l in range(len(hidden)):
        fl += 2 * 2 * dims[l] * dims[l + 1]
        fl += 2 * avg_deg * dims[l]
    return fl + 2 * hidden[-1] * n_classes


def param_count(n_features: int, hidden, n_classes: int) -> int:
    dims = (n_features, *hidden)
    n = sum(2 * dims[l] * dims[l + 1] + dims[l + 1] for l in range(len(hidden)))
    return n + hidden[-1] * n_classes + n_classes


def client_stats(node_mask, train_mask, nbr_mask, ghost_mask, *, fanout: int,
                 batch: int) -> dict:
    """Per-client real sizes of a partition (arrays with a leading client
    axis): nodes, ghosts, edges, valid batch nodes, and the mean degree of
    real nodes before and (for training nodes) after the fanout."""
    node = np.asarray(node_mask) > 0
    train = node & (np.asarray(train_mask) > 0)
    deg = (np.asarray(nbr_mask) > 0).sum(-1)
    n = node.sum(1)
    return {
        "nodes": n.astype(np.float64),
        "ghosts": (np.asarray(ghost_mask) > 0).sum(1).astype(np.float64),
        "edges": (deg * node).sum(1).astype(np.float64),
        "valid": np.minimum(train.sum(1), batch).astype(np.float64),
        "deg": float((deg * node).sum() / max(n.sum(), 1)),
        "deg_fanout": float((np.minimum(deg, fanout) * train).sum()
                            / max(train.sum(), 1)),
    }


def round_flops(stats: dict, *, n_features: int, hidden, n_classes: int,
                cohort: int, epochs: int) -> float:
    """A round's operations, in expectation over a uniform cohort: the loss
    pass forward over each client's real nodes, then per epoch forward plus
    twice-forward backward over its valid batch nodes."""
    loss_pass = stats["nodes"].mean() * forward_flops_per_node(
        n_features, hidden, n_classes, stats["deg"])
    train = epochs * stats["valid"].mean() * 3 * forward_flops_per_node(
        n_features, hidden, n_classes, stats["deg_fanout"])
    return cohort * (loss_pass + train)


def round_bytes(stats: dict, *, n_features: int, hidden, n_classes: int,
                cohort: int) -> float:
    """The least bytes a round must move, in expectation over a uniform
    cohort: read each client's own features, ghost features, ghost layer-1
    rows, neighbour indices, labels, masks and last losses once; write its
    losses and at least one batch of layer-1 rows once; read and write the
    weights once. fp32 and int32, 4 bytes each."""
    F, H1 = n_features, hidden[0]
    read = (stats["nodes"] * (F + 4) + stats["ghosts"] * (F + H1)
            + stats["edges"])
    write = stats["nodes"] + stats["valid"] * H1
    weights = 2 * param_count(n_features, hidden, n_classes)
    return 4.0 * (cohort * float((read + write).mean()) + weights)


def eval_flops(n_nodes: int, avg_deg: float, *, n_features: int, hidden,
               n_classes: int) -> float:
    """One full-graph evaluation forward over every node."""
    return n_nodes * forward_flops_per_node(n_features, hidden, n_classes,
                                            avg_deg)
