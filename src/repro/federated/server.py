"""FL server: client selection, FedAvg aggregation, global evaluation, and
the adaptive-tau update (Algorithm 1 lines 1-8)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sync import adaptive_tau
from repro.models.gcn import AGG_BACKENDS, gcn_full_forward, per_node_loss
from repro.utils.spans import span


def select_clients(rng: np.random.Generator, n_clients: int, m: int) -> np.ndarray:
    return rng.choice(n_clients, size=min(m, n_clients), replace=False)


def fedavg(stacked_params):
    """Mean over the leading (selected-client) axis — Algorithm 1 line 7."""
    return jax.tree_util.tree_map(lambda x: x.mean(axis=0), stacked_params)


def fedavg_weighted(stacked_params, weights: jnp.ndarray):
    w = weights / jnp.maximum(weights.sum(), 1e-12)

    def avg(x):
        wshape = (len(w),) + (1,) * (x.ndim - 1)
        return (x * w.reshape(wshape)).sum(axis=0)

    return jax.tree_util.tree_map(avg, stacked_params)


# ---------------------------------------------------------------------------
# evaluation (server holds the test set — paper §Experimental Settings)
# ---------------------------------------------------------------------------

def build_eval_graph(graph, max_deg: int = 32, seed: int = 0,
                     backend: str = "gather") -> dict:
    """``backend`` picks the full-forward neighbor aggregation (see
    models.gcn.neighbor_aggregate); ``segment``/``spmm`` precompute their
    static aggregation operands here (CSR edge arrays / the row-normalised
    adjacency) so every per-round eval and layer reuses them."""
    from repro.graph.csr import build_padded_neighbors, csr_from_padded

    if backend not in AGG_BACKENDS:
        raise ValueError(f"unknown eval backend {backend!r}; known: {AGG_BACKENDS}")
    idx, mask = build_padded_neighbors(graph.adjacency_lists(), max_deg, seed=seed)
    csr = None
    adj = None
    if backend == "segment":
        c = csr_from_padded(idx, mask)
        csr = {k: jnp.asarray(v) for k, v in c.items()}
    elif backend == "spmm":
        from repro.kernels.spmm.ops import adjacency_from_neighbors

        adj = adjacency_from_neighbors(jnp.asarray(idx), jnp.asarray(mask),
                                       graph.n_nodes)
    return {
        "features": jnp.asarray(graph.features),
        "labels": jnp.asarray(graph.labels),
        "nbr_idx": jnp.asarray(idx),
        "nbr_mask": jnp.asarray(mask),
        "test_mask": jnp.asarray(graph.test_mask),
        "val_mask": jnp.asarray(graph.val_mask),
        "n_classes": graph.n_classes,
        "backend": backend,
        "csr": csr,
        "adj": adj,
    }


@functools.partial(jax.jit, static_argnames=("backend",))
def _eval_logits(params, features, nbr_idx, nbr_mask, csr=None, adj=None,
                 backend: str = "gather"):
    return gcn_full_forward(params, features, nbr_idx, nbr_mask,
                            backend=backend, csr=csr, adj=adj)


@span("fed/eval")
def evaluate_global(params, eval_graph: dict, split: str = "test") -> dict:
    logits = _eval_logits(params, eval_graph["features"],
                          eval_graph["nbr_idx"], eval_graph["nbr_mask"],
                          csr=eval_graph.get("csr"),
                          adj=eval_graph.get("adj"),
                          backend=eval_graph.get("backend", "gather"))
    mask = np.asarray(eval_graph[f"{split}_mask"])
    labels = np.asarray(eval_graph["labels"])[mask]
    with span("fed/eval-wait"):
        lg = np.asarray(logits, np.float32)
    lg = lg[mask]
    nll = np.asarray(per_node_loss(jnp.asarray(lg), jnp.asarray(labels)))
    pred = lg.argmax(-1)
    acc = float((pred == labels).mean()) if len(labels) else 0.0
    return {
        "acc": acc,
        "loss": float(nll.mean()) if len(labels) else float("inf"),
        "f1": macro_f1(labels, pred, eval_graph["n_classes"]),
        "auc": macro_ovr_auc(labels, lg),
    }


def macro_f1(labels: np.ndarray, pred: np.ndarray, n_classes: int) -> float:
    f1s = []
    for c in range(n_classes):
        tp = float(((pred == c) & (labels == c)).sum())
        fp = float(((pred == c) & (labels != c)).sum())
        fn = float(((pred != c) & (labels == c)).sum())
        if tp + fp + fn == 0:
            continue
        f1s.append(2 * tp / max(2 * tp + fp + fn, 1e-12))
    return float(np.mean(f1s)) if f1s else 0.0


def macro_ovr_auc(labels: np.ndarray, logits: np.ndarray) -> float:
    """Macro one-vs-rest AUC via the rank statistic (no sklearn offline)."""
    aucs = []
    for c in np.unique(labels):
        pos = logits[labels == c, c]
        neg = logits[labels != c, c]
        if len(pos) == 0 or len(neg) == 0:
            continue
        ranks = np.argsort(np.argsort(np.concatenate([pos, neg])))
        r_pos = ranks[: len(pos)].sum() + len(pos)  # 1-based
        auc = (r_pos - len(pos) * (len(pos) + 1) / 2) / (len(pos) * len(neg))
        aucs.append(auc)
    return float(np.mean(aucs)) if aucs else 0.5


def update_tau(mcfg, test_loss: float, initial_loss: float, tau0: int) -> int:
    """Algorithm 1 line 8: adaptive (Eq. 11) or fixed interval."""
    if mcfg.adaptive_sync:
        return adaptive_tau(test_loss, initial_loss, tau0)
    return tau0
