"""The configurations' graphs: a synthetic stand-in matched to a dataset's
published statistics, made from the configuration's fixed data seed.

A copy of the generator in ``repro.graph.data.make_dataset`` (degree-
corrected stochastic block model, Gaussian-mixture features), kept here so
that a later change to the program cannot change the benchmark's inputs.
Every size comes from the configuration file.
"""
from __future__ import annotations

import numpy as np


def stable_hash(s: str) -> int:
    """FNV-1a over the name's bytes (Python's ``hash`` is salted per
    process, which would make every run draw a different graph)."""
    h = 2166136261
    for c in s.encode():
        h = ((h ^ c) * 16777619) & 0xFFFFFFFF
    return h


def make_graph(cfg: dict):
    """The configuration's graph as a ``repro.graph.data.GraphData``."""
    from repro.graph.data import DatasetSpec, GraphData

    d = cfg["dataset"]
    spec = DatasetSpec(d["name"], d["nodes"], d["edges"], d["features"],
                       d["classes"], d["train_frac"], d["val_frac"],
                       d["test_frac"])
    rng = np.random.default_rng(cfg["data_seed"] * 977
                                + stable_hash(d["name"]) % 10_000)
    n, f, c = spec.n_nodes, spec.n_features, spec.n_classes
    avg_deg = min(2.0 * spec.n_edges / spec.n_nodes, 64.0)

    class_p = rng.dirichlet(np.ones(c) * 5.0)
    labels = rng.choice(c, size=n, p=class_p).astype(np.int32)

    means = rng.standard_normal((c, f)).astype(np.float32) * 1.5
    features = (means[labels] + rng.standard_normal((n, f)).astype(np.float32)
                * d["feature_noise"])

    target_edges = int(n * avg_deg / 2)
    prop = rng.pareto(2.5, size=n) + 1.0
    prop /= prop.sum()
    src = rng.choice(n, size=target_edges * 3, p=prop)
    dst = rng.choice(n, size=target_edges * 3, p=prop)
    same = labels[src] == labels[dst]
    h = d["homophily"]
    accept = np.where(same, h, 1.0 - h) > rng.random(len(src))
    ok = accept & (src != dst)
    edges = np.stack([src[ok], dst[ok]], axis=1)
    lo, hi = edges.min(1), edges.max(1)
    uniq = np.unique(lo.astype(np.int64) * n + hi)
    edges = np.stack([uniq // n, uniq % n], axis=1).astype(np.int32)
    if len(edges) > target_edges:
        edges = edges[rng.permutation(len(edges))[:target_edges]]

    order = rng.permutation(n)
    n_train = int(spec.train_frac * n)
    n_val = int(spec.val_frac * n)
    train_mask = np.zeros(n, bool)
    val_mask = np.zeros(n, bool)
    test_mask = np.zeros(n, bool)
    train_mask[order[:n_train]] = True
    val_mask[order[n_train:n_train + n_val]] = True
    test_mask[order[n_train + n_val:]] = True
    return GraphData(name=d["name"], features=features, labels=labels,
                     edges=edges, n_classes=c, train_mask=train_mask,
                     val_mask=val_mask, test_mask=test_mask, spec=spec)
