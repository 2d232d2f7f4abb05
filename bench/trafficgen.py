"""The serving cells' traffic, drawn from ``--seed`` and one traffic file.

One general generator for every serving mix. The mix's parameters are data
(``bench/traffic/<workload>.json``): the offered rate, the policy mix, the
Zipf exponent of node popularity, the request-size law, the share and mix
of graph updates, and how often the cache is refreshed.

Every seed gets the same amount of work: ``round(rate * seconds)`` queries
and ``round(updates_per_query * queries)`` updates, each uniformly placed in
the window (a Poisson process given its count), request sizes and node
popularity ranks at stratified quantiles of their laws, policies and update
kinds in their exact shares. The seed picks the order, the arrival times,
which nodes are hot (a permutation of the node ids), the anchors of new
nodes and their feature noise. The popularity draw and the hot set follow
``repro.serve.loadgen``'s synthesis (Zipf ranks through a seeded
permutation), copied so that the program cannot change the inputs.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Schedule:
    q_due: np.ndarray          # (Q,) seconds into the window, sorted
    q_policy: np.ndarray       # (Q,) 0 historical, 1 fresh
    q_ids: list                # Q arrays of node ids
    u_due: np.ndarray          # (U,) seconds into the window, sorted
    u_kind: np.ndarray         # (U,) 0 edge, 1 node
    u_edge: np.ndarray         # (U, 2) endpoints of edge updates
    u_anchors: list            # U arrays: anchors of node updates
    u_feat: np.ndarray         # (U, F) float32 features of node updates
    n_new_nodes: int

    @property
    def n_queries(self) -> int:
        return len(self.q_due)


POLICIES = ("historical", "fresh")


def _stratified(rng, cdf: np.ndarray, n: int) -> np.ndarray:
    """``n`` draws from the discrete law with this CDF over 1..len(cdf), at
    the stratified quantiles (i + 0.5) / n, in an order the seed picks."""
    u = (np.arange(n) + 0.5) / n
    vals = np.searchsorted(cdf, u, side="left") + 1
    return rng.permutation(vals)


def _law(k: int, exponent: float) -> np.ndarray:
    p = np.arange(1, k + 1, dtype=np.float64) ** -exponent
    return np.cumsum(p / p.sum())


def make_schedule(traffic: dict, seed: int, seconds: float,
                  features: np.ndarray) -> Schedule:
    n, F = features.shape
    rng = np.random.default_rng((seed, 0x5E7))
    hot = np.random.default_rng((seed, 12345)).permutation(n)
    rank_cdf = _law(n, traffic["zipf_a"])

    nq = int(round(traffic["rate_qps"] * seconds))
    q_due = np.sort(rng.uniform(0.0, seconds, nq))
    sizes = _stratified(rng, _law(traffic["size_max"],
                                  traffic["size_exponent"]), nq)
    mix = traffic["policy_mix"]
    n_fresh = int(round(nq * mix["fresh"] / sum(mix.values())))
    q_policy = rng.permutation(np.r_[np.zeros(nq - n_fresh, np.int8),
                                     np.ones(n_fresh, np.int8)])
    ranks = _stratified(rng, rank_cdf, int(sizes.sum()))
    q_ids = np.split(hot[ranks - 1].astype(np.int64), np.cumsum(sizes)[:-1])

    nu = int(round(traffic["updates_per_query"] * nq))
    u_due = np.sort(rng.uniform(0.0, seconds, nu))
    umix = traffic["update_mix"]
    n_nodes = int(round(nu * umix["nodes"] / sum(umix.values())))
    u_kind = rng.permutation(np.r_[np.zeros(nu - n_nodes, np.int8),
                                   np.ones(n_nodes, np.int8)])
    n_anchor = rng.integers(1, traffic["anchors_max"] + 1, nu)
    ends = hot[_stratified(rng, rank_cdf, int(2 * nu + n_anchor.sum())) - 1]
    u_edge = ends[:2 * nu].reshape(nu, 2).astype(np.int64)
    u_anchors = np.split(ends[2 * nu:].astype(np.int64),
                         np.cumsum(n_anchor)[:-1])
    noise = rng.standard_normal((nu, F)).astype(np.float32)
    u_feat = (features[u_edge[:, 0]]
              + traffic["new_node_noise"] * noise).astype(np.float32)
    return Schedule(q_due=q_due, q_policy=q_policy, q_ids=q_ids, u_due=u_due,
                    u_kind=u_kind, u_edge=u_edge, u_anchors=u_anchors,
                    u_feat=u_feat, n_new_nodes=n_nodes)
