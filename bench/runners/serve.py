"""Serving cells: an open loop on the real clock against ``QueryEngine``.

Set-up makes the graph from the configuration's data seed and the weights
from ``--seed`` (on the device, in one jitted call), sizes the graph store
for every node the window will add, fills the layer-1 cache with one full
forward and compiles every bucket shape (``QueryEngine.warmup``) and the
feature write a node arrival makes.

The window: queries fall due on the schedule of ``bench/trafficgen.py``.
Whenever the loop is free it hands every query that is due and waiting to
``serve_batch``, one call per policy group, and applies the graph updates
that are due; every ``refresh_every`` calls it refreshes the cache. Each
query is timed from when it was due to when its call returned, so a stall
shows in the latency of every query queued behind it. Queries due inside the
window are all served, after its end if need be, and the window closes when
the last one returns.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from bench.graphgen import make_graph
from bench.harness import Check, GcLog, Outcome, memory_peak_bytes
from bench.references import fedais_gcn as ref
from bench.trafficgen import POLICIES, make_schedule


def make_server(cfg: dict, graph, seed: int, capacity: int):
    import jax

    from repro.graph.csr import build_padded_neighbors
    from repro.models.gcn import gcn_init
    from repro.serve import QueryEngine, ServedModel
    from repro.serve.updates import GraphStore

    hidden = tuple(cfg["model"]["hidden"])
    params = jax.jit(gcn_init, static_argnums=(1, 2, 3))(
        jax.random.PRNGKey(seed + 1), graph.n_features, graph.n_classes,
        hidden)
    idx, mask = build_padded_neighbors(graph.adjacency_lists(),
                                       cfg["max_deg"], seed=seed)
    store = GraphStore(graph.features, idx, mask, capacity=capacity,
                       seed=seed)
    sv = cfg["serve"]
    model = ServedModel(params, store, backend=sv["backend"], warm="refresh",
                        cache_dtype=sv["cache_dtype"])
    engine = QueryEngine(model, buckets=tuple(sv["buckets"]))
    engine.warmup()
    # the device write of a new node's features, at its own shape
    spare = np.array([capacity - 1], np.int64)
    model.set_features(spare, np.zeros((1, graph.n_features), np.float32))
    return engine


class Loop:
    """The open loop; ``log`` keeps, in execution order, what the reference
    must replay: updates, refreshes and the sampled queries."""

    def __init__(self, engine, sched, traffic: dict, spans, sample: set):
        self.eng, self.s, self.spans = engine, sched, spans
        self.refresh_every = traffic["refresh_every"]
        self.sample = sample
        self.log: list = []
        self.served: dict = {}
        self.latency = np.full(sched.n_queries, np.nan)
        self.late: list = []          # how late the loop woke for an event
        self.calls = 0

    def _update(self, i: int) -> None:
        s = self.s
        with self.spans.span("update"):
            if s.u_kind[i]:
                new = self.eng.model.n_active
                self.eng.add_nodes(s.u_feat[i][None, :],
                                   [(new, int(a)) for a in s.u_anchors[i]])
            else:
                self.eng.add_edges([tuple(s.u_edge[i])])
        self.log.append(("u", i))

    def _serve(self, group: list, policy: str, t0: float) -> None:
        with self.spans.span("serve_batch"):
            out, _ = self.eng.serve_batch([self.s.q_ids[q] for q in group],
                                          policy=policy)
        done = time.perf_counter()
        self.latency[group] = done - (t0 + self.s.q_due[group])
        for q, logits in zip(group, out):
            if q in self.sample:
                self.served[q] = np.asarray(logits)
                self.log.append(("q", q, policy))
        self.calls += 1
        if self.calls % self.refresh_every == 0:
            with self.spans.span("refresh"):
                self.eng.refresh()
            self.log.append(("r",))

    def run(self) -> float:
        """Serve the whole schedule; returns the window's start."""
        s = self.s
        nq, nu = s.n_queries, len(s.u_due)
        qi = ui = 0
        pending: list = []
        t0 = time.perf_counter()
        while qi < nq or pending:
            now = time.perf_counter() - t0
            while qi < nq and s.q_due[qi] <= now:
                pending.append(qi)
                qi += 1
            while ui < nu and s.u_due[ui] <= now:
                self._update(ui)
                ui += 1
            if pending:
                for p, policy in enumerate(POLICIES):
                    group = [q for q in pending if s.q_policy[q] == p]
                    if group:
                        self._serve(np.array(group), policy, t0)
                pending = []
                continue
            nxt = min(s.q_due[qi] if qi < nq else np.inf,
                      s.u_due[ui] if ui < nu else np.inf)
            if not np.isfinite(nxt):
                break
            with self.spans.span("generator-wait"):
                time.sleep(max(0.0, nxt - (time.perf_counter() - t0)))
            self.late.append(time.perf_counter() - t0 - nxt)
        return t0


def replay(cell, graph, seed: int, sched, loop, capacity: int):
    """The reference's replay of the window's events, in their order;
    returns it and the sampled queries in replay order."""
    rp = ref.ServeReplay(graph.features, graph.edges, capacity,
                         cell.config["max_deg"], seed)
    order = []
    for ev in loop.log:
        if ev[0] == "u":
            i = ev[1]
            if sched.u_kind[i]:
                rp.add_node(sched.u_feat[i], sched.u_anchors[i])
            else:
                rp.add_edges([tuple(sched.u_edge[i])])
        elif ev[0] == "r":
            rp.refresh()
        else:
            rp.query(sched.q_ids[ev[1]], ev[2])
            order.append(ev[1])
    return rp, order


def replay_logits(cell, graph, seed: int, sched, loop, capacity: int, *,
                  prec: str) -> list:
    rp, _ = replay(cell, graph, seed, sched, loop, capacity)
    params = ref.init_params(seed, graph.n_features, graph.n_classes,
                             cell.config["model"]["hidden"])
    return rp.logits(params, prec)


def logit_gap(got: list, want: list) -> float:
    """The widest gap between two sets of logits, over the largest
    reference logit."""
    if not want:
        return 0.0
    scale = max(float(np.max(np.abs(w))) for w in want)
    gap = max(float(np.max(np.abs(np.asarray(g) - w)))
              for g, w in zip(got, want))
    return gap / max(scale, 1e-30)


def compare(cell, graph, seed: int, sched, loop, capacity: int,
            final_store) -> dict:
    """Replay the window's events in the reference and compare every sampled
    query's logits; also the serving graph the updates left behind."""
    rp, order = replay(cell, graph, seed, sched, loop, capacity)
    params = ref.init_params(seed, graph.n_features, graph.n_classes,
                             cell.config["model"]["hidden"])
    want = rp.logits(params, cell.config["matmul_precision"])
    idx, mask = final_store
    n = rp.n_active
    mismatch = int((mask[:n] != rp.mask[:n]).sum()
                   + ((idx[:n] != rp.idx[:n]) & (rp.mask[:n] > 0)).sum())
    return {"serve_gap": logit_gap([loop.served[q] for q in order], want),
            "graph_mismatch": float(mismatch),
            "unanswered": float(np.isnan(loop.latency).sum()),
            "compared": len(order)}


def run(cell, *, seed, seconds, t_start, log, spans, tracer, chips,
        keep: bool = False):
    """One run; ``keep`` hands the replay's inputs back in the context
    (for calibration)."""
    cfg, tr = cell.config, cell.traffic
    graph = make_graph(cfg)
    sched = make_schedule(tr, seed, seconds, graph.features)
    capacity = graph.n_nodes + sched.n_new_nodes
    engine = make_server(cfg, graph, seed, capacity)
    rng = np.random.default_rng((seed, 0xC4EC))
    k = min(tr["check_sample"], sched.n_queries)
    sizes = np.array([len(i) for i in sched.q_ids])
    longest = np.argsort(-sizes, kind="stable")[:max(1, k // 16)]
    sample = set(longest.tolist()) | set(
        rng.choice(sched.n_queries, k - len(longest), replace=False).tolist())
    loop = Loop(engine, sched, tr, spans, sample)
    gc.collect()        # set-up's garbage, outside the window

    tracer.start()
    mark = log.mark()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    with GcLog() as gcl:
        loop.run()
    t1 = time.perf_counter()
    tracer.stop()
    window = log.since(mark)
    lat_ms = loop.latency * 1e3
    late = np.asarray(loop.late) * 1e3
    print(f"window: {sched.n_queries} queries, {len(sched.u_due)} updates, "
          f"{loop.calls} serve_batch calls in {t1 - t0:.3f} s; "
          f"{window['compiles']} compiles ({window['compile_s']:.3f} s) "
          f"and {gcl} inside it; generator woke late by p50 "
          f"{np.percentile(late, 50) if len(late) else 0:.3f} ms, max "
          f"{late.max() if len(late) else 0:.3f} ms over {len(late)} waits; "
          f"store grew {engine.model.store.n_grows} times",
          file=sys.stderr, flush=True)

    peak = memory_peak_bytes(chips)
    final_store = (engine.model.store.nbr_idx.copy(),
                   engine.model.store.nbr_mask.copy())
    ctx = {"calls": loop.calls, "queries": sched.n_queries,
           "updates": len(sched.u_due)}
    del engine
    gc.collect()
    numbers = compare(cell, graph, seed, sched, loop, capacity, final_store)
    print(f"compared {numbers.pop('compared')} sampled queries",
          file=sys.stderr, flush=True)
    if keep:
        ctx["replay"] = (graph, sched, loop, capacity, final_store)
    limits = tr["limits"]
    checks = [Check(k, numbers[k], limits[k]) for k in limits]
    answered = lat_ms[np.isfinite(lat_ms)]
    return Outcome(
        end_to_end={"setup_s": setup_s,
                    "serve_p95_ms": float(np.percentile(answered, 95)),
                    "serve_qps": sched.n_queries / (t1 - t0)},
        checks=checks, attempted=sched.n_queries,
        failed=int(np.isnan(lat_ms).sum()), memory_peak_bytes=peak, ctx=ctx)
