"""Training cells: back-to-back ``FedEngine.run`` calls on one engine.

Set-up makes the graph and the partition from the configuration's data
seed, builds one engine from ``--seed`` (weights, cohorts, sampling keys)
and drives its first call, which compiles every chunk shape and the eval
program. That first call is also what the reference follows: a callback
and a strategy hook, both read-only, keep the parameters, test losses,
tables, cohorts and batch losses at its first eval rounds. The window then
calls ``run`` on the same engine and state until ``--seconds`` have passed
and counts the rounds of the completed calls. After the window the
engine's own eval program is also run on the reference's round-0
parameters, beside the reference's full forward.
"""
from __future__ import annotations

import gc
import json
import math
import sys
import time

import numpy as np

from bench import flops
from bench.graphgen import make_graph
from bench.harness import Check, GcLog, Outcome, memory_peak_bytes
from bench.references import fedais_gcn as ref

# rounds whose batch losses the strategy hook keeps (the first is compared;
# the first three are reported)
COMPARED_LOSS_ROUNDS = 3


def make_data(cfg: dict):
    from repro.federated.partition import partition_graph

    graph = make_graph(cfg)
    fed = partition_graph(graph, cfg["clients"], alpha=cfg["alpha"],
                          max_deg=cfg["max_deg"], edge_keep=cfg["edge_keep"],
                          seed=cfg["data_seed"])
    return graph, fed


def method(cfg: dict):
    from repro.api import method_config

    return method_config(cfg["method"], tau0=cfg["tau0"],
                         local_epochs=cfg["local_epochs"], lr=cfg["lr"],
                         sample_ratio=cfg["sample_ratio"],
                         batch_cap=cfg["batch_cap"],
                         neighbor_fanout=cfg["neighbor_fanout"])


class Capture:
    """Keeps, at the eval rounds of the engine's first call, the parameters
    and test loss; at round 0 the loss pass and the cohort's own layer-1
    rows, at the last compared round the table norms. Reads only; inert
    once ``active`` is cleared."""

    fused_safe = True

    def __init__(self, rounds: tuple[int, ...], n_max: int):
        self.rounds, self.last, self.n_max = rounds, max(rounds), n_max
        self.active = True
        self.at: dict = {}

    def on_run_start(self, engine, state):
        pass

    def on_run_end(self, engine, state):
        pass

    def on_round_end(self, ctx):
        if not self.active or ctx.metrics is None or ctx.t not in self.rounds:
            return
        import jax
        import jax.numpy as jnp

        st = ctx.state
        rec = {"params": jax.tree_util.tree_map(np.asarray, st.params),
               "test_loss": float(ctx.metrics["loss"])}
        if ctx.t == 0:
            # the first cohort's loss pass, from the initial weights, and
            # its layer-1 rows after the round's pushes
            sel = ctx.engine.strategy.sels[0]
            rec["prev_loss"] = np.asarray(st.prev_loss)
            rec["sel0"] = sel
            rec["hist1_0"] = np.asarray(
                st.hist.hist1[jnp.asarray(sel), :self.n_max])
        if ctx.t == self.last:
            rec["norms"] = {
                "hist1": float(jnp.linalg.norm(st.hist.hist1)),
                "ghost_feat": float(jnp.linalg.norm(st.ghost_feat)),
                "prev_loss": float(jnp.linalg.norm(st.prev_loss))}
        self.at[ctx.t] = rec


def loss_log_strategy(mcfg, n_rounds: int):
    """The method's plain strategy, also keeping the cohorts and the
    (cohort, J) batch losses of the first ``n_rounds`` rounds from the
    streamed stats."""
    from repro.api import build_strategy
    from repro.api.strategies import MethodStrategy

    if type(build_strategy(mcfg)) is not MethodStrategy:
        raise SystemExit(f"bench: method {mcfg.name!r} no longer uses the "
                         "plain strategy the loss log extends")

    class LossLog(MethodStrategy):
        fusable = True

        def __init__(self, mcfg):
            super().__init__(mcfg)
            self.losses: list = []
            self.sels: list = []

        def post_round(self, engine, state, sel, stats):
            if len(self.losses) < n_rounds:
                self.sels.append(np.asarray(sel))
                self.losses.append(np.asarray(stats["epoch_losses"],
                                              np.float64))

    return LossLog(mcfg)


def make_engine(cell, graph, fed, seed: int):
    from repro.api import FedEngine
    from repro.api.callbacks import EvalCallback, HistoryCallback

    cfg, tr = cell.config, cell.traffic
    ev = cfg["eval_every"]
    capture = Capture((0, ev), fed.n_max)
    mcfg = method(cfg)
    eng = FedEngine(graph, fed, mcfg, rounds=tr["rounds_per_call"],
                    clients_per_round=cfg["cohort"], seed=seed,
                    strategy=loss_log_strategy(mcfg, COMPARED_LOSS_ROUNDS),
                    callbacks=[EvalCallback(ev), HistoryCallback(), capture],
                    train_backend=cfg["train_backend"],
                    eval_backend=cfg["eval_backend"],
                    sync_dtype=cfg["sync_dtype"])
    return eng, capture


def first_call(eng, capture):
    """Initial state and the engine's first call (set-up); returns the
    state and what the comparison reads from the call."""
    import jax

    state = eng.init_state()
    init = jax.tree_util.tree_map(np.asarray, state.params)
    eng.run(state)
    if eng.last_executor != "fused":
        raise SystemExit(f"bench: training ran {eng.last_executor!r}, not "
                         "the fused executor the cell measures")
    capture.active = False
    got = {"params": {-1: init}, "test_loss": {}, "norms": None,
           "losses": list(eng.strategy.losses),
           "prev_loss0": capture.at[0]["prev_loss"],
           "sel0": capture.at[0]["sel0"], "hist1_0": capture.at[0]["hist1_0"]}
    for t, rec in capture.at.items():
        got["params"][t] = rec["params"]
        got["test_loss"][t] = rec["test_loss"]
        if "norms" in rec:
            got["norms"] = rec["norms"]
    return state, got


def _leaf_norm_gap(got_a, got_b, want_a, want_b, grad_norms) -> float:
    """Worst leaf's gap between the norms of the program's and the
    reference's change from ``a`` to ``b``, over the larger of that leaf's
    reference norm and the median leaf's. Leaves whose first reference
    gradient is under a thousandth of the median leaf's are left out."""
    g = np.asarray(grad_norms, np.float64)
    kept = [k for k, gn in zip(ref.LEAVES, g) if gn >= 1e-3 * np.median(g)]
    ref_n = {k: float(np.linalg.norm(np.asarray(want_b[k], np.float64)
                                     - np.asarray(want_a[k], np.float64)))
             for k in kept}
    med = float(np.median(list(ref_n.values())))
    gaps = []
    for k in kept:
        got_n = float(np.linalg.norm(np.asarray(got_b[k], np.float64)
                                     - np.asarray(got_a[k], np.float64)))
        gaps.append(abs(got_n - ref_n[k]) / max(ref_n[k], med, 1e-30))
    return max(gaps)


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def first_loss_gap(got, want) -> float:
    """Worst client's gap of its first batch loss, over the larger of its
    reference loss and the cohort's median. A client that holds no training
    node has loss 0 on both sides, and reads 0 when the program agrees."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    return float(np.max(np.abs(got - want) / np.maximum(scale, 1e-30)))


def _widest(got, want) -> float:
    """Widest elementwise gap over the reference's largest magnitude."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def client_norm_gap(got, want) -> float:
    """Worst client's (the leading axis) gap between the program's norm and
    the reference's, over the larger of that client's reference norm and
    the median client's."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    axes = tuple(range(1, got.ndim))
    g_n, w_n = np.sqrt((got ** 2).sum(axes)), np.sqrt((want ** 2).sum(axes))
    scale = np.maximum(np.maximum(w_n, np.median(w_n)), 1e-30)
    return float(np.max(np.abs(g_n - w_n) / scale))


def program_eval(eval_graph: dict, params: dict) -> np.ndarray:
    """The engine's eval program, called as ``evaluate_global`` calls it,
    on ``params``: the server's logits for every node."""
    import jax
    from repro.federated import server

    # uncommitted, as the window's parameters are: committed arrays would
    # key a second compile of the program
    p = jax.device_put(params)
    return np.asarray(server._eval_logits(
        p, eval_graph["features"], eval_graph["nbr_idx"],
        eval_graph["nbr_mask"], csr=eval_graph.get("csr"),
        adj=eval_graph.get("adj"),
        backend=eval_graph.get("backend", "gather")))


def gaps(got: dict, want: dict, eval_every: int) -> dict:
    """Every number the comparison can read: the program (``got``) against
    the reference (``want``) over the first call's first rounds. The cell's
    traffic file says which of them are compared (its ``limits``)."""
    last = eval_every
    same_cohort = np.array_equal(got["sel0"], want["sel0"])
    out = {
        "first_loss_gap": first_loss_gap(got["losses"][0][:, 0],
                                         want["losses"][0][:, 0]),
        "loss_pass_gap": _widest(got["prev_loss0"], want["prev_loss0"]),
        "hist1_gap": (_widest(got["hist1_0"], want["hist1_0"])
                      if same_cohort else math.inf),
        "loss_gap": max(_rel(float(np.mean(g)), float(np.mean(w)))
                        for g, w in zip(got["losses"],
                                        want["losses"][:COMPARED_LOSS_ROUNDS])),
        "update_gap": _leaf_norm_gap(got["params"][-1], got["params"][0],
                                     want["params"][-1], want["params"][0],
                                     want["grad_norms"]),
        "test_loss_gap": _rel(got["test_loss"][0], want["test_loss"][0]),
        "change_gap": _leaf_norm_gap(got["params"][-1], got["params"][last],
                                     want["params"][-1], want["params"][last],
                                     want["grad_norms"]),
        "eval_last_gap": _rel(got["test_loss"][last], want["test_loss"][last]),
    }
    out["hist1_norm_gap"] = (client_norm_gap(got["hist1_0"], want["hist1_0"])
                             if same_cohort else math.inf)
    out["ghost_feat_gap"] = _rel(got["norms"]["ghost_feat"],
                                 want["norms"]["ghost_feat"])
    for k in ("hist1", "prev_loss"):
        out[f"{k}_last_gap"] = _rel(got["norms"][k], want["norms"][k])
    if "eval_logits" in got:
        out["eval_gap"] = _widest(got["eval_logits"], want["eval_logits"])
    return out


def reference_for(cell, graph, fed, seed: int, *, prec: str = "highest",
                  fault: str | None = None) -> dict:
    ev = cell.config["eval_every"]
    return ref.train_reference(cell.config, graph, fed, seed, ev + 1,
                               keep_rounds=(0, ev), prec=prec, fault=fault)


def with_eval(cell, got: dict, want: dict, eval_graph: dict) -> None:
    """The engine's eval program (``got``) and the reference's full forward
    (``want``), both on the reference's round-0 parameters."""
    p0 = want["params"][0]
    got["eval_logits"] = program_eval(eval_graph, p0)
    want["eval_logits"] = ref.eval_logits(p0, want["eval_graph"],
                                          cell.config["matmul_precision"])


def compare(cell, graph, fed, seed: int, got: dict, eval_graph: dict,
            log) -> list:
    """The checks of the cell's traffic file; the numbers it does not
    compare go to standard error for the record."""
    limits = cell.traffic["limits"]
    want = reference_for(cell, graph, fed, seed)
    mark = log.mark()
    with_eval(cell, got, want, eval_graph)
    print(f"eval check: {log.since(mark)['compiles']} compiles (0: the "
          "window's own eval program ran it)", file=sys.stderr, flush=True)
    numbers = gaps(got, want, cell.config["eval_every"])
    numbers["partition_errors"] = float(ref.check_partition(fed, graph,
                                                            cell.config))
    info = {k: v for k, v in numbers.items() if k not in limits}
    print(f"not compared: {json.dumps(info)}", file=sys.stderr, flush=True)
    return [Check(k, numbers[k], limits[k]) for k in limits]


def evals_per_call(rounds: int, eval_every: int) -> int:
    return sum(1 for t in range(rounds) if t % eval_every == 0
               or t == rounds - 1)


def layer_context(cell, graph, fed) -> dict:
    """Shapes and counts the per-layer readers turn into FLOPs and bytes."""
    cfg = cell.config
    hidden = cfg["model"]["hidden"]
    n_max = fed.n_max
    bsz = max(1, min(cfg["batch_cap"], int(round(n_max * cfg["sample_ratio"]))))
    st = flops.client_stats(fed.node_mask, fed.train_mask, fed.nbr_mask,
                            fed.ghost_mask, fanout=cfg["neighbor_fanout"],
                            batch=bsz)
    dims = dict(n_features=fed.n_features, hidden=hidden,
                n_classes=fed.n_classes)
    deg = np.minimum(np.bincount(np.asarray(graph.edges).reshape(-1),
                                 minlength=graph.n_nodes), cfg["max_deg"])
    rounds = cell.traffic["rounds_per_call"]
    return {
        "round_flops": flops.round_flops(st, cohort=cfg["cohort"],
                                         epochs=cfg["local_epochs"], **dims),
        "round_bytes": flops.round_bytes(st, cohort=cfg["cohort"], **dims),
        "eval_flops": flops.eval_flops(graph.n_nodes, float(deg.mean()),
                                       **dims),
        "evals_per_round": evals_per_call(rounds, cfg["eval_every"]) / rounds,
    }


def run(cell, *, seed, seconds, t_start, log, spans, tracer, chips):
    graph, fed = make_data(cell.config)
    eng, capture = make_engine(cell, graph, fed, seed)
    state, got = first_call(eng, capture)
    rounds_per_call = cell.traffic["rounds_per_call"]
    # set-up's garbage, so that no collection of it falls inside the window;
    # the objects that outlive set-up (about 90k, most of them JAX's own)
    # are frozen, so that a full collection in the window scans only what
    # the window allocates instead of stalling the host for 50-100 ms
    gc.collect()
    gc.freeze()

    tracer.start()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    mark = log.mark()
    calls, bad = 0, 0
    with GcLog() as gcl:
        while True:
            with spans.span("run-call"):
                res = eng.run(state)
            calls += 1
            bad += not np.isfinite(res.final["loss"])
            if time.perf_counter() - t0 >= seconds:
                break
    t1 = time.perf_counter()
    tracer.stop()
    window = log.since(mark)
    rounds = calls * rounds_per_call
    rate = rounds / (t1 - t0)
    per_call = [round(d, 4) for d in spans.durations("run-call")]
    print(f"window: {calls} calls, {rounds} rounds in {t1 - t0:.3f} s "
          f"(seconds per call: {per_call}); {window['compiles']} compiles "
          f"({window['compile_s']:.3f} s) and {gcl} inside it",
          file=sys.stderr, flush=True)

    peak = memory_peak_bytes(chips)
    ctx = dict(layer_context(cell, graph, fed), rounds=rounds, calls=calls,
               rounds_per_s=rate)
    eval_graph = eng.eval_graph
    del eng, state, res
    gc.unfreeze()
    gc.collect()
    checks = compare(cell, graph, fed, seed, got, eval_graph, log)
    return Outcome(end_to_end={"setup_s": setup_s, "train_rounds_per_s": rate},
                   checks=checks, attempted=rounds,
                   failed=bad * rounds_per_call, memory_peak_bytes=peak,
                   ctx=ctx)
