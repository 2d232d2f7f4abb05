#!/usr/bin/env python3
"""Run FedAIS training and serving once on a TPU, at Pubmed's published size.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the pod-sharded path on a 2x2 mesh

One chip: fused training through ``FedEngine``, the same engine with the
compiled Pallas SpMM kernel in training and eval (checked against the gather
backend), and serving a checkpoint through ``repro.serve`` (checked against
the training eval path). ``--chips 4``: the pod-sharded executor on
``make_pod_mesh(2, 2)`` against the mesh-less fused run, and nothing else.

Data is the synthetic Pubmed stand-in at full size (19,717 nodes, 500
features, 3 classes), split over 16 clients; weights come from seed 0.
Everything runs in this one process, which holds the chips. Each phase
prints one JSON line with what it did and its numbers (compile seconds and
rates are informational, on the host's clock). The last line of standard
output is ``{"ok": true, "device": {...}}``. A failed check raises and the
script exits non-zero without that line; a host without a TPU fails at the
device check.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

SEED = 0
N_CLIENTS, COHORT, ROUNDS, EVAL_EVERY, TAU0 = 16, 8, 4, 2, 4
N_QUERIES, N_NEW_EDGES = 300, 8
QUERY_SIZES = (1, 3, 8, 17, 32, 64, 128)
# the tiers of tests/test_train_backend.py (backend parity) and
# tests/test_pod_sharding.py (pod-sharded vs fused)
LOSS_RTOL, LOSS_ATOL, COMM_RTOL = 1e-4, 1e-6, 1e-2
CLOSE_KEYS = ("test_acc", "test_loss")
COMM_KEYS = ("comm_total", "comm_embed", "wall_clock")
POD_EXACT_KEYS = ("tau", "comm_total", "comm_embed", "flops", "wall_clock")
# XLA's f32 matmuls on the TPU default to one bf16 pass. That turns a
# ULP-level summation-order gap (spmm against gather, a sharded merge
# against a local one) into bf16 rounding flips: on a v5e one spmm forward
# left gather's logits by 2.1e-3 and two rounds of training moved the test
# loss 4.5e-2, against 1.2e-7 and 1.0e-5 with fp32 matmuls. The tiers above
# were set with fp32 matmuls, so the parity phases run both sides at fp32.
PARITY_PRECISION = "highest"
# one full-graph forward, spmm against gather, relative to the largest logit
LOGIT_RTOL = 1e-5
# fresh logits (a 1-hop recompute) against the warm cache's, relative to the
# largest logit
SERVE_RTOL = 1e-5


def emit(phase: str, **numbers) -> None:
    print(json.dumps({"phase": phase, **numbers}), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


class CompileLog:
    """Backend compiles seen by JAX in this process (count and seconds)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n, self.seconds = 0, 0.0

    def __call__(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration

    def mark(self) -> tuple[int, float]:
        return self.n, self.seconds

    def since(self, mark) -> dict:
        return {"compiles": self.n - mark[0],
                "compile_s": self.seconds - mark[1]}


def history_gaps(ref, got, close_keys, comm_keys, exact_keys=None) -> tuple:
    """Largest relative gap per float column, and the columns outside their
    tier: ``close_keys`` within LOSS_RTOL/LOSS_ATOL, ``comm_keys`` within
    COMM_RTOL, the rest (or ``exact_keys``) equal."""
    gaps, bad = {}, []
    keys = list(ref.history) if exact_keys is None else [
        *exact_keys, *close_keys]
    if exact_keys is None and set(ref.history) != set(got.history):
        bad.append("columns")
    for k in keys:
        a = np.asarray(ref.history[k], np.float64)
        b = np.asarray(got.history[k], np.float64)
        if a.shape != b.shape:
            bad.append(k)
        elif k in close_keys or k in comm_keys:
            gaps[k] = float(np.max(np.abs(b - a)
                                   / np.maximum(np.abs(a), 1e-30)))
            rtol, atol = ((LOSS_RTOL, LOSS_ATOL) if k in close_keys
                          else (COMM_RTOL, 0.0))
            if not np.allclose(b, a, rtol=rtol, atol=atol):
                bad.append(k)
        elif ref.history[k] != got.history[k]:
            bad.append(k)
    return gaps, bad


def device_check(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices; "
                 f"JAX found {len(devs)}")
    emit("device", platform=devs[0].platform, kind=devs[0].device_kind,
         count=len(devs), jax=jax.__version__)
    return devs


def load_data():
    from repro.federated.partition import partition_graph
    from repro.graph.data import make_dataset

    t0 = time.perf_counter()
    g = make_dataset("pubmed", scale=1, max_features=500, seed=SEED)
    fed = partition_graph(g, N_CLIENTS, alpha=0.5, seed=SEED)
    check(g.n_nodes == 19_717 and g.n_features == 500 and g.n_classes == 3,
          f"not Pubmed's published size: {g.n_nodes} nodes, "
          f"{g.n_features} features, {g.n_classes} classes")
    emit("data", nodes=g.n_nodes, features=g.n_features,
         edges=int(len(g.edges)), clients=fed.n_clients, n_max=fed.n_max,
         g_max=fed.g_max, seconds=time.perf_counter() - t0)
    return g, fed


def make_engine(g, fed, **kw):
    from repro.api import FedEngine, method_config

    kw.setdefault("rounds", ROUNDS)
    return FedEngine(g, fed, method_config("fedais", tau0=TAU0), seed=SEED,
                     clients_per_round=COHORT, eval_every=EVAL_EVERY, **kw)


def train_loss_strategy():
    """FedAIS's plain strategy, also keeping each round's mean local
    training loss from the streamed stats. ``post_round`` only reads those
    stats, so the fused executor may replay it at the end of a chunk."""
    from repro.api import build_strategy, method_config
    from repro.api.strategies import MethodStrategy

    mcfg = method_config("fedais", tau0=TAU0)
    check(type(build_strategy(mcfg)) is MethodStrategy,
          "fedais no longer uses the plain strategy")

    class TrainLossLog(MethodStrategy):
        fusable = True

        def __init__(self, mcfg):
            super().__init__(mcfg)
            self.losses: list[float] = []

        def post_round(self, engine, state, sel, stats):
            self.losses.append(float(np.mean(stats["epoch_losses"])))

    return TrainLossLog(mcfg)


def phase_train(g, fed, log: CompileLog):
    mark, t0 = log.mark(), time.perf_counter()
    strategy = train_loss_strategy()
    eng = make_engine(g, fed, strategy=strategy)
    state = eng.init_state()
    res = eng.run(state)
    first_s = time.perf_counter() - t0
    check(eng.last_executor == "fused",
          f"training ran {eng.last_executor!r}, not the fused executor")
    losses = np.asarray(res.history["test_loss"], np.float64)
    train = np.asarray(strategy.losses, np.float64)
    check(np.isfinite(losses).all() and np.isfinite(train).all(),
          f"non-finite losses: test {losses}, train {train}")
    check(len(train) == ROUNDS and train[-1] < train[0],
          f"training loss did not fall over {ROUNDS} rounds: {train}")
    check(np.isfinite(res.final["acc"]) and np.isfinite(res.final["loss"]),
          f"non-finite final eval {res.final}")
    first = log.since(mark)

    # the same engine again: compiled programs are reused, nothing recompiles
    mark, t0 = log.mark(), time.perf_counter()
    again = eng.run()
    warm_s = time.perf_counter() - t0
    emit("train", executor=eng.last_executor, rounds=ROUNDS,
         train_loss=train.tolist(), test_loss=losses.tolist(),
         final_acc=res.final["acc"], first_run_s=first_s, **first,
         warm_rounds_per_s=ROUNDS / warm_s,
         warm_recompiles=log.since(mark)["compiles"],
         warm_history_identical=again.history == res.history)
    return eng, state


def eval_args(eg) -> tuple[tuple, dict]:
    return ((eg["features"], eg["nbr_idx"], eg["nbr_mask"]),
            {"csr": eg["csr"], "adj": eg["adj"], "backend": eg["backend"]})


def phase_kernel(g, fed, log: CompileLog):
    import jax
    import jax.numpy as jnp

    from repro.federated.server import _eval_logits

    mark, t0 = log.mark(), time.perf_counter()
    with jax.default_matmul_precision(PARITY_PRECISION):
        gather = make_engine(g, fed, rounds=2)
        ref = gather.run()
        spmm = make_engine(g, fed, rounds=2, train_backend="spmm",
                           eval_backend="spmm")
        got = spmm.run()
        seconds = time.perf_counter() - t0
        check(spmm.last_executor == "fused",
              f"spmm training ran {spmm.last_executor!r}, not fused")

        # one full-graph forward from the same weights, through both backends
        st = spmm.init_state()
        args, kw = eval_args(spmm.eval_graph)
        want_args, want_kw = eval_args(gather.eval_graph)
        logits = np.asarray(_eval_logits(st.params, *args, **kw))
        want = np.asarray(_eval_logits(st.params, *want_args, **want_kw))
        fwd_gap = float(np.max(np.abs(logits - want))
                        / np.max(np.abs(want)))

        # the Mosaic kernel is in both programs: compiled, not interpreted
        sel = jnp.zeros((1, COHORT), jnp.int32)
        chunk = spmm._fused_chunk.lower(
            st.params, st.hist.hist1, st.hist.age, st.ghost_feat,
            st.prev_loss, st.key, st.arrays, sel, sel,
            jnp.zeros((1,), jnp.int32), jnp.asarray(TAU0, jnp.int32)).as_text()
        evl = _eval_logits.lower(st.params, *args, **kw).as_text()
    check("tpu_custom_call" in chunk,
          "the spmm training chunk holds no compiled Pallas kernel")
    check("tpu_custom_call" in evl,
          "the spmm eval program holds no compiled Pallas kernel")

    gaps, bad = history_gaps(ref, got, CLOSE_KEYS, COMM_KEYS)
    emit("kernel", executor=spmm.last_executor, rounds=2,
         matmul_precision=PARITY_PRECISION, kernel_in_train_chunk=True,
         kernel_in_eval=True, eval_logits_rel_gap=fwd_gap,
         test_loss_spmm=got.history["test_loss"],
         test_loss_gather=ref.history["test_loss"], rel_gaps=gaps,
         outside_tier=bad, seconds=seconds, **log.since(mark))
    check(fwd_gap <= LOGIT_RTOL,
          f"spmm eval logits leave gather's by {fwd_gap!r} (relative)")
    check(not bad, f"spmm history leaves the gather tier in {bad}: {gaps}")


def phase_serve(g, fed, state, log: CompileLog):
    from repro.launch.serve_fed import parity_check
    from repro.serve import QueryEngine, ServedModel, save_federation

    rng = np.random.default_rng(SEED)
    n = g.n_nodes
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as d:
        save_federation(d, ROUNDS, state)
        model = ServedModel.restore(d, g, fed, backend="segment", seed=SEED)
    engine = QueryEngine(model, fallback=False)
    mark = log.mark()
    programs = engine.warmup()
    warm = log.since(mark)

    # raises unless every node's served logits are bit-identical
    parity_check(model, engine, g, fed, state, SEED)
    mark = log.mark()

    def fresh_gap(ids) -> float:
        hist = engine.query(ids, policy="historical")
        fresh = engine.query(ids, policy="fresh")
        gap = float(np.max(np.abs(fresh - hist)))
        check(gap <= SERVE_RTOL * float(np.max(np.abs(hist))),
              f"fresh logits leave the warm cache's by {gap!r}")
        return gap

    # on the unchanged graph the fresh 1-hop recompute equals the warm cache
    sample = rng.choice(n, 128, replace=False)
    gap_before = fresh_gap(sample)

    lat = {"historical": [], "fresh": []}
    for i in range(N_QUERIES):
        policy = "fresh" if i % 2 else "historical"
        ids = rng.integers(0, n, rng.choice(QUERY_SIZES))
        t0 = time.perf_counter()
        logits = engine.query(ids, policy=policy)
        lat[policy].append(time.perf_counter() - t0)
        check(logits.shape == (len(ids), g.n_classes)
              and np.isfinite(logits).all(),
              f"bad {policy} logits for {len(ids)} ids: {logits.shape}")

    # streaming edges invalidate their endpoints; refresh re-embeds them
    edges = rng.integers(0, n, (N_NEW_EDGES, 2))
    affected = engine.add_edges(edges)
    stale = model.invalid_rows()
    check(len(affected) > 0 and set(affected.tolist()) <= set(stale.tolist()),
          f"add_edges invalidated {stale} for affected rows {affected}")
    refreshed = engine.refresh()
    check(refreshed == len(stale) and len(model.invalid_rows()) == 0,
          f"refresh re-embedded {refreshed} of {len(stale)} stale rows")
    gap_after = fresh_gap(affected)

    retraced = engine.trace_count - engine.trace_count_after_warmup
    check(retraced == 0, f"{retraced} serve programs traced after warm-up")
    check(engine.n_fallbacks == 0, f"{engine.n_fallbacks} fallbacks")
    p50 = {k: float(np.median(v)) * 1e3 for k, v in lat.items()}
    emit("serve", backend=model.backend, capacity=model.store.capacity,
         warmup_programs=programs, warmup_compile_s=warm["compile_s"],
         queries=N_QUERIES, query_p50_ms=p50,
         queries_per_s=N_QUERIES / sum(map(sum, lat.values())),
         edges_added=int(len(edges)), rows_refreshed=refreshed,
         recompiles_after_warmup=retraced,
         backend_compiles_after_warmup=log.since(mark)["compiles"],
         fallbacks=engine.n_fallbacks, parity_bit_identical=True,
         fresh_vs_hist_gap_before=gap_before,
         fresh_vs_hist_gap_after=gap_after)


def phase_pod(g, fed, log: CompileLog):
    import jax

    from repro.sharding.tables import make_pod_mesh

    mark, t0 = log.mark(), time.perf_counter()
    with jax.default_matmul_precision(PARITY_PRECISION):
        ref_eng = make_engine(g, fed)
        ref = ref_eng.run()
        ref_s = time.perf_counter() - t0

        mesh = make_pod_mesh(2, 2)
        t0 = time.perf_counter()
        pod = make_engine(g, fed, mesh=mesh)
        state = pod.init_state()
        got = pod.run(state)
        pod_s = time.perf_counter() - t0
    check(ref_eng.last_executor == "fused",
          f"reference ran {ref_eng.last_executor!r}, not fused")
    check(pod.last_executor == "pod_sharded",
          f"the 2x2 mesh ran {pod.last_executor!r}, not pod_sharded")

    # the K-sized tables are split over the pods: each device holds half
    hist1 = state.hist.hist1
    rows = sorted((s.device.id, int(s.data.shape[0]))
                  for s in hist1.addressable_shards)
    check(len({d for d, _ in rows}) == 4
          and all(r == hist1.shape[0] // 2 for _, r in rows),
          f"hist1 {hist1.shape} is not split over 2 pods: {rows}")

    gaps, bad = history_gaps(ref, got, CLOSE_KEYS, (), POD_EXACT_KEYS)
    emit("pod", executor=pod.last_executor, mesh=dict(mesh.shape),
         matmul_precision=PARITY_PRECISION,
         hist1_shape=list(hist1.shape), hist1_rows_per_device=rows,
         hist1_spec=str(hist1.sharding.spec),
         test_loss_pod=got.history["test_loss"],
         test_loss_fused=ref.history["test_loss"], rel_gaps=gaps,
         outside_tier=bad, fused_s=ref_s, pod_s=pod_s, **log.since(mark))
    check(not bad, f"pod-sharded history leaves the fused tier in {bad}: "
                   f"{gaps}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the pod-sharded path and its fused "
                         "reference on a 2x2 mesh")
    args = ap.parse_args(argv)

    import jax

    devs = device_check(args.chips)
    enable_compile_cache()
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)

    g, fed = load_data()
    if args.chips == 4:
        phase_pod(g, fed, log)
    else:
        _, state = phase_train(g, fed, log)
        phase_kernel(g, fed, log)
        phase_serve(g, fed, state, log)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
