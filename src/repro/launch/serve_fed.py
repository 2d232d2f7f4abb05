"""End-to-end federated serving pipeline: train -> checkpoint -> serve.

Trains a small federation with the FedEngine, checkpoints it via
``save_federation``, restores it into a :class:`ServedModel` + warmed
:class:`QueryEngine`, then drives heavy synthetic traffic (queries + live
graph updates) through the :class:`LoadGenerator` and writes the
schema-guarded ``BENCH_serve.json`` latency ledger at the repo root.

    PYTHONPATH=src python -m repro.launch.serve_fed --quick
    PYTHONPATH=src python -m repro.launch.serve_fed --quick --policy fresh \
        --mode closed --backend gather

``--parity-check`` additionally asserts the served "historical" logits over
every node are bit-identical to the training-side eval path before any
traffic runs (the same invariant tests/test_serve.py pins).

``--cache-dtype {fp32,bf16,int8}`` keeps the h1 embedding cache resident in
the quantized wire format (repro.federated.quant) — bf16 halves and int8
nearly quarters the resident bytes, dequantizing on read inside the
bucketed query path. The ledger gains a ``cache`` column (dtype, resident
bytes, test-split accuracy of the served logits) so BENCH_serve.json
records accuracy next to latency for each format. ``--parity-check`` stays
fp32-only: a quantized cache is lossy by design.

Before traffic runs, the pipeline A/Bs the engine's fused single-call
bucket path against the decomposed two-call reference (``fused=False``) on
the same warm model and gates fused p50 <= two-call p50 with zero
post-warmup recompiles; the result lands in the ledger's ``fused`` column.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def build_args(argv=None) -> argparse.Namespace:
    from repro.federated.quant import SYNC_DTYPES
    from repro.serve import CACHE_POLICIES, LOAD_MODES, SERVE_BACKENDS

    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--quick", action="store_true",
                    help="tiny federation + 200 queries / 20 updates (CI)")
    ap.add_argument("--dataset", default="pubmed")
    ap.add_argument("--scale", type=int, default=None,
                    help="synthetic dataset scale (default: 64 quick, 8 full)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=None,
                    help="training rounds (default: 3 quick, 30 full)")
    ap.add_argument("--cohort", type=int, default=4)
    ap.add_argument("--method", default="fedais")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="segment", choices=SERVE_BACKENDS)
    ap.add_argument("--warm", default="refresh", choices=("refresh", "tables"))
    ap.add_argument("--policy", default="historical", choices=CACHE_POLICIES,
                    help="dominant cache policy in the traffic mix")
    ap.add_argument("--mode", default="open", choices=LOAD_MODES)
    ap.add_argument("--rate", type=float, default=500.0,
                    help="open-loop Poisson arrival rate (req/s)")
    ap.add_argument("--concurrency", type=int, default=8,
                    help="closed-loop client count")
    ap.add_argument("--queries", type=int, default=None,
                    help="query count (default: 200 quick, 2000 full)")
    ap.add_argument("--updates", type=int, default=None,
                    help="streaming update count (default: 20 quick, 200 full)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (default: a temp dir)")
    ap.add_argument("--out", default=os.path.join(REPO_ROOT, "BENCH_serve.json"))
    ap.add_argument("--cache-dtype", default="fp32",
                    choices=list(SYNC_DTYPES),
                    help="resident wire format of the h1 embedding cache "
                         "(repro.federated.quant): bf16 halves and int8 "
                         "nearly quarters the resident bytes; dequantized "
                         "on read inside the bucketed query path")
    ap.add_argument("--parity-check", action="store_true",
                    help="assert served historical logits == training eval "
                         "logits bit-for-bit before running traffic "
                         "(fp32 cache only — a quantized cache is lossy "
                         "by design)")
    args = ap.parse_args(argv)
    if args.parity_check and args.cache_dtype != "fp32":
        ap.error("--parity-check demands bit-identical logits; a "
                 f"{args.cache_dtype} cache is lossy by design (the "
                 "accuracy column in BENCH_serve.json tracks its effect)")
    args.scale = args.scale if args.scale is not None else (64 if args.quick else 8)
    args.rounds = args.rounds if args.rounds is not None else (3 if args.quick else 30)
    args.queries = args.queries if args.queries is not None else (200 if args.quick else 2000)
    args.updates = args.updates if args.updates is not None else (20 if args.quick else 200)
    return args


def train_and_checkpoint(args, ckpt_dir: str):
    """Run the federation and save the serving checkpoint. Returns
    (graph, fed, state) so the caller can parity-check against it.
    If ``ckpt_dir`` already holds a checkpoint and no parity check is
    requested, training is skipped and the checkpoint reused (state=None)."""
    from repro.api import FedEngine, method_config
    from repro.checkpoint import latest_step
    from repro.graph.data import make_dataset
    from repro.federated.partition import partition_graph
    from repro.serve import save_federation

    g = make_dataset(args.dataset, scale=args.scale, seed=args.seed)
    fed = partition_graph(g, args.clients, alpha=0.5, seed=args.seed)
    have = latest_step(ckpt_dir)
    if have is not None and not args.parity_check:
        print(f"# reusing checkpoint step {have} in {ckpt_dir}")
        return g, fed, None
    mcfg = method_config(args.method, tau0=2)
    engine = FedEngine(g, fed, mcfg, rounds=args.rounds,
                       clients_per_round=args.cohort, seed=args.seed,
                       eval_every=args.rounds)
    state = engine.init_state()
    result = engine.run(state)
    path = save_federation(ckpt_dir, args.rounds, state)
    print(f"# trained {args.method} {args.rounds} rounds on {args.dataset} "
          f"scale={args.scale} K={args.clients}: "
          f"test_acc={result.final.get('acc', float('nan')):.3f}")
    print(f"# checkpoint: {path}")
    return g, fed, state


def parity_check(model, engine, graph, fed, state, seed: int) -> None:
    """Served historical logits must be bit-identical to the training-side
    full-graph eval path (build_eval_graph -> _eval_logits)."""
    from repro.federated.server import _eval_logits, build_eval_graph

    eg = build_eval_graph(graph, max_deg=fed.max_deg, seed=seed,
                          backend=model.backend)
    want = np.asarray(_eval_logits(
        state.params, eg["features"], eg["nbr_idx"], eg["nbr_mask"],
        csr=eg.get("csr"), adj=eg.get("adj"), backend=model.backend))
    n = graph.features.shape[0]
    got = np.concatenate([
        engine.query(np.arange(i, min(i + 128, n)), policy="historical")
        for i in range(0, n, 128)])
    if not np.array_equal(got, want):
        raise AssertionError("served historical logits are not bit-identical "
                             "to the training eval path")
    print(f"# parity-check: {n} nodes bit-identical to build_eval_graph")


def serve_accuracy(engine, graph) -> float:
    """Test-split accuracy of the served historical logits — the accuracy
    half of the accuracy-vs-latency cache column. Runs through the warmed
    bucketed query path, so a quantized cache pays its dequant-on-read and
    its rounding here exactly as production queries would."""
    n = graph.features.shape[0]
    logits = np.concatenate([
        engine.query(np.arange(i, min(i + 128, n)), policy="historical")
        for i in range(0, n, 128)])
    mask = np.asarray(graph.test_mask, bool)
    pred = np.asarray(logits).argmax(-1)
    return float((pred[mask] == np.asarray(graph.labels)[mask]).mean())


def fused_ab(engine, graph, seed: int, reps: int = 200) -> dict:
    """A/B the fused single-call bucket path against the decomposed two-call
    reference on the same warm model (smallest bucket, historical policy,
    interleaved reps). Asserts bit-parity first, then gates fused p50 <=
    two-call p50 with zero fused recompiles — the ``fused`` ledger column."""
    import time

    from repro.serve import QueryEngine

    twin = QueryEngine(engine.model, cache_policy="historical", fused=False)
    b = engine.buckets[0]
    n = graph.features.shape[0]
    rng = np.random.default_rng((seed, 0xAB))
    ids = rng.integers(0, n, size=b).astype(np.int64)
    # warm both paths on the bucket, then parity: both modes decode the same
    # cache bits and sum segments in the same slot order -> bit-identical
    want = engine.query(ids, policy="historical")
    got = twin.query(ids, policy="historical")
    if not np.array_equal(np.asarray(got), np.asarray(want)):
        raise AssertionError("two-call reference logits diverge from the "
                             "fused bucket path")
    fused_ts, two_ts = [], []
    for _ in range(reps):
        qs = rng.integers(0, n, size=b).astype(np.int64)
        t0 = time.perf_counter()
        engine.query(qs, policy="historical")
        fused_ts.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        twin.query(qs, policy="historical")
        two_ts.append(time.perf_counter() - t0)
    p50 = float(np.median(fused_ts) * 1e3)
    two_p50 = float(np.median(two_ts) * 1e3)
    recompiles = engine.trace_count - engine.trace_count_after_warmup
    col = {"bucket": int(b), "p50_ms": p50, "twocall_p50_ms": two_p50,
           "speedup": two_p50 / p50, "recompiles_after_warmup": recompiles}
    print(f"# fused A/B (bucket {b}, {reps} reps): fused p50={p50:.3f}ms vs "
          f"two-call p50={two_p50:.3f}ms ({col['speedup']:.2f}x)")
    if recompiles:
        raise SystemExit(f"fused A/B retraced {recompiles} serve shape(s) "
                         "after warmup")
    if p50 > two_p50:
        raise SystemExit(f"fused bucket path regressed: p50 {p50:.3f}ms > "
                         f"two-call {two_p50:.3f}ms")
    return col


def run_pipeline(args) -> dict:
    """The full train -> checkpoint -> restore -> serve pipeline. Returns the
    validated BENCH payload (and writes it to ``args.out``)."""
    import jax

    from repro.serve import (
        LoadGenerator,
        QueryEngine,
        ServedModel,
        validate_bench_serve,
    )

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="serve_fed_ckpt_")
    g, fed, state = train_and_checkpoint(args, ckpt_dir)

    model = ServedModel.restore(ckpt_dir, g, fed, backend=args.backend,
                                warm=args.warm, seed=args.seed,
                                cache_dtype=args.cache_dtype)
    engine = QueryEngine(model, cache_policy=args.policy)
    n_traces = engine.warmup()
    print(f"# restored step {model.restored_step}; warmup compiled "
          f"{n_traces} programs over buckets {engine.buckets}")

    if args.parity_check:
        parity_check(model, engine, g, fed, state, args.seed)
        # parity queries ran through the warmed buckets: must not retrace
        if engine.trace_count != engine.trace_count_after_warmup:
            raise AssertionError("parity check retraced a serve shape")

    # the accuracy half of the cache column, measured on the warm cache
    # before traffic mutates the graph
    acc = serve_accuracy(engine, g)
    cache_col = {
        "cache_dtype": model.cache_dtype,
        "resident_bytes": model.cache_resident_bytes(),
        "serve_accuracy": acc,
    }
    print(f"# cache: {model.cache_dtype} "
          f"{cache_col['resident_bytes']:,}B resident, "
          f"test accuracy {acc:.4f}")
    if engine.trace_count != engine.trace_count_after_warmup:
        raise AssertionError("accuracy sweep retraced a serve shape")

    # the fused-vs-two-call hot-path column, measured on the warm model
    # before traffic mutates the graph
    fused_col = fused_ab(engine, g, args.seed)

    mix =({"historical": 0.9, "fresh": 0.1} if args.policy == "historical"
           else {"fresh": 0.9, "historical": 0.1})
    gen = LoadGenerator(engine, seed=args.seed, n_queries=args.queries,
                        n_updates=args.updates, mode=args.mode,
                        rate=args.rate, concurrency=args.concurrency,
                        policy_mix=mix)
    ledger = gen.run()

    retraced = engine.trace_count - engine.trace_count_after_warmup
    if retraced:
        raise AssertionError(
            f"{retraced} serve recompiles after warmup — bucket shapes leaked")

    payload = ledger.summary(backend=args.backend, devices=jax.device_count(),
                             quick=bool(args.quick), mode=args.mode,
                             policy_mix=mix, model_summary=model.summary(),
                             cache=cache_col, fused=fused_col)
    problems = validate_bench_serve(payload)
    if problems:
        raise SystemExit("refusing to write invalid BENCH_serve.json:\n  "
                         + "\n  ".join(problems))
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {args.out}")
    print(f"# {payload['n_queries']} queries / {payload['n_updates']} updates "
          f"({args.mode}-loop): {payload['queries_per_s']:.1f} q/s, "
          f"p50={payload['p50_ms']:.2f}ms p99={payload['p99_ms']:.2f}ms, "
          f"occupancy={payload['batch_occupancy']:.2f}, "
          f"hit_rate={payload['cache_hit_rate']:.3f}")
    return payload


def main(argv=None) -> int:
    from repro.utils.compile_cache import enable_compile_cache

    args = build_args(argv)
    enable_compile_cache()
    run_pipeline(args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
