#!/usr/bin/env python3
"""Readings the limits of ``correct`` are set from; run by hand on the chip,
never by the benchmark's runs.

    python bench/calibrate.py --workload pubmed-silo16.train \
        --seeds 101 102 ... --control-seeds 101 102 103 [--seconds 4]

For each seed of ``--seeds``: the cell's set-up as a run makes it, then the
comparison with the reference (the lower readings). For each seed of
``--control-seeds``: the control, the reference at the next precision below
the configuration's, in the program's place; and for training each fault
planted in the reference (the upper readings). Serving seeds run a short
window of ``--seconds`` at the cell's own rate. Everything runs in this one
process, which holds the chip. Each reading is one JSON line on standard
output and in ``.bench_out/calibrate-<workload>.jsonl``.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

# the next precision below each stated one (the control's)
BELOW = {"highest": "high", "high": "bfloat16", "default": "bfloat16"}
FAULTS = ("half_batch", "no_exchange")


def calibrate_train(cell, args, emit):
    import jax

    from bench.runners import train as T

    from bench.references import fedais_gcn as ref

    graph, fed = T.make_data(cell.config)
    ev = cell.config["eval_every"]
    emit({"kind": "partition",
          "partition_errors": ref.check_partition(fed, graph, cell.config),
          "n_max": fed.n_max, "g_max": fed.g_max})
    for seed in args.seeds:
        t0 = time.perf_counter()
        eng, capture = T.make_engine(cell, graph, fed, seed)
        state, got = T.first_call(eng, capture)
        eval_graph = eng.eval_graph
        del eng, state
        want = T.reference_for(cell, graph, fed, seed)
        T.with_eval(cell, got, want, eval_graph)
        emit({"kind": "program", "seed": seed,
              **T.gaps(got, want, ev),
              "seconds": time.perf_counter() - t0})
    below = BELOW[cell.config["matmul_precision"]]
    for seed in args.control_seeds:
        want = T.reference_for(cell, graph, fed, seed)
        p0 = want["params"][0]
        want["eval_logits"] = ref.eval_logits(p0, want["eval_graph"],
                                              cell.config["matmul_precision"])
        for name, kw in [("control", {"prec": below})] + [
                (f, {"fault": f}) for f in FAULTS]:
            got = T.reference_for(cell, graph, fed, seed, **kw)
            if name == "control":
                # the server's forward at the lower precision, on the same
                # parameters
                got["eval_logits"] = ref.eval_logits(p0, want["eval_graph"],
                                                     below)
            emit({"kind": name, "seed": seed, **T.gaps(got, want, ev)})
    jax.clear_caches()


def calibrate_serve(cell, args, emit, log):
    from bench import harness
    from bench.runners import serve as S

    below = BELOW[cell.config["matmul_precision"]]
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = S.run(cell, seed=seed, seconds=args.seconds, t_start=t0,
                    log=log, spans=harness.Spans(),
                    tracer=harness.Tracer(ROOT, False, None), chips=1,
                    keep=seed in args.control_seeds)
        rec = {"kind": "program", "seed": seed,
               **{c.name: c.value for c in out.checks},
               "p95_ms": out.end_to_end["serve_p95_ms"],
               "qps": out.end_to_end["serve_qps"]}
        emit(rec)
        if seed in args.control_seeds:
            graph, sched, loop, capacity, store = out.ctx["replay"]
            want = S.replay_logits(cell, graph, seed, sched, loop, capacity,
                                   prec=cell.config["matmul_precision"])
            got = S.replay_logits(cell, graph, seed, sched, loop, capacity,
                                  prec=below)
            emit({"kind": "control", "seed": seed,
                  "serve_gap": S.logit_gap(got, want)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from repro.utils.compile_cache import enable_compile_cache

    cell = harness.resolve(ROOT, args.workload)
    harness.device_info(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log = harness.CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    os.makedirs(os.path.join(ROOT, ".bench_out"), exist_ok=True)
    path = os.path.join(ROOT, ".bench_out",
                        f"calibrate-{args.workload}.jsonl")
    with open(path, "w") as f:
        def emit(rec):
            line = json.dumps(dict(rec, workload=args.workload))
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        with jax.default_matmul_precision(cell.config["matmul_precision"]):
            if cell.traffic["runner"] == "train":
                calibrate_train(cell, args, emit)
            else:
                calibrate_serve(cell, args, emit, log)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
