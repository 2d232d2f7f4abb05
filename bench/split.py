#!/usr/bin/env python3
"""Where a training cell's time goes, by the program's named scopes and host
spans; run by hand on the chip, never by the benchmark's runs.

    python bench/split.py --workload pubmed-silo16.train --seed 7 \
        --seconds 40

Runs the cell once through its own runner (``bench/runners/train.py``: the
set-up, the window with the profiler on, the comparison), with the
program's spans recorded (``repro.utils.spans.record_to``), and reduces the
trace with ``bench/xspace.py``. Prints the set-up's phases on standard error
and, as the last line of standard output, one JSON object: ``correct``, the
per-layer numbers of ``xspace.train_layers`` and of the
``programs_per_round.train`` reader, the device seconds per scope, the
clock shift, the idle gaps by span and the window's rounds per second.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402


class StampedCompiles(harness.CompileLog):
    """The compile log, also keeping when each compile ended."""

    def __init__(self):
        super().__init__()
        self.ends: list[tuple[float, float]] = []

    def __call__(self, event: str, duration: float, **kw):
        super().__call__(event, duration, **kw)
        if event == self.EVENT:
            self.ends.append((time.perf_counter(), duration))


def setup_phases(records, t_call: float, t_start: float,
                 setup_s: float) -> dict:
    """Set-up split at the program's first spans: runtime start (process
    start to the runner's call), graph (to the partition), partition,
    engine build, the first call, and the rest (the engine's strategy and
    callbacks, its initial state, the collection before the window)."""
    first = {}
    for name, t0, t1 in records:
        first.setdefault(name, (t0, t1))
    part, build, call = (first[n] for n in ("fed/partition",
                                            "fed/engine-build", "fed/run"))
    out = {"runtime_start_s": t_call - t_start,
           "graph_s": part[0] - t_call,
           "partition_s": part[1] - part[0],
           "engine_build_s": build[1] - build[0],
           "first_call_s": call[1] - call[0]}
    out["rest_s"] = setup_s - sum(out.values())
    return out


def split(root: str, workload: str, seed: int, seconds: float, *,
          t_start: float, require_tpu: bool = True) -> dict:
    """One traced run of a training cell (see the module docstring).
    ``require_tpu=False`` is for the test suite only."""
    import jax

    from bench import xspace
    from bench.runners import train as T
    from repro.utils.spans import record_to

    cell = harness.resolve(root, workload)
    if cell.traffic["runner"] != "train":
        raise SystemExit(f"bench: {workload} is not a training cell")
    if require_tpu:
        harness.device_info(cell.chips)
    cfg = cell.config
    log = StampedCompiles()
    jax.monitoring.register_event_duration_secs_listener(log)
    spans = harness.Spans()
    tracer = harness.Tracer(root, True, spans)
    try:
        with jax.default_matmul_precision(cfg["matmul_precision"]), \
                record_to(spans.records):
            t_call = time.perf_counter()
            out = T.run(cell, seed=seed, seconds=seconds, t_start=t_start,
                        log=log, spans=spans, tracer=tracer,
                        chips=cell.chips)
    finally:
        jax.monitoring.unregister_event_duration_listener(log)
    summary = xspace.reduce_dir(tracer.dir, tracer.window,
                                {n for n, _, _ in spans.records})
    shutil.rmtree(tracer.dir, ignore_errors=True)

    records = spans.records
    setup_s = out.end_to_end["setup_s"]
    setup = setup_phases(records, t_call, t_start, setup_s)
    c0, c1 = next((t0, t1) for n, t0, t1 in records if n == "fed/run")
    first = [d for t, d in log.ends if c0 <= t <= c1]
    print("set-up " + ", ".join(f"{k[:-2].replace('_', ' ')} {v:.3f} s"
                                for k, v in setup.items())
          + f" = {setup_s:.3f} s; the first call held {len(first)} "
          f"compiles ({sum(first):.3f} s)", file=sys.stderr, flush=True)

    rounds, calls = out.ctx["rounds"], out.ctx["calls"]
    evals = calls * T.evals_per_call(cell.traffic["rounds_per_call"],
                                     cfg["eval_every"])
    layers = xspace.train_layers(summary, records, tracer.window,
                                 rounds=rounds, evals=evals)
    programs = harness.load_reader(root, "programs_per_round.train")(
        {"trace": summary, "rounds": rounds})
    if programs is not None:
        layers["programs_per_round.train"] = programs
    sec, n = summary["modules"].get(xspace.PROGRAM, (0.0, 0))
    scoped = sum(summary["scopes"].get(s, 0.0) for s in xspace.SCOPES)
    return {"workload": workload, "seed": seed,
            "correct": all(c.ok for c in out.checks),
            "setup_s": setup_s, "setup": setup,
            "first_call": {"compiles": len(first),
                           "compile_s": sum(first)},
            "calls": calls, "rounds": rounds,
            "rounds_per_s": out.end_to_end["train_rounds_per_s"],
            "layers": layers,
            "round_ms": 1e3 * sec / rounds if n else None,
            "scopes_ms": {k: 1e3 * v / rounds
                          for k, v in summary["scopes"].items()},
            "scopes_of_round": scoped / sec if sec else None,
            "unscoped_ops_ms": [[op, 1e3 * v / rounds]
                                for op, v in summary["unscoped_ops"]],
            **{k: summary[k] for k in ("clock_shift_ns", "idle_gaps",
                                       "busy_s", "window_s", "modules",
                                       "device_ops")}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from repro.utils.compile_cache import enable_compile_cache

    # the compile cache and its settings as bench/run.py has them, so that
    # both find each other's programs
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    out = split(ROOT, args.workload, args.seed, args.seconds,
                t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
