"""Benchmark driver: one module per paper table/figure + roofline + kernels.

    PYTHONPATH=src python -m benchmarks.run            # quick mode (CI)
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale sweeps
    PYTHONPATH=src python -m benchmarks.run --only fig3_comm
"""
from __future__ import annotations

import argparse
import importlib
import time
import traceback

from benchmarks.common import emit_csv, save_rows
from repro.utils.compile_cache import enable_compile_cache

BENCHMARKS = [
    "table2_accuracy",   # paper Table 2
    "fig3_comm",         # paper Fig. 3
    "fig4_costs",        # paper Fig. 4 (savings headline)
    "fig5_ablation",     # paper Fig. 5
    "fig6_clients",      # paper Fig. 6
    "fig7_sensitivity",  # paper Fig. 7
    "fig8_async",        # extension: sync vs async scheduling wall-clock
    "perf_round",        # round throughput: fused scanned executor vs stepwise
    "perf_serve",        # serving latency: checkpoint-backed online inference
    "kernel_bench",      # kernel layer (us_per_call + oracle deltas)
    "roofline",          # §Roofline from the dry-run artifacts
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sweeps")
    ap.add_argument("--only", default=None, choices=[*BENCHMARKS, None])
    args = ap.parse_args()
    enable_compile_cache()

    names = [args.only] if args.only else BENCHMARKS
    failures = 0
    for name in names:
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        try:
            rows = mod.run(quick=not args.full)
        except Exception:
            traceback.print_exc()
            failures += 1
            continue
        emit_csv(name, rows)
        save_rows(name, rows)
        print(f"# {name}: {len(rows)} rows in {time.time() - t0:.1f}s", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
