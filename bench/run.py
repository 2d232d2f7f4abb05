#!/usr/bin/env python3
"""Run one benchmark cell once on the TPU this process finds.

    python bench/run.py --workload pubmed-silo16.train --seed 7 \
        --seconds 20 --trace 0

Prints the compared numbers beside their limits on standard error and, as
the last line of standard output, one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``) and ``checks``. Exits non-zero, with no result line, when
JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)
# the TPU runtime's own logs would go to a fixed path outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # cache every program, however fast it compiled, so that only the first
    # run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
