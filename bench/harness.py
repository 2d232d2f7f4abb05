"""The benchmark's spine: find a cell's files by name, check the device, run
the cell's runner, read the per-layer metrics and print the result line.

Layout under a root directory (the checkout, or a test's temp directory):

    BENCHMARK.json                      cells, metrics, bounds
    bench/configs/<config>.json         one configuration, as it is run
    bench/traffic/<workload>.json       one cell's traffic: its runner and
                                        the parameters that runner reads
    bench/metrics/<metric>.py           one reader per per-layer metric:
                                        ``read(ctx) -> float | None``

Runners (``bench/runners/<runner>.py``) are code shared by every cell of
their kind; a cell names its runner in its traffic file. A later cell, mix or
metric enters as new files plus ``BENCHMARK.json`` entries.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field


@dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list       # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _metric_applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_spec(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(root: str, workload: str) -> Cell:
    """Everything one cell needs, found by name under ``root``."""
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic", f"{workload}.json")) as f:
        traffic = json.load(f)
    return Cell(
        name=workload, config_name=w["config"], config=config,
        traffic=traffic, chips=int(w["chips"]),
        end_to_end=[m for m in spec["end_to_end"]
                    if _metric_applies(m, workload)],
        per_layer=[m for m in spec["per_layer"]
                   if _metric_applies(m, workload)])


def load_reader(root: str, metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_runner(name: str):
    return importlib.import_module(f"bench.runners.{name}")


# ---------------------------------------------------------------------------
# device, compiles, spans
# ---------------------------------------------------------------------------

def device_info(chips: int) -> dict:
    """The accelerator JAX found; exits non-zero unless it is a TPU with at
    least ``chips`` devices. There is no fallback to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found platform "
                 f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} TPU devices; JAX found "
                 f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


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


class Spans:
    """Host spans the benchmark records around its calls into the program.
    Each is kept as (name, start, end) on ``time.perf_counter``; while
    ``annotate`` is on, each also enters the profiler's trace as a
    ``TraceAnnotation`` so idle device gaps can be named by it."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        else:
            ann = contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def durations(self, *names: str) -> list[float]:
        return [t1 - t0 for n, t0, t1 in self.records if n in names]


class GcLog:
    """Python's garbage collections while it is attached: how many of the
    oldest generation, and the seconds all of them took (a full collection
    scans every live object and stalls the host)."""

    def __init__(self):
        self.full, self.seconds, self._t0 = 0, 0.0, 0.0

    def __call__(self, phase: str, info: dict):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t0
            self.full += info["generation"] == 2

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def __str__(self):
        return (f"{self.full} full garbage collections, "
                f"{self.seconds:.3f} s in all collections")


@dataclass
class Check:
    """One number compared against its limit: correct iff value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclass
class Outcome:
    """What a runner hands back after its window and its comparison."""

    end_to_end: dict                 # metric name -> value
    checks: list                     # [Check]
    attempted: int
    failed: int
    memory_peak_bytes: int
    ctx: dict = field(default_factory=dict)   # what per-layer readers read


class Tracer:
    """The profiler around a runner's window, in a fixed directory inside
    the checkout that is emptied before and after."""

    def __init__(self, root: str, on: bool, spans: Spans):
        self.dir = os.path.join(root, ".bench_out", "trace")
        self.on = on
        self.spans = spans
        self.window = (0.0, 0.0)

    def start(self):
        self.t0 = time.perf_counter()
        if self.on:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
            self.spans.annotate = True
            jax.profiler.start_trace(self.dir)

    def stop(self):
        # the window ends before the profiler writes its trace out
        self.window = (self.t0, time.perf_counter())
        if self.on:
            import jax

            jax.profiler.stop_trace()
            self.spans.annotate = False


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        *, t_start: float, require_tpu: bool = True) -> dict:
    """Run ``workload`` once and return the result object (also printed by
    :func:`emit`). ``require_tpu=False`` is for the test suite only, which
    drives the rest of a run on the CPU at a small size."""
    cell = resolve(root, workload)
    import jax

    if require_tpu:
        dev = device_info(cell.chips)
    else:
        d = jax.devices()[0]
        dev = {"platform": d.platform, "kind": d.device_kind,
               "count": cell.chips}

    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    spans = Spans()
    tracer = Tracer(root, trace, spans)
    runner = load_runner(cell.traffic["runner"])
    try:
        # the precision the configuration states, for every program the
        # cell compiles
        with jax.default_matmul_precision(cell.config["matmul_precision"]):
            out: Outcome = runner.run(cell, seed=seed, seconds=seconds,
                                      t_start=t_start, log=log, spans=spans,
                                      tracer=tracer, chips=cell.chips)
    finally:
        jax.monitoring.unregister_event_duration_listener(log)
    gc.collect()

    device = dict(dev, memory_peak_bytes=out.memory_peak_bytes)
    result = {"correct": all(c.ok for c in out.checks),
              "attempted": out.attempted, "failed": out.failed}
    if trace:
        from bench import trace as trace_mod

        summary = trace_mod.reduce_dir(tracer.dir, tracer.window,
                                       {n for n, _, _ in spans.records})
        shutil.rmtree(tracer.dir, ignore_errors=True)
        ctx = dict(out.ctx, trace=summary, spans=spans, device=dev)
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    else:
        result["metrics"] = {m["name"]: {"value": out.end_to_end[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    # a number that is not finite is reported as 1e30, which fails its limit
    result["checks"] = {c.name: {"value": c.value if math.isfinite(c.value)
                                 else 1e30, "limit": c.limit}
                        for c in out.checks}
    return result


def emit(result: dict) -> None:
    """The numbers compared, beside their limits, as the last lines of
    standard error; the result object as the last line of standard out."""
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
