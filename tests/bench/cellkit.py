"""Throwaway benchmark roots at a size the CPU test suite can hold.

``make_root(tmp)`` writes a ``BENCHMARK.json``, configuration files and
traffic files for small copies of the committed cells into ``tmp`` and
copies the committed metric readers, so the harness resolves them by name
exactly as it resolves the real ones.
"""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

TINY_DATASET = {"nodes": 360, "edges": 1600, "features": 24}

# the serving runner's traffic and metrics, for the small serving cell (no
# committed cell serves yet: see PERF.md)
SERVE_TRAFFIC = {
    "runner": "serve", "policy_mix": {"historical": 0.9, "fresh": 0.1},
    "zipf_a": 1.3, "size_max": 128, "size_exponent": 1.5,
    "updates_per_query": 0.1, "update_mix": {"edges": 0.75, "nodes": 0.25},
    "anchors_max": 3, "new_node_noise": 0.1, "refresh_every": 4,
    "limits": {"serve_gap": 3e-06, "graph_mismatch": 0, "unanswered": 0}}
SERVE_END_TO_END = [
    {"name": "serve_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
     "source": "host_clock", "workloads": ["tiny.serve"]},
    {"name": "serve_qps", "unit": "queries/s", "better": "higher",
     "bound": 0.02, "source": "host_clock", "workloads": ["tiny.serve"]}]
SERVE_PER_LAYER = [
    {"name": name, "unit": unit, "better": "lower", "source": source,
     "layer": layer, "moves": "serve_p95_ms", "workloads": ["tiny.serve"]}
    for name, unit, source, layer in (
        ("device_idle.serve", "%", "device_trace", "device"),
        ("batch_ms.serve", "ms", "host_clock", "query engine"),
        ("program_ms.serve", "ms", "device_trace", "serve programs"),
        ("update_ms.serve", "ms", "host_clock", "graph updates"))]


def tiny_config(name: str = "pubmed-silo16", **over) -> dict:
    with open(os.path.join(REPO, "bench", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg["dataset"].update(TINY_DATASET)
    cfg.update({"clients": 4, "cohort": 2, "max_deg": 8, "batch_cap": 32})
    cfg.update(over)
    return cfg


def make_root(tmp, *, serve_rate: float = 200.0, limits=None) -> str:
    """A benchmark root with one tiny training cell and one tiny serving
    cell; ``limits`` overrides the compared numbers' limits."""
    root = str(tmp)
    os.makedirs(os.path.join(root, "bench", "configs"), exist_ok=True)
    os.makedirs(os.path.join(root, "bench", "traffic"), exist_ok=True)
    shutil.copytree(os.path.join(REPO, "bench", "metrics"),
                    os.path.join(root, "bench", "metrics"),
                    dirs_exist_ok=True)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cfg = tiny_config()
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(REPO, "bench", "traffic",
                           "pubmed-silo16.train.json")) as f:
        train = json.load(f)
    train["rounds_per_call"] = 6
    serve = dict(SERVE_TRAFFIC, rate_qps=serve_rate, check_sample=24)
    for t in (train, serve):
        t["limits"] = dict(t["limits"], **(limits or {}))
    for name, t in (("tiny.train", train), ("tiny.serve", serve)):
        with open(os.path.join(root, "bench", "traffic", f"{name}.json"),
                  "w") as f:
            json.dump(t, f)
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [
        {"name": "tiny.train", "config": "tiny", "traffic": "train",
         "chips": 1, "why": "test"},
        {"name": "tiny.serve", "config": "tiny", "traffic": "serve",
         "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.train"]
    spec["end_to_end"] += SERVE_END_TO_END
    spec["per_layer"] += SERVE_PER_LAYER
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root
