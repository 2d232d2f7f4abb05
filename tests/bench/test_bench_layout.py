"""A cell, a traffic mix and a per-layer metric enter the benchmark as new
files plus ``BENCHMARK.json`` entries, found by name, with no edit to any
file already under ``bench/``; and the command line refuses a host without
a TPU, or a checkout without the program, with no result line."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            if "__pycache__" in d:
                continue
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture
def copy_root(tmp_path):
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    return str(tmp_path)


def test_new_cell_mix_and_metric_are_new_files_only(copy_root):
    before = _digests(copy_root)
    cfg_path = os.path.join(copy_root, "bench", "configs", "pubmed-silo32.json")
    with open(os.path.join(copy_root, "bench", "configs",
                           "pubmed-silo16.json")) as f:
        cfg = json.load(f)
    cfg.update(name="pubmed-silo32", clients=32)
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(copy_root, "bench", "traffic",
                           "pubmed-silo32.train-long.json"), "w") as f:
        json.dump({"runner": "train", "rounds_per_call": 31,
                   "limits": {"loss_gap": 1e-3}}, f)
    with open(os.path.join(copy_root, "bench", "metrics",
                           "calls.train.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx['calls'])\n")

    spec = harness.load_spec(copy_root)
    spec["configs"].append({"name": "pubmed-silo32", "source": "test",
                            "file": "bench/configs/pubmed-silo32.json",
                            "reduced": ["max_deg"], "why": "test"})
    spec["workloads"].append({"name": "pubmed-silo32.train-long",
                              "config": "pubmed-silo32",
                              "traffic": "train-long", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "train_rounds_per_s" in (m["name"], m.get("moves")):
            m["workloads"].append("pubmed-silo32.train-long")
    spec["per_layer"].append({"name": "calls.train", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "round step",
                              "moves": "train_rounds_per_s",
                              "workloads": ["pubmed-silo32.train-long"]})
    with open(os.path.join(copy_root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    cell = harness.resolve(copy_root, "pubmed-silo32.train-long")
    assert cell.config["clients"] == 32
    assert cell.traffic["rounds_per_call"] == 31
    assert harness.load_runner(cell.traffic["runner"]).run
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "train_rounds_per_s"}
    names = [m["name"] for m in cell.per_layer]
    assert "calls.train" in names and "round_ms.train" in names
    assert harness.load_reader(copy_root, "calls.train")({"calls": 3}) == 3.0
    # the committed cells still resolve, to their own files
    old = harness.resolve(copy_root, "pubmed-silo16.train")
    assert old.config["clients"] == 16
    assert "calls.train" not in [m["name"] for m in old.per_layer]

    after = _digests(copy_root)
    assert {k: after[k] for k in before} == before


def test_every_committed_cell_resolves_with_its_readers():
    spec = harness.load_spec(REPO)
    for w in spec["workloads"]:
        cell = harness.resolve(REPO, w["name"])
        assert cell.traffic["runner"] in ("train", "serve")
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.load_reader(REPO, m["name"]))


def test_result_line_is_last_with_checks_last(capsys):
    result = {"correct": True, "attempted": 3, "failed": 0,
              "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1, "memory_peak_bytes": 10},
              "checks": {"loss_gap": {"value": 1e-6, "limit": 1e-4}}}
    harness.emit(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check loss_gap 1e-06 "
                                                   "limit 0.0001 ok")


def _run_cli(cwd, extra_env=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pubmed-silo16.train",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_a_host_without_a_tpu():
    p = _run_cli(REPO)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert "platform 'cpu'" in p.stderr
    assert '"correct"' not in p.stdout


def test_cli_refuses_a_checkout_holding_only_the_benchmark(tmp_path):
    spec = harness.load_spec(REPO)
    for rel in spec["paths"]:
        shutil.copytree(os.path.join(REPO, rel), tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = _run_cli(str(tmp_path))
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
