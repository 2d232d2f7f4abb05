"""``bench/split.py`` on the CPU at a small size: the cell's own runner
runs, the set-up and window spans the program records reach the per-layer
numbers, and the set-up's phases add up to its length. The CPU has no device plane, so the device's
numbers stay out; their reduction is tested on recorded chip traces."""
import time

import pytest

import cellkit
from bench import split


@pytest.fixture(scope="module")
def out(tmp_path_factory):
    root = cellkit.make_root(tmp_path_factory.mktemp("split"))
    return split.split(root, "tiny.train", 2147483659, 0.5,
                       t_start=time.perf_counter(), require_tpu=False)


def test_set_up_spans_are_read(out):
    layers = out["layers"]
    for name in ("partition_s.train", "engine_build_s.train",
                 "first_call_s.train"):
        assert layers[name] > 0.0, name
    setup = out["setup"]
    assert layers["partition_s.train"] == setup["partition_s"]
    assert layers["engine_build_s.train"] == setup["engine_build_s"]
    assert layers["first_call_s.train"] == setup["first_call_s"]
    assert min(setup.values()) >= 0.0
    assert sum(setup.values()) == pytest.approx(out["setup_s"], rel=1e-9)
    assert out["first_call"]["compiles"] >= 1
    assert out["correct"]


def test_window_spans_are_read_per_round_and_evaluation(out):
    layers = out["layers"]
    assert out["calls"] >= 1 and out["rounds"] == 6 * out["calls"]
    for name in ("host_prep_ms.train", "host_replay_ms.train",
                 "eval_host_ms.train"):
        assert layers[name] > 0.0, name
    assert not any(k in layers for k in ("loss_pass_ms.train",
                                         "programs_per_round.train"))
