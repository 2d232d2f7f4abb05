"""The trace reduction against a small trace recorded on one TPU v5 lite:
three calls of a jitted ``chunk`` (a 1024x1024 fp32 matmul with a sine)
inside ``run-call`` spans, each followed by a 20 ms sleep inside a
``generator-wait`` span and a call of a jitted lambda inside a
``serve_batch`` span. The expected numbers were read off the trace's event
list by hand."""
import os

import pytest
from jax.profiler import ProfileData

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "probe.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(ProfileData.from_file(DATA), window_s=0.1,
                        span_names=("run-call", "generator-wait",
                                    "serve_batch"))


def test_one_device(summary):
    assert summary["devices"] == 1
    assert summary["window_s"] == 0.1


def test_busy_time_is_the_union_of_the_operations(summary):
    # XLA Ops, merged: 43186 + (13 + 5912 + 11820) + 42854 + (13 + 5891 +
    # 11818) + 43101 + (14 + 5945 + 11818) ns; the copy-start, copy-done and
    # fusion of each lambda call do not overlap
    assert summary["busy_s"] == pytest.approx(182385e-9, abs=1e-12)


def test_time_per_program(summary):
    mods = summary["modules"]
    assert set(mods) == {"jit_chunk", "jit__lambda"}
    sec, n = mods["jit_chunk"]
    assert n == 3 and sec == pytest.approx((43188 + 42858 + 43104) * 1e-9)
    sec, n = mods["jit__lambda"]
    assert n == 3 and sec == pytest.approx((17751 + 17730 + 17783) * 1e-9)


def test_operations_that_took_most_time(summary):
    ops = dict(summary["device_ops"])
    assert list(ops) == ["%fusion", "%copy-done", "%copy-start"]
    assert ops["%fusion"] == pytest.approx(
        (43186 + 11820 + 42854 + 11818 + 43101 + 11818) * 1e-9)
    assert ops["%copy-done"] == pytest.approx((5912 + 5891 + 5945) * 1e-9)


def test_idle_gaps_named_by_the_open_host_span(summary):
    # every gap between device operations falls in a 20 ms sleep: three of
    # about 21 ms, two of 0.59 and 0.67 ms after the lambda calls, and six
    # of 1-2 ns between the lambda's copy and fusion
    assert summary["idle_gaps"] == [
        ["generator-wait", pytest.approx(64229275e-9, abs=1e-12)]]
