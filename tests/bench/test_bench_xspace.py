"""The raw-trace additions to the reduction (``bench/xspace.py``): the same
summary as ``ProfileData`` gives, the clock shift, gap naming by the
innermost span, device seconds per named scope, and self time of nested
spans. The expected numbers of the recorded traces were read off their
event lists by hand (see ``test_bench_trace.py`` for the probe)."""
import os
import subprocess
import sys

import pytest
from jax.profiler import ProfileData

from bench import harness, trace, xspace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PROBE = os.path.join(DATA, "probe.xplane.pb")
PROBE_SPANS = ("run-call", "generator-wait", "serve_batch")


@pytest.fixture(scope="module")
def probe():
    return xspace.load(PROBE)


def test_summary_reads_as_profile_data_gives_it(probe):
    want = trace.reduce(ProfileData.from_file(PROBE), 0.1, PROBE_SPANS)
    assert trace.reduce(probe, 0.1, PROBE_SPANS) == want


def test_loading_imports_no_tensorflow():
    code = ("import sys; from bench import xspace; "
            f"xspace.load({PROBE!r}); "
            "print('tensorflow' in sys.modules)")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.dirname(DATA)))
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "False"


def test_probe_clock_shift_is_the_latest_launch_over_its_execution(probe):
    # outermost launches (host ns) against device starts, in order:
    # PjitFunction(chunk) 39660709, 61306919, 82906048 against jit_chunk
    # 38640778, 60179756, 81915357; PjitFunction(<lambda>) 60652749,
    # 82335279, 104000688 against jit__lambda 59570502, 81227264,
    # 103034658; the largest difference is the second chunk's
    assert xspace.clock_shift_ns(probe) == 61306919 - 60179756


def test_probe_gaps_after_the_lambda_move_into_its_span(probe):
    # shifted by 1.127 ms, the 0.59 and 0.67 ms gaps that follow each
    # lambda's device work (59588253-60179756, 81244994-81915357) lie in
    # the serve_batch spans that called it, with the six 1-2 ns gaps
    # between the lambda's own operations; the three 20 ms sleeps keep
    # the rest
    gaps = dict(xspace.reduce(probe, 0.1, PROBE_SPANS)["idle_gaps"])
    assert set(gaps) == {"generator-wait", "serve_batch"}
    assert gaps["serve_batch"] == pytest.approx(
        (591503 + 670363) * 1e-9, abs=20e-9)
    assert gaps["generator-wait"] + gaps["serve_batch"] == pytest.approx(
        64229275e-9, abs=1e-12)


def test_probe_chunk_ops_are_unscoped(probe):
    scopes, ops = xspace.scope_seconds(probe)
    assert scopes == {
        "unscoped": pytest.approx((43186 + 42854 + 43101) * 1e-9)}
    assert ops == [["%fusion", pytest.approx((43186 + 42854 + 43101) * 1e-9)]]


@pytest.mark.parametrize("text,opcode", [
    ("%while.196 = (s32[], f32[8,16]{1,0}) while((s32[], f32[8,16]{1,0}) "
     "%tuple.3), condition=%cond, body=%body", "while"),
    ("%fusion.628 = f32[8,3423,32,500]{3,2,1,0:T(8,128)} fusion(f32[8]{0} "
     "%p), kind=kLoop", "fusion"),
    ("%conditional.13 = (f32[7976,256]{1,0:T(8,128)}, s32[]) "
     "conditional(pred[] %c, (f32[1]) %t, (f32[1]) %f)", "conditional"),
])
def test_opcode_of_an_instruction(text, opcode):
    assert xspace._opcode(text) == opcode


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(chunk)/while/body/closed_call/vmap(loss_pass)/dot_general:",
     "loss_pass"),
    ("jit(chunk)/while/body/closed_call/vmap(local_steps)/while/body/"
     "closed_call/ghost_pull/cond/branch_1_fun/mul:", "ghost_pull"),
    ("jit(chunk)/while/body/closed_call/vmap(local_steps)/while/body/"
     "closed_call/transpose(jvp())/dot_general:", "local_steps"),
    ("jit(chunk)/while/body/closed_call/merge/reduce_sum:", "merge"),
    ("jit(chunk)/while/body/dynamic_update_slice:", "unscoped"),
    ("", "unscoped"),
])
def test_innermost_scope_of_an_op_path(tf_op, scope):
    assert xspace._scope(tf_op) == scope


def test_self_time_leaves_out_nested_spans():
    recs = [("fed/replay", 0.0, 10.0), ("fed/eval", 2.0, 6.0),
            ("fed/eval-wait", 3.0, 5.0), ("fed/eval", 7.0, 8.0),
            ("fed/select", 11.0, 12.0)]
    assert xspace.self_times(recs, "fed/replay") == [5.0]
    assert xspace.self_times(recs, "fed/eval") == [2.0, 1.0]
    assert xspace.self_times(recs, "fed/eval-wait", "fed/select") == [2.0,
                                                                      1.0]


def test_self_time_of_a_span_with_a_child_at_its_start():
    recs = [("fed/run", 1.0, 9.0), ("fed/select", 1.0, 3.0),
            ("fed/dispatch", 3.0, 4.0)]
    assert xspace.self_times(recs, "fed/run") == [5.0]


def test_train_layers_per_round_and_per_evaluation():
    summary = {"modules": {"jit_chunk": (0.8, 4), "jit__eval_logits": (
        0.05, 2), "jit_broadcast_in_dim": (0.001, 10)},
        "scopes": {"loss_pass": 0.4, "local_steps": 0.2, "ghost_pull": 0.1,
                   "merge": 0.01, "unscoped": 0.09}}
    recs = [("fed/partition", -9.0, -8.0), ("fed/engine-build", -7.5, -7.0),
            ("fed/run", -6.0, -3.0),
            ("run-call", 0.0, 1.0), ("fed/run", 0.0, 1.0),
            ("fed/select", 0.0, 0.1), ("fed/dispatch", 0.1, 0.2),
            ("fed/wait", 0.2, 0.6), ("fed/replay", 0.6, 0.9),
            ("fed/eval", 0.7, 0.8), ("fed/eval-wait", 0.75, 0.8)]
    got = xspace.train_layers(summary, recs, (0.0, 1.0), rounds=10, evals=2)
    want = {"loss_pass_ms.train": 40.0, "local_steps_ms.train": 20.0,
            "ghost_pull_ms.train": 10.0, "merge_ms.train": 1.0,
            "host_prep_ms.train": 20.0, "host_replay_ms.train": 20.0,
            "eval_host_ms.train": 25.0, "partition_s.train": 1.0,
            "engine_build_s.train": 0.5, "first_call_s.train": 3.0}
    assert got == pytest.approx(want)


def test_train_layers_leave_out_what_the_program_did_not_record():
    summary = {"modules": {"jit_chunk": (0.8, 4)}, "scopes": {
        "unscoped": 0.8}}
    got = xspace.train_layers(summary, [("run-call", 0.0, 1.0)], (0.0, 1.0),
                              rounds=10, evals=2)
    assert got == {}


# ``scopes.xplane.pb``: three calls, on one TPU v5 lite, of a jitted
# ``chunk`` that scans 2 rounds of a 2-client vmapped update (512x512
# matmuls): ``loss_pass``, then a ``local_steps`` scan of 2 epochs whose
# ``ghost_pull`` is a ``lax.cond`` taken every other epoch, then ``merge``.
# Each call sits in ``run-call`` as ``fed/select`` (a 2 ms sleep),
# ``fed/dispatch``, ``fed/wait`` (``device_get``) and ``fed/replay`` (a 3 ms
# sleep), recorded through ``repro.utils.spans``.
SCOPED = os.path.join(DATA, "scopes.xplane.pb")
SCOPED_SPANS = ("run-call",)


@pytest.fixture(scope="module")
def scoped():
    return xspace.load(SCOPED)


def test_scoped_summary_reads_as_profile_data_gives_it(scoped):
    want = trace.reduce(ProfileData.from_file(SCOPED), 1.0, SCOPED_SPANS)
    assert trace.reduce(scoped, 1.0, SCOPED_SPANS) == want
    assert want["modules"]["jit_chunk"] == (
        pytest.approx((119214 + 119607 + 119738) * 1e-9), 3)


def test_scope_seconds_of_the_leaf_operations(scoped):
    # summed over the three calls' leaf operations (the two while loops
    # and the conditional left out, their bodies counted):
    # merge: %broadcast_multiply_fusion.2 1325 + %reduce_sum.20 2091;
    # loss_pass: %fusion.26 30016; ghost_pull: the taken branch's
    # %convolution_sine_fusion 90979; local_steps: %fusion.30 92108 (the
    # gradient), %copy.33 38881, %copy-done 14666, %dynamic_slice.12 3873,
    # %broadcast_in_dim.14 2266, %dynamic_update_slice.12 562,
    # %copy-start 125, %iota.6 13 ns
    scopes, ops = xspace.scope_seconds(scoped)
    assert scopes == pytest.approx({
        "merge": 3416e-9, "loss_pass": 30016e-9, "ghost_pull": 90979e-9,
        "local_steps": 152494e-9, "unscoped": 58446e-9})
    # the unscoped remainder: operations with no path (XLA's copies and
    # the input's conversion) and the scan's own slicing
    assert [op for op, _ in ops[:4]] == ["%copy.27", "%convert.6",
                                         "%dynamic-slice_bitcast_fusion.2",
                                         "%copy-done.1"]
    assert ops[0][1] == pytest.approx(19398e-9)


def test_scoped_clock_shift_dates_the_chunk_from_its_dispatch(scoped):
    # fed/dispatch 47253567, 54731897, 62434266 against jit_chunk
    # 46753617, 54076991, 61923675: the device stamps each chunk 0.50-0.65
    # ms before the host began to launch it
    assert xspace.clock_shift_ns(scoped) == 54731897 - 54076991


def test_gaps_are_named_by_the_innermost_span(scoped):
    # run-call encloses every gap; after the shift the two 7.2 and 7.7 ms
    # gaps between chunks fall in fed/replay, the 34 gaps of 1-3 ns between
    # a chunk's operations in its fed/dispatch
    gaps = xspace.reduce(scoped, 1.0, SCOPED_SPANS)["idle_gaps"]
    assert gaps == [["fed/replay", pytest.approx((7204431 + 7727348) * 1e-9)],
                    ["fed/dispatch", pytest.approx(52e-9)]]


@pytest.mark.parametrize("name", ["probe", "scoped"])
def test_innermost_naming_reads_as_trace_where_spans_do_not_nest(
        name, request):
    # among the benchmark's own spans alone, which never nest, and with no
    # clock shift, the innermost span is the one trace.reduce finds: the
    # naming can take its place
    space = request.getfixturevalue(name)
    names = PROBE_SPANS if name == "probe" else SCOPED_SPANS
    assert (xspace.idle_gaps(space, 0.0, names)
            == trace.reduce(space, 1.0, names)["idle_gaps"])


def _programs_per_round(summary, rounds):
    repo = os.path.dirname(os.path.dirname(os.path.dirname(DATA)))
    read = harness.load_reader(repo, "programs_per_round.train")
    return read({"trace": summary, "rounds": rounds})


def test_programs_per_round_counts_every_execution(scoped):
    # three executions of jit_chunk, each of 2 rounds
    summary = trace.reduce(scoped, 1.0, SCOPED_SPANS)
    assert _programs_per_round(summary, 6) == 0.5


def test_programs_per_round_reads_nothing_without_a_device_plane():
    summary = {"devices": 0, "busy_s": 0.0, "window_s": 1.0, "modules": {},
               "device_ops": [], "idle_gaps": []}
    assert _programs_per_round(summary, 6) is None
