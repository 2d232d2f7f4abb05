"""Client-axis sharding of the fused executor (repro.sharding.fed).

Parity contract: the shard-mapped executor is **allclose, not
bit-identical**, to the unsharded fused run. Server aggregation becomes a
psum all-reduce whose summation order reassociates with the device count
(sum-of-per-device-partial-sums vs one flat mean), so float32 params —
and everything downstream of them — drift by ~ULP per round. Everything
discrete must still match exactly: selections and the PRNG chain are
host/key-identical by construction, and the mantissa-quantized sampling
keys (PR 2) absorb ULP-level jitter so batch/fanout/sync decisions — and
therefore the integer-derived comm/flops/wall-clock columns — cannot flip.

Multi-device tests skip on a single-device host; CI's ``sharded`` lane
runs this file under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import jax
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.api import FedEngine, FedAvg, LossBiasedSelector, SyncScheduler, method_config
from repro.sharding.fed import (
    CLIENT_AXIS,
    client_axis_of,
    cohort_padding,
    make_client_mesh,
)

pytestmark = pytest.mark.sharded

N_DEV = len(jax.devices())
needs_devices = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs >=2 devices; run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")

EXACT_KEYS = ("tau", "comm_total", "comm_embed", "flops", "wall_clock")
CLOSE_KEYS = ("test_acc", "test_loss")


def _run(g, fed, *, mesh=None, m=4, rounds=5, seed=0, **kw):
    eng = FedEngine(g, fed, method_config("fedais", tau0=4), seed=seed,
                    rounds=rounds, clients_per_round=m, eval_every=2,
                    mesh=mesh, **kw)
    return eng, eng.run()


def _assert_allclose_history(ref, got):
    for k in EXACT_KEYS:
        assert ref.history[k] == got.history[k], f"history[{k!r}] diverged"
    for k in CLOSE_KEYS:
        np.testing.assert_allclose(
            np.asarray(got.history[k], np.float64),
            np.asarray(ref.history[k], np.float64),
            rtol=1e-4, atol=1e-6, err_msg=f"history[{k!r}]")


# ---------------------------------------------------------------------------
# sharded vs unsharded fused parity
# ---------------------------------------------------------------------------

@needs_devices
def test_sharded_matches_unsharded_fused(small_fed):
    g, fed = small_fed
    eng_u, res_u = _run(g, fed, m=4)
    eng_s, res_s = _run(g, fed, mesh=make_client_mesh(2), m=4)
    assert eng_u.last_executor == "fused"
    assert eng_s.last_executor == "sharded_fused"
    _assert_allclose_history(res_u, res_s)


@needs_devices
def test_sharded_matches_unsharded_weighted(small_fed):
    """WeightedFedAvg: the all-reduce must fold the client-size weights."""
    g, fed = small_fed
    kw = dict(aggregator="weighted", scheduler=SyncScheduler(fused=True))
    _, res_u = _run(g, fed, m=4, **kw)
    eng_s, res_s = _run(g, fed, mesh=make_client_mesh(2), m=4, **kw)
    assert eng_s.last_executor == "sharded_fused"
    _assert_allclose_history(res_u, res_s)


@needs_devices
def test_sharded_pairwise_merge_parity(small_fed):
    """merge_reduce='pairwise' on the 1-D client mesh: the fixed fp32
    binary-tree merge is a drop-in for the weighted psum within the same
    allclose contract (the knob the pod mesh already honors)."""
    g, fed = small_fed
    _, res_u = _run(g, fed, m=4)
    eng_s, res_s = _run(g, fed, mesh=make_client_mesh(2), m=4,
                        merge_reduce="pairwise")
    assert eng_s.last_executor == "sharded_fused"
    _assert_allclose_history(res_u, res_s)


def test_single_device_mesh_matches(small_fed):
    """A 1-device mesh still routes through shard_map (runs in the plain
    tier-1 lane too, so the sharded code path has everyday coverage)."""
    g, fed = small_fed
    _, res_u = _run(g, fed, m=3)
    eng_s, res_s = _run(g, fed, mesh=make_client_mesh(1), m=3)
    assert eng_s.last_executor == "sharded_fused"
    _assert_allclose_history(res_u, res_s)


# ---------------------------------------------------------------------------
# ragged-cohort padding is a no-op
# ---------------------------------------------------------------------------

def _one_chunk(g, fed, mesh, m):
    eng = FedEngine(g, fed, method_config("fedais", tau0=4), seed=0, rounds=4,
                    clients_per_round=m, eval_every=2, mesh=mesh)
    state = eng.init_state()
    eng._run_chunk(state, 0, 2)
    return eng, state


@needs_devices
def test_cohort_padding_is_noop(small_fed):
    """m=3 over 2 devices pads one zero-weight dummy client; the full
    client-state tables must match the unsharded run — ages (ints) exactly,
    so a stray dummy write-back to ANY row would be caught."""
    g, fed = small_fed
    assert cohort_padding(3, 2) == 1
    _, st_u = _one_chunk(g, fed, None, 3)
    eng_s, st_s = _one_chunk(g, fed, make_client_mesh(2), 3)
    assert eng_s.last_executor == "sharded_fused"
    np.testing.assert_array_equal(np.asarray(st_s.hist.age),
                                  np.asarray(st_u.hist.age))
    # float tables drift ~ULP-per-round through Adam off the reassociated
    # all-reduce; the exact int ages above are the real dummy-write-back guard
    np.testing.assert_allclose(np.asarray(st_s.hist.hist1),
                               np.asarray(st_u.hist.hist1),
                               rtol=1e-2, atol=1e-3)
    np.testing.assert_allclose(np.asarray(st_s.prev_loss),
                               np.asarray(st_u.prev_loss),
                               rtol=1e-2, atol=1e-3)


def test_cohort_padding_math():
    assert cohort_padding(8, 4) == 0
    assert cohort_padding(3, 8) == 5
    assert cohort_padding(9, 4) == 3
    assert cohort_padding(1, 1) == 0


# ---------------------------------------------------------------------------
# eligibility + clean fallback chain (sharded -> fused -> stepwise)
# ---------------------------------------------------------------------------

def test_no_mesh_is_ineligible(small_fed):
    g, fed = small_fed
    eng = FedEngine(g, fed, method_config("fedais"), rounds=1)
    ok, why = eng.sharded_eligibility()
    assert not ok and "no mesh" in why


def test_client_sharding_off_falls_back_to_fused(small_fed):
    g, fed = small_fed
    eng, _ = _run(g, fed, mesh=make_client_mesh(1), m=3, rounds=2,
                  client_sharding="off")
    assert eng.last_executor == "fused"


@needs_devices
def test_divisible_mode_falls_back_on_ragged_cohort(small_fed):
    g, fed = small_fed
    mesh = make_client_mesh(2)
    eng = FedEngine(g, fed, method_config("fedais"), rounds=2,
                    clients_per_round=3, mesh=mesh,
                    client_sharding="divisible")
    ok, why = eng.sharded_eligibility(3)
    assert not ok and "divide" in why
    assert eng.sharded_eligibility(4)[0]
    eng, _ = _run(g, fed, mesh=mesh, m=3, rounds=2,
                  client_sharding="divisible")
    assert eng.last_executor == "fused"       # padded path disabled -> fused


def test_non_mean_aggregator_falls_back_to_fused(small_fed):
    """An aggregator that traces in jit but is not a declared weighted-mean
    family cannot lower to the psum merge; the fused chunk serves it.
    Crucially a subclass overriding aggregate() must NOT inherit the base's
    allreduce_safe — the sharded merge would silently replace its rule with
    the hardcoded weighted mean."""
    g, fed = small_fed

    class TrimmedFedAvg(FedAvg):        # overrides aggregate, inherits flag
        def aggregate(self, stacked_params, weights=None):
            return super().aggregate(stacked_params, weights)

    eng, res = _run(g, fed, mesh=make_client_mesh(1), m=3, rounds=2,
                    aggregator=TrimmedFedAvg())
    ok, why = eng.sharded_eligibility()
    assert not ok and "allreduce_safe" in why
    assert eng.last_executor == "fused"
    assert np.isfinite(res.final["loss"])

    class VouchedMean(FedAvg):          # re-declares: vouches for the psum
        allreduce_safe = True

        def aggregate(self, stacked_params, weights=None):
            return super().aggregate(stacked_params, weights)

    eng = FedEngine(g, fed, method_config("fedais"), rounds=1,
                    mesh=make_client_mesh(1), aggregator=VouchedMean())
    assert eng.sharded_eligibility()[0]


def test_mesh_with_ineligible_fused_runs_stepwise(small_fed):
    """A mesh never forces the fused executor: when fused_eligibility fails
    (LossBiasedSelector reads per-round state) the run stays stepwise."""
    g, fed = small_fed
    eng, res = _run(g, fed, mesh=make_client_mesh(1), m=3, rounds=2,
                    selector=LossBiasedSelector())
    assert eng.last_executor == "stepwise"
    assert np.isfinite(res.final["loss"])


def test_engine_validates_sharding_options(small_fed):
    g, fed = small_fed
    with pytest.raises(ValueError, match="client_sharding"):
        FedEngine(g, fed, method_config("fedais"), rounds=1,
                  client_sharding="sometimes")
    two_axis = jax.make_mesh((1, 1), ("a", "b"), (AxisType.Auto,) * 2,
                             devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="clients"):
        FedEngine(g, fed, method_config("fedais"), rounds=1, mesh=two_axis)


# ---------------------------------------------------------------------------
# mesh construction helpers
# ---------------------------------------------------------------------------

def test_make_client_mesh_and_axis_resolution():
    mesh = make_client_mesh(1)
    assert dict(mesh.shape) == {CLIENT_AXIS: 1}
    assert client_axis_of(mesh) == CLIENT_AXIS
    one_axis = jax.make_mesh((1,), ("shards",), (AxisType.Auto,),
                             devices=jax.devices()[:1])
    assert client_axis_of(one_axis) == "shards"
    two_axis = jax.make_mesh((1, 1), ("a", "b"), (AxisType.Auto,) * 2,
                             devices=jax.devices()[:1])
    assert client_axis_of(two_axis) is None
    with pytest.raises(ValueError, match="devices"):
        make_client_mesh(len(jax.devices()) + 1)
