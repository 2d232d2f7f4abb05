"""Substrate tests: optimizer, checkpointing, data pipeline, utils, sharding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import st  # hypothesis strategies, or a skip-stub when absent

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.data.pipeline import TokenPipeline, make_lm_batch
from repro.optim import adamw_init, adamw_update, sgd_update
from repro.optim.schedules import constant, cosine_decay, linear_warmup_cosine
from repro.utils.hlo import collective_stats
from repro.utils.roofline import RooflineReport
from repro.utils.tree import (
    global_norm_clip,
    tree_bytes,
    tree_count_params,
    tree_isfinite,
    tree_l2_norm,
)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_converges_quadratic():
    params = {"x": jnp.asarray([5.0, -3.0])}
    opt = adamw_init(params)
    loss = lambda p: jnp.sum(jnp.square(p["x"]))
    for _ in range(300):
        g = jax.grad(loss)(params)
        params, opt = adamw_update(g, opt, params, 0.05, weight_decay=0.0)
    assert float(loss(params)) < 1e-3


def test_adamw_bf16_moments():
    params = {"x": jnp.ones((4,), jnp.bfloat16)}
    opt = adamw_init(params, jnp.bfloat16)
    assert opt.mu["x"].dtype == jnp.bfloat16
    g = {"x": jnp.ones((4,), jnp.bfloat16)}
    p2, o2 = adamw_update(g, opt, params, 0.1)
    assert p2["x"].dtype == jnp.bfloat16
    assert bool(tree_isfinite(p2))


def test_sgd_direction():
    p = {"x": jnp.asarray([1.0])}
    g = {"x": jnp.asarray([2.0])}
    out = sgd_update(g, p, 0.5)
    np.testing.assert_allclose(np.asarray(out["x"]), [0.0])


def test_schedules():
    assert float(constant(0.1)(jnp.asarray(5))) == pytest.approx(0.1)
    cd = cosine_decay(1.0, 100)
    assert float(cd(jnp.asarray(0))) == pytest.approx(1.0)
    assert float(cd(jnp.asarray(100))) == pytest.approx(0.1, abs=1e-5)
    wu = linear_warmup_cosine(1.0, 10, 100)
    assert float(wu(jnp.asarray(5))) == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path, key):
    tree = {"a": jax.random.normal(key, (4, 3)),
            "nested": {"b": jnp.arange(5, dtype=jnp.int32)}}
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 7, tree)
    assert latest_step(d) == 7
    restored = load_checkpoint(d, 7, tree)
    np.testing.assert_allclose(np.asarray(restored["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(restored["nested"]["b"]),
                                  np.asarray(tree["nested"]["b"]))


def test_checkpoint_shape_mismatch_raises(tmp_path, key):
    tree = {"a": jnp.zeros((2, 2))}
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 1, tree)
    with pytest.raises(ValueError):
        load_checkpoint(d, 1, {"a": jnp.zeros((3, 3))})


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic_and_restartable():
    p = TokenPipeline(1024, 32, 4, seed=1)
    a = p.batch(10)["tokens"]
    b = p.batch(10)["tokens"]
    np.testing.assert_array_equal(a, b)
    c = p.batch(11)["tokens"]
    assert not np.array_equal(a, c)


def test_pipeline_learnable_structure():
    """The Markov stream must be predictable: transition entropy << uniform."""
    p = TokenPipeline(256, 64, 8, seed=0, noise_prob=0.0, markov_states=16)
    toks = p.batch(0)["tokens"] % 16
    trans = np.zeros((16, 16))
    for row in toks:
        for a, b in zip(row[:-1], row[1:]):
            trans[a, b] += 1
    trans = trans / np.maximum(trans.sum(-1, keepdims=True), 1)
    ent = -(trans * np.log(np.maximum(trans, 1e-12))).sum(-1).mean()
    assert ent < 0.9 * np.log(16)


def test_make_lm_batch_shift():
    p = TokenPipeline(128, 16, 2, seed=0)
    b = make_lm_batch(p, 0)
    raw = p.batch(0)["tokens"]
    np.testing.assert_array_equal(np.asarray(b["tokens"]), raw[:, :-1])
    np.testing.assert_array_equal(np.asarray(b["labels"]), raw[:, 1:])


# ---------------------------------------------------------------------------
# utils
# ---------------------------------------------------------------------------

def test_tree_helpers(key):
    tree = {"a": jnp.ones((3, 4)), "b": jnp.ones((2,))}
    assert tree_count_params(tree) == 14
    assert tree_bytes(tree) == 14 * 4
    assert float(tree_l2_norm(tree)) == pytest.approx(np.sqrt(14))
    clipped, norm = global_norm_clip(tree, 1.0)
    assert float(tree_l2_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


def test_collective_stats_parser():
    hlo = """
  %ag = bf16[16,4096,512]{2,1,0} all-gather(%x), replica_groups={}
  %ar = f32[1024]{0} all-reduce(%y), to_apply=%sum
  %ars = f32[8,128]{1,0} reduce-scatter(%z), dimensions={0}
  %a2a = bf16[32,64]{1,0} all-to-all(%w), dimensions={0}
  %cp = u32[4]{0} collective-permute(%v), source_target_pairs={{0,1}}
  %dot1 = f32[8,8]{1,0} dot(%a, %b), lhs_contracting_dims={1}
"""
    s = collective_stats(hlo)
    assert s.count_by_kind["all-gather"] == 1
    assert s.bytes_by_kind["all-gather"] == 16 * 4096 * 512 * 2
    assert s.bytes_by_kind["all-reduce"] == 1024 * 4
    assert s.total_count == 5


def test_collective_stats_start_done_not_double_counted():
    hlo = """
  %ag0 = bf16[128]{0} all-gather-start(%x)
  %ag1 = bf16[128]{0} all-gather-done(%ag0)
"""
    s = collective_stats(hlo)
    assert s.count_by_kind["all-gather"] == 1


def test_roofline_report_terms():
    r = RooflineReport(arch="x", shape="train_4k", mesh="pod1", chips=256,
                       hlo_flops=256 * 197e12,        # exactly 1s compute
                       hlo_bytes=256 * 819e9 * 0.5,   # 0.5s memory
                       collective_bytes=256 * 50e9 * 0.25,
                       model_flops=256 * 197e12 * 0.8)
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(0.5)
    assert r.collective_s == pytest.approx(0.25)
    assert r.dominant == "compute"
    assert r.mfu_upper_bound == pytest.approx(0.8)


# ---------------------------------------------------------------------------
# sharding specs (pure logic; no devices needed)
# ---------------------------------------------------------------------------

def test_param_specs_shard_big_dims():
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.sharding.specs import param_spec_tree
    if len(jax.devices()) != 1:
        pytest.skip("expects single-device CPU")
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2)

    class Leaf:
        def __init__(self, shape):
            self.shape = shape
            self.ndim = len(shape)

    params = {
        "embed": Leaf((1024, 64)),
        "units": {"b0": {"attn": {"wq": Leaf((8, 64, 64)), "ln": {"scale": Leaf((64,))}}}},
    }
    specs = param_spec_tree(params, mesh, fsdp=False)
    assert specs["embed"] == P("model", None)
    assert specs["units"]["b0"]["attn"]["wq"] == P(None, None, "model")
    assert specs["units"]["b0"]["attn"]["ln"]["scale"] == P(None)
