"""Correctness tests for the §Perf beyond-paper optimizations: every
optimized path must match its paper-faithful baseline numerically
(optimizations change cost, never semantics)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config, long_context_variant
from repro.models import lm
from repro.sharding.specs import param_spec_tree


def _grad_err(ga, gb):
    return max(float(jnp.max(jnp.abs(x - y))) for x, y in
               zip(jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)))


@pytest.mark.parametrize("arch", ["dbrx-132b", "arctic-480b"])
def test_moe_einsum_matches_sort(arch, key):
    """H1: the partition-friendly einsum dispatch == the sort dispatch
    (at no-drop capacity), including grouped routing."""
    cfg = get_smoke_config(arch)
    params = lm.init_lm(key, cfg)
    tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    a, aux_a = lm.lm_forward(params, cfg, tokens)
    for overrides in ({"moe_impl": "einsum"}, {"moe_impl": "einsum", "moe_group_size": 8}):
        cfg2 = dataclasses.replace(cfg, **overrides)
        b, aux_b = lm.lm_forward(params, cfg2, tokens)
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   atol=3e-5)
        assert abs(float(aux_a) - float(aux_b)) < 1e-5


def test_moe_einsum_gradients_match(key):
    cfg = get_smoke_config("dbrx-132b")
    params = lm.init_lm(key, cfg)
    tokens = jax.random.randint(key, (2, 17), 0, cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    cfg2 = dataclasses.replace(cfg, moe_impl="einsum", moe_group_size=8)
    ga = jax.grad(lambda p: lm.lm_loss(p, cfg, batch)[0])(params)
    gb = jax.grad(lambda p: lm.lm_loss(p, cfg2, batch)[0])(params)
    assert _grad_err(ga, gb) < 2e-5


def test_rwkv_chunked_scan_matches(key):
    """H2.2: chunked WKV with boundary remat == plain scan (fwd + grad)."""
    cfg = get_smoke_config("rwkv6-1.6b")
    params = lm.init_lm(key, cfg)
    tokens = jax.random.randint(key, (2, 17), 0, cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    cfg2 = dataclasses.replace(cfg, rwkv_chunk=4)
    a, _ = lm.lm_forward(params, cfg, tokens)
    b, _ = lm.lm_forward(params, cfg2, tokens)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    ga = jax.grad(lambda p: lm.lm_loss(p, cfg, batch)[0])(params)
    gb = jax.grad(lambda p: lm.lm_loss(p, cfg2, batch)[0])(params)
    assert _grad_err(ga, gb) < 1e-5


@pytest.mark.parametrize("arch", ["deepseek-67b", "gemma3-12b", "recurrentgemma-2b"])
def test_flash_vjp_gradients_match_einsum(arch, key):
    """H3: GQA-native flash custom_vjp == einsum attention (fwd + grad)."""
    cfg = get_smoke_config(arch)
    params = lm.init_lm(key, cfg)
    tokens = jax.random.randint(key, (2, 13), 0, cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    cfg2 = dataclasses.replace(cfg, attn_impl="chunked", attn_chunk_size=4)
    a, _ = lm.lm_forward(params, cfg, tokens)
    b, _ = lm.lm_forward(params, cfg2, tokens)
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                               atol=3e-5)
    ga = jax.grad(lambda p: lm.lm_loss(p, cfg, batch)[0])(params)
    gb = jax.grad(lambda p: lm.lm_loss(p, cfg2, batch)[0])(params)
    assert _grad_err(ga, gb) < 2e-5


def test_long_context_variant_degrades_global_to_local():
    cfg = get_config("gemma3-12b")
    lc = long_context_variant(cfg)
    assert "attn" not in lc.block_pattern
    assert lc.block_pattern.count("local") == len(lc.block_pattern)
    # archs without the flag are unchanged
    ds = get_config("deepseek-67b")
    assert long_context_variant(ds).block_pattern == ds.block_pattern


def test_dp_profile_replicates_params(key):
    """H2.1: the dp profile replicates every weight (PartitionSpec())."""
    from jax.sharding import PartitionSpec as P
    if len(jax.devices()) != 1:
        pytest.skip("single-device test")
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2)
    cfg = get_smoke_config("rwkv6-1.6b")
    shapes = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
    specs = param_spec_tree(shapes, mesh, profile="dp")
    assert all(s == P() for s in jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)))
