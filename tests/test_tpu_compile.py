"""The SpMM kernel compiles for a TPU v5e, at the shapes the main path runs.

Interpret mode (every other kernel test) cannot see the chip compiler's
tiling, SMEM/VMEM and layout refusals. These tests compile ``block_spmm``
with ``interpret=False`` for a described ``v5e:2x2`` topology — no chip is
attached, nothing runs — and check that the Mosaic kernel is in the program
(``tpu_custom_call``). Shapes are full Pubmed (19,717 nodes, F=500, 16
clients at alpha 0.5: n_tot = n_max + g_max = 3,423 + 11,560) with the
FedAIS batch of 256, plus every ``AUTOTUNE_TABLE`` entry.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the tests run under
several xdist workers.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.spmm.ops import AUTOTUNE_TABLE, best_block_sizes, block_spmm

pytestmark = pytest.mark.kernels

N_NODES, N_FEAT, H1 = 19_717, 500, 256
N_TOT, BATCH = 3_423 + 11_560, 256
SERVE_CAPACITY = N_NODES + -(-N_NODES // 4)       # GraphStore's 25% headroom

MAIN_PATH_SHAPES = {
    "train_batch": (BATCH, N_TOT, N_FEAT),          # layer-0 batch aggregation
    "eval_full_graph": (N_NODES, N_NODES, N_FEAT),  # spmm eval, layer 0
    "serve_bucket": (128, SERVE_CAPACITY, H1),      # largest bucket, layer 1
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU library / compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _operands(one_chip, n, m, d):
    bn, bm, _ = best_block_sizes(n, m, d)
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt,
                                                             sharding=one_chip)
    return sds((n, m)), sds((m, d)), sds((-(-n // bn), -(-m // bm)), jnp.int32)


def _compiled_forward(one_chip, n, m, d):
    a, x, mask = _operands(one_chip, n, m, d)
    fwd = jax.jit(lambda a, x, mask: block_spmm(a, x, mask, interpret=False))
    return fwd.lower(a, x, mask).compile()


@pytest.mark.parametrize("shape", list(MAIN_PATH_SHAPES.values()),
                         ids=list(MAIN_PATH_SHAPES))
def test_forward_compiles_at_main_path_shapes(one_chip, no_persistent_cache,
                                              shape):
    assert "tpu_custom_call" in _compiled_forward(one_chip, *shape).as_text()


@pytest.mark.parametrize("shape", sorted(AUTOTUNE_TABLE), ids=str)
def test_forward_compiles_at_every_autotune_entry(one_chip,
                                                  no_persistent_cache, shape):
    assert "tpu_custom_call" in _compiled_forward(one_chip, *shape).as_text()


def test_backward_compiles_at_train_batch_shape(one_chip, no_persistent_cache):
    """The custom VJP's transposed kernel call (dx = A^T @ dy)."""
    a, x, mask = _operands(one_chip, *MAIN_PATH_SHAPES["train_batch"])
    grad = jax.jit(jax.grad(
        lambda x, a, mask: block_spmm(a, x, mask, interpret=False).sum()))
    assert "tpu_custom_call" in grad.lower(x, a, mask).compile().as_text()
