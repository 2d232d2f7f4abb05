"""Block-sparse SpMM Pallas kernel: Y = A @ X with block skipping.

This is the FedGCN neighbor-aggregation hot spot adapted to TPU
(DESIGN.md §4): instead of PyG's irregular row gather/scatter, the
(normalised) adjacency is viewed as a grid of (bn x bm) dense tiles; tiles
that contain no edges are skipped via a host-computed block mask, and live
tiles run as dense MXU matmuls with all operands resident in VMEM.

Grid: (n_row_blocks, n_col_blocks, n_contract_blocks) — the contraction
dimension is innermost so the fp32 accumulator scratch is revisited.

The block mask is a scalar-prefetch operand: it lands in SMEM before the
grid runs and the body reads one (ni, mi) liveness scalar per step. (A
(1, 1) VMEM block of it breaks the TPU's (8, 128) block tiling rule as
soon as the mask grid has more than one tile.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _spmm_kernel(mask_ref, a_ref, x_ref, y_ref, acc_ref, *, n_contract: int):
    ni, mi = pl.program_id(0), pl.program_id(2)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(mask_ref[ni, mi] != 0)
    def _accumulate():
        a = a_ref[...].astype(jnp.float32)
        x = x_ref[...].astype(jnp.float32)
        # fp32 contraction: the aggregation must match the gather backend's
        # exact fp32 mean, not a bf16-pass approximation of it
        acc_ref[...] += jax.lax.dot_general(
            a, x, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    @pl.when(mi == n_contract - 1)
    def _finalize():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_m", "block_d", "interpret")
)
def spmm_pallas(
    a: jnp.ndarray,        # (N, M) adjacency tile source (already padded)
    x: jnp.ndarray,        # (M, D) features (already padded)
    block_mask: jnp.ndarray,  # (N/bn, M/bm) int32 — 1 where the A tile has edges
    *,
    block_n: int = 128,
    block_m: int = 128,
    block_d: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    N, M = a.shape
    D = x.shape[1]
    grid = (N // block_n, D // block_d, M // block_m)
    kernel = functools.partial(_spmm_kernel, n_contract=grid[2])
    # index maps take the prefetched mask ref as a trailing argument
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                                          # block mask
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_m), lambda ni, di, mi, _: (ni, mi)),  # A tile
            pl.BlockSpec((block_m, block_d), lambda ni, di, mi, _: (mi, di)),  # X tile
        ],
        out_specs=pl.BlockSpec((block_n, block_d), lambda ni, di, mi, _: (ni, di)),
        scratch_shapes=[pltpu.VMEM((block_n, block_d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        interpret=interpret,
    )(block_mask, a, x)
