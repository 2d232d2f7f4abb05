"""Fault-aware twin of ``FedEngine._build_fused_chunk``.

The plain fused chunk assumes every cohort member's update merges. Under
a non-empty ``FaultPlan`` the engine routes through this builder instead:
the same scanned ``round_step`` (identical PRNG chain, identical vmapped
LocalUpdate on the *real* cohort ids), extended with three per-round
per-member stacks evaluated on the host from the plan —

* ``w_stack``   (S, m) aggregation weights, 0.0 for dropped members
  (adding a 0.0-weighted, zeroed row to a float sum is exact, so the
  masked merge reproduces the stepwise subset merge bit-for-bit);
* ``cmult_stack`` (S, m) corruption multipliers (NaN / inf /
  corrupt_scale on corrupted members, 1.0 elsewhere) applied to the
  uploaded params in-trace;
* an in-trace ``UpdateGuard``: per-member all-finite check plus optional
  L2 delta-norm ceiling; members failing it get weight 0 and are counted
  into the streamed ``n_quarantined`` stat.

Members that are dropped OR quarantined also lose their historical
write-back: their scatter ids are rewritten to the out-of-range row K,
which JAX drops (the same no-op guarantee the sharded executors' padding
relies on). When *no* member survives a round, the merge falls back to
the carried params — a server no-op round, exactly like the stepwise
path's empty merge.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.federated.quant import check_sync_dtype, quant_roundtrip

__all__ = ["build_faulty_chunk"]


def build_faulty_chunk(vm, light_stats: Sequence[str], *,
                       uses_weights: bool, finite_guard: bool = True,
                       max_norm: Optional[float] = None,
                       sync_dtype: str = "fp32"):
    """Build the jitted fault-aware fused chunk.

    ``uses_weights`` selects the merge rule to reproduce exactly:
    WeightedFedAvg's normalize-then-sum when True, FedAvg's sum-then-
    divide when False. ``finite_guard=False`` disables the in-trace
    guard (matching an engine constructed with ``guard=None``, where
    non-finite updates poison the merge — by explicit user choice).
    ``sync_dtype`` round-trips the written-back float rows through the
    repro.federated.quant codec, matching the other executors' wire.
    """
    check_sync_dtype(sync_dtype)
    light_stats = tuple(light_stats)

    def chunk(params, hist1, age, ghost_feat, prev_loss, key, arrays,
              sel_stack, fan_stack, w_stack, cmult_stack, eoffs, tau):
        m = sel_stack.shape[1]
        K = hist1.shape[0]

        def bcast(v, x):
            return v.reshape((m,) + (1,) * (x.ndim - 1))

        def round_step(carry, xs):
            params, hist1, age, ghost_feat, prev_loss, key = carry
            sel, fanouts, w, cmult, eoff = xs
            ks = jax.random.split(key, m + 1)       # same chain as dispatch
            key, keys = ks[0], ks[1:]
            client = {k: v[sel] for k, v in arrays.items()}
            out = vm(params, client, arrays["features"], hist1,
                     hist1[sel], age[sel], ghost_feat[sel], prev_loss[sel],
                     tau, fanouts, eoff, keys)
            new_params, new_hist1, new_age, new_ghost_feat, stats = out

            # corruption: poison the uploaded params (NaN/inf/scale), not
            # the client's local state — the client itself is healthy
            new_params = jax.tree_util.tree_map(
                lambda x: x * bcast(cmult, x).astype(x.dtype), new_params)

            with jax.named_scope("merge"):
                # finite/norm guard over each member's uploaded params
                if finite_guard:
                    ok = jnp.ones((m,), bool)
                    sumsq = jnp.zeros((m,), jnp.float32)
                    for x, r in zip(jax.tree_util.tree_leaves(new_params),
                                    jax.tree_util.tree_leaves(params)):
                        flat = x.reshape(m, -1)
                        ok &= jnp.all(jnp.isfinite(flat), axis=1)
                        if max_norm is not None:
                            d = flat - r.reshape(1, -1)
                            d = jnp.where(jnp.isfinite(d), d, 0.0)
                            sumsq += jnp.sum(d * d, axis=1)
                    if max_norm is not None:
                        ok &= jnp.sqrt(sumsq) <= max_norm
                else:
                    ok = jnp.ones((m,), bool)

                dispatched = w > 0.0                    # not dropped by the plan
                alive = dispatched & ok
                n_quar = jnp.sum(dispatched & ~ok)

                # zero non-survivor rows BEFORE weighting: NaN * 0 is NaN, and
                # a zeroed row added to a float sum is exact — so the masked
                # full-m merge equals the stepwise survivor-subset merge
                safe = jax.tree_util.tree_map(
                    lambda x: jnp.where(bcast(alive, x), x, jnp.zeros((), x.dtype)),
                    new_params)
                wa = jnp.where(alive, w, 0.0)
                if uses_weights:                        # WeightedFedAvg, exactly
                    wn = wa / jnp.maximum(wa.sum(), 1e-12)
                    merged = jax.tree_util.tree_map(
                        lambda x: (x * bcast(wn, x)).sum(axis=0), safe)
                else:                                   # FedAvg (mean), exactly
                    count = jnp.maximum(alive.sum(), 1)
                    merged = jax.tree_util.tree_map(
                        lambda x: x.sum(axis=0) / count, safe)
                any_alive = alive.any()
                params = jax.tree_util.tree_map(
                    lambda mrg, old: jnp.where(any_alive, mrg, old),
                    merged, params)

                # non-survivors lose their write-back too: out-of-range row K
                # makes the scatter drop (same trick as sharded dummy padding)
                wb = jnp.where(alive, sel, K)
                loss_wb = stats["loss_all"]
                new_hist1_wb, new_ghost_feat_wb = new_hist1, new_ghost_feat
                if sync_dtype != "fp32":
                    new_hist1_wb = quant_roundtrip(new_hist1, sync_dtype)
                    new_ghost_feat_wb = quant_roundtrip(new_ghost_feat, sync_dtype)
                    loss_wb = quant_roundtrip(loss_wb, sync_dtype)
                hist1 = hist1.at[wb].set(new_hist1_wb)
                age = age.at[wb].set(new_age)
                ghost_feat = ghost_feat.at[wb].set(new_ghost_feat_wb)
                prev_loss = prev_loss.at[wb].set(loss_wb)

            light = {k: stats[k] for k in light_stats}
            light["n_quarantined"] = n_quar
            return (params, hist1, age, ghost_feat, prev_loss, key), light

        return jax.lax.scan(round_step,
                            (params, hist1, age, ghost_feat, prev_loss, key),
                            (sel_stack, fan_stack, w_stack, cmult_stack, eoffs))

    return jax.jit(chunk, donate_argnums=(0, 1, 2, 3, 4, 5))
