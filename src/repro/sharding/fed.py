"""Client-axis sharding for the federated engine (and its dry-run).

The fused executor (FedEngine._build_fused_chunk) vmaps LocalUpdate over
the m selected clients of each round. On a multi-device mesh that cohort
axis is the natural unit of scale-out: every device trains m/D of the
cohort against replicated global state, server aggregation lowers to a
weighted all-reduce (``jax.lax.psum`` inside the shard-mapped body —
exactly WeightedFedAvg's sum(w*x)/sum(w), plain FedAvg when the weights
are uniform), and the historical/ghost write-back all-gathers the
cohort's fresh embeddings across devices — the embedding-synchronization
network phase of the real deployment.

``build_sharded_chunk`` is the sharded twin of the engine's fused chunk:
the same scanned ``round_step`` signature (plus an explicit per-client
weight stack), with the client half wrapped in ``shard_map`` over a
``("clients",)`` mesh axis. ``launch/fed_dryrun.py`` lowers exactly this
chunk on the production chip counts to report its collectives;
``tests/test_sharding.py`` pins it allclose to the unsharded fused
executor on a forced multi-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

Ragged cohorts (m not divisible by the mesh axis) are padded with dummy
clients built from three no-op guarantees:

* client id ``n_clients`` is out of range — JAX clamps out-of-bounds
  *gathers* (the dummy trains on a real client's data, harmlessly) and
  DROPS out-of-bounds *scatters* (the dummy's hist/ghost/prev_loss
  write-back never lands);
* aggregation weight 0 — the weighted psum ignores the dummy's params;
* the PRNG chain splits for the REAL cohort only (dummies get a zero
  key), so padded runs stay on the exact key trajectory of the
  unsharded executor.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.federated.quant import check_sync_dtype, quant_roundtrip

CLIENT_AXIS = "clients"


def make_client_mesh(n_devices: Optional[int] = None, *,
                     axis: str = CLIENT_AXIS) -> Mesh:
    """A flat ``(n_devices,)`` mesh with one client-sharding axis. On CPU,
    force fake devices first: ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (before the JAX backend initializes).

    The axis is ``AxisType.Auto``, as on every mesh the engine builds: the
    executors place arrays by ``NamedSharding`` and ``shard_map`` specs and
    leave the rest to jit's propagation. (``jax.make_mesh`` defaults to
    Explicit axes, which would carry a sharding in every array's type.)"""
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"make_client_mesh needs 1..{len(devs)} devices, asked for {n} "
            "(force more with XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return jax.make_mesh((n,), (axis,), (AxisType.Auto,), devices=devs[:n])


def client_axis_of(mesh: Mesh) -> Optional[str]:
    """The mesh axis the client cohort shards over: ``"clients"`` if
    present, else the sole axis of a 1-axis mesh, else None."""
    if CLIENT_AXIS in mesh.shape:
        return CLIENT_AXIS
    if len(mesh.shape) == 1:
        return next(iter(mesh.shape))
    return None


def cohort_padding(m: int, n_shards: int) -> int:
    """Dummy clients appended so the cohort splits evenly across shards."""
    return (-m) % n_shards


def replicate_to_mesh(tree, mesh: Mesh):
    """Commit every leaf to the mesh fully replicated (a no-op for leaves
    already there) so jit donation can update buffers in place from the
    first sharded chunk onward."""
    sh = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)


def pairwise_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Deterministic fp32 binary-tree reduction over the leading axis:
    pairs sum left-to-right level by level, so the association order is
    fixed by the leading-axis length alone (never by how XLA schedules an
    all-reduce). Used by ``reduce="pairwise"`` merges on both the 1-D
    client mesh and the 2-D pod mesh (repro.sharding.tables)."""
    while x.shape[0] > 1:
        n = x.shape[0]
        even = (n // 2) * 2
        y = x[0:even:2] + x[1:even:2]
        if n % 2:
            y = jnp.concatenate([y, x[even:]], axis=0)
        x = y
    return x[0]


def weighted_merge(axes, w, reduce: str):
    """The sharded executors' aggregation rule: sum(w·x)/sum(w) across the
    mesh ``axes`` — a weighted psum all-reduce (``reduce="psum"``) or a
    deterministic fp32 binary tree over all-gathered per-device partial
    sums (``reduce="pairwise"``). Returns the per-leaf merge function
    ``wmean(x, old)``: when every weight is zero (a round where the whole
    cohort dropped out under a FaultPlan — never a healthy run, where
    padding always leaves real positive weights) the merge degrades to
    the carried ``old`` leaf instead of dividing 0/0 into NaN params.
    With any surviving weight the guard is exact: ``max(wsum, tiny)``
    equals ``wsum`` and the ``where`` passes the quotient through
    bit-unchanged.

    The quotient is ``num * (1 / wsum)``, not ``num / wsum``: XLA rewrites
    ``FedAvg``'s ``mean`` (a division by the constant cohort size) into a
    multiply by the rounded reciprocal, so this form keeps a one-device
    mesh bit-identical to the unsharded merge under uniform weights."""
    if reduce == "psum":
        wsum = jax.lax.psum(w.sum(), axes)
        num_of = lambda xw: jax.lax.psum(xw, axes)
    else:   # "pairwise": association fixed by device count, not by XLA
        wsum = pairwise_sum(jax.lax.all_gather(w.sum(), axes))
        num_of = lambda xw: pairwise_sum(jax.lax.all_gather(xw, axes, axis=0))
    inv = 1.0 / jnp.maximum(wsum, 1e-12)

    def wmean(x, old):
        wb = w.reshape((-1,) + (1,) * (x.ndim - 1))
        num = num_of((x * wb).sum(axis=0))
        return jnp.where(wsum > 0.0, num * inv, old)
    return wmean


def _client_step(vm, mesh: Mesh, axis: str, reduce: str):
    """The per-round client half, shard-mapped over the cohort axis:
    vmapped LocalUpdate on each device's cohort shard + weighted merge
    (all-reduce, or the deterministic pairwise tree). Per-client outputs
    stay sharded on their leading axis (out_specs P(axis)); the aggregated
    params come back replicated."""

    def step(params, client, feats_all, hist1_all, h1s, ages, gfs, pls,
             tau, fanouts, eoff, keys, w):
        out = vm(params, client, feats_all, hist1_all, h1s, ages, gfs, pls,
                 tau, fanouts, eoff, keys)
        new_params, new_hist1, new_age, new_ghost, stats = out
        with jax.named_scope("merge"):
            wmean = weighted_merge(axis, w, reduce)
            agg = jax.tree_util.tree_map(wmean, new_params, params)
        return agg, new_hist1, new_age, new_ghost, stats

    c, r = P(axis), P()
    return shard_map(
        step, mesh=mesh,
        in_specs=(r, c, r, r, c, c, c, c, r, c, r, c, c),
        out_specs=(r, c, c, c, c),
        check_vma=False)


def build_sharded_chunk(vm, mesh: Mesh, axis: str, m_real: int,
                        light_stats: Sequence[str], *,
                        reduce: str = "psum",
                        sync_dtype: str = "fp32"):
    """The sharded twin of FedEngine._build_fused_chunk: one jitted donated
    chunk scanning ``round_step`` over S rounds, with the vmapped client
    half shard-mapped over ``axis``.

    Same argument order as the unsharded chunk plus ``w_stack`` (S, m_pad)
    — per-client aggregation weights with zeros on padding — between
    ``fan_stack`` and ``eoffs``. ``sel_stack``/``fan_stack`` arrive padded
    to a multiple of the mesh axis; ``m_real`` is the true cohort size
    (static), which fixes the PRNG split count and the slice of per-round
    stats streamed back to the host tail. ``reduce`` picks the merge:
    ``"psum"`` (weighted all-reduce) or ``"pairwise"`` (fp32 fixed tree
    over gathered partials — the same ``merge_reduce`` knob the pod mesh
    honors, so 1-D meshes no longer silently fall back to psum).
    ``sync_dtype`` round-trips the written-back float rows through the
    repro.federated.quant codec (the write-back IS a wire in the real
    deployment); ``"fp32"`` adds zero trace ops.
    """
    if reduce not in ("psum", "pairwise"):
        raise ValueError(f"unknown reduce {reduce!r}; known: psum | pairwise")
    check_sync_dtype(sync_dtype)
    step = _client_step(vm, mesh, axis, reduce)
    light_stats = tuple(light_stats)

    def chunk(params, hist1, age, ghost_feat, prev_loss, key, arrays,
              sel_stack, fan_stack, w_stack, eoffs, tau):
        m_pad = sel_stack.shape[1]
        pad = m_pad - m_real

        def round_step(carry, xs):
            params, hist1, age, ghost_feat, prev_loss, key = carry
            sel, fanouts, w, eoff = xs
            # the unsharded executor's exact key chain: split for the real
            # cohort only, dummies ride along on a constant zero key
            ks = jax.random.split(key, m_real + 1)
            key, keys = ks[0], ks[1:]
            if pad:
                keys = jnp.concatenate(
                    [keys, jnp.zeros((pad,) + keys.shape[1:], keys.dtype)])
            client = {k: v[sel] for k, v in arrays.items()}
            out = step(params, client, arrays["features"], hist1,
                       hist1[sel], age[sel], ghost_feat[sel], prev_loss[sel],
                       tau, fanouts, eoff, keys, w)
            params, new_hist1, new_age, new_ghost_feat, stats = out
            with jax.named_scope("merge"):
                loss_wb = stats["loss_all"]
                if sync_dtype != "fp32":
                    new_hist1 = quant_roundtrip(new_hist1, sync_dtype)
                    new_ghost_feat = quant_roundtrip(new_ghost_feat, sync_dtype)
                    loss_wb = quant_roundtrip(loss_wb, sync_dtype)
                # out-of-range padding ids make these scatters drop, never land
                hist1 = hist1.at[sel].set(new_hist1)
                age = age.at[sel].set(new_age)
                ghost_feat = ghost_feat.at[sel].set(new_ghost_feat)
                prev_loss = prev_loss.at[sel].set(loss_wb)
            light = {k: stats[k][:m_real] for k in light_stats}
            return (params, hist1, age, ghost_feat, prev_loss, key), light

        return jax.lax.scan(round_step,
                            (params, hist1, age, ghost_feat, prev_loss, key),
                            (sel_stack, fan_stack, w_stack, eoffs))

    return jax.jit(chunk, donate_argnums=(0, 1, 2, 3, 4, 5))


def abstract_chunk_args(mesh: Mesh, *, n_clients: int, cohort: int,
                        n_max: int, g_max: int, n_feat: int, n_classes: int,
                        max_deg: int = 16, rounds: int = 1,
                        loss_buckets: tuple | None = None):
    """ShapeDtypeStructs (with replicated NamedShardings) matching
    ``build_sharded_chunk``'s signature, for lowering the chunk without
    real data — the dry-run path. ``cohort`` is the padded cohort size the
    chunk receives (a multiple of the mesh's client axis). ``loss_buckets``
    adds the loss-pass layout arrays a LocalUpdate built with the same
    geometry reads."""
    from repro.models.gcn import HIDDEN, gcn_init

    r = NamedSharding(mesh, P())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=r)

    params = jax.eval_shape(
        lambda: gcn_init(jax.random.PRNGKey(0), n_feat, n_classes))
    params = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=r),
        params)
    K, n_tot = n_clients, n_max + g_max
    arrays = {
        "features": sds((K, n_max, n_feat), jnp.float32),
        "labels": sds((K, n_max), jnp.int32),
        "node_mask": sds((K, n_max), jnp.float32),
        "train_mask": sds((K, n_max), jnp.float32),
        "nbr_idx": sds((K, n_max, max_deg), jnp.int32),
        "nbr_mask": sds((K, n_max, max_deg), jnp.float32),
        "ghost_owner": sds((K, g_max), jnp.int32),
        "ghost_row": sds((K, g_max), jnp.int32),
        "ghost_mask": sds((K, g_max), jnp.float32),
    }
    if loss_buckets is not None:
        S = sum(w * c for w, c in loss_buckets)
        arrays["loss_idx"] = sds((K, S), jnp.int32)
        arrays["loss_mask"] = sds((K, S), jnp.float32)
        arrays["loss_pos"] = sds((K, n_max), jnp.int32)
    return (
        params,
        sds((K, n_tot, HIDDEN[0]), jnp.float32),   # hist1
        sds((K, n_tot), jnp.int32),                # age
        sds((K, g_max, n_feat), jnp.float32),      # ghost features
        sds((K, n_max), jnp.float32),              # prev loss
        sds((2,), jnp.uint32),                     # PRNG key chain head
        arrays,
        sds((rounds, cohort), jnp.int32),          # sel_stack
        sds((rounds, cohort), jnp.int32),          # fan_stack
        sds((rounds, cohort), jnp.float32),        # w_stack
        sds((rounds,), jnp.int32),                 # eoffs
        sds((), jnp.int32),                        # tau
    )
