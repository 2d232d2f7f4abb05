"""FedAIS Algorithm 1 — the client LocalUpdate and its method-space.

One ``MethodConfig`` describes every method in the paper (FedAIS, its
ablations FedAIS1/FedAIS2, and the five baselines) as feature toggles over
the same LocalUpdate, so cost/accuracy comparisons are apples-to-apples.

``make_local_update(mcfg, dims)`` returns a jit-compiled function running J
local epochs for ONE client: importance-sampled batches (Eq. 7-8), forward
with historical embeddings (Eq. 6), local Adam steps, historical pushes, and
ghost pulls every tau epochs. It is vmapped over the selected clients by the
simulator — the cross-client pull then lowers to a gather over the stacked
client axis (the all-to-all of the real deployment).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.historical import pull_ghosts, pull_ghosts_prefetched, push_embeddings
from repro.federated.quant import check_sync_dtype, quant_roundtrip
from repro.core.importance import (
    importance_probs,
    loss_delta_scores,
    sample_batch,
    stable_rank,
    uniform_probs,
)
from repro.models.gcn import (
    AGG_BACKENDS,
    gcn_batch_forward,
    gcn_batch_rows_forward,
    gcn_loss_pass_forward,
    per_node_loss,
)
from repro.optim import adamw_init, adamw_update


@dataclass(frozen=True)
class MethodConfig:
    name: str = "fedais"
    importance_sampling: bool = True     # FedAIS / FedAIS1 (off: uniform/all)
    adaptive_sync: bool = True           # FedAIS / FedAIS2 (off: fixed tau)
    use_all_samples: bool = False        # FedAll/FedPNS/FedGraph/FedSage+/FedAIS2
    sample_ratio: float = 0.7            # r: fraction of local nodes per epoch
    neighbor_fanout: int = 10            # max sampled neighbors per node
    tau0: int = 2                        # initial / fixed sync interval
    local_epochs: int = 4                # J
    lr: float = 0.01
    use_generator: bool = False          # FedSage+: impute ghosts, no sync
    bandit_fanout: bool = False          # FedGraph-lite: learned fanout
    use_ghosts: bool = True              # FedLocal ablation: ignore cross-client
    batch_cap: int = 256                 # padded batch size upper bound
    # repro.api resolution hooks (string keys into the api registries):
    strategy: str = "auto"               # method-strategy kind; "auto" infers
    aggregator: str = "fedavg"           # server aggregation ("fedavg"|"weighted")
    scheduler: str = "sync"              # round scheduling ("sync"|"async")


def batch_size_for(mcfg: MethodConfig, n_max: int) -> int:
    if mcfg.use_all_samples:
        return n_max
    return max(1, min(mcfg.batch_cap, int(round(n_max * mcfg.sample_ratio))))


def fanout_width(max_fanout: int, max_deg: int) -> int:
    """Static width ``W`` of the training step's neighbor lists: the most
    slots a batch row can keep, ``min(max_deg, max_fanout)``, where
    ``max_fanout`` bounds the fanouts the method's strategy passes to
    LocalUpdate (``MethodStrategy.max_fanout``). Below ``max_deg`` the step
    compacts each row's kept slots to ``W`` before it gathers a feature or
    embedding row; at ``max_deg`` it aggregates the padded rows under a
    keep mask."""
    return max(1, min(max_deg, int(max_fanout)))


def kept_neighbors(b_idx: jnp.ndarray, b_mask: jnp.ndarray,
                   ranks: jnp.ndarray, fanout, width: int):
    """The batch rows' kept neighbor lists ``(idx, mask)``, ``width`` wide.

    ``b_idx``/``b_mask`` are the rows' padded (b, K) lists and ``ranks`` a
    (b, K) key per slot, padding keyed last. A row keeps its real slots at
    positions < ``fanout`` of the keys' stable ascending order (one top-k
    over mantissa-quantized keys, ``importance.stable_rank``; near-equal
    draws tie and resolve by slot index). At ``width == K`` the padded
    lists come back with the other slots masked. Below K the kept slots
    are packed to the front of (b, width) lists, in slot order, before any
    feature or embedding row is gathered, so a row's aggregate adds its
    neighbours in the padded form's order, without the masked zeros. A
    fanout above ``width`` keeps ``width`` slots.
    """
    k = b_idx.shape[-1]
    if width >= k:
        return b_idx, b_mask * (stable_rank(ranks) < fanout)
    keep = (b_mask > 0) & (stable_rank(ranks) < jnp.minimum(fanout, width))
    # a one-hot pick over the (b, W, K) ints, not an int gather, which can
    # lower to a loop of one dynamic-slice per row on a TPU
    slots = jnp.arange(width)
    pos = jnp.cumsum(keep, axis=-1) - 1
    pick = keep[:, None, :] & (pos[:, None, :] == slots[:, None])
    c_idx = jnp.where(pick, b_idx[:, None, :], 0).sum(-1)
    c_mask = (slots < keep.sum(-1, keepdims=True)).astype(b_mask.dtype)
    return c_idx, c_mask


def ghost_need(b_idx: jnp.ndarray, b_mask: jnp.ndarray, valid: jnp.ndarray,
               n_max: int, ghost_mask: jnp.ndarray) -> jnp.ndarray:
    """(g_max,) 1 where a valid batch row's kept neighbor is that ghost."""
    referenced = (b_idx >= n_max) & (b_mask > 0) & valid[:, None]
    slot = jnp.where(referenced, b_idx - n_max, 0)
    need = jnp.zeros(ghost_mask.shape, jnp.float32).at[slot.reshape(-1)].max(
        referenced.reshape(-1).astype(jnp.float32))
    return need * ghost_mask


# vmap axes of local_update over the selected-client cohort: per-client
# slices map on their leading axis; params / full tables / scalars broadcast
VMAP_IN_AXES = (None, 0, None, None, 0, 0, 0, 0, None, 0, None, 0)
# ghost_source="prefetched": the two table-snapshot args become per-client
# pre-gathered (g_max, F)/(g_max, H1) source rows and map on their leading axis
VMAP_IN_AXES_PREFETCHED = (None, 0, 0, 0, 0, 0, 0, 0, None, 0, None, 0)


def make_vmapped_update(mcfg: MethodConfig, n_max: int, g_max: int, h1_dim: int,
                        *, ghost_source: str = "tables",
                        sync_dtype: str = "fp32",
                        train_backend: str = "gather",
                        loss_buckets: tuple | None = None,
                        width: int | None = None):
    """The cohort-stacked LocalUpdate every executor vmaps over the selected
    clients — shared by the engine's stepwise/fused paths and the sharded
    round_step (repro.sharding.fed), so all of them run one computation.
    ``ghost_source="prefetched"`` builds the pod-sharded variant (see
    ``make_local_update``)."""
    axes = VMAP_IN_AXES if ghost_source == "tables" else VMAP_IN_AXES_PREFETCHED
    return jax.vmap(make_local_update(mcfg, n_max, g_max, h1_dim,
                                      ghost_source=ghost_source,
                                      sync_dtype=sync_dtype,
                                      train_backend=train_backend,
                                      loss_buckets=loss_buckets,
                                      width=width),
                    in_axes=axes)


def make_local_update(mcfg: MethodConfig, n_max: int, g_max: int, h1_dim: int,
                      *, ghost_source: str = "tables",
                      sync_dtype: str = "fp32",
                      train_backend: str = "gather",
                      loss_buckets: tuple | None = None,
                      width: int | None = None):
    """Build the jit-able LocalUpdate for one client (Algorithm 1 lines 10-19).

    ``ghost_source`` picks where the tau-gated embedding sync reads from:

    * ``"tables"`` (default): gather from the replicated round-start
      snapshots ``feats_all`` (K, n_max, F) / ``hist1_all`` (K, n_tot, H1).
    * ``"prefetched"``: the same two positional arguments instead carry THIS
      client's pre-gathered ghost-source rows — (g_max, F) owner features
      and (g_max, H1) owner layer-1 rows, exchanged cross-pod by the
      table-sharded executor before the cohort step. Same values (both are
      round-start snapshots), so the two modes are computationally
      identical per client.

    ``sync_dtype`` selects the ghost-pull wire format (repro.federated.
    quant): in ``"tables"`` mode the pulled feature/h1 rows are
    round-tripped through the codec here — the semantic anchor every
    single-host executor shares. In ``"prefetched"`` mode the rows arrive
    already wire-quantized (the pod executor encodes the physical
    all-to-all and the partition-time feature exchange), so this function
    applies no second round-trip. ``"fp32"`` adds zero trace ops.

    ``train_backend`` selects the neighbor aggregation of the loss pass
    and the training step: ``gather`` is the bit-parity default; ``segment``
    derives its jit-stable bucketed CSR in-trace from the batch rows and
    never materializes the (b, K, d) gather; ``spmm`` runs the Pallas kernel
    (grads flow through its custom VJP). Allclose parity across backends is
    pinned per method by tests/test_train_backend.py.

    ``loss_buckets`` is the partition's static degree-bucket geometry
    (``FederatedGraph.loss_buckets``). Given it, the client arrays carry the
    layout (``loss_idx``, ``loss_mask``, ``loss_pos``) and the ``gather``
    backend's loss pass reads it (``gcn_loss_pass_forward``) instead of
    gathering all K padded slots of every row.

    The training step's rows and fanout are drawn in-trace. ``width`` is
    the most slots a row keeps, at least every fanout the step is passed
    (``fanout_width``; the engine's ``FedEngine.fanout_width``). Below the
    K padded slots, each epoch compacts every batch row's kept slots to
    ``width`` columns (``gcn_batch_rows_forward``), so its forward and
    backward run on (b, W) neighbor lists; the draws and the kept set are
    those of the padded form. None keeps the K padded slots under a keep
    mask. ``stats["n_kept_slots"]`` counts the real slots read.
    """
    if ghost_source not in ("tables", "prefetched"):
        raise ValueError(f"unknown ghost_source {ghost_source!r}; "
                         "known: tables | prefetched")
    if train_backend not in AGG_BACKENDS:
        raise ValueError(f"unknown train_backend {train_backend!r}; "
                         f"known: {AGG_BACKENDS}")
    check_sync_dtype(sync_dtype)
    bsz = batch_size_for(mcfg, n_max)

    def local_update(
        params: Any,                # global model from server
        client: dict,               # this client's stacked-slice arrays
        feats_all: jnp.ndarray,     # (K, n_max, F) — ghost pull source
                                    #   [prefetched: (g_max, F) source rows]
        hist1_all: jnp.ndarray,     # (K, n_tot, H1) — ghost pull source (snapshot)
                                    #   [prefetched: (g_max, H1) source rows]
        hist1: jnp.ndarray,         # (n_tot, H1) this client's table
        age: jnp.ndarray,           # (n_tot,)
        ghost_feat: jnp.ndarray,    # (g_max, F) current synced ghost features
        prev_loss: jnp.ndarray,     # (n_max,) loss at previous round (-1 = never)
        tau: jnp.ndarray,           # scalar int32 — current sync interval
        fanout: jnp.ndarray,        # scalar int32 — neighbor fanout (bandit-controllable)
        epoch_offset: jnp.ndarray,  # scalar int32 — global batch-epoch counter (t*J)
        key: jnp.ndarray,
    ):
        train_mask = client["train_mask"] * client["node_mask"]

        # ---- lines 11-12: loss pass + selection probabilities ----
        all_idx = jnp.arange(n_max)
        with jax.named_scope("loss_pass"):
            if loss_buckets is not None and train_backend == "gather":
                logits_all, _ = gcn_loss_pass_forward(
                    params, client["features"], ghost_feat, hist1,
                    client["loss_idx"], client["loss_mask"],
                    client["loss_pos"], loss_buckets)
            else:
                logits_all, _, _ = gcn_batch_forward(
                    params, client["features"], ghost_feat, hist1,
                    client["nbr_idx"], client["nbr_mask"], all_idx,
                    backend=train_backend,
                )
            loss_all = (per_node_loss(logits_all, client["labels"])
                        * client["node_mask"])
            if mcfg.importance_sampling:
                scores = loss_delta_scores(loss_all, prev_loss, train_mask)
                probs = importance_probs(scores, train_mask)
            else:
                probs = uniform_probs(train_mask)

        opt_state = adamw_init(params)
        n_sync = jnp.zeros((), jnp.int32)
        n_ghost_pulled = jnp.zeros((), jnp.float32)
        n_kept_slots = jnp.zeros((), jnp.float32)
        max_deg = client["nbr_idx"].shape[-1]
        w = max_deg if width is None else fanout_width(width, max_deg)

        def epoch(carry, j):
            (params, opt_state, hist1, age, ghost_feat, n_sync, n_pulled,
             n_kept, key) = carry
            key, k_batch, k_nbr = jax.random.split(key, 3)

            # ---- line 14: batch selection ----
            if mcfg.use_all_samples:
                batch_idx = all_idx
                valid = train_mask > 0
            else:
                batch_idx, valid = sample_batch(k_batch, probs, bsz, train_mask)

            # ---- neighbor fanout subsampling ----
            b_idx = client["nbr_idx"][batch_idx]
            b_nbr_mask = client["nbr_mask"][batch_idx]
            ranks = jax.random.uniform(k_nbr, b_nbr_mask.shape)
            ranks = jnp.where(b_nbr_mask > 0, ranks, 2.0)
            b_idx, b_mask = kept_neighbors(b_idx, b_nbr_mask, ranks, fanout, w)
            if not mcfg.use_ghosts:
                b_mask = b_mask * (b_idx < n_max)

            # ---- lines 15-17: sync every tau epochs (pull ghosts) ----
            # j is the GLOBAL batch-epoch counter (Algorithm 1: the paper's j
            # runs over local batch training epochs; tau gates it across
            # rounds — round 0 epoch 0 always syncs as the warm-up).
            # Only the ghosts the current batch actually references are
            # transferred ("the selected cross-client neighbor embeddings",
            # Algorithm 1 line 16) — importance sampling thus directly
            # shrinks the communication volume.
            with jax.named_scope("ghost_pull"):
                j_global = epoch_offset + j
                do_sync = ((j_global % jnp.maximum(tau, 1)) == 0) & jnp.asarray(
                    mcfg.use_ghosts and not mcfg.use_generator)

                need = ghost_need(b_idx, b_mask, valid, n_max,
                                  client["ghost_mask"])

                def pull(_):
                    if ghost_source == "tables":
                        gf, gh = pull_ghosts(hist1_all, feats_all,
                                             client["ghost_owner"],
                                             client["ghost_row"],
                                             client["ghost_mask"])
                    else:
                        gf, gh = pull_ghosts_prefetched(feats_all, hist1_all,
                                                        client["ghost_mask"])
                    if sync_dtype != "fp32" and ghost_source == "tables":
                        gf = quant_roundtrip(gf, sync_dtype)
                        gh = quant_roundtrip(gh, sync_dtype)
                    new_ghost_feat = jnp.where(need[:, None] > 0, gf, ghost_feat)
                    new_hist = hist1.at[n_max:].set(
                        jnp.where(need[:, None] > 0, gh, hist1[n_max:]))
                    return new_ghost_feat, new_hist, n_sync + 1, n_pulled + need.sum()

                def nopull(_):
                    return ghost_feat, hist1, n_sync, n_pulled

                ghost_feat, hist1, n_sync, n_pulled = jax.lax.cond(do_sync, pull, nopull, None)

            # ---- line 18: batch forward/backward + local step ----
            def batch_loss(p):
                logits, h1, _ = gcn_batch_rows_forward(
                    p, client["features"], ghost_feat, hist1, b_idx, b_mask,
                    batch_idx, backend=train_backend,
                )
                w = valid.astype(jnp.float32) * train_mask[batch_idx]
                nll = per_node_loss(logits, client["labels"][batch_idx])
                return (nll * w).sum() / jnp.maximum(w.sum(), 1.0), h1

            (loss, h1), grads = jax.value_and_grad(batch_loss, has_aux=True)(params)
            params, opt_state = adamw_update(grads, opt_state, params, mcfg.lr)

            # ---- historical push of fresh in-batch embeddings ----
            hist1, age = push_embeddings(hist1, age, batch_idx, h1,
                                         valid & (client["node_mask"][batch_idx] > 0))
            return (params, opt_state, hist1, age, ghost_feat, n_sync, n_pulled,
                    n_kept + b_mask.sum(), key), loss

        carry = (params, opt_state, hist1, age, ghost_feat, n_sync,
                 n_ghost_pulled, n_kept_slots, key)
        with jax.named_scope("local_steps"):
            carry, epoch_losses = jax.lax.scan(epoch, carry,
                                               jnp.arange(mcfg.local_epochs))
        (params, opt_state, hist1, age, ghost_feat, n_sync, n_ghost_pulled,
         n_kept_slots, key) = carry

        stats = {
            "loss_all": loss_all,                 # becomes prev_loss next round
            "epoch_losses": epoch_losses,
            "n_sync": n_sync,
            "n_ghost_pulled": n_ghost_pulled,
            # real slots the batch aggregation read, summed over the epochs:
            # over epochs * bsz * W, the share of gathered slots that were real
            "n_kept_slots": n_kept_slots,
        }
        return params, hist1, age, ghost_feat, stats

    return local_update
