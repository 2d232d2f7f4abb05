"""Pod-sharded placement: no per-device resident or collective scales with K.

Client sharding (repro.sharding.fed) splits each round's cohort across
devices but replicates all global state. This module places EVERY K-sized
array — the (K, n_tot, H1) ``hist1``/``age`` tables, the (K, g_max, F)
synced-ghost and (K, n_max) prev-loss tables, AND the static client arrays
(features, padded adjacency, labels/masks) — as pod shards over a
``("pods", "clients")`` 2-D mesh: pod p owns the rows of its resident
clients (the K axis block-partitioned with ``NamedSharding``, zero-row
padded to divisibility by the same ``pod_table_padding`` contract), while
each round's cohort still splits over all P×C devices. Four exchanges
replace the replicated dataflow, each sized by what the round touches:

* **owner-keyed cohort fetch** — the m selected clients' table rows AND
  static arrays are pulled from their owner pods by a masked psum (each
  row has exactly one non-zero contributor), O(m·row) bytes. Cohort
  dummies (id Kp) have no owner and fetch zeros — every consumer of
  all-zero client data is NaN-guarded, and the dummy's outputs are
  discarded anyway (weight 0, write-back dropped).
* **gated ghost-bucket all-to-all** — the cross-pod layer-1 embedding
  sync (``federated.partition.ghost_exchange_buckets``), now under a
  ``lax.cond`` on a host-derived per-round predicate
  (``sync_round_gates``): the tau schedule decides on the host whether ANY
  of the round's J local epochs syncs, and non-sync rounds skip the
  exchange entirely — zero bytes, not masked bytes. Bit-parity holds
  because the LocalUpdate never reads the prefetched sources on such
  rounds (its per-epoch ``do_sync`` cond derives from the same eoff/tau).
* **static ghost-feature fetch** — the layer-0 ghost sources come from a
  partition-time bucketed owner exchange
  (``federated.partition.exchange_ghost_features``) that materializes a
  pod-sharded (Kp, g_max, F) source table once; per round the cohort's
  rows ride the same gated owner-keyed fetch.
* **cohort-keyed write-back** — fresh rows all-gather only within the pod
  row (m/P rows), then a host-routed bucket ``all_to_all``
  (``federated.partition.writeback_routing``) delivers each row straight
  to its owner pod — P·cap rows per device, cap ≈ m/P² in expectation,
  instead of the dense m-row cohort all-gather.

Aggregation stays the weighted psum all-reduce of the client-sharded
executor, with ``reduce="pairwise"`` for the deterministic fp32 tree
(``sharding.fed.weighted_merge``).

Parity contract (tests/test_pod_sharding.py): history is allclose to the
client-sharded and unsharded fused runs with every discrete column exact —
the per-client computation is identical (``pull_ghosts_prefetched`` hands
each client the same round-start snapshot rows; skipped exchanges feed
rounds that never read them), only the merge's summation order differs.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.federated.partition import GhostBuckets, pod_table_padding
from repro.federated.quant import check_sync_dtype
from repro.federated.quant import decode as quant_decode
from repro.federated.quant import encode as quant_encode
from repro.sharding.fed import CLIENT_AXIS, pairwise_sum, weighted_merge

__all__ = [
    "POD_AXIS", "make_pod_mesh", "pod_axes_of", "pad_tables_to_pods",
    "shard_tables_to_mesh", "pairwise_sum", "sync_round_gates",
    "build_pod_sharded_chunk", "abstract_pod_chunk_args",
]

POD_AXIS = "pods"

# client-array keys the pod-sharded executor keeps on device. The
# "prefetched" LocalUpdate never reads ghost_owner/ghost_row (the bucketed
# exchanges already routed by them on the host), so those two stay off the
# mesh entirely.
POD_ARRAY_KEYS = ("features", "labels", "node_mask", "train_mask",
                  "nbr_idx", "nbr_mask", "ghost_mask", "loss_idx", "loss_mask",
                  "loss_pos")


def make_pod_mesh(n_pods: int, n_client_shards: Optional[int] = None) -> Mesh:
    """A ``(n_pods, n_client_shards)`` mesh with ``("pods", "clients")``
    axes: tables shard over the first, each round's cohort over both. With
    ``n_client_shards=None`` all visible devices are used (they must split
    evenly). Both axes are ``AxisType.Auto`` (see ``make_client_mesh``).
    On CPU, force fake devices first:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    devs = jax.devices()
    if n_pods < 1:
        raise ValueError(f"need n_pods >= 1, got {n_pods}")
    if n_client_shards is None:
        if len(devs) % n_pods:
            raise ValueError(
                f"{len(devs)} devices do not split into {n_pods} pods; pass "
                "n_client_shards explicitly")
        n_client_shards = len(devs) // n_pods
    n = n_pods * n_client_shards
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"make_pod_mesh needs 1..{len(devs)} devices, asked for "
            f"{n_pods}x{n_client_shards} (force more with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return jax.make_mesh((n_pods, n_client_shards), (POD_AXIS, CLIENT_AXIS),
                         (AxisType.Auto,) * 2, devices=devs[:n])


def pod_axes_of(mesh: Mesh) -> Optional[tuple[str, str]]:
    """The (table, cohort) axis pair of a pod mesh: ``("pods", "clients")``
    when both axes are present, else None (not a pod mesh)."""
    if POD_AXIS in mesh.shape and CLIENT_AXIS in mesh.shape:
        return (POD_AXIS, CLIENT_AXIS)
    return None


def pad_tables_to_pods(tables, n_pods: int):
    """Pad every (K, ...) leaf of a pytree (tuple of tables, dict of client
    arrays) with zero rows so K splits evenly over the pod axis. Returns
    the same structure (unchanged when already divisible)."""
    leaves = jax.tree_util.tree_leaves(tables)
    K = leaves[0].shape[0]
    pad = pod_table_padding(K, n_pods)      # the bucket builder's Kp rule
    if not pad:
        return tables
    return jax.tree_util.tree_map(
        lambda t: jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)), tables)


def shard_tables_to_mesh(tables, mesh: Mesh):
    """Commit every (Kp, ...) leaf to the mesh sharded over the pod axis on
    its leading (client) dimension — pod p holds its residents' rows,
    replicated across the ``"clients"`` axis. Works on any pytree (the
    four-table tuple, the static client-array dict, a lone gsrc array)."""
    sh = NamedSharding(mesh, P(POD_AXIS))
    return jax.tree_util.tree_map(lambda t: jax.device_put(t, sh), tables)


def sync_round_gates(eoffs, tau: int, local_epochs: int, *,
                     enabled: bool = True) -> np.ndarray:
    """Host-derived per-round sync predicate: does ANY of the round's J
    local epochs hit the tau schedule? Epoch j of a round with epoch
    offset e syncs iff ``(e + j) % max(tau, 1) == 0`` (the LocalUpdate's
    ``do_sync``, with ``enabled = use_ghosts and not use_generator``
    folding in the method's static toggles). tau is a host int between
    chunks (the sync controller updates it at eval boundaries), so the
    gate is exact — rounds where it is False skip the ghost exchanges
    entirely and contribute zero collective bytes."""
    eoffs = np.asarray(eoffs, np.int64).reshape(-1)
    if not enabled:
        return np.zeros(eoffs.shape, bool)
    t = max(int(tau), 1)
    j = np.arange(int(local_epochs), dtype=np.int64)
    return (((eoffs[:, None] + j) % t) == 0).any(axis=1)


def _pod_step(vm, mesh: Mesh, buckets: GhostBuckets, reduce: str,
              sync_dtype: str = "fp32"):
    """The per-round client half over a ``("pods", "clients")`` mesh:
    owner-keyed cohort fetch of static arrays + table rows, the gated ghost
    exchange, vmapped LocalUpdate on each device's cohort slice, weighted
    merge, and the bucket-routed write-back. Pod-sharded in/out specs are
    P("pods"); cohort specs P(("pods", "clients")); routing replicated.

    ``sync_dtype`` quantizes the two embedding wires (repro.federated.
    quant): the gated ghost all-to-all and the write-back bucket exchange
    physically move codec payloads (int8 codes + per-row fp32 scales, or
    bf16 halves) and decode at the receiver. The int32 ``age`` table and
    the routing metadata always ride unquantized; merge accumulators stay
    fp32. ``"fp32"`` leaves the lowered collectives byte-identical."""
    check_sync_dtype(sync_dtype)
    P_, C = mesh.shape[POD_AXIS], mesh.shape[CLIENT_AXIS]
    rpp = buckets.rows_per_pod
    axes = (POD_AXIS, CLIENT_AXIS)

    def step(params, arrays, gsrc, hist_sh, age_sh, gfeat_sh, pl_sh,
             sel, tau, fanouts, eoff, keys, w, gate, wdst, wpos, wrecv,
             send_client, send_row, send_mask, recv_src, recv_pos, recv_mask):
        p_i = jax.lax.axis_index(POD_AXIS)
        c_i = jax.lax.axis_index(CLIENT_AXIS)
        mL = keys.shape[0]
        msl = C * mL                       # one pod row's cohort slice

        # ---- owner-keyed fetch of the cohort's rows (tables + statics) ----
        # exactly one (pod, clients=0) device contributes each row; the psum
        # broadcasts it (ints stay exact, floats gain only +0.0 terms).
        # Dummies (id Kp) have owner_pod == P_ — nobody contributes, they
        # train on all-zero data and their outputs are discarded anyway.
        owner_pod = sel // rpp
        local_row = jnp.clip(sel - owner_pod * rpp, 0, rpp - 1)
        own = (owner_pod == p_i) & (c_i == 0)

        def fetch(tbl):
            rows = jnp.where(own.reshape((-1,) + (1,) * (tbl.ndim - 1)),
                             tbl[local_row], 0)
            return jax.lax.psum(rows, axes)

        d = p_i * C + c_i

        def cohort_fetch(tbl):
            return jax.lax.dynamic_slice_in_dim(fetch(tbl), d * mL, mL, 0)

        client = {k: cohort_fetch(v) for k, v in arrays.items()}
        hist_l = cohort_fetch(hist_sh)
        age_l = cohort_fetch(age_sh)
        gfeat_l = cohort_fetch(gfeat_sh)
        pl_l = cohort_fetch(pl_sh)

        # ---- gated ghost exchange: only when the tau schedule syncs ----
        # the whole block — bucketed hist1 all-to-all, recv reassembly, and
        # both ghost-source cohort fetches — sits under one lax.cond on the
        # replicated host-derived gate, so non-sync rounds move ZERO bytes.
        # The zeros branch is safe: the LocalUpdate's per-epoch do_sync is
        # False for every epoch of a gated-off round, so it never reads them.
        g_max = recv_src.shape[1]
        H1 = hist_sh.shape[-1]

        def with_sync(_):
            # send_* arrive (1, P, B) — this pod's row of the (P, P, B) plan
            sc, sr, sm = send_client[0], send_row[0], send_mask[0]
            sbuf = hist_sh[sc, sr] * sm[..., None]              # (P, B, H1)
            # the all-to-all moves codec payloads (int8 codes + per-row
            # fp32 scales / bf16 halves) and decodes at the receiver; per-
            # row encoding commutes with the send gather, so the decoded
            # rows equal the "tables"-mode pull's round-trip bit-for-bit
            q, s = quant_encode(sbuf, sync_dtype)
            rq = jax.lax.all_to_all(q, POD_AXIS, 0, 0, tiled=True)
            rs = (jax.lax.all_to_all(s, POD_AXIS, 0, 0, tiled=True)
                  if s is not None else None)
            rbuf = quant_decode(rq, rs, sync_dtype)
            gh_res = rbuf[recv_src, recv_pos] * recv_mask[..., None]
            return cohort_fetch(gh_res), cohort_fetch(gsrc)

        def without_sync(_):
            return (jnp.zeros((mL, g_max, H1), hist_sh.dtype),
                    jnp.zeros((mL, g_max, gsrc.shape[-1]), gsrc.dtype))

        ghs_l, gfs_l = jax.lax.cond(gate, with_sync, without_sync, None)

        out = vm(params, client, gfs_l, ghs_l, hist_l, age_l, gfeat_l, pl_l,
                 tau, fanouts, eoff, keys)
        new_params, new_hist1, new_age, new_gfeat, stats = out

        # ---- aggregation: weighted all-reduce, or fp32 pairwise tree ----
        with jax.named_scope("merge"):
            wmean = weighted_merge(axes, w, reduce)
            agg = jax.tree_util.tree_map(wmean, new_params, params)

        # ---- cohort-keyed bucket write-back ----
        # stage 1: gather the pod row's cohort slice (m/P rows) across the
        # clients axis — device order makes slice index i = global cohort
        # index p_i*msl + i, matching the host routing. stage 2: scatter
        # rows into per-destination send buckets (dummy dst == P_ drops) and
        # swap with one pods all-to-all; each pod lands its received rows at
        # the host-routed local targets (sentinel rpp drops unused slots).
        dst = jax.lax.dynamic_slice_in_dim(wdst, p_i * msl, msl, 0)
        pos = jax.lax.dynamic_slice_in_dim(wpos, p_i * msl, msl, 0)
        tgt = jax.lax.dynamic_slice_in_dim(wrecv, p_i, 1, 0)[0].reshape(-1)
        cap = wrecv.shape[-1]

        def route(x):
            rows = jax.lax.all_gather(x, CLIENT_AXIS, axis=0, tiled=True)
            sbuf = jnp.zeros((P_, cap) + rows.shape[1:], x.dtype)
            sbuf = sbuf.at[dst, pos].set(rows)
            rbuf = jax.lax.all_to_all(sbuf, POD_AXIS, 0, 0, tiled=True)
            return rbuf.reshape((P_ * cap,) + rbuf.shape[2:])

        def write_back(table, fresh):
            # float tables ride the exchange as codec payloads (codes +
            # scales both take the gather/scatter/all-to-all route); the
            # int32 age table and the fp32 passthrough skip the codec
            if sync_dtype != "fp32" and jnp.issubdtype(fresh.dtype, jnp.floating):
                q, s = quant_encode(fresh, sync_dtype)
                rows = quant_decode(route(q),
                                    route(s) if s is not None else None,
                                    sync_dtype)
            else:
                rows = route(fresh)
            return table.at[tgt].set(rows)

        with jax.named_scope("merge"):
            hist_sh = write_back(hist_sh, new_hist1)
            age_sh = write_back(age_sh, new_age)
            gfeat_sh = write_back(gfeat_sh, new_gfeat)
            pl_sh = write_back(pl_sh, stats["loss_all"])
        return agg, hist_sh, age_sh, gfeat_sh, pl_sh, stats

    t, c, r = P(POD_AXIS), P(axes), P()
    return shard_map(
        step, mesh=mesh,
        in_specs=(r, t, t, t, t, t, t, r, r, c, r, c, c, r, r, r, r,
                  t, t, t, t, t, t),
        out_specs=(r, t, t, t, t, c),
        check_vma=False)


def build_pod_sharded_chunk(vm, mesh: Mesh, m_real: int,
                            buckets: GhostBuckets,
                            light_stats: Sequence[str], *,
                            reduce: str = "psum",
                            sync_dtype: str = "fp32"):
    """The pod-sharded twin of ``sharding.fed.build_sharded_chunk``: one
    jitted donated chunk scanning ``round_step`` over S rounds with the
    historical tables AND static client arrays resident as pod shards.

    Signature (vs the client-sharded chunk): ``arrays`` carries only the
    ``POD_ARRAY_KEYS`` leaves padded to ``buckets.n_clients_padded`` rows
    and committed with ``P("pods")`` shardings (``pad_tables_to_pods`` +
    ``shard_tables_to_mesh``), ``gsrc`` is the partition-time (Kp, g_max,
    F) ghost-source feature table, and three host-routed per-round stacks
    follow tau: ``gates`` (S,) bool from ``sync_round_gates``, and the
    ``writeback_routing`` plan's ``wb_dst``/``wb_pos`` (S, m) +
    ``wb_recv`` (S, P, P, cap). ``vm`` must be the
    ``ghost_source="prefetched"`` vmapped LocalUpdate. Cohort padding uses
    dummy id ``n_clients_padded`` (no owner pod: fetches zero, write-backs
    drop). ``reduce`` picks the merge: ``"psum"`` (weighted all-reduce) or
    ``"pairwise"`` (fp32 tree). ``sync_dtype`` quantizes the ghost
    all-to-all and write-back exchanges on the physical wire (``vm`` must
    be built with the same ``sync_dtype`` so all executors agree)."""
    if reduce not in ("psum", "pairwise"):
        raise ValueError(f"unknown reduce {reduce!r}; known: psum | pairwise")
    step = _pod_step(vm, mesh, buckets, reduce, sync_dtype)
    light_stats = tuple(light_stats)
    bkt = tuple(jnp.asarray(a) for a in (
        buckets.send_client, buckets.send_row, buckets.send_mask,
        buckets.recv_src, buckets.recv_pos, buckets.recv_mask))

    def chunk(params, hist1, age, ghost_feat, prev_loss, key, arrays, gsrc,
              sel_stack, fan_stack, w_stack, eoffs, tau, gates,
              wb_dst, wb_pos, wb_recv):
        m_pad = sel_stack.shape[1]
        pad = m_pad - m_real

        def round_step(carry, xs):
            params, hist1, age, ghost_feat, prev_loss, key = carry
            sel, fanouts, w, eoff, gate, wdst, wpos, wrecv = xs
            # the unsharded executor's exact key chain: split for the real
            # cohort only, dummies ride along on a constant zero key
            ks = jax.random.split(key, m_real + 1)
            key, keys = ks[0], ks[1:]
            if pad:
                keys = jnp.concatenate(
                    [keys, jnp.zeros((pad,) + keys.shape[1:], keys.dtype)])
            out = step(params, arrays, gsrc, hist1, age, ghost_feat,
                       prev_loss, sel, tau, fanouts, eoff, keys, w, gate,
                       wdst, wpos, wrecv, *bkt)
            params, hist1, age, ghost_feat, prev_loss, stats = out
            light = {k: stats[k][:m_real] for k in light_stats}
            return (params, hist1, age, ghost_feat, prev_loss, key), light

        return jax.lax.scan(round_step,
                            (params, hist1, age, ghost_feat, prev_loss, key),
                            (sel_stack, fan_stack, w_stack, eoffs, gates,
                             wb_dst, wb_pos, wb_recv))

    return jax.jit(chunk, donate_argnums=(0, 1, 2, 3, 4, 5))


def abstract_pod_chunk_args(mesh: Mesh, buckets: GhostBuckets, *,
                            n_clients: int, cohort: int, n_max: int,
                            g_max: int, n_feat: int, n_classes: int,
                            max_deg: int = 16, rounds: int = 1,
                            wb_cap: Optional[int] = None,
                            loss_buckets: Optional[tuple] = None):
    """ShapeDtypeStructs matching ``build_pod_sharded_chunk``'s signature:
    the four tables, the static client arrays, and the ghost-source table
    all padded to ``buckets.n_clients_padded`` rows with ``P("pods")``
    NamedShardings; cohort stacks, sync gates, and write-back routing
    replicated. ``wb_cap`` fixes the bucket capacity (default: the
    worst-case pow2(cohort / P) — every slice row owned by one pod).
    ``loss_buckets`` adds the loss-pass layout arrays. The ``--pods``
    dry-run path."""
    from repro.models.gcn import HIDDEN, gcn_init

    P_ = mesh.shape[POD_AXIS]
    t = NamedSharding(mesh, P(POD_AXIS))
    r = NamedSharding(mesh, P())
    Kp, n_tot = buckets.n_clients_padded, n_max + g_max
    if wb_cap is None:
        msl = max(1, cohort // P_)
        wb_cap = 1 << (msl - 1).bit_length()

    def ts(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=t)

    def rs(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=r)

    params = jax.eval_shape(
        lambda: gcn_init(jax.random.PRNGKey(0), n_feat, n_classes))
    params = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=r),
        params)
    arrays = {
        "features": ts((Kp, n_max, n_feat), jnp.float32),
        "labels": ts((Kp, n_max), jnp.int32),
        "node_mask": ts((Kp, n_max), jnp.float32),
        "train_mask": ts((Kp, n_max), jnp.float32),
        "nbr_idx": ts((Kp, n_max, max_deg), jnp.int32),
        "nbr_mask": ts((Kp, n_max, max_deg), jnp.float32),
        "ghost_mask": ts((Kp, g_max), jnp.float32),
    }
    if loss_buckets is not None:
        S = sum(w * c for w, c in loss_buckets)
        arrays["loss_idx"] = ts((Kp, S), jnp.int32)
        arrays["loss_mask"] = ts((Kp, S), jnp.float32)
        arrays["loss_pos"] = ts((Kp, n_max), jnp.int32)
    return (
        params,
        ts((Kp, n_tot, HIDDEN[0]), jnp.float32),   # hist1
        ts((Kp, n_tot), jnp.int32),                # age
        ts((Kp, g_max, n_feat), jnp.float32),      # ghost features
        ts((Kp, n_max), jnp.float32),              # prev loss
        rs((2,), jnp.uint32),                      # PRNG key chain head
        arrays,
        ts((Kp, g_max, n_feat), jnp.float32),      # gsrc (static ghost feats)
        rs((rounds, cohort), jnp.int32),           # sel_stack
        rs((rounds, cohort), jnp.int32),           # fan_stack
        rs((rounds, cohort), jnp.float32),         # w_stack
        rs((rounds,), jnp.int32),                  # eoffs
        rs((), jnp.int32),                         # tau
        rs((rounds,), jnp.bool_),                  # sync gates
        rs((rounds, cohort), jnp.int32),           # wb_dst
        rs((rounds, cohort), jnp.int32),           # wb_pos
        rs((rounds, P_, P_, wb_cap), jnp.int32),   # wb_recv
    )
