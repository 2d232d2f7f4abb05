"""Multi-pod dry-run of the PAPER'S OWN workload: one FedAIS round chunk
(Algorithm 1) with the client cohort sharded across the production mesh.

This is now a thin caller of the engine's own sharded executor: it lowers
``repro.sharding.fed.build_sharded_chunk`` — the exact scanned
``round_step`` ``FedEngine`` runs when given a mesh — over abstract
client-sharded arguments, so the dry-run and real training share one
code path. The vmapped client axis shard_maps over a ``("clients",)``
mesh axis: the cross-client ghost pull reads the replicated historical
tables, FedAvg lowers to a weighted all-reduce (psum), and the
historical/ghost write-back all-gathers the cohort's fresh embeddings —
exactly the embedding-synchronization network phase of the real
deployment. This is the FedGCN-scale companion to launch/dryrun.py's LM
cases.

    PYTHONPATH=src python -m repro.launch.fed_dryrun --mesh pod1
    PYTHONPATH=src python -m repro.launch.fed_dryrun --mesh pod1 --pods 16

``--pods P`` lowers the pod-table mode instead (repro.sharding.tables): a
``("pods", "clients")`` 2-D mesh where EVERY K-sized array — historical
tables AND static client arrays — stays resident as pod shards, the ghost
exchange is a tau-gated bucketed all-to-all, and the write-back a
host-routed cohort-keyed bucket exchange. The report then carries a
``pods`` placement ledger classifying every per-device resident and
per-round collective by what its bytes scale with: ``k_sharded`` (K/P),
``replicated`` (K-independent), ``cohort_scaled`` (m), ``sync_gated``
(ghost cut x the tau schedule's sync fraction; ZERO on non-sync rounds).
``validate_fed_dryrun`` schema-guards the ledger before any write, and
``--assert-k-flat K2`` lowers the chunk at two client counts and fails
unless the replicated/cohort-scaled columns are byte-identical (the CI
smoke proof that nothing scales with K):

    PYTHONPATH=src python -m repro.launch.fed_dryrun --mesh host \\
        --force-devices 8 --pods 8 --clients 100000 --assert-k-flat 10000 \\
        --cohort 64 --n-max 64 --g-max 8 --features 32

``--sync-dtype {fp32,bf16,int8}`` lowers the chunk with the quantized
embedding wire (repro.federated.quant) and prices the ghost all-to-all +
write-back exchanges at that dtype in the ledger's ``quant`` section;
``--assert-quant-bytes`` lowers fp32 AND int8 at fixed K and fails unless
int8 at least halves those wires (analytic ledger and measured HLO) while
every per-device resident stays byte-identical:

    PYTHONPATH=src python -m repro.launch.fed_dryrun --mesh host \\
        --force-devices 8 --pods 8 --clients 1024 --assert-quant-bytes \\
        --cohort 64 --n-max 64 --g-max 8 --features 32

Run as a script this forces fake XLA host devices (512 by default, so
both pod chip counts fit on CPU); importing the module never touches
``XLA_FLAGS`` — pass ``--force-devices N`` (0 disables) or use
``--mesh host`` to run on whatever devices already exist.
"""
import argparse
import json
import os
import time

import jax
import numpy as np

from repro.api.engine import _LIGHT_STATS
from repro.api.registry import method_config
from repro.core.fedais import make_vmapped_update
from repro.federated.partition import ghost_exchange_buckets
from repro.federated.quant import SYNC_DTYPES, wire_bytes
from repro.launch.mesh import production_chip_count
from repro.models.gcn import HIDDEN, gcn_flops_per_node, gcn_param_count
from repro.sharding.fed import (
    abstract_chunk_args,
    build_sharded_chunk,
    client_axis_of,
    cohort_padding,
    make_client_mesh,
)
from repro.sharding.tables import (
    abstract_pod_chunk_args,
    build_pod_sharded_chunk,
    make_pod_mesh,
    sync_round_gates,
)
from repro.utils.hlo import collective_stats
from repro.utils.roofline import RooflineReport

# abstract_pod_chunk_args' padded-adjacency width (the synthetic topology
# has no real adjacency; the ledger's nbr_* rows use the same constant)
DRYRUN_MAX_DEG = 16
# horizon for probing the tau schedule's sync fraction
SYNC_PROBE_ROUNDS = 64

# chip counts come from the production mesh definition (launch/mesh.py)
MESH_CHIPS = {
    "pod1": production_chip_count(multi_pod=False),
    "pod2": production_chip_count(multi_pod=True),
}


def _force_host_devices(n: int) -> None:
    """Fake XLA host devices; only effective before the backend initializes
    (caller flags win for duplicates, preserving any prior forced count)."""
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n} "
        + os.environ.get("XLA_FLAGS", ""))


def synthetic_ghost_buckets(n_clients: int, n_max: int, g_max: int,
                            n_pods: int, *, fill: float = 1.0, seed: int = 0):
    """A partition-shaped ghost topology for lowering the pod chunk without
    real data: each client's ghost slots point at uniform random (owner,
    row) pairs, ``fill`` controlling the occupied fraction (the ghost-cut
    knob the write-back bytes should track)."""
    rng = np.random.default_rng(seed)
    mask = (rng.random((n_clients, g_max)) < fill).astype(np.float32)
    owner = rng.integers(0, n_clients, size=(n_clients, g_max)).astype(np.int32)
    owner = np.where(mask > 0, owner, -1)
    row = rng.integers(0, n_max, size=(n_clients, g_max)).astype(np.int32)
    return ghost_exchange_buckets(owner, row, mask, n_pods)


def pod_placement_ledger(buckets, *, n_pods: int, cohort_pad: int,
                         wb_cap: int, n_max: int, g_max: int, n_feat: int,
                         n_classes: int, tau: int, local_epochs: int,
                         max_deg: int = DRYRUN_MAX_DEG,
                         rounds: int = 1, sync_dtype: str = "fp32") -> dict:
    """The analytic placement ledger for the pod-sharded chunk: every
    per-device resident array and per-round collective payload, in bytes,
    grouped by what it scales with. ``k_sharded`` rows are exactly
    ``rows_per_pod`` (= Kp/P) table rows; ``replicated``/``cohort_scaled``
    entries never mention K; ``sync_gated`` entries only move bytes on
    rounds where the tau schedule syncs (``sync_round_gates``), so their
    effective per-round cost is the nominal payload times the schedule's
    sync fraction — and exactly 0 on non-sync rounds.

    The ``quant`` section prices the three embedding wires the codec
    actually quantizes (``repro.federated.quant``) at ``sync_dtype``: the
    ghost hist1 all-to-all and both write-back stages, where the float
    tables ride as payload+scale and the int32 ``age`` rows stay 4-byte.
    Every other ledger entry is dtype-independent — residents and the
    owner-keyed cohort fetch stay fp32 regardless of the wire format."""
    H1 = HIDDEN[0]
    n_tot = n_max + g_max
    P, B = n_pods, buckets.bucket_size
    rpp = buckets.rows_per_pod
    m, S = cohort_pad, rounds
    n_params = gcn_param_count(n_feat, n_classes)
    # bytes of one client's table + static rows (everything the owner-keyed
    # cohort fetch moves per selected client, and the write-back returns)
    table_row = (n_tot * H1 + n_tot + g_max * n_feat + n_max) * 4
    static_row = (n_max * (n_feat + 3 + 2 * max_deg) + g_max) * 4
    k_sharded = {
        "hist1": rpp * n_tot * H1 * 4,
        "age": rpp * n_tot * 4,
        "ghost_feat": rpp * g_max * n_feat * 4,
        "prev_loss": rpp * n_max * 4,
        "features": rpp * n_max * n_feat * 4,
        "labels": rpp * n_max * 4,
        "node_mask": rpp * n_max * 4,
        "train_mask": rpp * n_max * 4,
        "nbr_idx": rpp * n_max * max_deg * 4,
        "nbr_mask": rpp * n_max * max_deg * 4,
        "ghost_mask": rpp * g_max * 4,
        "ghost_src_feat": rpp * g_max * n_feat * 4,
        "recv_buckets": rpp * g_max * 12,
    }
    replicated = {
        "params": n_params * 4,
        "cohort_stacks": S * (m * 12 + 5),     # sel/fan/w + eoff/gate
        "wb_routing": S * (m * 8 + P * P * wb_cap * 4),
    }
    ghost_cut = {"send_buckets": P * B * 12}
    eoffs = np.arange(SYNC_PROBE_ROUNDS) * local_epochs
    frac = float(sync_round_gates(eoffs, tau, local_epochs).mean())
    a2a = P * B * H1 * 4
    gfetch = m * g_max * (H1 + n_feat) * 4

    # the quantized embedding wires: the ghost all-to-all moves the (P, B,
    # H1) hist1 buffer as codec payload (+ per-row scales at int8); the
    # write-back stages route the three float tables as payload+scale while
    # the int32 age rows always stay 4 bytes per element
    def quant_row(d):
        return (wire_bytes((n_tot, H1), d) + n_tot * 4
                + wire_bytes((g_max, n_feat), d) + wire_bytes((n_max,), d))

    def quant_wires(d):
        return {
            "ghost_all_to_all": wire_bytes((P, B, H1), d),
            "wb_stage1_all_gather": (m // P) * quant_row(d),
            "wb_stage2_all_to_all": P * wb_cap * quant_row(d),
        }

    wire, fp32w = quant_wires(sync_dtype), quant_wires("fp32")
    return {
        "schema_version": 2,
        "n_pods": P,
        "table_shard_rows_per_pod": rpp,
        "ghost_cut_entries": buckets.n_entries,
        "bucket_size": B,
        "wb_cap": int(wb_cap),
        "per_device_resident_bytes": {
            "k_sharded": k_sharded,
            "replicated": replicated,
            "ghost_cut_scaled": ghost_cut,
        },
        "per_round_collective_bytes": {
            "cohort_scaled": {
                "fetch_psum_tables": m * table_row,
                "fetch_psum_statics": m * static_row,
                "merge_allreduce": n_params * 4,
                "wb_stage1_all_gather": (m // P) * table_row,
                "wb_stage2_all_to_all": P * wb_cap * table_row,
            },
            "sync_gated": {
                "ghost_all_to_all": a2a,
                "ghost_fetch_psum": gfetch,
            },
        },
        "sync": {
            "tau": int(tau),
            "local_epochs": int(local_epochs),
            "rounds_probed": SYNC_PROBE_ROUNDS,
            "sync_fraction": frac,
            "ghost_all_to_all_effective_bytes": int(round(a2a * frac)),
            "ghost_fetch_effective_bytes": int(round(gfetch * frac)),
            "non_sync_round_ghost_bytes": 0,
        },
        "quant": {
            "sync_dtype": sync_dtype,
            "wire_collective_bytes": wire,
            "fp32_collective_bytes": fp32w,
            "reduction": {k: round(fp32w[k] / wire[k], 2) for k in wire},
        },
    }


_POD_LEDGER_KEYS = ("schema_version", "n_pods", "table_shard_rows_per_pod",
                    "ghost_cut_entries", "bucket_size", "wb_cap",
                    "per_device_resident_bytes",
                    "per_round_collective_bytes", "sync", "quant",
                    "all_to_all_bytes", "all_gather_bytes")
# the fp32 column of the quant section must restate these nominal entries
_QUANT_NOMINAL = {"ghost_all_to_all": ("sync_gated", "ghost_all_to_all"),
                  "wb_stage1_all_gather": ("cohort_scaled",
                                           "wb_stage1_all_gather"),
                  "wb_stage2_all_to_all": ("cohort_scaled",
                                           "wb_stage2_all_to_all")}
_TOP_KEYS = ("status", "arch", "mesh", "chips", "clients", "cohort",
             "collectives", "roofline")


def validate_fed_dryrun(result: dict) -> list[str]:
    """Schema-check a fed_dryrun result row before it is written (the
    ``validate_bench_round`` pattern). Returns a list of problems (empty =
    valid): required keys present and typed, every ledger class a dict of
    non-negative ints, the sync fraction in [0, 1], the non-sync-round
    ghost bytes pinned to 0 (the gated-exchange contract), and the quant
    section's fp32 column restating the nominal collective entries (with
    the wire column never exceeding it, and equal to it at fp32)."""
    errs: list[str] = []
    if not isinstance(result, dict):
        return [f"result is {type(result).__name__}, expected dict"]
    for k in _TOP_KEYS:
        if k not in result:
            errs.append(f"missing key {k!r}")
    if errs:
        return errs
    if not isinstance(result["collectives"], dict):
        errs.append("collectives must be a dict of byte counts")
    if "pods" not in result:
        return errs
    pods = result["pods"]
    if not isinstance(pods, dict):
        return errs + ["pods must be a dict"]
    for k in _POD_LEDGER_KEYS:
        if k not in pods:
            errs.append(f"pods missing key {k!r}")
    if errs:
        return errs
    for section in ("per_device_resident_bytes",
                    "per_round_collective_bytes"):
        for cls, entries in pods[section].items():
            if not isinstance(entries, dict) or not entries:
                errs.append(f"pods.{section}.{cls} must be a non-empty dict")
                continue
            for name, v in entries.items():
                if not isinstance(v, int) or v < 0:
                    errs.append(f"pods.{section}.{cls}.{name} must be a "
                                f"non-negative int, got {v!r}")
    sync = pods["sync"]
    frac = sync.get("sync_fraction")
    if not isinstance(frac, (int, float)) or not 0.0 <= frac <= 1.0:
        errs.append(f"pods.sync.sync_fraction must be in [0, 1], got {frac!r}")
    if sync.get("non_sync_round_ghost_bytes") != 0:
        errs.append("pods.sync.non_sync_round_ghost_bytes must be 0 "
                    "(the ghost exchange is gated off entirely)")
    a2a = sync.get("ghost_all_to_all_effective_bytes")
    nominal = pods["per_round_collective_bytes"]["sync_gated"].get(
        "ghost_all_to_all", 0)
    if not isinstance(a2a, int) or a2a != int(round(nominal * frac)):
        errs.append("pods.sync.ghost_all_to_all_effective_bytes must equal "
                    "ghost_all_to_all x sync_fraction")
    quant = pods["quant"]
    dtype = quant.get("sync_dtype")
    if dtype not in SYNC_DTYPES:
        errs.append(f"pods.quant.sync_dtype must be one of {SYNC_DTYPES}, "
                    f"got {dtype!r}")
    wire = quant.get("wire_collective_bytes", {})
    fp32w = quant.get("fp32_collective_bytes", {})
    for name, (cls, nom_key) in _QUANT_NOMINAL.items():
        w, f = wire.get(name), fp32w.get(name)
        if not isinstance(w, int) or w <= 0:
            errs.append(f"pods.quant.wire_collective_bytes.{name} must be a "
                        f"positive int, got {w!r}")
            continue
        nom = pods["per_round_collective_bytes"][cls].get(nom_key)
        if f != nom:
            errs.append(f"pods.quant.fp32_collective_bytes.{name} must "
                        f"restate {cls}.{nom_key} ({nom}), got {f!r}")
        if w > f:
            errs.append(f"pods.quant.wire_collective_bytes.{name} ({w}) "
                        f"exceeds its fp32 nominal ({f})")
        if dtype == "fp32" and w != f:
            errs.append(f"pods.quant.{name}: fp32 wire must be bit-inert "
                        f"({w} != {f})")
    return errs


def assert_k_flat(res_a: dict, res_b: dict) -> list[str]:
    """The K-flatness contract between two dry-runs that differ ONLY in
    ``--clients``: every replicated resident and every cohort-scaled
    collective must be byte-identical, the k_sharded residents must scale
    exactly with rows_per_pod (= Kp/P), and the HLO's all-gather /
    all-reduce byte totals (write-back stage 1 + cohort fetch psums + merge
    — the only members of those kinds) must not move. Returns a list of
    violations (empty = the placement is K-flat)."""
    errs: list[str] = []
    pa, pb = res_a["pods"], res_b["pods"]
    ka, kb = res_a["clients"], res_b["clients"]
    for section, cls in (("per_device_resident_bytes", "replicated"),
                         ("per_round_collective_bytes", "cohort_scaled")):
        ea, eb = pa[section][cls], pb[section][cls]
        for name in sorted(set(ea) | set(eb)):
            if ea.get(name) != eb.get(name):
                errs.append(
                    f"{cls}.{name}: {ea.get(name)}B at K={ka} vs "
                    f"{eb.get(name)}B at K={kb} — scales with K")
    gf_a = pa["per_round_collective_bytes"]["sync_gated"]["ghost_fetch_psum"]
    gf_b = pb["per_round_collective_bytes"]["sync_gated"]["ghost_fetch_psum"]
    if gf_a != gf_b:
        errs.append(f"sync_gated.ghost_fetch_psum: {gf_a}B vs {gf_b}B — "
                    "scales with K")
    ra, rb = pa["table_shard_rows_per_pod"], pb["table_shard_rows_per_pod"]
    for name, va in pa["per_device_resident_bytes"]["k_sharded"].items():
        vb = pb["per_device_resident_bytes"]["k_sharded"].get(name, -1)
        if va * rb != vb * ra:
            errs.append(f"k_sharded.{name}: {va}B/{ra} rows vs {vb}B/{rb} "
                        "rows — not linear in K/P")
    for kind in ("all-gather", "all-reduce"):
        ba = res_a["collectives"].get(kind, 0)
        bb = res_b["collectives"].get(kind, 0)
        if ba != bb:
            errs.append(f"HLO {kind}: {ba}B at K={ka} vs {bb}B at K={kb} — "
                        "a lowered collective scales with K")
    return errs


def assert_quant_bytes(res_fp32: dict, res_int8: dict) -> list[str]:
    """The quantized-wire contract between two dry-runs that differ ONLY
    in ``--sync-dtype`` (fp32 vs int8): every quantized embedding wire —
    the ghost all-to-all and both write-back stages — must cost at most
    half its fp32 bytes (analytically, per the ledger's quant section, AND
    as measured off the lowered HLO's all-to-all / all-gather totals),
    while the per-device resident ledger stays byte-identical (tables are
    stored fp32; only the wire narrows). Returns violations (empty =
    int8 halves the embedding sync)."""
    errs: list[str] = []
    pa, pb = res_fp32["pods"], res_int8["pods"]
    qa = pa["quant"]["wire_collective_bytes"]
    qb = pb["quant"]["wire_collective_bytes"]
    for name in sorted(qa):
        if qb[name] * 2 > qa[name]:
            errs.append(f"quant.{name}: int8 wire {qb[name]}B is not <= "
                        f"half of fp32 {qa[name]}B")
    for kind in ("all-to-all", "all-gather"):
        ba = res_fp32["collectives"].get(kind, 0)
        bb = res_int8["collectives"].get(kind, 0)
        if bb * 2 > ba:
            errs.append(f"HLO {kind}: int8 lowers to {bb}B, not <= half of "
                        f"fp32's {ba}B — the wire is not quantized")
    if pa["per_device_resident_bytes"] != pb["per_device_resident_bytes"]:
        errs.append("per_device_resident_bytes differ between fp32 and int8 "
                    "— residents must stay fp32 regardless of wire dtype")
    return errs


def dryrun_mesh(mesh_name: str, args) -> dict:
    """Lower one sharded round chunk on ``mesh_name``'s chip count and
    report collectives + roofline. With ``--pods P`` the mesh is the 2-D
    ``("pods", "clients")`` grid and the historical tables shard over the
    pod axis (repro.sharding.tables) — the collectives then include the
    ghost-bucket all-to-all and a cohort-sized (K-independent) write-back
    all-gather instead of replicated-table traffic. Returns the result row
    (status key "ok"/"error")."""
    chips = MESH_CHIPS.get(mesh_name, len(jax.devices()))
    K = args.clients or chips
    m = args.cohort or K
    pods = args.pods
    mcfg = method_config("fedais", local_epochs=4, batch_cap=args.n_max)
    buckets = None
    pad = cohort_padding(m, chips)
    sync_dtype = getattr(args, "sync_dtype", "fp32")
    if pods:
        if chips % pods:
            raise ValueError(f"{chips} chips do not split into {pods} pods")
        mesh = make_pod_mesh(pods, chips // pods)
        buckets = synthetic_ghost_buckets(K, args.n_max, args.g_max, pods,
                                          fill=args.ghost_fill)
        vm = make_vmapped_update(mcfg, args.n_max, args.g_max, HIDDEN[0],
                                 ghost_source="prefetched",
                                 sync_dtype=sync_dtype,
                                 width=mcfg.neighbor_fanout)
        chunk = build_pod_sharded_chunk(vm, mesh, m, buckets, _LIGHT_STATS,
                                        sync_dtype=sync_dtype)
        sargs = abstract_pod_chunk_args(
            mesh, buckets, n_clients=K, cohort=m + pad, n_max=args.n_max,
            g_max=args.g_max, n_feat=args.features, n_classes=args.classes,
            max_deg=DRYRUN_MAX_DEG)
    else:
        mesh = make_client_mesh(chips)
        axis = client_axis_of(mesh)
        vm = make_vmapped_update(mcfg, args.n_max, args.g_max, HIDDEN[0],
                                 sync_dtype=sync_dtype,
                                 width=mcfg.neighbor_fanout)
        chunk = build_sharded_chunk(vm, mesh, axis, m_real=m,
                                    light_stats=_LIGHT_STATS,
                                    sync_dtype=sync_dtype)
        sargs = abstract_chunk_args(
            mesh, n_clients=K, cohort=m + pad, n_max=args.n_max,
            g_max=args.g_max, n_feat=args.features, n_classes=args.classes)

    t0 = time.time()
    compiled = chunk.lower(*sargs).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    mem = compiled.memory_analysis()
    coll = collective_stats(compiled.as_text())

    n_params = gcn_param_count(args.features, args.classes)
    # per-round model flops: J epochs x batch fwd+bwd over the m-cohort
    flops_model = 3.0 * gcn_flops_per_node(args.features, args.classes, 8.0) \
        * args.n_max * mcfg.local_epochs * m
    rep = RooflineReport(
        arch="fedgcn-graphsage", shape=f"K{K}", mesh=mesh_name, chips=chips,
        hlo_flops=float(cost.get("flops", 0.0)) * chips,
        hlo_bytes=float(cost.get("bytes accessed", 0.0)) * chips,
        collective_bytes=float(coll.total_bytes) * chips,
        model_flops=flops_model,
    )
    result = {
        "status": "ok", "arch": "fedgcn-graphsage", "shape": f"K{K}",
        "mesh": mesh_name, "chips": chips, "clients": K, "cohort": m,
        "cohort_pad": pad, "sync_dtype": sync_dtype,
        "gcn_params": n_params,
        "compile_s": round(time.time() - t0, 1),
        "collectives": {k: int(v) for k, v in coll.bytes_by_kind.items()},
        "roofline": rep.row(),
        "memory": {"temp_bytes": getattr(mem, "temp_size_in_bytes", None)},
    }
    if pods:
        # the placement ledger the pod mode exists for: classify every
        # resident and collective by what its bytes scale with, and read
        # the write-back bucket capacity off the lowered args themselves
        # (sargs[-1] is wb_recv (S, P, P, cap) — cap depends on m only)
        wb_cap = sargs[-1].shape[-1]
        ledger = pod_placement_ledger(
            buckets, n_pods=pods, cohort_pad=m + pad, wb_cap=wb_cap,
            n_max=args.n_max, g_max=args.g_max, n_feat=args.features,
            n_classes=args.classes, tau=args.tau,
            local_epochs=mcfg.local_epochs, sync_dtype=sync_dtype)
        ledger["all_to_all_bytes"] = int(
            coll.bytes_by_kind.get("all-to-all", 0))
        ledger["all_gather_bytes"] = int(
            coll.bytes_by_kind.get("all-gather", 0))
        result["pods"] = ledger
    print(rep.pretty())
    print(f"    [{mesh_name}] K={K}" + (f" pods={pods}" if pods else "")
          + f" compile={result['compile_s']}s collectives: {coll.summary()}")
    if pods:
        p = result["pods"]
        resid = p["per_device_resident_bytes"]
        print(f"    [{mesh_name}] K/P={p['table_shard_rows_per_pod']} rows/pod "
              f"({sum(resid['k_sharded'].values()):,}B sharded, "
              f"{sum(resid['replicated'].values()):,}B replicated); "
              f"ghost a2a {p['sync']['ghost_all_to_all_effective_bytes']:,}B "
              f"effective at sync fraction {p['sync']['sync_fraction']:.2f} "
              f"(0B on non-sync rounds)")
        q = p["quant"]
        if q["sync_dtype"] != "fp32":
            cuts = ", ".join(
                f"{name} {q['wire_collective_bytes'][name]:,}B "
                f"({q['reduction'][name]}x)"
                for name in sorted(q["wire_collective_bytes"]))
            print(f"    [{mesh_name}] {q['sync_dtype']} wire: {cuts}")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="pod1",
                    choices=["pod1", "pod2", "both", "host"],
                    help="pod chip counts, or 'host' = all existing devices")
    ap.add_argument("--clients", type=int, default=0, help="default: one per chip")
    ap.add_argument("--cohort", type=int, default=0,
                    help="clients selected per round (default: all K) — fix "
                         "it while sweeping --clients to see which "
                         "collectives scale with the total client count")
    ap.add_argument("--pods", type=int, default=0,
                    help="shard the historical tables over this many pods "
                         "(a ('pods','clients') 2-D mesh; 0 = replicated "
                         "tables, cohort-only sharding)")
    ap.add_argument("--ghost-fill", type=float, default=0.5,
                    help="occupied fraction of ghost slots in the synthetic "
                         "pod topology — the ghost-cut knob the --pods "
                         "write-back bytes should track")
    ap.add_argument("--tau", type=int, default=8,
                    help="staleness threshold for the --pods ledger's sync "
                         "fraction (the tau schedule gates the ghost "
                         "all-to-all; with J=4 local epochs tau=8 syncs "
                         "every other round)")
    ap.add_argument("--assert-k-flat", type=int, default=0, metavar="K2",
                    help="with --pods: lower the chunk a second time at K2 "
                         "clients and fail unless every replicated resident "
                         "and cohort-scaled collective is byte-identical "
                         "(the CI proof that nothing scales with K)")
    ap.add_argument("--sync-dtype", default="fp32", choices=list(SYNC_DTYPES),
                    help="wire format for the embedding sync (repro."
                         "federated.quant): ghost all-to-all + write-back "
                         "exchange payloads; fp32 is bit-inert")
    ap.add_argument("--assert-quant-bytes", action="store_true",
                    help="with --pods: lower the chunk at fp32 AND int8 and "
                         "fail unless int8 at least halves the ghost "
                         "all-to-all + write-back bytes (ledger and lowered "
                         "HLO) with per-device residents byte-identical "
                         "(the CI proof the codec narrows only the wire)")
    ap.add_argument("--n-max", type=int, default=512)
    ap.add_argument("--g-max", type=int, default=256)
    ap.add_argument("--features", type=int, default=128)
    ap.add_argument("--classes", type=int, default=41)   # reddit-like
    ap.add_argument("--force-devices", type=int, default=None,
                    help="force N fake XLA host devices before the backend "
                         "initializes (default: 512 for pod meshes, off for "
                         "--mesh host; 0 disables)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    if args.force_devices is None and args.mesh != "host":
        args.force_devices = max(MESH_CHIPS.values())
    if args.force_devices:
        _force_host_devices(args.force_devices)

    if args.assert_k_flat and not (args.pods and args.clients):
        ap.error("--assert-k-flat needs --pods and an explicit --clients")
    if args.assert_quant_bytes and not args.pods:
        ap.error("--assert-quant-bytes needs --pods")

    meshes = ["pod1", "pod2"] if args.mesh == "both" else [args.mesh]
    rc = 0
    for mesh_name in meshes:
        try:
            result = dryrun_mesh(mesh_name, args)
        except Exception as e:
            print(f"[{mesh_name}] ERROR: {type(e).__name__}: {e}")
            rc = 1
            continue
        problems = validate_fed_dryrun(result)
        if problems:
            print(f"[{mesh_name}] INVALID result, not writing:")
            for p in problems:
                print(f"    - {p}")
            rc = 1
            continue
        if args.assert_k_flat:
            args2 = argparse.Namespace(**{**vars(args),
                                          "clients": args.assert_k_flat})
            try:
                result2 = dryrun_mesh(mesh_name, args2)
            except Exception as e:
                print(f"[{mesh_name}] ERROR at K={args.assert_k_flat}: "
                      f"{type(e).__name__}: {e}")
                rc = 1
                continue
            violations = assert_k_flat(result, result2)
            if violations:
                print(f"[{mesh_name}] K-FLATNESS VIOLATED "
                      f"(K={args.clients} vs K={args.assert_k_flat}):")
                for v in violations:
                    print(f"    - {v}")
                rc = 1
                continue
            print(f"    [{mesh_name}] K-flat: replicated residents, "
                  f"cohort-scaled collectives, and lowered all-gather/"
                  f"all-reduce bytes identical at K={args.clients} and "
                  f"K={args.assert_k_flat}; k_sharded exactly linear in K/P")
        if args.assert_quant_bytes:
            variants = {args.sync_dtype: result}
            try:
                for d in ("fp32", "int8"):
                    if d not in variants:
                        args_d = argparse.Namespace(**{**vars(args),
                                                       "sync_dtype": d})
                        variants[d] = dryrun_mesh(mesh_name, args_d)
            except Exception as e:
                print(f"[{mesh_name}] ERROR lowering quant variant: "
                      f"{type(e).__name__}: {e}")
                rc = 1
                continue
            violations = assert_quant_bytes(variants["fp32"],
                                            variants["int8"])
            if violations:
                print(f"[{mesh_name}] QUANT-BYTES CONTRACT VIOLATED "
                      f"(fp32 vs int8):")
                for v in violations:
                    print(f"    - {v}")
                rc = 1
                continue
            c32, c8 = (variants[d]["collectives"] for d in ("fp32", "int8"))
            print(f"    [{mesh_name}] quant-bytes: int8 cuts the lowered "
                  f"all-to-all {c32.get('all-to-all', 0):,}B -> "
                  f"{c8.get('all-to-all', 0):,}B and all-gather "
                  f"{c32.get('all-gather', 0):,}B -> "
                  f"{c8.get('all-gather', 0):,}B (>= 2x each); per-device "
                  f"residents byte-identical")
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            tag = f"_pods{args.pods}" if args.pods else ""
            with open(os.path.join(args.out, f"fedgcn_{mesh_name}{tag}.json"),
                      "w") as f:
                json.dump(result, f, indent=1)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
