"""Frozen copy of the pre-refactor ``run_federated`` monolith (the seed's
src/repro/federated/simulator.py round loop), kept ONLY as the parity oracle
for tests/test_api.py: the composable FedEngine must reproduce this loop's
per-round history bit-for-bit. Do not "improve" this file — its value is
that it never changes."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.engine import RunResult
from repro.core.fedais import (
    MethodConfig,
    batch_size_for,
    fanout_width,
    make_local_update,
)
from repro.core.historical import init_historical
from repro.federated import baselines as B
from repro.federated.costs import CostMeter, DelayModel, embed_sync_bytes, model_bytes
from repro.federated.partition import FederatedGraph
from repro.federated.server import (
    build_eval_graph,
    evaluate_global,
    fedavg,
    select_clients,
    update_tau,
)
from repro.graph.data import GraphData
from repro.models.gcn import HIDDEN, gcn_flops_per_node, gcn_init, gcn_param_count


def _client_slice(fed: FederatedGraph, arrays: dict, ids: np.ndarray) -> dict:
    return {k: v[ids] for k, v in arrays.items()}


def legacy_run_federated(
    graph: GraphData,
    fed: FederatedGraph,
    mcfg: MethodConfig,
    *,
    rounds: int = 30,
    clients_per_round: int = 10,
    seed: int = 0,
    target_acc: float | None = None,
    delay: DelayModel = DelayModel(),
    eval_every: int = 1,
    verbose: bool = False,
) -> RunResult:
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    K, n_max, g_max = fed.n_clients, fed.n_max, fed.g_max
    F, H1 = fed.n_features, HIDDEN[0]

    # ---- device-resident stacked client arrays ----
    arrays = {
        "features": jnp.asarray(fed.features),
        "labels": jnp.asarray(fed.labels),
        "node_mask": jnp.asarray(fed.node_mask),
        "train_mask": jnp.asarray(fed.train_mask),
        "nbr_idx": jnp.asarray(fed.nbr_idx),
        "nbr_mask": jnp.asarray(fed.nbr_mask),
        "ghost_owner": jnp.asarray(fed.ghost_owner),
        "ghost_row": jnp.asarray(fed.ghost_row),
        "ghost_mask": jnp.asarray(fed.ghost_mask),
    }

    params = gcn_init(jax.random.PRNGKey(seed + 1), F, fed.n_classes)
    n_params = gcn_param_count(F, fed.n_classes)
    hist = init_historical(K, n_max, g_max, F, H1)
    ghost_feat = jnp.zeros((K, g_max, F), jnp.float32)
    prev_loss = jnp.full((K, n_max), -1.0, jnp.float32)

    # the engine's neighbor-list width: the largest fanout the method passes
    most = max(B.FANOUT_ACTIONS) if mcfg.bandit_fanout else mcfg.neighbor_fanout
    local_update = make_local_update(mcfg, n_max, g_max, H1,
                                     width=fanout_width(most, fed.max_deg))
    vm = jax.jit(jax.vmap(local_update,
                          in_axes=(None, 0, None, None, 0, 0, 0, 0, None, 0, None, 0)))

    eval_graph = build_eval_graph(graph, max_deg=fed.max_deg, seed=seed)
    result = RunResult(method=mcfg.name, dataset=graph.name)

    # FedSage+ generator / FedGraph bandit state
    gen_params = None
    rev = rev_mask = None
    if mcfg.use_generator:
        gen_params = B.generator_init(jax.random.PRNGKey(seed + 2), F)
        rev_np, rev_mask_np = B.ghost_reverse_map(fed)
        rev, rev_mask = jnp.asarray(rev_np), jnp.asarray(rev_mask_np)
    bandit = B.FanoutBandit(K, seed=seed) if mcfg.bandit_fanout else None
    last_client_loss = np.zeros(K)

    avg_deg = float(fed.nbr_mask.sum() / np.maximum(fed.node_mask.sum(), 1))
    fwd_flops_node = gcn_flops_per_node(F, fed.n_classes, avg_deg)
    bsz = batch_size_for(mcfg, n_max)
    tau = mcfg.tau0
    initial_loss = None

    for t in range(rounds):
        sel = select_clients(rng, K, clients_per_round)
        sel_j = jnp.asarray(sel)
        key, *ks = jax.random.split(key, len(sel) + 1)
        keys = jnp.stack(ks)

        # fanout per client (bandit or fixed)
        if bandit is not None:
            fanouts = jnp.asarray([bandit.choose(int(k)) for k in sel], jnp.int32)
        else:
            fanouts = jnp.full((len(sel),), mcfg.neighbor_fanout, jnp.int32)

        # FedSage+ : impute ghost features + local ghost h1, train generator
        hist1_all, age_all = hist.hist1, hist.age
        if mcfg.use_generator:
            gen_params, gen_loss = B.generator_train_step(
                gen_params,
                arrays["features"].reshape(K * n_max, F),
                jnp.minimum(arrays["nbr_idx"].reshape(K * n_max, -1), n_max * K - 1),
                arrays["nbr_mask"].reshape(K * n_max, -1)
                * (arrays["nbr_idx"].reshape(K * n_max, -1) < n_max),
                arrays["node_mask"].reshape(K * n_max),
            )
            imputed = jax.vmap(B.generator_impute, in_axes=(None, 0, 0, 0, 0))(
                gen_params, arrays["features"], rev, rev_mask, arrays["ghost_mask"])
            ghost_feat = imputed

        client_data = _client_slice(fed, arrays, sel)
        out = vm(
            params, client_data, arrays["features"], hist1_all,
            hist.hist1[sel_j], hist.age[sel_j], ghost_feat[sel_j],
            prev_loss[sel_j], jnp.asarray(tau, jnp.int32), fanouts,
            jnp.asarray(t * mcfg.local_epochs, jnp.int32), keys,
        )
        new_params_stack, new_hist1, new_age, new_ghost_feat, stats = out

        # ---- merge: FedAvg + historical write-back ----
        params = fedavg(new_params_stack)
        hist = hist._replace(
            hist1=hist.hist1.at[sel_j].set(new_hist1),
            age=hist.age.at[sel_j].set(new_age),
        )
        ghost_feat = ghost_feat.at[sel_j].set(new_ghost_feat)
        prev_loss = prev_loss.at[sel_j].set(stats["loss_all"])

        # ---- cost accounting ----
        round_cost = CostMeter()
        n_sync = np.asarray(stats["n_sync"])
        n_pulled = np.asarray(stats["n_ghost_pulled"])
        sizes = fed.client_sizes[sel]
        gen_bytes = model_bytes(B.generator_param_count(F)) if mcfg.use_generator else 0.0
        per_client_compute = []
        for i, k in enumerate(sel):
            comm_model = 2 * model_bytes(n_params) + 2 * gen_bytes
            comm_embed = embed_sync_bytes(n_pulled[i], (F, H1))
            nodes_processed = sizes[i] + mcfg.local_epochs * min(bsz, max(int(sizes[i]), 1))
            flops = 3.0 * fwd_flops_node * nodes_processed          # fwd+bwd ≈ 3x fwd
            if mcfg.use_generator:
                flops += 6.0 * F * 64 * sizes[i]
            round_cost.comm_model_bytes += comm_model
            round_cost.comm_embed_bytes += comm_embed
            round_cost.compute_flops += flops
            per_client_compute.append(delay.compute_time(flops))
        o = delay.comm_time(
            round_cost.comm_embed_bytes / max(len(sel), 1) + 2 * model_bytes(n_params))
        round_cost.wall_clock_s = max(per_client_compute) + o / max(tau, 1)
        round_cost.sync_events = int(n_sync.sum())
        result.costs.add(round_cost)

        # ---- bandit reward ----
        if bandit is not None:
            mean_losses = np.asarray(stats["epoch_losses"]).mean(axis=1)
            for i, k in enumerate(sel):
                reward = last_client_loss[k] - float(mean_losses[i]) if last_client_loss[k] else 0.0
                bandit.update(int(k), reward)
                last_client_loss[k] = float(mean_losses[i])

        # ---- server eval + adaptive tau (Eq. 11) ----
        if t % eval_every == 0 or t == rounds - 1:
            ev = evaluate_global(params, eval_graph, "test")
            if initial_loss is None:
                initial_loss = max(ev["loss"], 1e-6)
            tau = update_tau(mcfg, ev["loss"], initial_loss, mcfg.tau0)
            result.record(
                round=t, test_acc=ev["acc"], test_loss=ev["loss"], f1=ev["f1"],
                auc=ev["auc"], tau=tau,
                comm_total=result.costs.comm_total_bytes,
                comm_embed=result.costs.comm_embed_bytes,
                flops=result.costs.compute_flops,
                wall_clock=result.costs.wall_clock_s,
            )
            if verbose:
                print(f"[{mcfg.name}] round {t:3d} acc={ev['acc']:.4f} "
                      f"loss={ev['loss']:.4f} tau={tau} "
                      f"comm={result.costs.comm_total_bytes/1e6:.1f}MB")
            if target_acc is not None and ev["acc"] >= target_acc:
                break

    final_eval = evaluate_global(params, eval_graph, "test")
    result.final = dict(final_eval, **result.costs.snapshot())
    return result
