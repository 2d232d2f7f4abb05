"""FedEngine: the composable federated training engine (Algorithm 1).

The engine owns only the method-agnostic spine of a round:

    select clients -> strategy hooks -> vmapped LocalUpdate -> aggregate
    -> historical write-back -> cost accounting -> callbacks

Everything method- or policy-specific is a pluggable component (see
repro.api.protocols / strategies / callbacks / registry). The per-client
LocalUpdate is jit-compiled once per MethodConfig and vmapped over the m
selected clients; the cross-client ghost pull inside lowers to a gather
over the stacked client axis (on a TPU mesh this is the all-to-all of the
real deployment — see launch/fed_dryrun.py).

Two executors share that compiled client step:

* the **stepwise** path (``run_round`` = ``dispatch`` + ``merge``): one
  XLA call per round plus eager host-side aggregation/write-back. The
  AsyncScheduler's per-event loop always uses it.
* the **fused** path (``run_fused``): the whole round — vmapped
  LocalUpdate, aggregation, historical/ghost/prev_loss write-back — is one
  traced ``round_step``, ``lax.scan``-ned across every round between eval
  boundaries and jitted with ``donate_argnums`` on the big mutable buffers
  (params, hist1, age, ghost_feat, prev_loss, PRNG key), so the (K, n_tot,
  H1) tables update in place instead of being copied every round. Light
  per-round stats stream out as stacked scan outputs and the host tail
  (cost accounting, strategy.post_round, callbacks) replays them at the
  chunk boundary — bit-identical history to the stepwise loop, pinned by
  tests/test_fused.py. ``SyncScheduler`` auto-selects it whenever every
  component declares itself fusable (see ``FedEngine.fused_eligibility``).

When a device ``mesh`` is configured, the fused chunk additionally shards
its vmapped client axis across the mesh's ``("clients",)`` axis
(``repro.sharding.fed.build_sharded_chunk``): each device trains its slice
of the cohort, aggregation lowers to a weighted all-reduce, ragged cohorts
pad with zero-weight dummy clients, and history stays allclose to the
unsharded fused run (see ``FedEngine.sharded_eligibility`` and
tests/test_sharding.py; fp32 all-reduce reassociation forfeits bit-parity).

On a 2-D ``("pods", "clients")`` mesh with ``table_sharding`` allowing it,
EVERY K-sized array shards its K axis over the pod axis
(``repro.sharding.tables.build_pod_sharded_chunk``): each pod owns its
resident clients' hist1/age/ghost_feat/prev_loss rows AND their static
arrays (features/adjacency/labels/masks, cached as pod shards once per
engine together with the bucketed-exchange-built ghost-source feature
table), the cohort's rows are fetched from owner pods per round, the
cross-client ghost pull is a partition-time-bucketed ``all_to_all`` keyed
by ``ghost_owner`` and gated per round on the host-derived tau-sync
predicate (non-sync rounds skip it entirely), and the write-back is a
host-routed cohort-keyed bucket exchange (only touched rows reach their
owner pod) — no per-device resident or per-round collective scales with K
(see ``FedEngine.pod_sharded_eligibility``, the soft fallback chain
pod-sharded -> client-sharded -> fused -> stepwise,
tests/test_pod_sharding.py, and the ``launch/fed_dryrun.py --pods`` byte
ledger). ``merge_reduce="pairwise"`` swaps the merges' psum for a
deterministic fp32 binary-tree over gathered partial sums on BOTH mesh
kinds (1-D client and 2-D pod).

``repro.federated.simulator.run_federated`` is a thin compatibility shim
over ``FedEngine(...).run()`` and is proven history-identical to the legacy
monolith by tests/test_api.py.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.callbacks import (
    EarlyStopCallback,
    EvalCallback,
    HistoryCallback,
    RoundContext,
    VerboseCallback,
    default_callbacks,
)
from repro.api.protocols import (
    AdaptiveSyncController,
    PaperCostModel,
    UniformSelector,
)
from repro.api.registry import (
    build_aggregator,
    build_scheduler,
    build_strategy,
    method_config,
)
from repro.core.fedais import (
    MethodConfig,
    batch_size_for,
    fanout_width,
    make_vmapped_update,
)
from repro.core.historical import init_historical
from repro.faults import (
    FaultCounters,
    FaultPlan,
    UpdateGuard,
    build_faulty_chunk,
    corrupt_params_stack,
    guard_mask,
)
from repro.federated.costs import CostMeter, DelayModel
from repro.federated.partition import (
    FederatedGraph,
    exchange_ghost_features,
    ghost_exchange_buckets,
    writeback_routing,
)
from repro.federated.quant import check_sync_dtype, quant_roundtrip
from repro.federated.server import build_eval_graph, evaluate_global
from repro.graph.data import GraphData
from repro.models.gcn import (
    AGG_BACKENDS,
    HIDDEN,
    gcn_flops_per_node,
    gcn_init,
    gcn_param_count,
)
from repro.sharding.fed import (
    build_sharded_chunk,
    client_axis_of,
    cohort_padding,
    replicate_to_mesh,
)
from repro.sharding.tables import (
    POD_ARRAY_KEYS,
    build_pod_sharded_chunk,
    pad_tables_to_pods,
    pod_axes_of,
    shard_tables_to_mesh,
    sync_round_gates,
)
from repro.utils.spans import span

_CLIENT_ARRAY_KEYS = (
    "features", "labels", "node_mask", "train_mask",
    "nbr_idx", "nbr_mask", "ghost_owner", "ghost_row", "ghost_mask",
    "loss_idx", "loss_mask", "loss_pos",
)

# Per-round stats streamed out of the fused scan (everything except the
# (m, n_max) loss_all table, which stays in the on-device carry as prev_loss).
_LIGHT_STATS = ("epoch_losses", "n_sync", "n_ghost_pulled", "n_kept_slots")

# Default-stack callbacks proven side-effect-free on non-eval rounds (they
# only act when EvalCallback set ctx.metrics, i.e. at chunk boundaries) —
# the exact types, not subclasses: an override could observe mid-chunk state
# the fused executor no longer materializes per round.
_FUSED_SAFE_CALLBACKS = (EvalCallback, HistoryCallback, VerboseCallback,
                         EarlyStopCallback)


@dataclass
class RunResult:
    method: str
    dataset: str
    history: dict = field(default_factory=dict)     # per-round lists
    final: dict = field(default_factory=dict)
    costs: CostMeter = field(default_factory=CostMeter)

    def record(self, **kv):
        for k, v in kv.items():
            self.history.setdefault(k, []).append(v)

    def rounds_to_acc(self, target: float) -> int | None:
        for i, a in enumerate(self.history.get("test_acc", [])):
            if a >= target:
                return i + 1
        return None

    def comm_to_acc(self, target: float) -> float | None:
        for a, c in zip(self.history.get("test_acc", []), self.history.get("comm_total", [])):
            if a >= target:
                return c
        return None


@dataclass
class EngineState:
    """Everything mutable across rounds; components read/write this."""

    rng: np.random.Generator          # host RNG (client selection, ...)
    key: jnp.ndarray                  # device PRNG chain
    params: Any                       # global model pytree
    hist: Any                         # HistoricalState (hist1/age tables)
    ghost_feat: jnp.ndarray           # (K, g_max, F) synced/imputed ghosts
    prev_loss: jnp.ndarray            # (K, n_max) last-seen per-node loss
    arrays: dict                      # device-resident stacked client arrays
    result: RunResult
    tau: int = 1                      # current sync interval
    initial_loss: Optional[float] = None
    round: int = 0
    last_eval: Optional[tuple] = None  # (round, metrics) from EvalCallback
    # per-update staleness of the merge being post-processed (None on the
    # sync paths, where merge order == dispatch order by construction);
    # strategies read it to attribute async rewards to dispatch versions
    last_staleness: Optional[np.ndarray] = None
    # what the engine/scheduler did about faults (dropped uploads,
    # quarantined updates, async timeouts/retries/evictions, ...)
    fault_events: FaultCounters = field(default_factory=FaultCounters)


def _client_slice(arrays: dict, ids: np.ndarray) -> dict:
    return {k: v[ids] for k, v in arrays.items()}


class FedEngine:
    """Composable federated trainer over a partitioned graph.

    ``method`` is a registered method name (see repro.api.registry) or an
    explicit MethodConfig. Any pluggable component can be overridden via
    keyword; the defaults reproduce the paper's Algorithm 1 exactly.
    """

    @span("fed/engine-build")
    def __init__(
        self,
        graph: GraphData,
        fed: FederatedGraph,
        method: Union[str, MethodConfig],
        *,
        rounds: int = 30,
        clients_per_round: int = 10,
        seed: int = 0,
        target_acc: float | None = None,
        delay: DelayModel = DelayModel(),
        eval_every: int = 1,
        verbose: bool = False,
        selector=None,
        aggregator=None,
        sync=None,
        cost_model=None,
        strategy=None,
        scheduler=None,
        callbacks: Optional[Sequence] = None,
        eval_backend: str = "gather",
        train_backend: str = "gather",
        mesh=None,
        client_sharding: str = "auto",
        table_sharding: str = "auto",
        merge_reduce: str = "psum",
        sync_dtype: str = "fp32",
        faults: Optional[FaultPlan] = None,
        guard: Union[UpdateGuard, bool, None] = True,
    ):
        self.graph, self.fed = graph, fed
        self.mcfg = method_config(method) if isinstance(method, str) else method
        self.rounds = rounds
        self.clients_per_round = clients_per_round
        self.seed = seed

        # ---- pluggable components ----
        self.strategy = strategy if strategy is not None else build_strategy(self.mcfg)
        self.selector = selector if selector is not None else UniformSelector()
        if aggregator is None:
            aggregator = build_aggregator(self.mcfg.aggregator)
        elif isinstance(aggregator, str):   # registry key, e.g. "weighted"
            aggregator = build_aggregator(aggregator)
        self.aggregator = aggregator
        self.sync = sync if sync is not None else AdaptiveSyncController()
        if cost_model is None:
            cost_model = PaperCostModel(delay)
        elif delay != DelayModel():
            # same fail-fast contract as the callbacks/knobs conflict below
            raise ValueError("`delay` only configures the default "
                             "PaperCostModel; give your explicit cost_model "
                             "its own delay instead")
        self.cost_model = cost_model
        if scheduler is None:
            scheduler = self.mcfg.scheduler     # registry key, "sync" default
        if isinstance(scheduler, str):
            scheduler = build_scheduler(scheduler)
        self.scheduler = scheduler
        if callbacks is None:
            self.callbacks = default_callbacks(eval_every=eval_every, verbose=verbose,
                                               target_acc=target_acc)
        else:
            # an explicit callback stack replaces the default one wholesale;
            # the convenience knobs only parameterize the default stack
            if eval_every != 1 or verbose or target_acc is not None:
                raise ValueError(
                    "eval_every/verbose/target_acc only configure the default "
                    "callback stack; with an explicit `callbacks` list, drop "
                    "them and add EvalCallback/VerboseCallback/"
                    "EarlyStopCallback to your list instead")
            self.callbacks = list(callbacks)

        # ---- client-axis sharding (the fused executor's scale-out knob) ----
        if client_sharding not in ("auto", "divisible", "off"):
            raise ValueError(
                f"unknown client_sharding {client_sharding!r}; known: "
                "auto (pad ragged cohorts) | divisible (shard only when the "
                "cohort splits evenly) | off")
        if table_sharding not in ("auto", "pods", "replicated"):
            raise ValueError(
                f"unknown table_sharding {table_sharding!r}; known: "
                "auto (pod-shard when the mesh has a 'pods' axis) | pods | "
                "replicated")
        if merge_reduce not in ("psum", "pairwise"):
            raise ValueError(
                f"unknown merge_reduce {merge_reduce!r}; known: psum "
                "(weighted all-reduce) | pairwise (fp32 fixed-tree over "
                "gathered partials)")
        # wire format of every historical-embedding exchange (ghost pull,
        # write-back, pod collectives) — repro.federated.quant. "fp32" is
        # bit-inert; bf16/int8 quantize the wire, accumulators stay fp32.
        self.sync_dtype = check_sync_dtype(sync_dtype)
        # batch neighbor aggregation inside every executor's LocalUpdate
        # (models.gcn.gcn_batch_forward backend=...): "gather" is the
        # bit-parity default; "segment" runs the bucketed in-trace CSR and
        # never materializes the (b, K, d) gather; "spmm" the Pallas kernel
        if train_backend not in AGG_BACKENDS:
            raise ValueError(f"unknown train_backend {train_backend!r}; "
                             f"known: {AGG_BACKENDS}")
        self.train_backend = train_backend
        self.mesh = mesh
        self.client_sharding = client_sharding
        self.table_sharding = table_sharding
        self.merge_reduce = merge_reduce
        self.client_axis = None
        self.pod_axes = None
        if mesh is not None:
            self.pod_axes = pod_axes_of(mesh)
            self.client_axis = client_axis_of(mesh)
            if self.client_axis is None and self.pod_axes is None:
                raise ValueError(
                    "client sharding needs a mesh with a 'clients' axis (or "
                    f"a single axis); got axes {tuple(mesh.shape)}")
        if table_sharding == "pods" and self.pod_axes is None:
            raise ValueError(
                "table_sharding='pods' needs a mesh with ('pods', 'clients') "
                f"axes; got {None if mesh is None else tuple(mesh.shape)}")
        # "stepwise"|"fused"|"fused_faulty"|"sharded_fused"|"pod_sharded"
        self.last_executor: Optional[str] = None

        # ---- fault injection + merge guard (repro.faults) ----
        # `faults` is a seeded FaultPlan; an empty plan (or None) is inert
        # by contract — every fault branch below gates on the plan actually
        # firing, so empty-plan runs stay bit-identical to pre-fault code.
        # `guard` is the merge-side finite/norm admission rule: True (the
        # default) checks finiteness only, an UpdateGuard instance adds a
        # delta-norm ceiling, False/None disables guarding entirely (and
        # lets a poisoned update NaN the merge — explicit opt-out).
        if faults is not None and not isinstance(faults, FaultPlan):
            raise ValueError(f"faults must be a FaultPlan or None, got "
                             f"{type(faults).__name__}")
        self.faults = faults
        self._faults_active = faults is not None and not faults.empty
        if guard is True:
            self._guard: Optional[UpdateGuard] = UpdateGuard()
        elif guard is False or guard is None:
            self._guard = None
        elif isinstance(guard, UpdateGuard):
            self._guard = guard
        else:
            raise ValueError("guard must be an UpdateGuard, True (finite "
                             f"check only) or False/None, got {guard!r}")
        self._faulty_chunk = None           # built lazily under a live plan

        # ---- static geometry + compiled LocalUpdate ----
        self.F, self.H1 = fed.n_features, HIDDEN[0]
        self.n_params = gcn_param_count(self.F, fed.n_classes)
        avg_deg = float(fed.nbr_mask.sum() / np.maximum(fed.node_mask.sum(), 1))
        self.fwd_flops_node = gcn_flops_per_node(self.F, fed.n_classes, avg_deg)
        self.bsz = batch_size_for(self.mcfg, fed.n_max)
        # W: the neighbor slots a batch row's aggregation reads per epoch
        self.fanout_width = fanout_width(self.strategy.max_fanout(self),
                                         fed.max_deg)
        # the raw vmapped step is shared by every executor: the stepwise path
        # jits it standalone, the fused path traces it inside the scanned
        # round_step, the sharded path shard_maps it (same computation, one
        # compilation each)
        self._vm_raw = make_vmapped_update(self.mcfg, fed.n_max, fed.g_max,
                                           self.H1, sync_dtype=self.sync_dtype,
                                           train_backend=self.train_backend,
                                           loss_buckets=fed.loss_buckets,
                                           width=self.fanout_width)
        self._vm = jax.jit(self._vm_raw)
        self._fused_chunk = None            # built lazily by run_fused
        self._sharded_chunk = None          # built lazily when mesh is set
        self._sharded_chunk_m = None        # cohort size it was traced for
        self._pod_chunk = None              # built lazily in pod-table mode
        self._pod_chunk_m = None
        self._ghost_buckets = None          # partition-time all-to-all plan
        self._pod_static = None             # pod-sharded static arrays + gsrc
        self._sizes_f32 = jnp.asarray(fed.client_sizes, jnp.float32)
        self.eval_graph = build_eval_graph(graph, max_deg=fed.max_deg, seed=seed,
                                           backend=eval_backend)

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def init_state(self) -> EngineState:
        fed, seed = self.fed, self.seed
        K, n_max, g_max, F = fed.n_clients, fed.n_max, fed.g_max, self.F
        arrays = {k: jnp.asarray(getattr(fed, k)) for k in _CLIENT_ARRAY_KEYS}
        state = EngineState(
            rng=np.random.default_rng(seed),
            key=jax.random.PRNGKey(seed),
            params=gcn_init(jax.random.PRNGKey(seed + 1), F, fed.n_classes),
            hist=init_historical(K, n_max, g_max, F, self.H1),
            ghost_feat=jnp.zeros((K, g_max, F), jnp.float32),
            prev_loss=jnp.full((K, n_max), -1.0, jnp.float32),
            arrays=arrays,
            result=RunResult(method=self.mcfg.name, dataset=self.graph.name),
            tau=self.sync.initial(self.mcfg),
        )
        self.strategy.setup(self, state)
        return state

    def dispatch(self, state: EngineState, sel: np.ndarray, t: int):
        """Client half of a round: RNG split, strategy hooks, vmapped
        LocalUpdate for the cohort ``sel`` departing from server version
        ``t`` (the global batch-epoch offset). Returns the stacked outputs
        ``(params, hist1, age, ghost_feat, stats)``."""
        state.round = t
        sel_j = jnp.asarray(sel)
        state.key, *ks = jax.random.split(state.key, len(sel) + 1)
        keys = jnp.stack(ks)

        fanouts = self.strategy.choose_fanouts(self, sel)
        self.strategy.pre_round(self, state, sel)

        client_data = _client_slice(state.arrays, sel)
        return self._vm(
            state.params, client_data, state.arrays["features"], state.hist.hist1,
            state.hist.hist1[sel_j], state.hist.age[sel_j], state.ghost_feat[sel_j],
            state.prev_loss[sel_j], jnp.asarray(state.tau, jnp.int32), fanouts,
            jnp.asarray(t * self.mcfg.local_epochs, jnp.int32), keys,
        )

    def merge(self, state: EngineState, t: int, sel: np.ndarray, out,
              *, staleness: np.ndarray | None = None, aggregator=None,
              wall_clock_s: float | None = None,
              virtual_time: float | None = None) -> bool:
        """Server half of a round ``t``: aggregation, historical write-back,
        cost accounting, strategy/callback hooks. Async schedulers pass the
        per-update ``staleness`` (for discounted weights), a staleness-aware
        ``aggregator``, and the virtual-clock ``wall_clock_s`` actually
        waited (overriding the lockstep max(compute)+sync billing).

        When an ``UpdateGuard`` is configured (the default), every arriving
        update must be finite (and inside the guard's delta-norm ceiling)
        to aggregate or write back its historical rows; failures are
        quarantined — counted in ``state.fault_events.n_quarantined``,
        never averaged in. An all-pass guard takes the original unfiltered
        code path, so guarded healthy runs stay bit-identical to unguarded
        ones. A merge left with no survivor (everyone dropped out or was
        quarantined) is a server no-op round: params and tables carry over
        unchanged. Returns True if a callback requested stop."""
        state.round = t
        new_params_stack, new_hist1, new_age, new_ghost_feat, stats = out

        # cost/post_round observe the FULL pre-guard cohort below: the
        # client work and its upload happened even when the merge refuses
        # the update (identical to the pre-guard path when nothing fires)
        full_sel, full_stats, full_staleness = np.asarray(sel), stats, staleness
        if self._guard is not None and len(full_sel):
            ok = guard_mask(new_params_stack, state.params,
                            self._guard.max_norm)
            if not ok.all():
                state.fault_events.n_quarantined += int((~ok).sum())
                keep = np.flatnonzero(ok)
                sel = full_sel[keep]
                if staleness is not None:
                    staleness = np.asarray(staleness)[keep]
                (new_params_stack, new_hist1, new_age, new_ghost_feat,
                 stats) = jax.tree_util.tree_map(
                    lambda x: x[keep],
                    (new_params_stack, new_hist1, new_age, new_ghost_feat,
                     stats))

        if len(sel) == 0:
            # every update dropped out or was quarantined: server no-op
            state.fault_events.n_empty_merges += 1
        else:
            sel_j = jnp.asarray(sel)
            agg = self.aggregator if aggregator is None else aggregator
            weights = jnp.asarray(self.fed.client_sizes[sel], jnp.float32)
            if staleness is None:
                state.params = agg.aggregate(new_params_stack, weights)
            else:
                state.params = agg.aggregate(new_params_stack, weights,
                                             staleness)

            # Only an async buffer can merge the same client twice
            # (re-selected while its previous update was still in flight):
            # every update aggregates, but the client-state write-back keeps
            # only the freshest entry (``sel`` arrives sorted by dispatch
            # version, so the last occurrence wins). Sync cohorts are
            # sampled without replacement and never duplicated, so they skip
            # the host np.unique + fancy-index round-trip entirely
            # (``staleness is None`` marks the sync path).
            if staleness is not None and len(np.unique(sel)) != len(sel):
                _, last_rev = np.unique(np.asarray(sel)[::-1],
                                        return_index=True)
                w = np.sort(len(sel) - 1 - last_rev)
                sel_j = jnp.asarray(np.asarray(sel)[w])
                new_hist1, new_age = new_hist1[w], new_age[w]
                new_ghost_feat = new_ghost_feat[w]
                loss_all = stats["loss_all"][w]
            else:
                loss_all = stats["loss_all"]
            if self.sync_dtype != "fp32":
                # the write-back is a wire: float rows round-trip through
                # the codec (age stays int32/exact) on every executor
                new_hist1 = quant_roundtrip(new_hist1, self.sync_dtype)
                new_ghost_feat = quant_roundtrip(new_ghost_feat,
                                                 self.sync_dtype)
                loss_all = quant_roundtrip(loss_all, self.sync_dtype)
            state.hist = state.hist._replace(
                hist1=state.hist.hist1.at[sel_j].set(new_hist1),
                age=state.hist.age.at[sel_j].set(new_age),
            )
            state.ghost_feat = state.ghost_feat.at[sel_j].set(new_ghost_feat)
            state.prev_loss = state.prev_loss.at[sel_j].set(loss_all)

        if len(full_sel):
            cost = self.cost_model.round_cost(self, state, full_sel,
                                              full_stats)
        else:
            cost = CostMeter()          # nothing arrived, nothing billed
        if wall_clock_s is not None:
            cost.wall_clock_s = wall_clock_s    # overlapped (virtual-clock) billing
        state.result.costs.add(cost)
        state.last_staleness = full_staleness   # aligned with full_sel
        try:
            if len(full_sel):
                self.strategy.post_round(self, state, full_sel, full_stats)
        finally:
            state.last_staleness = None

        ctx = RoundContext(engine=self, state=state, t=t, rounds=self.rounds,
                           virtual_time=virtual_time, staleness=staleness)
        for cb in self.callbacks:
            cb.on_round_end(ctx)
        return ctx.stop

    def run_round(self, state: EngineState, t: int) -> bool:
        """One lockstep federated round; True if a callback requested stop."""
        self.last_executor = "stepwise"
        state.round = t
        sel = self.selector.select(self, state)
        out = self.dispatch(state, sel, t)
        wall = None
        if self._faults_active:
            sel, out, wall = self._inject_faults(state, t, sel, out)
        return self.merge(state, t, sel, out, wall_clock_s=wall)

    def _inject_faults(self, state: EngineState, t: int, sel, out):
        """Apply the FaultPlan between dispatch and merge (the stepwise
        sync path): corrupt the marked members' uploaded params (the merge
        guard quarantines them), drop lost members' uploads entirely, and
        re-bill the round's wall clock with straggler delay factors (the
        lockstep server waits for every dispatched member, stragglers
        included, but the merge overhead ``o`` is priced from the
        survivors — dropped uploads never reach the server).
        Returns (surviving_sel, filtered_out, wall_override)."""
        plan = self.faults
        sel = np.asarray(sel)
        full_sel, full_stats = sel, out[-1]
        cmask = plan.corruptions(t, sel)
        if cmask.any():
            out = (corrupt_params_stack(out[0], cmask, plan.corrupt_value()),
                   ) + tuple(out[1:])
        drop = plan.drops(t, sel)
        if drop.any():
            state.fault_events.n_dropped += int(drop.sum())
            keep = np.flatnonzero(~drop)
            sel = sel[keep]
            out = jax.tree_util.tree_map(lambda x: x[keep], out)
        wall = None
        if plan.straggler_frac > 0.0:
            times = np.asarray(self.cost_model.client_compute_times(
                self, state, full_sel, full_stats), np.float64)
            times = times * plan.delay_factors(full_sel)
            o = self.cost_model.sync_overhead(self, sel, out[-1])
            wall = float(np.max(times)) + o / max(state.tau, 1)
        return sel, out, wall

    # ------------------------------------------------------------------
    # fused executor (the SyncScheduler hot path)
    # ------------------------------------------------------------------

    def fused_eligibility(self) -> tuple[bool, str]:
        """Can this engine run the fused scanned executor bit-identically?

        Every component must declare itself safe for deferred host
        observation: the selector precomputes a whole chunk's cohorts from
        the host RNG alone, the aggregator traces inside jit, the strategy
        has no per-round host hooks, the cost model prices rounds purely
        from streamed stats, and the callbacks are the exact default-stack
        types (side-effect-free on non-eval rounds). Returns (ok, reason).
        """
        from repro.api.strategies import MethodStrategy

        scls = type(self.strategy)
        fusable = getattr(self.strategy, "fusable", None)
        if fusable is None:
            fusable = (scls.pre_round is MethodStrategy.pre_round
                       and scls.post_round is MethodStrategy.post_round)
        if not fusable:
            return False, f"strategy {scls.__name__} has per-round host hooks"
        if not getattr(self.selector, "precomputable", False):
            return False, (f"selector {type(self.selector).__name__} reads "
                           "per-round state (not precomputable)")
        if not getattr(self.aggregator, "jit_safe", False):
            return False, (f"aggregator {type(self.aggregator).__name__} "
                           "is not jit-traceable (jit_safe)")
        if not getattr(self.cost_model, "fused_safe",
                       isinstance(self.cost_model, PaperCostModel)):
            return False, (f"cost model {type(self.cost_model).__name__} "
                           "not declared fused_safe")
        for cb in self.callbacks:
            if not getattr(cb, "fused_safe",
                           type(cb) in _FUSED_SAFE_CALLBACKS):
                return False, (f"callback {type(cb).__name__} may observe "
                               "per-round state (not fused_safe)")
        if self._faults_active:
            # the fault-aware fused chunk lowers aggregation to a hardcoded
            # masked weighted mean (like the sharded executors); a custom
            # merge rule must take the stepwise path, which supports the
            # full fault plan through dispatch/merge
            why = self._allreduce_unsafe_reason()
            if why:
                return False, ("fault-aware fused chunk needs a mean-family "
                               "merge: " + why)
        return True, ""

    def sharded_eligibility(self, m: int | None = None) -> tuple[bool, str]:
        """Can the fused chunk shard its client axis over ``self.mesh``?

        Refines ``fused_eligibility`` (which must already hold — the
        sharded executor is a variant of the fused one, never of the
        stepwise loop): server aggregation must lower to a weighted
        all-reduce inside the shard-mapped round body (``allreduce_safe``
        mean-family aggregators), and with ``client_sharding="divisible"``
        the cohort ``m`` must split evenly across the mesh axis instead of
        being padded. Ineligible configs fall back to the unsharded fused
        chunk (and from there to stepwise, per ``fused_eligibility``).
        """
        if self.mesh is None:
            return False, "no mesh configured"
        if self.client_sharding == "off":
            return False, "client_sharding='off'"
        if self.client_axis is None:
            return False, ("mesh has no 'clients' (or single) axis to shard "
                           "the cohort over")
        why = self._allreduce_unsafe_reason()
        if why:
            return False, why
        why = self._sharded_faults_unsafe_reason()
        if why:
            return False, why
        if m is not None and self.client_sharding == "divisible":
            shards = self.mesh.shape[self.client_axis]
            if m % shards:
                return False, (f"cohort size {m} does not divide mesh axis "
                               f"size {shards} (client_sharding='divisible' "
                               "disables padding)")
        return True, ""

    def _sharded_faults_unsafe_reason(self) -> str:
        """Why the active FaultPlan cannot run on the sharded executors
        (empty string when it can). Dropout rides the executors' existing
        zero-weight dummy mechanics; corruption needs the in-trace guard
        only the fault-aware fused chunk (and the stepwise merge) carry."""
        if self._faults_active and self.faults.corrupt > 0.0:
            return ("sharded executors support dropout/straggler faults "
                    "only; corrupt updates need the fault-aware fused "
                    "chunk's in-trace guard")
        return ""

    def _allreduce_unsafe_reason(self) -> str:
        """Why the aggregator cannot lower to the sharded executors' merge
        (empty string when it can). The sharded merges never call
        aggregator.aggregate — they lower to the hardcoded weighted psum /
        pairwise mean — so the flag must be vouched by the class that
        PROVIDES aggregate: a subclass overriding aggregate without
        re-declaring allreduce_safe must not inherit eligibility (its
        override would be silently replaced by the mean)."""
        provider = next((c for c in type(self.aggregator).__mro__
                         if "aggregate" in c.__dict__), None)
        if provider is None or not provider.__dict__.get("allreduce_safe", False):
            return (f"aggregator {type(self.aggregator).__name__} does "
                    "not declare its aggregate() a weighted-mean "
                    "family (allreduce_safe) rule")
        return ""

    def pod_sharded_eligibility(self, m: int | None = None) -> tuple[bool, str]:
        """Can the fused chunk run with pod-sharded historical tables?

        Refines ``sharded_eligibility`` for the ``("pods", "clients")``
        2-D mesh mode (repro.sharding.tables): the mesh must carry both
        axes, ``table_sharding`` must allow it, and — like the
        client-sharded executor — the aggregator must be an
        ``allreduce_safe`` weighted-mean family. Cohorts pad over the FULL
        device count (pods x clients); ``client_sharding="divisible"``
        demands divisibility instead. Ineligible configs fall soft down
        the chain: pod-sharded -> client-sharded -> fused -> stepwise.
        """
        if self.mesh is None:
            return False, "no mesh configured"
        if self.pod_axes is None:
            return False, ("mesh has no ('pods', 'clients') axes "
                           f"(got {tuple(self.mesh.shape)})")
        if self.table_sharding == "replicated":
            return False, "table_sharding='replicated'"
        if self.client_sharding == "off":
            return False, "client_sharding='off'"
        why = self._allreduce_unsafe_reason()
        if why:
            return False, why
        why = self._sharded_faults_unsafe_reason()
        if why:
            return False, why
        if m is not None and self.client_sharding == "divisible":
            shards = self.mesh.devices.size
            if m % shards:
                return False, (f"cohort size {m} does not divide the mesh's "
                               f"{shards} devices (client_sharding="
                               "'divisible' disables padding)")
        return True, ""

    def _build_fused_chunk(self):
        """One jitted chunk: scan the traced round_step over S rounds with
        the big mutable buffers donated (updated in place, never copied)."""
        vm, agg, sizes = self._vm_raw, self.aggregator, self._sizes_f32
        sync_dtype = self.sync_dtype

        def chunk(params, hist1, age, ghost_feat, prev_loss, key,
                  arrays, sel_stack, fan_stack, eoffs, tau):
            m = sel_stack.shape[1]

            def round_step(carry, xs):
                params, hist1, age, ghost_feat, prev_loss, key = carry
                sel, fanouts, eoff = xs
                ks = jax.random.split(key, m + 1)       # same chain as dispatch
                key, keys = ks[0], ks[1:]
                client = {k: v[sel] for k, v in arrays.items()}
                out = vm(params, client, arrays["features"], hist1,
                         hist1[sel], age[sel], ghost_feat[sel], prev_loss[sel],
                         tau, fanouts, eoff, keys)
                new_params, new_hist1, new_age, new_ghost_feat, stats = out
                with jax.named_scope("merge"):
                    params = agg.aggregate(new_params, sizes[sel])
                    loss_wb = stats["loss_all"]
                    if sync_dtype != "fp32":
                        new_hist1 = quant_roundtrip(new_hist1, sync_dtype)
                        new_ghost_feat = quant_roundtrip(new_ghost_feat,
                                                         sync_dtype)
                        loss_wb = quant_roundtrip(loss_wb, sync_dtype)
                    hist1 = hist1.at[sel].set(new_hist1)
                    age = age.at[sel].set(new_age)
                    ghost_feat = ghost_feat.at[sel].set(new_ghost_feat)
                    prev_loss = prev_loss.at[sel].set(loss_wb)
                light = {k: stats[k] for k in _LIGHT_STATS}
                return (params, hist1, age, ghost_feat, prev_loss, key), light

            return jax.lax.scan(round_step,
                                (params, hist1, age, ghost_feat, prev_loss, key),
                                (sel_stack, fan_stack, eoffs))

        return jax.jit(chunk, donate_argnums=(0, 1, 2, 3, 4, 5))

    def _call_sharded_chunk(self, state: EngineState, sels, fans, eoffs,
                            drop_stack=None):
        """Run one chunk through the shard-mapped executor
        (repro.sharding.fed.build_sharded_chunk): pad ragged cohorts with
        zero-weight dummy clients, derive per-client aggregation weights
        from the aggregator's semantics (client sizes for WeightedFedAvg,
        uniform for FedAvg), and hand the donated buffers — committed to
        the mesh fully replicated — to the scanned sharded round_step.
        ``drop_stack`` (FaultPlan dropout) turns dropped members into
        zero-weight out-of-range dummies: the same mechanics as ragged
        padding, so their merge weight and write-back vanish exactly."""
        mesh, axis = self.mesh, self.client_axis
        m = len(sels[0])
        if self._sharded_chunk is None or self._sharded_chunk_m != m:
            self._sharded_chunk = build_sharded_chunk(
                self._vm_raw, mesh, axis, m, _LIGHT_STATS,
                reduce=self.merge_reduce, sync_dtype=self.sync_dtype)
            self._sharded_chunk_m = m
        pad = cohort_padding(m, mesh.shape[axis])
        sel_stack = np.stack(sels).astype(np.int32)
        fan_stack = np.stack([np.asarray(f) for f in fans])
        w_stack = self._cohort_weights(sel_stack)
        if drop_stack is not None and drop_stack.any():
            w_stack[drop_stack] = 0.0
            sel_stack[drop_stack] = self.fed.n_clients
        if pad:
            # out-of-range id: gathers clamp (dummy trains on real data,
            # harmlessly), scatters drop (its write-back never lands);
            # weight 0 keeps it out of the aggregation all-reduce
            sel_stack = np.pad(sel_stack, ((0, 0), (0, pad)),
                               constant_values=self.fed.n_clients)
            fan_stack = np.pad(fan_stack, ((0, 0), (0, pad)), mode="edge")
            w_stack = np.pad(w_stack, ((0, 0), (0, pad)))
        (state.params, hist1, age, state.ghost_feat, state.prev_loss,
         state.key, state.arrays) = replicate_to_mesh(
            (state.params, state.hist.hist1, state.hist.age, state.ghost_feat,
             state.prev_loss, state.key, state.arrays), mesh)
        return self._sharded_chunk(
            state.params, hist1, age, state.ghost_feat, state.prev_loss,
            state.key, state.arrays, jnp.asarray(sel_stack),
            jnp.asarray(fan_stack), jnp.asarray(w_stack), jnp.asarray(eoffs),
            jnp.asarray(state.tau, jnp.int32))

    def _cohort_weights(self, sel_stack: np.ndarray) -> np.ndarray:
        """Per-client aggregation weights for the sharded merges: client
        sizes when the aggregator folds them in (WeightedFedAvg), uniform
        otherwise (FedAvg)."""
        if getattr(self.aggregator, "uses_weights", False):
            return self.fed.client_sizes[sel_stack].astype(np.float32)
        return np.ones(sel_stack.shape, np.float32)

    def _pod_static_arrays(self, buckets, n_pods: int):
        """The pod-sharded STATIC residents, built once per engine (per pod
        split): the client arrays the prefetched LocalUpdate reads
        (``POD_ARRAY_KEYS`` — ghost_owner/ghost_row stay off the mesh)
        padded to the pod grid and committed as ``P("pods")`` shards, plus
        the (Kp, g_max, F) ghost-source feature table from the bucketed
        owner exchange. Never written back — reused across chunks, so the
        per-device resident cost is K/P rows for the life of the run."""
        if self._pod_static is None:
            statics = pad_tables_to_pods(
                {k: jnp.asarray(getattr(self.fed, k))
                 for k in POD_ARRAY_KEYS}, n_pods)
            gsrc = jnp.asarray(
                exchange_ghost_features(buckets, self.fed.features,
                                        dtype=self.sync_dtype))
            self._pod_static = shard_tables_to_mesh((statics, gsrc),
                                                    self.mesh)
        return self._pod_static

    def _call_pod_chunk(self, state: EngineState, sels, fans, eoffs,
                        drop_stack=None):
        """Run one chunk with every K-sized array sharded over the pod axis
        (repro.sharding.tables.build_pod_sharded_chunk): pad the K axis to
        the pod grid, commit the four tables + static arrays as pod shards,
        pad ragged cohorts with dummy clients whose id has no owner pod
        (fetches zero, write-backs drop), route the cohort-keyed write-back
        and the tau-sync gates on the host, and slice the tables back to K
        rows after."""
        mesh = self.mesh
        n_pods = mesh.shape[self.pod_axes[0]]
        n_dev = mesh.devices.size
        if self._ghost_buckets is None or self._ghost_buckets.n_pods != n_pods:
            self._ghost_buckets = ghost_exchange_buckets(
                self.fed.ghost_owner, self.fed.ghost_row,
                self.fed.ghost_mask, n_pods)
            self._pod_static = None         # re-shard for the new pod split
        buckets = self._ghost_buckets
        m = len(sels[0])
        if self._pod_chunk is None or self._pod_chunk_m != m:
            vm = make_vmapped_update(self.mcfg, self.fed.n_max,
                                     self.fed.g_max, self.H1,
                                     ghost_source="prefetched",
                                     sync_dtype=self.sync_dtype,
                                     train_backend=self.train_backend,
                                     loss_buckets=self.fed.loss_buckets,
                                     width=self.fanout_width)
            self._pod_chunk = build_pod_sharded_chunk(
                vm, mesh, m, buckets, _LIGHT_STATS,
                reduce=self.merge_reduce, sync_dtype=self.sync_dtype)
            self._pod_chunk_m = m
        pad = cohort_padding(m, n_dev)
        sel_stack = np.stack(sels).astype(np.int32)
        fan_stack = np.stack([np.asarray(f) for f in fans])
        w_stack = self._cohort_weights(sel_stack)
        if drop_stack is not None and drop_stack.any():
            # dropped members become ownerless dummies (same id as ragged
            # padding): fetch zero rows, zero merge weight, no write-back
            w_stack[drop_stack] = 0.0
            sel_stack[drop_stack] = buckets.n_clients_padded
        if pad:
            sel_stack = np.pad(sel_stack, ((0, 0), (0, pad)),
                               constant_values=buckets.n_clients_padded)
            fan_stack = np.pad(fan_stack, ((0, 0), (0, pad)), mode="edge")
            w_stack = np.pad(w_stack, ((0, 0), (0, pad)))
        plan = writeback_routing(sel_stack, n_pods, n_dev // n_pods,
                                 buckets.rows_per_pod)
        gates = sync_round_gates(
            eoffs, state.tau, self.mcfg.local_epochs,
            enabled=self.mcfg.use_ghosts and not self.mcfg.use_generator)
        arrays_sh, gsrc_sh = self._pod_static_arrays(buckets, n_pods)
        K = self.fed.n_clients
        tables = pad_tables_to_pods(
            (state.hist.hist1, state.hist.age, state.ghost_feat,
             state.prev_loss), n_pods)
        hist1, age, ghost_feat, prev_loss = shard_tables_to_mesh(tables, mesh)
        state.params, state.key = replicate_to_mesh(
            (state.params, state.key), mesh)
        carry, light = self._pod_chunk(
            state.params, hist1, age, ghost_feat, prev_loss, state.key,
            arrays_sh, gsrc_sh, jnp.asarray(sel_stack),
            jnp.asarray(fan_stack), jnp.asarray(w_stack), jnp.asarray(eoffs),
            jnp.asarray(state.tau, jnp.int32), jnp.asarray(gates),
            jnp.asarray(plan.dst), jnp.asarray(plan.pos),
            jnp.asarray(plan.recv))
        if buckets.n_clients_padded == K:
            # divisible K: the carried tables come back pod-sharded and feed
            # the next chunk's (no-op) pad + device_put directly — shards
            # stay resident on their pods across chunk boundaries
            return carry, light
        (params, hist1, age, ghost_feat, prev_loss, key) = carry
        # ragged K: drop the pod-padding rows again; state keeps the K-row
        # view every host-side consumer (selectors, eval, fallback) expects
        return ((params, hist1[:K], age[:K], ghost_feat[:K], prev_loss[:K],
                 key), light)

    def _call_faulty_chunk(self, state: EngineState, sels, fans, eoffs,
                           drop_stack, cmask_stack):
        """Run one chunk through the fault-aware fused executor
        (repro.faults.build_faulty_chunk): dropped members get weight 0,
        corrupted members get a poison multiplier, and the in-trace guard
        zeroes + counts non-finite/norm-exploded updates — reproducing the
        stepwise dispatch -> corrupt -> drop -> guarded-merge path inside
        one scanned XLA call."""
        if self._faulty_chunk is None:
            g = self._guard
            self._faulty_chunk = build_faulty_chunk(
                self._vm_raw, _LIGHT_STATS,
                uses_weights=getattr(self.aggregator, "uses_weights", False),
                finite_guard=g is not None,
                max_norm=None if g is None else g.max_norm,
                sync_dtype=self.sync_dtype)
        sel_stack = np.stack(sels).astype(np.int32)
        w_stack = self._cohort_weights(sel_stack)
        w_stack[drop_stack] = 0.0
        cmult_stack = np.ones(sel_stack.shape, np.float32)
        cmult_stack[cmask_stack] = self.faults.corrupt_value()
        return self._faulty_chunk(
            state.params, state.hist.hist1, state.hist.age, state.ghost_feat,
            state.prev_loss, state.key, state.arrays,
            jnp.asarray(sel_stack), jnp.stack(fans), jnp.asarray(w_stack),
            jnp.asarray(cmult_stack), jnp.asarray(eoffs),
            jnp.asarray(state.tau, jnp.int32))

    def _run_chunk(self, state: EngineState, t0: int, n_rounds: int) -> bool:
        """Select cohorts for rounds [t0, t0+n_rounds) on the host, run them
        as ONE donated scanned XLA call, then replay the host tail (cost
        accounting, post_round, callbacks) per round from the streamed
        stats. Returns True if a callback requested stop.

        Under an active FaultPlan, per-round dropout/corruption masks are
        drawn on the host for the whole chunk (the plan's (round, client)
        coordinates make them executor-independent) and threaded into the
        executor: the sharded paths absorb dropout through their
        zero-weight dummy mechanics, corruption routes to the fault-aware
        fused chunk (``fused_faulty``), and the replay tail mirrors the
        stepwise merge's billing — dropped members are billed nothing,
        stragglers stretch the round's wall clock, survivor-free rounds
        count as empty merges."""
        with span("fed/select", round=t0, rounds=n_rounds):
            sels, fans = [], []
            for t in range(t0, t0 + n_rounds):
                state.round = t
                sel = np.asarray(self.selector.select(self, state))
                sels.append(sel)
                fans.append(self.strategy.choose_fanouts(self, sel))
            if any(len(s) != len(sels[0]) for s in sels):
                raise ValueError(
                    "fused executor needs constant cohort sizes across a chunk; "
                    "precomputable selectors must return fixed-size cohorts")
            eoffs = np.arange(t0, t0 + n_rounds, dtype=np.int32) * self.mcfg.local_epochs

            drop_stack = cmask_stack = None
            if self._faults_active:
                ts = range(t0, t0 + n_rounds)
                drop_stack = np.stack(
                    [self.faults.drops(t, s) for t, s in zip(ts, sels)])
                cmask_stack = np.stack(
                    [self.faults.corruptions(t, s) for t, s in zip(ts, sels)])
                state.fault_events.n_dropped += int(drop_stack.sum())

        with span("fed/dispatch", round=t0, rounds=n_rounds):
            if self.mesh is not None and self.pod_sharded_eligibility(len(sels[0]))[0]:
                self.last_executor = "pod_sharded"
                carry, light = self._call_pod_chunk(state, sels, fans, eoffs,
                                                    drop_stack=drop_stack)
            elif self.mesh is not None and self.sharded_eligibility(len(sels[0]))[0]:
                self.last_executor = "sharded_fused"
                carry, light = self._call_sharded_chunk(state, sels, fans, eoffs,
                                                        drop_stack=drop_stack)
            elif self._faults_active:
                self.last_executor = "fused_faulty"
                carry, light = self._call_faulty_chunk(state, sels, fans, eoffs,
                                                       drop_stack, cmask_stack)
            else:
                self.last_executor = "fused"
                if self._fused_chunk is None:
                    self._fused_chunk = self._build_fused_chunk()
                carry, light = self._fused_chunk(
                    state.params, state.hist.hist1, state.hist.age, state.ghost_feat,
                    state.prev_loss, state.key, state.arrays,
                    jnp.asarray(np.stack(sels)), jnp.stack(fans), jnp.asarray(eoffs),
                    jnp.asarray(state.tau, jnp.int32))
            (state.params, hist1, age, state.ghost_feat, state.prev_loss,
             state.key) = carry
            state.hist = state.hist._replace(hist1=hist1, age=age)

        with span("fed/wait", round=t0, rounds=n_rounds):
            light = jax.device_get(light)   # one host transfer per chunk
        with span("fed/replay", round=t0, rounds=n_rounds):
            n_quar_rounds = light.pop("n_quarantined", None)
            if n_quar_rounds is not None:
                state.fault_events.n_quarantined += int(np.sum(n_quar_rounds))
            for i, t in enumerate(range(t0, t0 + n_rounds)):
                state.round = t
                stats_t = {k: v[i] for k, v in light.items()}
                sel_t, stats_b, wall = sels[i], stats_t, None
                if self._faults_active:
                    plan = self.faults
                    if drop_stack is not None and drop_stack[i].any():
                        # dropped uploads never reach the server: bill survivors
                        keep = np.flatnonzero(~drop_stack[i])
                        sel_t = sels[i][keep]
                        stats_b = {k: v[keep] for k, v in stats_t.items()}
                    if plan.straggler_frac > 0.0:
                        # same formula as the stepwise _inject_faults billing:
                        # the lockstep server waits for every dispatched member
                        # (stragglers included; compute times are stats-free in
                        # PaperCostModel, so the sharded executor's dummy rows
                        # for dropped members don't leak in), while the merge
                        # overhead o prices only the survivor uploads
                        times = np.asarray(self.cost_model.client_compute_times(
                            self, state, sels[i], stats_t), np.float64)
                        times = times * plan.delay_factors(sels[i])
                        o = self.cost_model.sync_overhead(self, sel_t, stats_b)
                        wall = float(np.max(times)) + o / max(state.tau, 1)
                    n_quar_t = (0 if n_quar_rounds is None
                                else int(n_quar_rounds[i]))
                    if len(sel_t) - n_quar_t <= 0:
                        state.fault_events.n_empty_merges += 1
                if len(sel_t):
                    cost = self.cost_model.round_cost(self, state, sel_t, stats_b)
                else:
                    cost = CostMeter()
                if wall is not None:
                    cost.wall_clock_s = wall
                state.result.costs.add(cost)
                if len(sel_t):
                    self.strategy.post_round(self, state, sel_t, stats_b)
                ctx = RoundContext(engine=self, state=state, t=t, rounds=self.rounds)
                for cb in self.callbacks:
                    cb.on_round_end(ctx)
                if ctx.stop:
                    return True
        return False

    def run_fused(self, state: EngineState) -> None:
        """Run all rounds through the scanned executor, chunked at eval
        boundaries so the EvalCallback cadence (server eval + tau update +
        early stop) observes exactly the rounds the stepwise loop would."""
        eval_every = next((cb.eval_every for cb in self.callbacks
                           if isinstance(cb, EvalCallback)), None)
        t = 0
        while t < self.rounds:
            if eval_every is None:          # no eval: one chunk for the run
                t_end = self.rounds - 1
            else:                           # chunk ends at the next eval round
                nxt = t if t % eval_every == 0 else (t // eval_every + 1) * eval_every
                t_end = min(nxt, self.rounds - 1)
            if self._run_chunk(state, t, t_end - t + 1):
                return
            t = t_end + 1

    @span("fed/run")
    def run(self, state: EngineState | None = None) -> RunResult:
        if state is None:
            state = self.init_state()
        for cb in self.callbacks:
            cb.on_run_start(self, state)
        self.scheduler.run(self, state)
        if state.last_eval is not None and state.last_eval[0] == state.round:
            # EvalCallback already scored this round's (unchanged) params;
            # don't pay for the same server eval twice
            final_eval = state.last_eval[1]
        else:
            final_eval = evaluate_global(state.params, self.eval_graph, "test")
        state.result.final = dict(final_eval, **state.result.costs.snapshot())
        for cb in self.callbacks:
            cb.on_run_end(self, state)
        return state.result
