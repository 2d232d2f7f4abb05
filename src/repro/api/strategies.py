"""Method strategies: the per-method round hooks the legacy loop hid behind
``if mcfg.use_generator:`` / ``if mcfg.bandit_fanout:`` branches.

A MethodStrategy owns all method-specific mutable state (FedSage+ generator
parameters, FedGraph bandit tables) and exposes four round hooks plus two
cost hooks, so the FedEngine round loop and the PaperCostModel stay
branch-free. New methods subclass MethodStrategy, register a kind with
``register_strategy_kind``, then register a method name in
repro.api.registry pointing at that kind.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from repro.federated import baselines as B
from repro.federated.costs import model_bytes

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.api.engine import EngineState, FedEngine
    from repro.core.fedais import MethodConfig


class MethodStrategy:
    """Default (plain) strategy: fixed fanout, no extra state or cost."""

    def __init__(self, mcfg: "MethodConfig"):
        self.mcfg = mcfg

    def setup(self, engine: "FedEngine", state: "EngineState") -> None:
        """Allocate method-specific state before round 0."""

    def choose_fanouts(self, engine: "FedEngine", sel: np.ndarray) -> jnp.ndarray:
        """Per-selected-client neighbor fanout for this round."""
        return jnp.full((len(sel),), self.mcfg.neighbor_fanout, jnp.int32)

    def max_fanout(self, engine: "FedEngine") -> int:
        """Largest fanout ``choose_fanouts`` can return. It sets the width
        of the training step's neighbor lists (``FedEngine.fanout_width``),
        and a row keeps no more slots than that: ``neighbor_fanout`` for the
        fixed fanout; a subclass with its own ``choose_fanouts`` gets
        ``max_deg``, every padded slot, unless it overrides this with its
        policy's bound."""
        if type(self).choose_fanouts is MethodStrategy.choose_fanouts:
            return self.mcfg.neighbor_fanout
        return engine.fed.max_deg

    def pre_round(self, engine: "FedEngine", state: "EngineState",
                  sel: np.ndarray) -> None:
        """Before the vmapped LocalUpdate (e.g. ghost-feature imputation)."""

    def post_round(self, engine: "FedEngine", state: "EngineState",
                   sel: np.ndarray, stats: dict) -> None:
        """After merge (e.g. bandit reward updates)."""

    # ---- cost hooks (consumed by PaperCostModel) ----

    def round_model_bytes(self, engine: "FedEngine") -> float:
        """Extra per-client model-channel bytes (rides the up/down-link)."""
        return 0.0

    def extra_flops(self, engine: "FedEngine", client_size):
        """Extra per-client compute on top of the GCN fwd+bwd. ``client_size``
        may be a scalar or an int ndarray over the cohort (the vectorized
        cost model passes the whole selection at once); implementations must
        be elementwise arithmetic."""
        return 0.0


class GeneratorStrategy(MethodStrategy):
    """FedSage+ lite: a locally trained generator imputes ghost features, so
    no embedding sync happens; generator params ride the model link."""

    def setup(self, engine, state):
        self.gen_params = B.generator_init(
            jax.random.PRNGKey(engine.seed + 2), engine.F)
        rev_np, rev_mask_np = B.ghost_reverse_map(engine.fed)
        self.rev, self.rev_mask = jnp.asarray(rev_np), jnp.asarray(rev_mask_np)

    def pre_round(self, engine, state, sel):
        arrays = state.arrays
        K, n_max, F = engine.fed.n_clients, engine.fed.n_max, engine.F
        self.gen_params, _gen_loss = B.generator_train_step(
            self.gen_params,
            arrays["features"].reshape(K * n_max, F),
            jnp.minimum(arrays["nbr_idx"].reshape(K * n_max, -1), n_max * K - 1),
            arrays["nbr_mask"].reshape(K * n_max, -1)
            * (arrays["nbr_idx"].reshape(K * n_max, -1) < n_max),
            arrays["node_mask"].reshape(K * n_max),
        )
        imputed = jax.vmap(B.generator_impute, in_axes=(None, 0, 0, 0, 0))(
            self.gen_params, arrays["features"], self.rev, self.rev_mask,
            arrays["ghost_mask"])
        state.ghost_feat = imputed

    def round_model_bytes(self, engine):
        return 2 * model_bytes(B.generator_param_count(engine.F))

    def extra_flops(self, engine, client_size):
        return 6.0 * engine.F * 64 * client_size


class BanditStrategy(MethodStrategy):
    """FedGraph lite: per-client epsilon-greedy bandit over fanout actions,
    rewarded by the round-over-round local-loss improvement.

    Reward attribution assumes a client's updates are observed in dispatch
    order. Synchronous merges guarantee that (one update per client per
    round). Async merges restack each buffer by (dispatch version, cohort
    position), so a client selected twice while in flight rewards oldest ->
    freshest within the merge — matching the engine write-back's
    dedup-keeps-freshest rule — but a straggler can still arrive in a LATER
    merge than a fresher update it departed before. Such out-of-order
    arrivals are skipped: their "improvement" would be measured against a
    loss the bandit already advanced past, inverting the reward's sign.
    ``state.last_staleness`` carries the per-update staleness the async
    merge observed (None on sync paths, where every update is this
    round's and the skip can never fire — legacy rewards bit-for-bit).
    """

    def setup(self, engine, state):
        self.bandit = B.FanoutBandit(engine.fed.n_clients, seed=engine.seed)
        self.last_client_loss = np.zeros(engine.fed.n_clients)
        # dispatch version of each client's last rewarded update
        self.last_reward_version = np.full(engine.fed.n_clients, -1, np.int64)

    def choose_fanouts(self, engine, sel):
        return jnp.asarray([self.bandit.choose(int(k)) for k in sel], jnp.int32)

    def max_fanout(self, engine):
        return max(B.FANOUT_ACTIONS)

    def post_round(self, engine, state, sel, stats):
        mean_losses = np.asarray(stats["epoch_losses"]).mean(axis=1)
        staleness = state.last_staleness
        if staleness is None:               # sync: every update is this round's
            versions = np.full(len(sel), state.round, np.int64)
        else:
            versions = state.round - np.asarray(staleness, np.int64)
        for i, k in enumerate(sel):
            v = int(versions[i])
            if v < self.last_reward_version[k]:
                continue    # stale straggler ordered after a fresher update
            reward = (self.last_client_loss[k] - float(mean_losses[i])
                      if self.last_client_loss[k] else 0.0)
            self.bandit.update(int(k), reward)
            self.last_client_loss[k] = float(mean_losses[i])
            self.last_reward_version[k] = v


# ---------------------------------------------------------------------------
# strategy-kind registry
# ---------------------------------------------------------------------------

STRATEGY_KINDS: dict[str, type] = {
    "plain": MethodStrategy,
    "generator": GeneratorStrategy,
    "bandit": BanditStrategy,
}


def register_strategy_kind(kind: str, cls: type, *, overwrite: bool = False) -> type:
    """Register a MethodStrategy subclass under a string kind (idempotent
    for the same class; raises on silent overwrite unless ``overwrite``)."""
    existing = STRATEGY_KINDS.get(kind)
    if existing is not None and existing is not cls and not overwrite:
        raise KeyError(f"strategy kind {kind!r} already registered to {existing!r}")
    STRATEGY_KINDS[kind] = cls
    return cls


def strategy_kind_for(mcfg: "MethodConfig") -> str:
    """Resolve a config to a strategy kind: the explicit ``mcfg.strategy``
    wins; ``'auto'`` infers from the legacy feature flags (this is the ONLY
    place those flags are branched on — never in the round loop)."""
    kind = getattr(mcfg, "strategy", "auto") or "auto"
    if kind != "auto":
        return kind
    if mcfg.use_generator:
        return "generator"
    if mcfg.bandit_fanout:
        return "bandit"
    return "plain"


def build_strategy(mcfg: "MethodConfig") -> MethodStrategy:
    kind = strategy_kind_for(mcfg)
    try:
        cls = STRATEGY_KINDS[kind]
    except KeyError:
        raise KeyError(
            f"unknown strategy kind {kind!r}; known: {sorted(STRATEGY_KINDS)}"
        ) from None
    return cls(mcfg)
