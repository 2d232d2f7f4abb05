"""The training step's compacted neighbour lists (``core.fedais.kept_neighbors``
at ``FedEngine.fanout_width``): each batch row's kept slots, compacted to ``W`` columns
before any feature or embedding row is gathered, give the numbers of the
padded 32-slot form under its keep mask, with the same kept set, the same
ghosts pulled and the same random draws."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.api import FedEngine, MethodStrategy, method_config
from repro.core.fedais import (
    fanout_width,
    ghost_need,
    kept_neighbors,
    make_vmapped_update,
)
from repro.models.gcn import HIDDEN, gcn_batch_rows_forward, gcn_init, per_node_loss

K = 32
N_OWN, N_GHOST, F, H = 40, 24, 12, (16, 8)
DEGREES = (0, 1, 9, 10, 11, 32)


@pytest.fixture(scope="module")
def rows():
    """A batch of 30 rows, five of each degree in ``DEGREES``, their real
    slots scattered over the 32 (as a padded row of any layout holds them),
    own and ghost neighbours mixed, some ids repeated, and draws with ties."""
    rng = np.random.default_rng(5)
    deg = np.repeat(DEGREES, 5)
    b = len(deg)
    mask = np.zeros((b, K), np.float32)
    for r, d in enumerate(deg):
        mask[r, rng.choice(K, d, replace=False)] = 1.0
    idx = rng.integers(0, N_OWN + N_GHOST, (b, K)).astype(np.int32)
    idx[3, :] = idx[3, 0]                                  # one id repeated
    idx = np.where(mask > 0, idx, 0)
    draws = rng.random((b, K)).astype(np.float32)
    draws[7, :16] = draws[7, 0]                            # tied draws
    ranks = np.where(mask > 0, draws, 2.0).astype(np.float32)
    batch = rng.choice(N_OWN, b, replace=False).astype(np.int32)
    valid = rng.random(b) < 0.8
    ghost_mask = (rng.random(N_GHOST) < 0.9).astype(np.float32)
    return tuple(jnp.asarray(a) for a in
                 (idx, mask, ranks, batch, valid, ghost_mask))


@pytest.fixture(scope="module")
def model():
    rng = np.random.default_rng(6)
    params = gcn_init(jax.random.PRNGKey(0), F, 3, hidden=H)
    feats = jnp.asarray(rng.standard_normal((N_OWN, F)), jnp.float32)
    ghost = jnp.asarray(rng.standard_normal((N_GHOST, F)), jnp.float32)
    hist1 = jnp.asarray(rng.standard_normal((N_OWN + N_GHOST, H[0])),
                        jnp.float32)
    labels = jnp.asarray(rng.integers(0, 3, N_OWN), jnp.int32)
    return params, feats, ghost, hist1, labels


def _lists(rows, fanout, width, use_ghosts):
    idx, mask, ranks, *_ = rows
    c_idx, c_mask = kept_neighbors(idx, mask, ranks, fanout, width)
    if not use_ghosts:
        c_mask = c_mask * (c_idx < N_OWN)
    return c_idx, c_mask


def _kept_set(idx, mask):
    return [sorted(np.asarray(i)[np.asarray(m) > 0].tolist())
            for i, m in zip(idx, mask)]


@pytest.mark.parametrize("backend", ["gather", "segment"])
@pytest.mark.parametrize("use_ghosts", [True, False])
@pytest.mark.parametrize("fanout", [2, 5, 10])
def test_compacted_step_matches_padded(rows, model, fanout, use_ghosts,
                                       backend):
    params, feats, ghost, hist1, labels = model
    _, _, _, batch, valid, ghost_mask = rows
    padded = _lists(rows, fanout, K, use_ghosts)
    compact = _lists(rows, fanout, fanout, use_ghosts)
    assert compact[0].shape == (len(batch), fanout)

    # the same kept neighbours, each row keeping min(degree, fanout)
    assert _kept_set(*compact) == _kept_set(*padded)
    if use_ghosts:
        deg = np.asarray(rows[1]).sum(-1)
        np.testing.assert_array_equal(np.asarray(compact[1]).sum(-1),
                                      np.minimum(deg, fanout))
    # the same ghosts pulled
    np.testing.assert_array_equal(
        ghost_need(*compact, valid, N_OWN, ghost_mask),
        ghost_need(*padded, valid, N_OWN, ghost_mask))

    def loss(p, lists):
        logits, h1, _ = gcn_batch_rows_forward(
            p, feats, ghost, hist1, *lists, batch, backend=backend)
        return per_node_loss(logits, labels[batch]).mean(), (logits, h1)

    step = jax.jit(jax.value_and_grad(loss, has_aux=True))
    with jax.default_matmul_precision("highest"):
        (_, (logits_p, h1_p)), grads_p = step(params, padded)
        (_, (logits_c, h1_c)), grads_c = step(params, compact)
    close = dict(rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(logits_c, logits_p, **close)
    np.testing.assert_allclose(h1_c, h1_p, **close)
    for k in grads_p:
        np.testing.assert_allclose(grads_c[k], grads_p[k], err_msg=k, **close)


def test_kept_slots_packed_in_slot_order(rows):
    """Each row's kept slots come first, in the order the padded row holds
    them, so a sum over them adds what the padded form adds, in its order."""
    idx, mask, ranks, *_ = rows
    for fanout in (2, 5, 10):
        p_idx, p_mask = (np.asarray(a) for a in
                         kept_neighbors(idx, mask, ranks, fanout, K))
        c_idx, c_mask = (np.asarray(a) for a in
                         kept_neighbors(idx, mask, ranks, fanout, fanout))
        for r in range(len(p_idx)):
            kept = p_idx[r][p_mask[r] > 0]
            n = len(kept)
            np.testing.assert_array_equal(c_idx[r, :n], kept)
            np.testing.assert_array_equal(c_mask[r], np.arange(fanout) < n)


def test_fanout_width():
    assert fanout_width(10, 32) == 10
    assert fanout_width(5, 32) == 5
    # never wider than the padded rows
    assert fanout_width(40, 32) == 32
    assert fanout_width(10, 8) == 8


class _WideFanout(MethodStrategy):
    """A fanout policy of its own, above the method's ``neighbor_fanout``;
    it keeps each round's stats."""
    fusable = True

    def __init__(self, mcfg, fanout):
        super().__init__(mcfg)
        self.fanout, self.stats = fanout, []

    def choose_fanouts(self, engine, sel):
        return jnp.full((len(sel),), self.fanout, jnp.int32)

    def post_round(self, engine, state, sel, stats):
        self.stats.append(stats)


class _Recorder(MethodStrategy):
    """The fixed fanout; keeps each round's stats."""
    fusable = True

    def __init__(self, mcfg):
        super().__init__(mcfg)
        self.stats = []

    def post_round(self, engine, state, sel, stats):
        self.stats.append(stats)


def _engine(small_fed, strategy, mcfg, rounds=1):
    g, fed = small_fed
    return FedEngine(g, fed, mcfg, rounds=rounds, clients_per_round=3, seed=0,
                     strategy=strategy, eval_every=rounds)


def test_engine_width_follows_the_strategy(small_fed):
    """W is the strategy's largest fanout: ``neighbor_fanout`` for a fixed
    fanout, the bandit's largest arm, and every padded slot for a policy
    that does not bound its own fanouts."""
    g, fed = small_fed
    assert fed.max_deg == 32
    for nf in (10, 5):
        eng = FedEngine(g, fed, method_config("fedais", neighbor_fanout=nf))
        assert eng.fanout_width == nf
    assert FedEngine(g, fed, "fedgraph").fanout_width == 32
    mcfg = method_config("fedais", neighbor_fanout=5)
    assert _engine(small_fed, _WideFanout(mcfg, 20),
                   mcfg).fanout_width == fed.max_deg


def test_strategy_fanout_above_neighbor_fanout_keeps_its_slots(small_fed):
    """A strategy that passes fanout 20 under ``neighbor_fanout`` 5 keeps 20
    slots a row, as a fixed fanout of 20 does on the same draws, and more
    than a fixed fanout of 5: its fanout is not cut to the method's."""
    def kept(strategy, mcfg):
        _engine(small_fed, strategy, mcfg).run()
        return np.asarray(strategy.stats[0]["n_kept_slots"])

    mc5 = method_config("fedais", neighbor_fanout=5, batch_cap=32)
    mc20 = method_config("fedais", neighbor_fanout=20, batch_cap=32)
    wide = kept(_WideFanout(mc5, 20), mc5)
    np.testing.assert_array_equal(wide, kept(_Recorder(mc20), mc20))
    assert (wide > kept(_Recorder(mc5), mc5)).all()


def test_engine_reports_kept_slots(small_fed):
    """``n_kept_slots`` streams out of the fused rounds: a share of the
    rounds' epochs * bsz * W gathered slots, above 0 and at most 1."""
    mcfg = method_config("fedais", neighbor_fanout=5)
    rec = _Recorder(mcfg)
    eng = _engine(small_fed, rec, mcfg, rounds=2)
    assert eng.fused_eligibility()[0]
    eng.run()
    assert len(rec.stats) == 2
    for st in rec.stats:
        kept = np.asarray(st["n_kept_slots"])
        assert kept.shape == (3,)
        share = kept / (eng.mcfg.local_epochs * eng.bsz * eng.fanout_width)
        assert ((share > 0) & (share <= 1)).all()


def _cohort_args(fed, mcfg, sel):
    n_max, g_max = fed.n_max, fed.g_max
    keys = ("features", "labels", "node_mask", "train_mask", "nbr_idx",
            "nbr_mask", "ghost_owner", "ghost_row", "ghost_mask")
    client = {k: jnp.asarray(getattr(fed, k)[sel]) for k in keys}
    m, n_tot = len(sel), n_max + g_max
    rng = np.random.default_rng(0)
    hist1_all = rng.standard_normal((fed.n_clients, n_tot, HIDDEN[0]),
                                    np.float32)
    return (gcn_init(jax.random.PRNGKey(1), fed.n_features, fed.n_classes),
            client, jnp.asarray(fed.features), jnp.asarray(hist1_all),
            jnp.asarray(hist1_all[sel]), jnp.zeros((m, n_tot), jnp.int32),
            jnp.zeros((m, g_max, fed.n_features), jnp.float32),
            jnp.full((m, n_max), -1.0, jnp.float32), jnp.asarray(1, jnp.int32),
            jnp.full((m,), mcfg.neighbor_fanout, jnp.int32),
            jnp.asarray(0, jnp.int32), jax.random.split(jax.random.PRNGKey(3), m))


@pytest.mark.parametrize("method", ["fedais", "fedlocal"])
def test_local_update_compacted_matches_padded(small_fed, method):
    """The whole vmapped LocalUpdate, one epoch: compacted to fanout 5
    against the same step forced onto the padded 32 slots. The draws, the
    kept slots and the ghosts pulled are the same; the numbers differ only
    in the order the slot sums run."""
    _, fed = small_fed
    mcfg = method_config(method, neighbor_fanout=5, batch_cap=32,
                         local_epochs=1)
    sel = np.array([0, 3, 5])
    args = _cohort_args(fed, mcfg, sel)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(make_vmapped_update(mcfg, fed.n_max, fed.g_max,
                                          HIDDEN[0], width=5))(*args)
        want = jax.jit(make_vmapped_update(mcfg, fed.n_max, fed.g_max,
                                           HIDDEN[0]))(*args)
    g_stats, w_stats = got[4], want[4]
    for k in ("n_kept_slots", "n_ghost_pulled", "n_sync"):
        np.testing.assert_array_equal(g_stats[k], w_stats[k], err_msg=k)
    # the loss pass is the same forward; XLA may fuse the two programs apart
    want_loss = np.asarray(w_stats["loss_all"])
    np.testing.assert_allclose(g_stats["loss_all"], want_loss, rtol=1e-6,
                               atol=1e-6 * np.abs(want_loss).max())
    assert (np.asarray(g_stats["n_kept_slots"]) > 0).all()
    np.testing.assert_allclose(g_stats["epoch_losses"], w_stats["epoch_losses"],
                               rtol=1e-6)
    # one Adam step: an element whose gradient is at rounding level moves by
    # up to lr either way, so the parameters are compared as norms
    for g, w in zip(jax.tree_util.tree_leaves(got[0]),
                    jax.tree_util.tree_leaves(want[0])):
        np.testing.assert_allclose(np.linalg.norm(g), np.linalg.norm(w),
                                   rtol=1e-4)
    np.testing.assert_array_equal(got[2], want[2])           # age
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-6)


def _subjaxprs(params):
    for v in params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def _compaction_eqns(jaxpr, k):
    """Equations that compact the cohort's (m, b, K) neighbour lists: the
    running count of kept slots, or a boolean (m, b, W, K) pick."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cumsum":
            found.append(eqn)
        for v in eqn.outvars:
            aval = v.aval
            if (getattr(aval, "dtype", None) == jnp.bool_
                    and len(aval.shape) == 4 and aval.shape[-1] == k):
                found.append(eqn)
        for sub in _subjaxprs(eqn.params):
            found += _compaction_eqns(sub, k)
    return found


@pytest.mark.parametrize("method,compacts", [("fedgraph", False),
                                             ("fedais", True)])
def test_padded_width_has_no_compaction_ops(small_fed, method, compacts):
    """At ``W == max_deg`` (the bandit's fanouts reach 32) the epoch step is
    the padded path: no count of kept slots and no (b, W, K) pick in its
    jaxpr."""
    g, fed = small_fed
    mcfg = method_config(method, batch_cap=32, local_epochs=1)
    width = FedEngine(g, fed, mcfg).fanout_width
    assert (width < fed.max_deg) == compacts
    args = _cohort_args(fed, mcfg, np.array([0, 3]))
    jaxpr = jax.make_jaxpr(make_vmapped_update(
        mcfg, fed.n_max, fed.g_max, HIDDEN[0], width=width))(*args).jaxpr
    assert bool(_compaction_eqns(jaxpr, fed.max_deg)) == compacts
