"""The loss pass's degree-bucketed neighbour layout
(``federated.partition.loss_pass_layout``) and the forward that reads it
(``models.gcn.gcn_loss_pass_forward``): the same numbers as the padded
``gather`` forward over every row, with only the masked slots left out."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import method_config
from repro.core.fedais import make_vmapped_update
from repro.federated.partition import (
    LOSS_PASS_WIDTHS,
    loss_pass_layout,
    partition_graph,
)
from repro.graph.data import make_dataset
from repro.models.gcn import (
    HIDDEN,
    gcn_batch_forward,
    gcn_init,
    gcn_loss_pass_forward,
)

# the paper's method space (api/registry.py)
METHODS = ("fedais", "fedais1", "fedais2", "fedall", "fedrandom", "fedpns",
           "fedsage+", "fedgraph", "fedlocal")


@pytest.fixture(scope="module")
def fed():
    """12 clients at Dirichlet 0.05: clients 0 and 11 hold no node, client 8
    holds one node and no training node, and some rows have 32 neighbours."""
    g = make_dataset("pubmed", scale=64, seed=0)
    fed = partition_graph(g, 12, alpha=0.05, seed=1)
    sizes = fed.client_sizes
    n_train = (fed.train_mask * fed.node_mask).sum(1)
    assert sizes[0] == 0 and sizes[11] == 0
    assert sizes[8] > 0 and n_train[8] == 0
    assert fed.nbr_mask.sum(-1).max() == fed.max_deg == 32
    return fed


def _with_dummy(a: np.ndarray) -> np.ndarray:
    """One all-zero client appended, as the pod-sharded executor pads its
    tables and its cohorts' dummies fetch."""
    return np.concatenate([a, np.zeros((1,) + a.shape[1:], a.dtype)])


def _assert_close(got, want):
    """Equal but for the order in which XLA sums a row's slots: within
    1e-6 of each number and of the largest one (the padded sum adds the
    masked zeros, and XLA:CPU does not sum either in slot order)."""
    want = np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("k", list(range(12)) + [12])
def test_forward_matches_padded_gather(fed, k):
    rng = np.random.default_rng(k)
    params = gcn_init(jax.random.PRNGKey(1), fed.n_features, fed.n_classes)
    arrays = {n: _with_dummy(getattr(fed, n))[k] for n in
              ("features", "nbr_idx", "nbr_mask", "loss_idx", "loss_mask",
               "loss_pos")}
    ghost_feat = rng.standard_normal((fed.g_max, fed.n_features),
                                     np.float32)
    hist1 = rng.standard_normal((fed.n_max + fed.g_max, HIDDEN[0]),
                                np.float32)
    want = jax.jit(lambda a, gf, h: gcn_batch_forward(
        params, a["features"], gf, h, a["nbr_idx"], a["nbr_mask"],
        jnp.arange(fed.n_max), backend="gather"))(arrays, ghost_feat, hist1)
    got = jax.jit(lambda a, gf, h: gcn_loss_pass_forward(
        params, a["features"], gf, h, a["loss_idx"], a["loss_mask"],
        a["loss_pos"], fed.loss_buckets))(
            arrays, ghost_feat, hist1)
    for g, w in zip(got, want):                         # logits, h1
        _assert_close(g, w)


@pytest.mark.parametrize("method", METHODS)
def test_local_update_matches_padded_loss_pass(fed, method):
    """The whole vmapped LocalUpdate, with the layout and without it (the
    padded forward), on a cohort of an empty client, the client with no
    training node, the largest client and a zero dummy: every output the
    same but the loss pass's ``loss_all``, which differs by rounding only:
    the batch sampler's keys are mantissa-quantized, so the batches, and
    with them the training that follows, are the same."""
    mcfg = method_config(method, batch_cap=16, local_epochs=2)
    K, n_max, g_max, F = fed.n_clients, fed.n_max, fed.g_max, fed.n_features
    keys = ("features", "labels", "node_mask", "train_mask", "nbr_idx",
            "nbr_mask", "ghost_owner", "ghost_row", "ghost_mask",
            "loss_idx", "loss_mask", "loss_pos")
    arrays = {n: _with_dummy(getattr(fed, n)) for n in keys}
    sel = np.array([0, 8, int(np.argmax(fed.client_sizes)), K])
    client = {n: jnp.asarray(v[sel]) for n, v in arrays.items()}
    m, n_tot = len(sel), n_max + g_max
    rng = np.random.default_rng(0)
    hist1_all = rng.standard_normal((K + 1, n_tot, HIDDEN[0]), np.float32)
    args = (
        gcn_init(jax.random.PRNGKey(1), F, fed.n_classes), client,
        jnp.asarray(arrays["features"]), jnp.asarray(hist1_all),
        jnp.asarray(hist1_all[sel]), jnp.zeros((m, n_tot), jnp.int32),
        jnp.asarray(rng.standard_normal((m, g_max, F), np.float32)),
        jnp.full((m, n_max), -1.0, jnp.float32), jnp.asarray(2, jnp.int32),
        jnp.full((m,), mcfg.neighbor_fanout, jnp.int32),
        jnp.asarray(0, jnp.int32), jax.random.split(jax.random.PRNGKey(3), m))
    padded = jax.jit(make_vmapped_update(mcfg, n_max, g_max, HIDDEN[0]))
    layout = jax.jit(make_vmapped_update(mcfg, n_max, g_max, HIDDEN[0],
                                         loss_buckets=fed.loss_buckets))
    want, got = padded(*args), layout(*args)
    _assert_close(got[4].pop("loss_all"), want[4].pop("loss_all"))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(g, w)


def _packed_rows(rng, K, n_max, D):
    """Random (K, n_max, D) neighbour rows with the real slots packed in
    front, as the partitioner writes them: degrees 0..D, a client with no
    row at all and some rows at full degree."""
    deg = rng.integers(0, D + 1, size=(K, n_max))
    deg[rng.random((K, n_max)) < 0.3] = 0
    deg[rng.random((K, n_max)) < 0.05] = D
    deg[rng.integers(K)] = 0
    mask = (np.arange(D) < deg[..., None]).astype(np.float32)
    idx = rng.integers(1, 4 * n_max, size=(K, n_max, D)).astype(np.int32)
    return idx * (mask > 0), mask


@pytest.mark.parametrize("seed,K,n_max,D", [
    (0, 1, 7, 32), (1, 4, 50, 32), (2, 9, 33, 32), (3, 5, 40, 8),
    (4, 6, 21, 5), (5, 3, 64, 40), (6, 16, 12, 1), (7, 2, 90, 17)])
def test_layout_places_every_row_once(seed, K, n_max, D):
    rng = np.random.default_rng(seed)
    nbr_idx, nbr_mask = _packed_rows(rng, K, n_max, D)
    idx, mask, pos, buckets = loss_pass_layout(nbr_idx, nbr_mask)
    deg = nbr_mask.sum(-1).astype(int)
    widths = [w for w, _ in buckets]
    S = sum(w * c for w, c in buckets)
    assert idx.shape == mask.shape == (K, S) and pos.shape == (K, n_max)
    assert widths == [min(w, D) for w in LOSS_PASS_WIDTHS[:-1]] + [D]
    # (first row, first slot) of each bucket among the bucket outputs
    row0 = np.cumsum([0] + [c for _, c in buckets])
    slot0 = np.cumsum([0] + [w * c for w, c in buckets])

    counts = np.zeros((K, len(buckets)), int)
    for k in range(K):
        seen = np.zeros(S, bool)
        for i in range(n_max):
            if deg[k, i] == 0:
                assert pos[k, i] == row0[-1]     # the appended zero row
                continue
            b = int(np.searchsorted(row0, pos[k, i], side="right")) - 1
            w = widths[b]
            # the first bucket wide enough: the one before is too narrow
            assert w >= deg[k, i] and (b == 0 or widths[b - 1] < deg[k, i])
            at = slot0[b] + (pos[k, i] - row0[b]) * w
            assert not seen[at:at + w].any()     # no two rows share slots
            seen[at:at + w] = True
            np.testing.assert_array_equal(idx[k, at:at + w], nbr_idx[k, i, :w])
            np.testing.assert_array_equal(mask[k, at:at + w],
                                          nbr_mask[k, i, :w])
            counts[k, b] += 1
        # the slots of no row are masked, so the layout's real slots are
        # exactly the client's
        assert not mask[k, ~seen].any()
        assert mask[k].sum() == nbr_mask[k].sum()
    assert [c for _, c in buckets] == list(counts.max(0))
