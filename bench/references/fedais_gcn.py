"""Plain reference of FedAIS training and of serving its GCN.

Written from the algorithm (FedAIS, arXiv 2409.14655, Algorithm 1; the
GraphSAGE-style GCN of Eq. 2 and 6) and the semantics the configuration
states, in straightforward ``jax.numpy``: one client at a time, one epoch at
a time, dense gathers, every matmul at an explicit precision. It imports
nothing of the program under test and takes nothing the program made: the
weights come from the seed here, the graph from ``bench/graphgen.py``. The
partition's padded arrays are the configuration's input data;
:func:`check_partition` holds them to the ownership and kept edges that
:func:`owner_map` draws from the configuration's data seed.

The seeded draws follow the same conventions as the system (NumPy's
``default_rng(seed)`` for the cohort, JAX's threefry keys for the weights
and the per-epoch batch and fanout draws), so that both sample the same
nodes and the comparison is of arithmetic, not of luck.

``fault`` plants a fault in the reference for calibration: ``"half_batch"``
averages the batch loss over its first half only, ``"no_exchange"`` never
pulls ghost rows. The benchmark's runs never set it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUANT_DROP_BITS = 12        # mantissa bits zeroed from every sampling key
IMPORTANCE_FLOOR = 1e-8     # uniform floor of the importance probabilities
GUMBEL_MIN = 1e-20          # lower end of the Gumbel uniform draw
TAU_MAX = 64                # adaptive sync interval clamp (Eq. 11)
LEAVES = ("w_self0", "w_nbr0", "b0", "w_self1", "w_nbr1", "b1", "w_cls",
          "b_cls")


# ---------------------------------------------------------------------------
# graph and weights
# ---------------------------------------------------------------------------

def adjacency(n: int, edges: np.ndarray) -> list[list[int]]:
    adj: list[list[int]] = [[] for _ in range(n)]
    for u, v in np.asarray(edges).tolist():
        adj[u].append(v)
        adj[v].append(u)
    return adj


def padded_neighbors(adj: list[list[int]], max_deg: int, seed: int):
    """Each node's neighbours in padded rows of ``max_deg`` slots; a node
    with more keeps a uniform sample of ``max_deg``, drawn in node order
    from ``default_rng(seed)`` and sorted."""
    rng = np.random.default_rng(seed)
    n = len(adj)
    idx = np.zeros((n, max_deg), np.int32)
    mask = np.zeros((n, max_deg), np.float32)
    for i, nbrs in enumerate(adj):
        if len(nbrs) > max_deg:
            nbrs = np.sort(rng.choice(nbrs, size=max_deg, replace=False))
        idx[i, :len(nbrs)] = nbrs
        mask[i, :len(nbrs)] = 1.0
    return idx, mask


def init_params(seed: int, n_features: int, n_classes: int, hidden) -> dict:
    """Glorot-normal weights and zero biases from ``PRNGKey(seed + 1)``:
    keys split five ways, in layer order self, neighbour, ..., classifier."""
    dims = (n_features, *hidden)
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 2 * len(hidden) + 1)

    def glorot(k, a, b):
        return jax.random.normal(k, (a, b), jnp.float32) * (2.0 / (a + b)) ** 0.5

    p = {}
    for l in range(len(hidden)):
        p[f"w_self{l}"] = glorot(ks[2 * l], dims[l], dims[l + 1])
        p[f"w_nbr{l}"] = glorot(ks[2 * l + 1], dims[l], dims[l + 1])
        p[f"b{l}"] = jnp.zeros((dims[l + 1],), jnp.float32)
    p["w_cls"] = glorot(ks[-1], hidden[-1], n_classes)
    p["b_cls"] = jnp.zeros((n_classes,), jnp.float32)
    return p


def owner_map(graph, cfg: dict):
    """Which client holds each node, and the edges the clients keep, drawn
    as the configuration states from ``default_rng(data_seed)``: per class
    in label order, the class's ids shuffled and split by multinomial counts
    of Dirichlet(``alpha``) shares over the clients; then each within-client
    edge kept with probability ``edge_keep`` (one uniform draw per edge, in
    edge order); every cross-client edge is kept."""
    rng = np.random.default_rng(cfg["data_seed"])
    K, labels = cfg["clients"], np.asarray(graph.labels)
    assign = np.empty(graph.n_nodes, np.int64)
    for cls in range(graph.n_classes):
        ids = np.flatnonzero(labels == cls)
        rng.shuffle(ids)
        share = rng.dirichlet(np.full(K, cfg["alpha"]))
        assign[ids] = np.repeat(np.arange(K), rng.multinomial(len(ids), share))
    e = np.asarray(graph.edges, np.int64)
    same = assign[e[:, 0]] == assign[e[:, 1]]
    within = e[same]
    if cfg["edge_keep"] < 1.0 and len(within):
        within = within[rng.random(len(within)) < cfg["edge_keep"]]
    return assign, np.concatenate([within, e[~same]])


def check_partition(fed, graph, cfg: dict) -> int:
    """Count the ways the partition departs from :func:`owner_map` over the
    raw graph (0 for a sound partition): nodes not held exactly once, or
    held by another client, or with other features, label or train flag;
    neighbour rows that are not the node's kept neighbours (all of them, or
    a sample of ``max_deg`` distinct ones where it has more); ghost slots
    that are not exactly the client's cross-client neighbours."""
    n, D = graph.n_nodes, cfg["max_deg"]
    assign, kept = owner_map(graph, cfg)
    gid = np.asarray(fed.global_ids, np.int64)
    own = np.asarray(fed.node_mask) > 0
    ko, io = np.nonzero(own)
    held = gid[ko, io]
    inside = (held >= 0) & (held < n)
    errors = int((~inside).sum())
    ko, io, held = ko[inside], io[inside], held[inside]
    errors += int((np.bincount(held, minlength=n) != 1).sum())
    errors += int((assign[held] != ko).sum())
    errors += int(np.any(fed.features[ko, io] != graph.features[held],
                         axis=1).sum())
    errors += int((fed.labels[ko, io] != graph.labels[held]).sum())
    errors += int(((fed.train_mask[ko, io] > 0)
                   != np.asarray(graph.train_mask)[held]).sum())

    real = np.asarray(fed.ghost_mask) > 0
    ghost_gid = np.full(real.shape, -1, np.int64)
    gk, gr = np.nonzero(real)
    owner = np.asarray(fed.ghost_owner)[gk, gr]
    row = np.asarray(fed.ghost_row)[gk, gr]
    ok = (owner >= 0) & (owner < gid.shape[0]) & (row >= 0) & (row < gid.shape[1])
    ghost_gid[gk[ok], gr[ok]] = gid[owner[ok], row[ok]]
    errors += int((~ok).sum())
    table = np.concatenate([gid, ghost_gid], axis=1)          # (K, n_tot)

    nbrs: list[list[int]] = [[] for _ in range(n)]
    for u, v in kept.tolist():
        nbrs[u].append(v)
        nbrs[v].append(u)
    mask = np.asarray(fed.nbr_mask) > 0
    errors += int(mask[~own].sum())          # padding rows have no neighbours
    rows = table[ko[:, None], np.asarray(fed.nbr_idx)[ko, io]]
    for u, r, m in zip(held.tolist(), rows, mask[ko, io]):
        got, want = r[m].tolist(), set(nbrs[u])
        if len(want) <= D:
            errors += len(set(got) ^ want) + len(got) - len(set(got))
        else:
            errors += (abs(len(got) - D) + len(set(got) - want)
                       + len(got) - len(set(got)))

    for k in range(gid.shape[0]):
        slots = ghost_gid[k][real[k]].tolist()
        mine = held[ko == k]
        need = {v for u in mine.tolist() for v in nbrs[u] if assign[v] != k}
        errors += len(set(slots) ^ need) + len(slots) - len(set(slots))
    return errors


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _mm(a, b, prec):
    return jnp.matmul(a, b, precision=prec)


def _mean_rows(table, idx, mask):
    s = (table[idx] * mask[..., None]).sum(1)
    return s / jnp.maximum(mask.sum(-1, keepdims=True), 1.0)


def _layer(p, l, h_self, h_agg, prec):
    return jax.nn.relu(_mm(h_self, p[f"w_self{l}"], prec)
                       + _mm(h_agg, p[f"w_nbr{l}"], prec) + p[f"b{l}"])


def _nll(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return lse - gold


def _quantize(x):
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    keep = jnp.uint32(0xFFFFFFFF & ~((1 << QUANT_DROP_BITS) - 1))
    return jax.lax.bitcast_convert_type(u & keep, jnp.float32)


def _client_forward(p, feats, ghost_feat, hist1, nbr_idx, nbr_mask, rows,
                    keep, prec):
    """Logits and fresh layer-1 rows of ``rows`` of one client: layer 0 over
    own and ghost features, layer 1 over the historical layer-1 table with
    the fresh rows written in (no gradient through history)."""
    table0 = jnp.concatenate([feats, ghost_feat], axis=0)
    idx = nbr_idx[rows]
    mask = nbr_mask[rows] if keep is None else nbr_mask[rows] * keep
    h1 = _layer(p, 0, feats[rows], _mean_rows(table0, idx, mask), prec)
    table1 = jax.lax.stop_gradient(hist1).at[rows].set(h1)
    h2 = _layer(p, 1, h1, _mean_rows(table1, idx, mask), prec)
    return _mm(h2, p["w_cls"], prec) + p["b_cls"], h1


@functools.partial(jax.jit, static_argnames=("hp", "prec", "fault"))
def local_update(p, c, feats_all, hist1_all, hist1, age, ghost_feat,
                 prev_loss, tau, epoch0, key, *, hp, prec, fault):
    """Algorithm 1 lines 10-19 for one client: loss pass and importance
    probabilities, then J epochs of sampled batch, fanout, tau-gated ghost
    pull, AdamW step and historical push."""
    (n_max, bsz, J, fanout, lr, b1, b2, eps, wd) = hp
    tm = c["train_mask"] * c["node_mask"]
    rows = jnp.arange(n_max)
    logits, _ = _client_forward(p, c["features"], ghost_feat, hist1,
                                c["nbr_idx"], c["nbr_mask"], rows, None, prec)
    loss_all = _nll(logits, c["labels"]) * c["node_mask"]
    score = jnp.where(prev_loss < 0.0, jnp.abs(loss_all),
                      jnp.abs(loss_all - prev_loss)) * tm
    s = score * tm + IMPORTANCE_FLOOR * tm
    probs = s / jnp.maximum(s.sum(), 1e-30)

    zeros = {k: jnp.zeros_like(v) for k, v in p.items()}
    mu, nu = dict(zeros), dict(zeros)
    losses, grad_norms = [], None
    for j in range(J):
        key, kb, kn = jax.random.split(key, 3)
        logp = jnp.log(jnp.maximum(probs, 1e-30)) + jnp.where(tm > 0, 0.0,
                                                              -1e30)
        u = jax.random.uniform(kb, probs.shape, minval=GUMBEL_MIN, maxval=1.0)
        g = -jnp.log(-jnp.log(u))
        batch = jnp.argsort(-_quantize(logp + g), stable=True)[:bsz]
        valid = tm[batch] > 0
        bmask = c["nbr_mask"][batch]
        r = jax.random.uniform(kn, bmask.shape)
        r = _quantize(jnp.where(bmask > 0, r, 2.0))
        order = jnp.argsort(jnp.argsort(r, axis=-1, stable=True), axis=-1,
                            stable=True)
        keep = (order < fanout).astype(jnp.float32)

        if fault != "no_exchange":
            bidx = c["nbr_idx"][batch]
            ref = (bidx >= n_max) & (bmask * keep > 0) & valid[:, None]
            slot = jnp.where(ref, bidx - n_max, 0)
            need = jnp.zeros(ghost_feat.shape[:1], jnp.float32).at[
                slot.reshape(-1)].max(ref.reshape(-1).astype(jnp.float32))
            need = need * c["ghost_mask"]
            owner = jnp.maximum(c["ghost_owner"], 0)
            gm = c["ghost_mask"][:, None]
            gf = feats_all[owner, c["ghost_row"]] * gm
            gh = hist1_all[owner, c["ghost_row"]] * gm
            sync = (epoch0 + j) % jnp.maximum(tau, 1) == 0
            take = sync & (need[:, None] > 0)
            ghost_feat = jnp.where(take, gf, ghost_feat)
            hist1 = hist1.at[n_max:].set(jnp.where(take, gh, hist1[n_max:]))

        w = valid.astype(jnp.float32) * tm[batch]
        if fault == "half_batch":
            w = w * (jnp.arange(bsz) < bsz // 2)

        def batch_loss(q):
            lg, h1 = _client_forward(q, c["features"], ghost_feat, hist1,
                                     c["nbr_idx"], c["nbr_mask"], batch, keep,
                                     prec)
            nll = _nll(lg, c["labels"][batch])
            return (nll * w).sum() / jnp.maximum(w.sum(), 1.0), h1

        (loss, h1), grads = jax.value_and_grad(batch_loss, has_aux=True)(p)
        if j == 0:
            grad_norms = jnp.stack([jnp.linalg.norm(grads[k]) for k in LEAVES])
        step = j + 1
        b1c, b2c = 1.0 - b1 ** step, 1.0 - b2 ** step
        new_p = {}
        for k in p:
            mu[k] = mu[k] * b1 + grads[k] * (1.0 - b1)
            nu[k] = nu[k] * b2 + jnp.square(grads[k]) * (1.0 - b2)
            upd = (mu[k] / b1c) / (jnp.sqrt(nu[k] / b2c) + eps)
            new_p[k] = p[k] - lr * (upd + wd * p[k])
        p = new_p
        pushed = valid & (c["node_mask"][batch] > 0)
        hist1 = hist1.at[batch].set(jnp.where(pushed[:, None], h1,
                                              hist1[batch]))
        age = (age + 1).at[batch].set(jnp.where(pushed, 0, age[batch] + 1))
        losses.append(loss)
    return p, hist1, age, ghost_feat, loss_all, jnp.stack(losses), grad_norms


@functools.partial(jax.jit, static_argnames=("prec",))
def full_forward(p, feats, nbr_idx, nbr_mask, *, prec):
    h = feats
    for l in range(2):
        h = _layer(p, l, h, _mean_rows(h, nbr_idx, nbr_mask), prec)
    return _mm(h, p["w_cls"], prec) + p["b_cls"]


def eval_logits(p, eval_graph, prec) -> np.ndarray:
    """The server's logits for every node of the full graph."""
    feats, idx, mask, _, _ = eval_graph
    return np.asarray(full_forward(p, feats, idx, mask, prec=prec))


def test_loss(p, eval_graph, prec) -> float:
    _, _, _, labels, test = eval_graph
    lg = eval_logits(p, eval_graph, prec)
    nll = np.asarray(_nll(jnp.asarray(lg), labels), np.float64)
    return float(nll[test].mean())


# ---------------------------------------------------------------------------
# federated training
# ---------------------------------------------------------------------------

def train_reference(cfg: dict, graph, fed, seed: int, rounds: int, *,
                    keep_rounds, prec: str = "highest",
                    fault: str | None = None) -> dict:
    """Run ``rounds`` FedAIS rounds from ``seed``. Returns each round's
    (cohort, J) batch losses, the parameters after the rounds in
    ``keep_rounds`` (and before round 0 under key -1), the test loss at each
    eval round, round 0's cohort and its clients' own layer-1 rows after
    the round's pushes, the norms of the tables after the last round, the
    per-leaf norms of round 0's first gradients (the largest over its
    cohort), and the server's
    evaluation graph."""
    K, n_max, g_max = fed.n_clients, fed.n_max, fed.g_max
    F, C = fed.n_features, fed.n_classes
    H1 = cfg["model"]["hidden"][0]
    m, J = cfg["cohort"], cfg["local_epochs"]
    bsz = max(1, min(cfg["batch_cap"], int(round(n_max * cfg["sample_ratio"]))))
    opt = cfg["optimizer"]
    hp = (n_max, bsz, J, cfg["neighbor_fanout"], cfg["lr"], opt["b1"],
          opt["b2"], opt["eps"], opt["weight_decay"])

    adj = adjacency(graph.n_nodes, graph.edges)
    e_idx, e_mask = padded_neighbors(adj, cfg["max_deg"], seed)
    eval_graph = (jnp.asarray(graph.features), jnp.asarray(e_idx),
                  jnp.asarray(e_mask), jnp.asarray(graph.labels),
                  np.asarray(graph.test_mask, bool))

    arrays = {k: jnp.asarray(getattr(fed, k)) for k in (
        "features", "labels", "node_mask", "train_mask", "nbr_idx",
        "nbr_mask", "ghost_owner", "ghost_row", "ghost_mask")}
    params = init_params(seed, F, C, cfg["model"]["hidden"])
    hist1 = jnp.zeros((K, n_max + g_max, H1), jnp.float32)
    age = jnp.zeros((K, n_max + g_max), jnp.int32)
    ghost = jnp.zeros((K, g_max, F), jnp.float32)
    prev = jnp.full((K, n_max), -1.0, jnp.float32)
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    tau, initial_loss = cfg["tau0"], None
    out = {"losses": [], "params": {-1: jax.tree_util.tree_map(np.asarray,
                                                                params)},
           "test_loss": {}, "grad_norms": None, "eval_graph": eval_graph}

    for t in range(rounds):
        sel = rng.choice(K, size=min(m, K), replace=False)
        ks = jax.random.split(key, len(sel) + 1)
        key = ks[0]
        snap = hist1                  # round-start table every client reads
        results = []
        for i, k in enumerate(sel.tolist()):
            c = {name: v[k] for name, v in arrays.items()}
            results.append(local_update(
                params, c, arrays["features"], snap, hist1[k], age[k],
                ghost[k], prev[k], jnp.int32(tau), jnp.int32(t * J), ks[i + 1],
                hp=hp, prec=prec, fault=fault))
        if out["grad_norms"] is None:
            # per leaf, the largest over the cohort: a client that holds no
            # training node has a first gradient of nought
            out["grad_norms"] = np.max(np.stack(
                [np.asarray(r[6], np.float64) for r in results]), axis=0)
        params = {name: jnp.mean(jnp.stack([r[0][name] for r in results]), 0)
                  for name in params}
        sj = jnp.asarray(sel)
        hist1 = hist1.at[sj].set(jnp.stack([r[1] for r in results]))
        age = age.at[sj].set(jnp.stack([r[2] for r in results]))
        ghost = ghost.at[sj].set(jnp.stack([r[3] for r in results]))
        prev = prev.at[sj].set(jnp.stack([r[4] for r in results]))
        out["losses"].append(np.stack([np.asarray(r[5], np.float64)
                                       for r in results]))
        if t == 0:
            out["prev_loss0"] = np.asarray(prev)
            out["sel0"] = sel
            out["hist1_0"] = np.asarray(hist1[sj, :n_max])
        if t in keep_rounds:
            out["params"][t] = jax.tree_util.tree_map(np.asarray, params)
        if t % cfg["eval_every"] == 0:
            loss = test_loss(params, eval_graph, prec)
            out["test_loss"][t] = loss
            if initial_loss is None:
                initial_loss = max(loss, 1e-6)
            if math.isfinite(loss):
                tau = math.ceil(math.sqrt(max(loss, 0.0) / initial_loss)
                                * cfg["tau0"])
                tau = max(1, min(TAU_MAX, tau))
            else:
                tau = cfg["tau0"]
    out["norms"] = {"hist1": float(jnp.linalg.norm(hist1)),
                    "ghost_feat": float(jnp.linalg.norm(ghost)),
                    "prev_loss": float(jnp.linalg.norm(prev))}
    return out


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

class ServeReplay:
    """The serving graph and its layer-1 cache, replayed from the events the
    window ran, in their order.

    Semantics: a row's cached layer-1 embedding is the one computed, from
    its features and its neighbour row, when it was last written (the warm
    fill over every allocated row, or a refresh); an edge insert adds each
    endpoint to the other's row (first free slot; a full row replaces a
    slot drawn from ``default_rng(seed)``; duplicates and self-loops change
    nothing) and makes the endpoints of a change stale; a new node is
    stale, as are the endpoints its edges change; a refresh rewrites every
    stale row. A ``historical`` query reads the cache for itself and its
    neighbours; a ``fresh`` query recomputes all of them from the graph as
    it stands. Embeddings are kept as records (row, neighbour row at write
    time), computed in blocks once the replay is done."""

    def __init__(self, features: np.ndarray, edges: np.ndarray, capacity: int,
                 max_deg: int, seed: int):
        n, F = features.shape
        idx, mask = padded_neighbors(adjacency(n, edges), max_deg, seed)
        self.n, self.n_active, self.D = n, n, max_deg
        self.feat = np.zeros((capacity, F), np.float32)
        self.feat[:n] = features
        self.idx = np.zeros((capacity, max_deg), np.int32)
        self.mask = np.zeros((capacity, max_deg), np.float32)
        self.idx[:n], self.mask[:n] = idx, mask
        self.warm = (self.idx.copy(), self.mask.copy())
        self.rng = np.random.default_rng(seed)
        self.valid = np.ones(capacity, bool)
        self.cache: dict[int, int] = {}        # row -> record (absent: warm)
        self.records: list = []                # (row, zero_feat, idx, mask)
        self.warm_records: dict[int, int] = {}
        self.queries: list = []                # (self recs, nbr recs, masks)

    def _record(self, row: int, zero: bool, idx, mask) -> int:
        self.records.append((row, zero, np.array(idx), np.array(mask)))
        return len(self.records) - 1

    def _cached(self, row: int) -> int:
        if row in self.cache:
            return self.cache[row]
        if row not in self.warm_records:
            self.warm_records[row] = self._record(
                row, row >= self.n, self.warm[0][row], self.warm[1][row])
        return self.warm_records[row]

    def _insert(self, u: int, v: int) -> bool:
        live = self.mask[u] > 0
        if v in self.idx[u][live]:
            return False
        slot = (int(self.rng.integers(self.D)) if live.all()
                else int(np.argmin(live)))
        self.idx[u, slot], self.mask[u, slot] = v, 1.0
        return True

    def add_edges(self, edges) -> set:
        touched = set()
        for u, v in edges:
            u, v = int(u), int(v)
            if u == v:
                continue
            changed = self._insert(u, v)
            changed |= self._insert(v, u)
            if changed:
                touched.update((u, v))
        self.valid[list(touched)] = False
        return touched

    def add_node(self, feat: np.ndarray, anchors) -> None:
        new = self.n_active
        self.n_active += 1
        self.feat[new] = feat
        self.valid[new] = False
        self.add_edges([(new, int(a)) for a in anchors])

    def refresh(self) -> None:
        for r in np.flatnonzero(~self.valid[:self.n_active]).tolist():
            self.cache[r] = self._record(r, False, self.idx[r], self.mask[r])
        self.valid[:self.n_active] = True

    def query(self, ids: np.ndarray, policy: str) -> None:
        selfs, nbrs, masks = [], [], []
        for q in np.asarray(ids).tolist():
            row_idx, row_mask = self.idx[q], self.mask[q]
            if policy == "fresh":
                s = self._record(q, False, row_idx, row_mask)
                nb = [self._record(int(j), False, self.idx[j], self.mask[j])
                      if m > 0 else s for j, m in zip(row_idx, row_mask)]
            else:
                s = self._cached(q)
                nb = [self._cached(int(j)) if m > 0 else s
                      for j, m in zip(row_idx, row_mask)]
            selfs.append(s)
            nbrs.append(nb)
            masks.append(np.array(row_mask))
        self.queries.append((np.array(selfs), np.array(nbrs),
                             np.stack(masks)))

    def logits(self, params, prec: str, block: int = 2048) -> list:
        """Logits of every replayed query, in replay order."""
        rec_row = np.array([r[0] for r in self.records], np.int32)
        rec_zero = np.array([r[1] for r in self.records], bool)
        rec_idx = np.stack([r[2] for r in self.records]).astype(np.int32)
        rec_mask = np.stack([r[3] for r in self.records]).astype(np.float32)
        feat = jnp.asarray(self.feat)
        h1 = []
        for i in range(0, len(rec_row), block):
            sl = slice(i, i + block)
            pad = block - len(rec_row[sl])
            h1.append(_record_h1(
                params, feat, jnp.asarray(np.pad(rec_row[sl], (0, pad))),
                jnp.asarray(np.pad(rec_zero[sl], (0, pad))),
                jnp.asarray(np.pad(rec_idx[sl], ((0, pad), (0, 0)))),
                jnp.asarray(np.pad(rec_mask[sl], ((0, pad), (0, 0)))),
                prec=prec)[:block - pad])
        h1 = jnp.concatenate(h1)
        return [np.asarray(_query_logits(params, h1, jnp.asarray(s),
                                         jnp.asarray(nb), jnp.asarray(m),
                                         prec=prec))
                for s, nb, m in self.queries]


@functools.partial(jax.jit, static_argnames=("prec",))
def _record_h1(p, feat, row, zero, idx, mask, *, prec):
    own = jnp.where(zero[:, None], 0.0, feat[row])
    return _layer(p, 0, own, _mean_rows(feat, idx, mask), prec)


@functools.partial(jax.jit, static_argnames=("prec",))
def _query_logits(p, h1, selfs, nbrs, mask, *, prec):
    agg = (h1[nbrs] * mask[..., None]).sum(1) / jnp.maximum(
        mask.sum(-1, keepdims=True), 1.0)
    h2 = _layer(p, 1, h1[selfs], agg, prec)
    return _mm(h2, p["w_cls"], prec) + p["b_cls"]
