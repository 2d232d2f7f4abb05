"""GraphSAGE-style GCN (the paper's model: 2 hidden layers, 256/128) with
historical-embedding support — the JAX realisation of paper Eq. (2)/(6).

The client-side forward prunes the computation graph to the batch nodes plus
their direct 1-hop neighbors; deeper recursion is replaced by table lookups:
layer-0 neighbors read exact own features / synced ghost features, layer-1
neighbors read fresh in-batch values scattered over the historical table.
Gradients flow only through fresh (in-batch) entries — GNNAutoScale
semantics extended across clients.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init

HIDDEN = (256, 128)


def gcn_init(key, n_features: int, n_classes: int, hidden=HIDDEN, dtype=jnp.float32) -> dict:
    dims = (n_features, *hidden)
    ks = jax.random.split(key, 2 * len(hidden) + 1)
    params: dict = {}
    for l in range(len(hidden)):
        params[f"w_self{l}"] = dense_init(ks[2 * l], dims[l], dims[l + 1], dtype)
        params[f"w_nbr{l}"] = dense_init(ks[2 * l + 1], dims[l], dims[l + 1], dtype)
        params[f"b{l}"] = jnp.zeros((dims[l + 1],), dtype)
    params["w_cls"] = dense_init(ks[-1], hidden[-1], n_classes, dtype)
    params["b_cls"] = jnp.zeros((n_classes,), dtype)
    return params


AGG_BACKENDS = ("gather", "segment", "spmm")


def _aggregate(table: jnp.ndarray, nbr_idx: jnp.ndarray, nbr_mask: jnp.ndarray) -> jnp.ndarray:
    """Mean-aggregate neighbor rows. table (M, d); nbr_idx/mask (b, K)."""
    gathered = table[nbr_idx] * nbr_mask[..., None]
    deg = jnp.maximum(nbr_mask.sum(-1, keepdims=True), 1.0)
    return gathered.sum(1) / deg


def neighbor_aggregate(
    table: jnp.ndarray,
    nbr_idx: jnp.ndarray,
    nbr_mask: jnp.ndarray,
    *,
    backend: str = "gather",
    csr: dict | None = None,
    adj: jnp.ndarray | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Mean-aggregate neighbor rows through a pluggable backend.

    ``gather``   the dense (b, K, d) gather — the bit-parity default.
    ``segment``  CSR ``segment_sum`` over edge arrays. ``csr`` may be the
                 precomputed form (``graph.csr.csr_from_padded``, eval /
                 serve: only the E real edges) or None, in which case the
                 jit-stable bucketed form is derived in-trace from the
                 (possibly traced) batch rows
                 (``graph.csr.bucketed_csr_from_padded`` — the training hot
                 path). Either way the padded (b, K, d) gather is never
                 materialized; the sum always runs over ``b + 1`` segments
                 (padding slots land in the sliced-off overflow segment).
    ``spmm``     the block-sparse Pallas kernel (kernels/spmm) against a
                 row-normalised adjacency, block mask derived from the
                 neighbor list; differentiable in ``table`` (custom VJP —
                 the training path takes grads through it). ``interpret``
                 auto-detects (compiled on TPU, interpreter elsewhere).
                 Pass a precomputed ``adj`` (build_eval_graph does) so the
                 adjacency is built once per graph, not per layer per call.

    ``segment``/``spmm`` are numerically equivalent to ``gather`` within FP
    tolerance (different summation order), pinned by tests/test_fused.py
    and tests/test_train_backend.py.
    """
    if backend == "gather":
        return _aggregate(table, nbr_idx, nbr_mask)
    if backend == "segment":
        if csr is None:
            from repro.graph.csr import bucketed_csr_from_padded

            csr = bucketed_csr_from_padded(nbr_idx, nbr_mask)
        b = nbr_idx.shape[0]
        seg = jax.ops.segment_sum(table[csr["src"]], csr["dst"],
                                  num_segments=b + 1)
        return seg[:b] * csr["inv_deg"][:, None]
    if backend == "spmm":
        from repro.kernels.spmm.ops import neighbor_spmm

        return neighbor_spmm(table, nbr_idx, nbr_mask, adj=adj,
                             interpret=interpret)
    raise ValueError(f"unknown aggregation backend {backend!r}; known: {AGG_BACKENDS}")


def _sage_layer(params: dict, l: int, h_self: jnp.ndarray, h_agg: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.relu(
        h_self @ params[f"w_self{l}"] + h_agg @ params[f"w_nbr{l}"] + params[f"b{l}"]
    )


def gcn_batch_forward(
    params: dict,
    features: jnp.ndarray,      # (n, F) own features
    ghost_feat: jnp.ndarray,    # (g, F) synced ghost features (historical l=0)
    hist1: jnp.ndarray,         # (n + g, H1) historical layer-1 embeddings
    nbr_idx: jnp.ndarray,       # (n, K) into [own | ghost]
    nbr_mask: jnp.ndarray,      # (n, K)
    batch_idx: jnp.ndarray,     # (b,) rows of this batch
    nbr_keep: jnp.ndarray | None = None,   # optional (b, K) extra neighbor mask
    *,
    backend: str = "gather",
    interpret: bool | None = None,
):
    """Returns (logits (b, C), fresh_h1 (b, H1), h2 (b, H2)).

    ``backend`` picks the batch neighbor aggregation (``neighbor_aggregate``):
    the batch shapes (b, K) are static under jit even when ``batch_idx`` is
    traced, so the segment backend's bucketed CSR and the spmm backend's
    (b, n_tot) adjacency are derived in-trace, once, and shared by both
    layers (layer 0's and layer 1's tables have the same row count).
    """
    table0 = jnp.concatenate([features, ghost_feat], axis=0)
    b_idx = nbr_idx[batch_idx]
    b_mask = nbr_mask[batch_idx]
    if nbr_keep is not None:
        b_mask = b_mask * nbr_keep

    csr = adj = None
    if backend == "segment":
        from repro.graph.csr import bucketed_csr_from_padded

        csr = bucketed_csr_from_padded(b_idx, b_mask)
    elif backend == "spmm":
        from repro.kernels.spmm.ops import adjacency_from_neighbors

        adj = adjacency_from_neighbors(b_idx, b_mask, table0.shape[0])

    def agg(table):
        return neighbor_aggregate(table, b_idx, b_mask, backend=backend,
                                  csr=csr, adj=adj, interpret=interpret)

    h_self0 = features[batch_idx]
    agg0 = agg(table0)
    h1 = _sage_layer(params, 0, h_self0, agg0)                  # (b, 256)

    # fresh in-batch values over the historical table (stop-grad on history)
    table1 = jax.lax.stop_gradient(hist1).at[batch_idx].set(h1)
    agg1 = agg(table1)
    h2 = _sage_layer(params, 1, h1, agg1)                       # (b, 128)

    logits = h2 @ params["w_cls"] + params["b_cls"]
    return logits, h1, h2


def gcn_loss_pass_forward(
    params: dict,
    features: jnp.ndarray,      # (n, F) own features
    ghost_feat: jnp.ndarray,    # (g, F) synced ghost features (historical l=0)
    hist1: jnp.ndarray,         # (n + g, H1) historical layer-1 embeddings
    idx: jnp.ndarray,           # (S,) the buckets' neighbour slots
    mask: jnp.ndarray,          # (S,)
    pos: jnp.ndarray,           # (n,) each row's place in the bucket outputs
    buckets: tuple,             # ((width, capacity), ...), static
):
    """Returns (logits (n, C), h1 (n, H1)) of every row of one client: the
    loss pass, ``gcn_batch_forward`` over ``arange(n)`` with the ``gather``
    backend, over the degree-bucketed layout of
    ``federated.partition.loss_pass_layout``.

    Each bucket holds the first ``width`` slots of its rows, which hold all
    of their real neighbours. One gather per layer fetches every bucket's
    slots; each bucket then takes the masked mean over its slots, as
    ``_aggregate`` does without the masked zeros (XLA may order the sum
    otherwise, so a row can differ from the padded form in its last bits).
    One gather of n rows puts the aggregates back in row order. A gather
    per bucket instead stalled the round chunk at silo16's sizes on a TPU
    v5e.
    """
    def agg(table):
        gathered = table[idx] * mask[:, None]
        parts, start = [], 0
        for width, cap in buckets:
            if cap:
                end = start + width * cap
                deg = mask[start:end].reshape(cap, width).sum(-1, keepdims=True)
                parts.append(gathered[start:end].reshape(cap, width, -1).sum(1)
                             / jnp.maximum(deg, 1.0))
                start = end
        parts.append(jnp.zeros((1, table.shape[1]), table.dtype))
        return jnp.concatenate(parts)[pos]

    h1 = _sage_layer(params, 0, features,
                     agg(jnp.concatenate([features, ghost_feat], axis=0)))
    h2 = _sage_layer(params, 1, h1, agg(hist1.at[:features.shape[0]].set(h1)))
    return h2 @ params["w_cls"] + params["b_cls"], h1


def gcn_full_forward(params, features, nbr_idx, nbr_mask, *,
                     backend: str = "gather", csr: dict | None = None,
                     adj: jnp.ndarray | None = None,
                     interpret: bool | None = None):
    """Exact full-graph forward (server-side evaluation; no history).

    This is the per-round O(N·K·F) eval hot spot; ``backend`` selects the
    neighbor-aggregation implementation (see ``neighbor_aggregate``).
    """
    h = features
    for l in range(len(HIDDEN)):
        agg = neighbor_aggregate(h, nbr_idx, nbr_mask, backend=backend,
                                 csr=csr, adj=adj, interpret=interpret)
        h = _sage_layer(params, l, h, agg)
    return h @ params["w_cls"] + params["b_cls"]


def per_node_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """(b, C), (b,) -> (b,) cross-entropy per node (no reduction)."""
    logits32 = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits32, axis=-1)
    gold = jnp.take_along_axis(logits32, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return lse - gold


def gcn_param_count(n_features: int, n_classes: int, hidden=HIDDEN) -> int:
    dims = (n_features, *hidden)
    total = 0
    for l in range(len(hidden)):
        total += 2 * dims[l] * dims[l + 1] + dims[l + 1]
    total += hidden[-1] * n_classes + n_classes
    return total


def gcn_flops_per_node(n_features: int, n_classes: int, avg_deg: float, hidden=HIDDEN) -> float:
    """Forward FLOPs per training node (matmuls + aggregation)."""
    dims = (n_features, *hidden)
    fl = 0.0
    for l in range(len(hidden)):
        fl += 2 * 2 * dims[l] * dims[l + 1]       # self + nbr matmuls
        fl += 2 * avg_deg * dims[l]               # mean aggregation
    fl += 2 * hidden[-1] * n_classes
    return fl
