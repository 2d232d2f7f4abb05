"""``correct`` comes out true for the program as it is and false with the
timed path broken underneath: one run per fault the cells can have, driven
through the harness at a small size on the CPU (the harness's look for a
chip skipped), against the committed limits."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cellkit
from bench import harness

SEED = 2147483659


def _run(tmp_path, workload, seconds=0.5):
    root = cellkit.make_root(tmp_path)
    return harness.run(root, workload, SEED, seconds, False,
                       t_start=time.perf_counter(), require_tpu=False)


def _bf16_layer(params, l, h_self, h_agg):
    """The GCN layer with its matmul inputs rounded to bfloat16: a program
    that computes below the fp32 the configuration states."""
    def mm(a, w):
        return jnp.matmul(a.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    return jax.nn.relu(mm(h_self, params[f"w_self{l}"])
                       + mm(h_agg, params[f"w_nbr{l}"]) + params[f"b{l}"])


def _unchanged_state(monkeypatch):
    """Every round hands back the parameters it was given."""
    from repro.api.engine import FedEngine

    build = FedEngine._build_fused_chunk

    def stuck(self):
        real = build(self)

        def chunk(params, *rest):
            keep = jax.tree_util.tree_map(jnp.copy, params)
            carry, light = real(params, *rest)
            return (keep,) + tuple(carry[1:]), light

        return chunk

    monkeypatch.setattr(FedEngine, "_build_fused_chunk", stuck)


def _half_batch(monkeypatch):
    """The second half of every sampled batch is left out of the loss; the
    mean is taken over the rest."""
    import repro.core.fedais as fedais

    real = fedais.sample_batch

    def half(key, probs, batch_size, mask):
        idx, valid = real(key, probs, batch_size, mask)
        return idx, valid & (jnp.arange(batch_size) < batch_size // 2)

    monkeypatch.setattr(fedais, "sample_batch", half)


def _no_exchange(monkeypatch):
    """The cross-client exchange of ghost rows is left out: pulls return
    nothing."""
    import repro.core.fedais as fedais

    def nothing(hist1_all, feats_all, owner, row, mask):
        g = owner.shape[0]
        return (jnp.zeros((g, feats_all.shape[-1]), feats_all.dtype),
                jnp.zeros((g, hist1_all.shape[-1]), hist1_all.dtype))

    monkeypatch.setattr(fedais, "pull_ghosts", nothing)


def _wrong_push(monkeypatch):
    """The historical push writes each fresh layer-1 row at half its value."""
    import repro.core.fedais as fedais

    real = fedais.push_embeddings

    def halved(hist1, age, batch_idx, values, valid):
        return real(hist1, age, batch_idx, values * 0.5, valid)

    monkeypatch.setattr(fedais, "push_embeddings", halved)


def _lower_precision_train(monkeypatch):
    import repro.models.gcn as gcn

    monkeypatch.setattr(gcn, "_sage_layer", _bf16_layer)


def _altered_answer(monkeypatch):
    """One logit of every served chunk is altered where it is produced."""
    from repro.serve.engine import QueryEngine

    real = QueryEngine._serve_chunk

    def altered(self, ids, policy):
        logits, info = real(self, ids, policy)
        logits = np.array(logits)
        logits[0, 0] += 0.5
        return logits, info

    monkeypatch.setattr(QueryEngine, "_serve_chunk", altered)


def _lower_precision_serve(monkeypatch):
    import repro.serve.engine as engine

    monkeypatch.setattr(engine, "_sage_layer", _bf16_layer)


@pytest.mark.parametrize("workload", ["tiny.train", "tiny.serve"])
def test_sound_program_is_correct(tmp_path, workload):
    res = _run(tmp_path, workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload,fault", [
    ("tiny.train", _unchanged_state),
    ("tiny.train", _half_batch),
    ("tiny.train", _no_exchange),
    ("tiny.train", _wrong_push),
    ("tiny.train", _lower_precision_train),
    ("tiny.serve", _altered_answer),
    ("tiny.serve", _lower_precision_serve),
], ids=lambda v: getattr(v, "__name__", v))
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, workload,
                                          fault):
    fault(monkeypatch)
    res = _run(tmp_path, workload)
    assert not res["correct"], res["checks"]
