"""The program's host spans (``repro.utils.spans``) and the named scopes in
the round chunk, which a profiler trace carries into per-layer numbers."""
import os
import re
import subprocess
import sys
import threading
import types

import jax
import numpy as np
import pytest

from repro.api import FedEngine, method_config
from repro.api.callbacks import EvalCallback
from repro.api.engine import _LIGHT_STATS
from repro.core.fedais import make_vmapped_update
from repro.faults import build_faulty_chunk
from repro.federated.partition import (
    ghost_exchange_buckets,
    loss_pass_layout,
    partition_graph,
)
from repro.graph.data import make_dataset
from repro.models.gcn import HIDDEN
from repro.sharding.fed import (
    abstract_chunk_args,
    build_sharded_chunk,
    make_client_mesh,
)
from repro.sharding.tables import (
    abstract_pod_chunk_args,
    build_pod_sharded_chunk,
    make_pod_mesh,
)
from repro.utils.spans import record_to, span

SCOPES = ("loss_pass", "local_steps", "ghost_pull", "merge")


def _scopes_in(text: str) -> set[str]:
    """The scope names found as components of the ``op_name`` paths of a
    compiled program's HLO (``vmap(local_steps)`` counts as
    ``local_steps``)."""
    found = set()
    for path in re.findall(r'op_name="([^"]+)"', text):
        for part in path.split("/"):
            inner = part
            while (m := re.fullmatch(r"[\w-]+\((.*)\)", inner)):
                inner = m.group(1)
            if inner in SCOPES:
                found.add(inner)
    return found


def test_span_records_to_an_attached_sink_only():
    with span("fed/outside"):
        pass
    sink = []
    with record_to(sink):
        with span("fed/outer", round=3, rounds=2):
            with span("fed/inner"):
                pass
    with span("fed/after"):
        pass
    assert [n for n, _, _ in sink] == ["fed/inner", "fed/outer"]
    (_, i0, i1), (_, o0, o1) = sink
    assert o0 <= i0 <= i1 <= o1


def test_spans_import_no_jax_and_record_without_it():
    code = ("import sys; from repro.utils.spans import record_to, span; "
            "sink = []\n"
            "with record_to(sink):\n"
            "    with span('fed/partition'):\n"
            "        pass\n"
            "print('jax' in sys.modules, [n for n, _, _ in sink])")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, PYTHONPATH=src))
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "False ['fed/partition']"


def test_span_as_decorator_records_each_call():
    @span("fed/step")
    def step(x):
        return x + 1

    sink = []
    with record_to(sink):
        assert step(1) == 2 and step(2) == 3
    assert [n for n, _, _ in sink] == ["fed/step", "fed/step"]


def test_a_sink_takes_only_the_spans_of_its_thread():
    sink, other = [], []

    def work():
        with span("fed/elsewhere"):
            pass
        with record_to(other):
            with span("fed/own"):
                pass

    with record_to(sink):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=30)
        with span("fed/here"):
            pass
    assert not t.is_alive()
    assert [n for n, _, _ in sink] == ["fed/here"]
    assert [n for n, _, _ in other] == ["fed/own"]


def test_record_to_restores_the_sink_attached_before():
    outer, inner = [], []
    with record_to(outer):
        with record_to(inner):
            with span("fed/a"):
                pass
        with span("fed/b"):
            pass
    assert [n for n, _, _ in inner] == ["fed/a"]
    assert [n for n, _, _ in outer] == ["fed/b"]


def test_layout_span_carries_the_slot_counts(monkeypatch):
    """``fed/loss-pass-layout`` hands the profiler the neighbour slots one
    client's loss pass gathers and the padded slots it would gather."""
    seen = []

    class Annotation:
        def __init__(self, name, **args):
            seen.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setitem(sys.modules, "jax.profiler",
                        types.SimpleNamespace(TraceAnnotation=Annotation))
    # degrees 1, 3, 0 and 4, 2, 0 over 4 slots: buckets of width 1, 2 and
    # 4 hold one row each
    deg = np.array([[1, 3, 0], [4, 2, 0]])
    mask = (np.arange(4) < deg[..., None]).astype(np.float32)
    sink = []
    with record_to(sink):
        *_, buckets = loss_pass_layout(mask.astype(np.int32), mask)
    assert buckets == ((1, 1), (2, 1), (4, 1), (4, 0), (4, 0), (4, 0))
    assert seen == [("fed/loss-pass-layout",
                     {"gathered_slots": 7, "padded_slots": 12})]
    assert [n for n, _, _ in sink] == ["fed/loss-pass-layout"]


def test_partition_records_its_layout_span():
    g = make_dataset("pubmed", scale=64, seed=0)
    sink = []
    with record_to(sink):
        partition_graph(g, 4, alpha=0.5, seed=0)
    assert [n for n, _, _ in sink] == ["fed/loss-pass-layout",
                                       "fed/partition"]


@pytest.fixture(scope="module")
def tiny():
    g = make_dataset("pubmed", scale=64, seed=0)
    fed = partition_graph(g, 4, alpha=0.5, seed=0)
    return g, fed


def _engine(g, fed, rounds=6):
    return FedEngine(g, fed, method_config("fedais", tau0=2, batch_cap=16),
                     rounds=rounds, clients_per_round=2, seed=0,
                     callbacks=[EvalCallback(3)])


def test_engine_spans_cover_set_up_chunks_and_evals(tiny):
    g, fed = tiny
    sink = []
    with record_to(sink):
        partition_graph(g, 4, alpha=0.5, seed=0)
        eng = _engine(g, fed)
        eng.run()
    names = [n for n, _, _ in sink]
    # rounds 0-5 with evals at 0, 3 and 5: chunks [0], [1-3], [4-5]
    chunks = 3
    assert names.count("fed/partition") == 1
    assert names.count("fed/engine-build") == 1
    assert names.count("fed/run") == 1
    for phase in ("fed/select", "fed/dispatch", "fed/wait", "fed/replay"):
        assert names.count(phase) == chunks, phase
    assert names.count("fed/eval") == names.count("fed/eval-wait") == 3
    # the chunk phases follow each other inside the run, evals inside the
    # replay
    run = next(r for r in sink if r[0] == "fed/run")
    phases = [r for r in sink if r[0] in ("fed/select", "fed/dispatch",
                                          "fed/wait", "fed/replay")]
    assert [r[0] for r in phases] == ["fed/select", "fed/dispatch",
                                      "fed/wait", "fed/replay"] * chunks
    assert all(run[1] <= r[1] <= r[2] <= run[2] for r in phases)
    assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
    replays = [r for r in phases if r[0] == "fed/replay"]
    for ev in (r for r in sink if r[0] == "fed/eval"):
        assert any(p[1] <= ev[1] <= ev[2] <= p[2] for p in replays)


def test_unread_counter_is_not_streamed(tiny):
    g, fed = tiny
    eng = _engine(g, fed, rounds=2)
    state = eng.init_state()
    out = eng.dispatch(state, np.array([0, 1]), 0)
    assert set(out[-1]) == {"loss_all", *_LIGHT_STATS}
    assert "mean_importance_entropy" not in _LIGHT_STATS


def _fused_args(eng, state, m=2, rounds=2):
    sels = np.stack([np.arange(m)] * rounds)
    fans = jax.numpy.stack([eng.strategy.choose_fanouts(eng, s)
                            for s in sels])
    eoffs = np.arange(rounds, dtype=np.int32) * eng.mcfg.local_epochs
    return (state.params, state.hist.hist1, state.hist.age, state.ghost_feat,
            state.prev_loss, state.key, state.arrays,
            jax.numpy.asarray(sels), fans, jax.numpy.asarray(eoffs),
            jax.numpy.asarray(state.tau, jax.numpy.int32))


def test_fused_chunk_carries_every_scope(tiny):
    g, fed = tiny
    eng = _engine(g, fed)
    state = eng.init_state()
    hlo = eng._build_fused_chunk().lower(
        *_fused_args(eng, state)).compile().as_text()
    assert _scopes_in(hlo) == set(SCOPES)


def test_faulty_chunk_carries_every_scope(tiny):
    g, fed = tiny
    eng = _engine(g, fed)
    state = eng.init_state()
    args = list(_fused_args(eng, state))
    ones = jax.numpy.ones(args[7].shape, jax.numpy.float32)
    args[9:9] = [ones, ones]            # w_stack, cmult_stack before eoffs
    chunk = build_faulty_chunk(eng._vm_raw, _LIGHT_STATS, uses_weights=False)
    assert _scopes_in(chunk.lower(*args).compile().as_text()) == set(SCOPES)


def test_sharded_chunks_carry_every_scope(tiny):
    _, fed = tiny
    mcfg = method_config("fedais", tau0=2, batch_cap=16)
    dims = dict(n_clients=fed.n_clients, cohort=2, n_max=fed.n_max,
                g_max=fed.g_max, n_feat=fed.n_features,
                n_classes=fed.n_classes, max_deg=fed.max_deg,
                loss_buckets=fed.loss_buckets)
    mesh = make_client_mesh(1)
    vm = make_vmapped_update(mcfg, fed.n_max, fed.g_max, HIDDEN[0],
                             loss_buckets=fed.loss_buckets)
    chunk = build_sharded_chunk(vm, mesh, "clients", 2, _LIGHT_STATS)
    hlo = chunk.lower(*abstract_chunk_args(mesh, **dims)).compile().as_text()
    assert _scopes_in(hlo) == set(SCOPES)

    pods = make_pod_mesh(1, 1)
    buckets = ghost_exchange_buckets(fed.ghost_owner, fed.ghost_row,
                                     fed.ghost_mask, 1)
    vm = make_vmapped_update(mcfg, fed.n_max, fed.g_max, HIDDEN[0],
                             ghost_source="prefetched",
                             loss_buckets=fed.loss_buckets)
    chunk = build_pod_sharded_chunk(vm, pods, 2, buckets, _LIGHT_STATS)
    hlo = chunk.lower(*abstract_pod_chunk_args(pods, buckets, **dims)
                      ).compile().as_text()
    assert _scopes_in(hlo) == set(SCOPES)
