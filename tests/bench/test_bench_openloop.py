"""The serving cells' open loop times each query from when it was due: a
stall of the engine shows in the latency of every query queued behind it
and moves the 95th percentile over all queries."""
import time

import numpy as np

from bench.runners.serve import Loop
from bench.harness import Spans
from bench.trafficgen import Schedule

N, GAP, STALL_CALL, STALL_S = 100, 0.01, 20, 0.5


class FakeEngine:
    """Answers after a fixed short service time; one call stalls."""

    def __init__(self, stall_call=None):
        self.calls, self.stall_call = 0, stall_call
        self.stall_start = self.stall_end = None

        class Model:
            n_active = 10

        self.model = Model()

    def serve_batch(self, requests, policy=None):
        self.calls += 1
        if self.calls == self.stall_call:
            self.stall_start = time.perf_counter()
            time.sleep(STALL_S)
            self.stall_end = time.perf_counter()
        else:
            time.sleep(0.001)
        return [np.zeros((len(r), 3), np.float32) for r in requests], {}

    def refresh(self):
        return 0


def schedule():
    return Schedule(q_due=np.arange(N) * GAP, q_policy=np.zeros(N, np.int8),
                    q_ids=[np.array([1]) for _ in range(N)],
                    u_due=np.zeros(0), u_kind=np.zeros(0, np.int8),
                    u_edge=np.zeros((0, 2), np.int64), u_anchors=[],
                    u_feat=np.zeros((0, 4), np.float32), n_new_nodes=0)


def drive(engine):
    loop = Loop(engine, schedule(), {"refresh_every": 4}, Spans(), set())
    t0 = loop.run()
    return loop, t0


def test_stall_shows_in_the_latency_of_queued_queries():
    eng = FakeEngine(stall_call=STALL_CALL)
    loop, t0 = drive(eng)
    lat = loop.latency
    assert np.isfinite(lat).all()
    due = t0 + schedule().q_due
    # every query due while the engine stalled waited for the stall to end:
    # its latency runs from its due time, not from when the loop took it
    behind = (due >= eng.stall_start) & (due < eng.stall_end)
    assert behind.sum() >= 10
    assert (lat[behind] >= eng.stall_end - due[behind]).all()


def test_stall_moves_p95_over_all_queries():
    calm, _ = drive(FakeEngine())
    stalled, _ = drive(FakeEngine(stall_call=STALL_CALL))
    p95_calm = np.percentile(calm.latency, 95)
    p95_stalled = np.percentile(stalled.latency, 95)
    # about half the queries queue behind a 0.5 s stall
    assert p95_stalled > 0.3
    assert p95_stalled > p95_calm + 0.2
