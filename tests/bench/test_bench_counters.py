"""The FLOP, least-byte and model-FLOP counters against hand counts on a
tiny shape: F=4 features, hidden (3, 2), 2 classes."""
import numpy as np
import pytest

from bench import flops

DIMS = dict(n_features=4, hidden=(3, 2), n_classes=2)


def test_forward_flops_per_node_by_hand():
    # layer 0: 2*2*4*3 matmuls + 2*deg*4 aggregation; layer 1: 2*2*3*2 +
    # 2*deg*3; classifier 2*2*2 -> 80 + 14*deg
    for deg in (0.0, 1.0, 2.0, 4 / 3):
        assert flops.forward_flops_per_node(4, (3, 2), 2, deg) == \
            pytest.approx(80 + 14 * deg)


def test_param_count_by_hand():
    # (2*4*3 + 3) + (2*3*2 + 2) + (2*2 + 2)
    assert flops.param_count(4, (3, 2), 2) == 27 + 14 + 6


@pytest.fixture
def stats():
    node = np.array([[1, 1, 0], [1, 0, 0]], np.float32)
    train = np.array([[1, 0, 0], [1, 0, 0]], np.float32)
    nbr = np.zeros((2, 3, 2), np.float32)
    nbr[0, 0] = [1, 1]
    nbr[0, 1] = [1, 0]
    nbr[0, 2] = [1, 1]            # a padding row: not a real node, not counted
    nbr[1, 0] = [1, 0]
    ghost = np.array([[1, 0], [1, 1]], np.float32)
    return flops.client_stats(node, train, nbr, ghost, fanout=1, batch=1)


def test_client_stats_count_real_nodes_and_edges(stats):
    np.testing.assert_array_equal(stats["nodes"], [2, 1])
    np.testing.assert_array_equal(stats["ghosts"], [1, 2])
    np.testing.assert_array_equal(stats["edges"], [3, 1])
    np.testing.assert_array_equal(stats["valid"], [1, 1])
    assert stats["deg"] == pytest.approx(4 / 3)
    assert stats["deg_fanout"] == pytest.approx(1.0)


def test_round_flops_by_hand(stats):
    # cohort 2 x (loss pass 1.5 nodes x fwd(4/3) + 3 epochs x 1 valid x
    # 3 x fwd(1)) = 2 x (1.5 x 98.667 + 9 x 94)
    got = flops.round_flops(stats, cohort=2, epochs=3, **DIMS)
    assert got == pytest.approx(2 * (1.5 * (80 + 14 * 4 / 3) + 9 * 94))


def test_round_bytes_by_hand(stats):
    # per client read nodes*(F+4) + ghosts*(F+H1) + edges = 26, 23; write
    # nodes + valid*H1 = 5, 4; weights read and written: 2 x 47
    got = flops.round_bytes(stats, cohort=2, **DIMS)
    assert got == pytest.approx(4 * (2 * (31 + 27) / 2 + 94))


def test_eval_flops_by_hand():
    assert flops.eval_flops(5, 2.0, **DIMS) == pytest.approx(5 * 108)
