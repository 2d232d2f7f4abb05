"""The reference's partition check counts nothing on the program's own
partition and counts each way a partition can lose what the configuration
states: a node, an owner, a neighbour, a ghost."""
import numpy as np
import pytest

import cellkit
from bench.references import fedais_gcn as ref
from bench.runners import train as T


@pytest.fixture(scope="module")
def data():
    cfg = cellkit.tiny_config()
    graph, fed = T.make_data(cfg)
    return cfg, graph, fed


def _copy(fed):
    import dataclasses

    return dataclasses.replace(fed, **{
        f.name: np.array(getattr(fed, f.name))
        for f in dataclasses.fields(fed)
        if isinstance(getattr(fed, f.name), np.ndarray)})


def _drop_node(fed):
    k, i = np.argwhere(fed.node_mask > 0)[0]
    fed.node_mask[k, i] = 0.0


def _swap_owner(fed):
    (k0, i0), (k1, i1) = np.argwhere(fed.node_mask > 0)[[0, -1]]
    fed.global_ids[k0, i0], fed.global_ids[k1, i1] = (fed.global_ids[k1, i1],
                                                      fed.global_ids[k0, i0])


def _drop_neighbour(fed):
    deg = fed.nbr_mask.sum(-1)
    k, i = np.argwhere((deg > 0) & (deg < fed.max_deg))[0]
    fed.nbr_mask[k, i, int(deg[k, i]) - 1] = 0.0


def _drop_ghost(fed):
    k, s = np.argwhere(fed.ghost_mask > 0)[0]
    fed.ghost_mask[k, s] = 0.0


def test_sound_partition_counts_nothing(data):
    cfg, graph, fed = data
    assert ref.check_partition(fed, graph, cfg) == 0


@pytest.mark.parametrize("fault", [_drop_node, _swap_owner, _drop_neighbour,
                                   _drop_ghost], ids=lambda f: f.__name__)
def test_lost_part_of_the_partition_is_counted(data, fault):
    cfg, graph, fed = data
    bad = _copy(fed)
    fault(bad)
    assert ref.check_partition(bad, graph, cfg) > 0
