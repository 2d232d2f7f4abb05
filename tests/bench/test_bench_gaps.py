"""The training comparison's numbers on cohorts that hold a client with no
training node: its batch loss is 0 on both sides."""
import math

import numpy as np
import pytest

from bench.runners import train as T

WANT = np.array([1.10, 1.09, 0.0, 1.12])


@pytest.mark.parametrize("empty_at", [0, 2, 3])
def test_first_loss_gap_reads_an_empty_client_as_agreeing(empty_at):
    want = np.roll(WANT, empty_at - 2)
    got = want * (1 + 1e-7)
    gap = T.first_loss_gap(got, want)
    assert math.isfinite(gap)
    assert gap == pytest.approx(1e-7, rel=1e-3)


def test_first_loss_gap_catches_a_loss_on_an_empty_client():
    got = WANT.copy()
    got[2] = 0.01
    # over the cohort's median loss, (1.09 + 1.10) / 2
    assert T.first_loss_gap(got, WANT) == pytest.approx(0.01 / 1.095,
                                                        rel=1e-6)


def test_client_norm_gap_reads_an_empty_client_as_agreeing():
    rng = np.random.default_rng(0)
    want = rng.normal(size=(4, 5, 3))
    want[1] = 0.0
    assert T.client_norm_gap(want * (1 + 1e-6), want) == pytest.approx(1e-6)


def test_client_norm_gap_catches_rows_written_at_half_their_value():
    rng = np.random.default_rng(1)
    want = rng.normal(size=(4, 5, 3))
    got = want.copy()
    got[2] *= 0.5
    norms = np.sqrt((want ** 2).sum((1, 2)))
    gap = 0.5 * norms[2] / max(norms[2], np.median(norms))
    assert T.client_norm_gap(got, want) == pytest.approx(gap)


def test_first_loss_gap_is_relative_to_each_client_above_the_median():
    want = np.array([1.0, 1.0, 4.0])
    got = np.array([1.0, 1.0, 4.4])
    assert T.first_loss_gap(got, want) == pytest.approx(0.1)
