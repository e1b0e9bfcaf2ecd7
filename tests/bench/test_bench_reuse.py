"""The ``ivf_scan_reuse`` reader: rows scored over distinct rows streamed
in the traced batches' IVF fine scan."""
import numpy as np
import pytest

import benchkit
from bench import run, work


def _reader():
    return run.Spec(benchkit.REPO).reader("ivf_scan_reuse")


def test_reuse_counts_rows_scored_over_distinct_rows():
    list_rows = np.array([5, 3, 7, 2])
    # batch 0: lists 0, 2, 2, 3 scored (21 rows), 0, 2, 3 distinct (14)
    # batch 1: list 1 twice (6 rows), once distinct (3)
    w0 = work.ivf_scan_work(np.array([[0, 2], [2, 3]]), list_rows, d=4)
    w1 = work.ivf_scan_work(np.array([[1], [1]]), list_rows, d=4)
    r = run.Run(work={0: {"fine": w0}, 1: {"fine": w1}})
    assert _reader()(r) == pytest.approx((21 + 6) / (14 + 3))


def test_reuse_is_one_when_no_list_is_shared():
    list_rows = np.array([5, 3, 7, 2])
    w = work.ivf_scan_work(np.array([[0, 1], [2, 3]]), list_rows, d=8)
    assert _reader()(run.Run(work={4: {"fine": w}})) == pytest.approx(1.0)


@pytest.mark.parametrize("w", [{}, {3: {"fine": None}}])
def test_reuse_reads_nothing_without_an_ivf_scan(w):
    """Untraced runs carry no work, and the flat cells' fine work is None:
    the reader returns nothing and does not raise."""
    assert _reader()(run.Run(work=w)) is None
