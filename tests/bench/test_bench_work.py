"""Work counts and the table of peaks (``bench/work.py``)."""
import numpy as np
import pytest

import benchkit  # noqa: F401  (puts the checkout on sys.path)
from bench import work


def test_ivf_bytes_count_each_probed_list_once():
    list_rows = np.array([5, 3, 7, 2])
    probe = np.array([[0, 2], [2, 3]])           # lists 0, 2, 3 probed
    w = work.ivf_scan_work(probe, list_rows, d=4)
    assert w.bytes == (5 + 7 + 2) * 4
    assert w.ops == 2 * (5 + 7 + 7 + 2) * 4


def test_flat_and_rerank_counts():
    assert work.flat_scan_work(m=3, n=100, d=8) == work.Work(800.0, 4800.0)
    assert work.rerank_work(m=2, kappa=5, dim=3) == work.Work(120.0, 60.0)
    total = work.flat_scan_work(3, 100, 8) + work.rerank_work(2, 5, 3)
    assert total == work.Work(920.0, 4860.0)


def test_least_time_takes_the_slower_bound():
    p = work.peaks("TPU v5 lite")
    assert p.hbm_bytes_per_s == 819e9 and p.int8_ops_per_s == 393e12
    memory_bound = work.Work(bytes=819e9, ops=1.0)
    assert p.least_s(memory_bound) == pytest.approx(1.0)
    compute_bound = work.Work(bytes=1.0, ops=2 * 393e12)
    assert p.least_s(compute_bound) == pytest.approx(2.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")
