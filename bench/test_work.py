"""CPU checks of the benchmark's work counters and peak table.

  JAX_PLATFORMS=cpu python -m pytest -q bench/test_work.py
"""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import work  # noqa: E402


def test_peaks_known_kind():
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_peaks_refuse_unknown_kind():
    with pytest.raises(ValueError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


def test_peak_table_names_its_source():
    with open(work._PEAKS) as f:
        assert "TPU v5e" in json.load(f)["source"]


def test_least_time_takes_the_larger_bound():
    p = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000, 50, p) == 10.0     # compute bound
    assert work.least_time(100, 500, p) == 50.0     # memory bound


def test_lookup_bytes_by_hand():
    # 4 ids over 3 rows, 1 pending, D = 2: reads 3*(8+4); the pending row
    # reads 8+4, writes 8, clears 8+8 and moves its version 8; outputs
    # 4*(8+4)
    assert work.lookup_bytes(4, 3, 1, 2) == 36 + 44 + 48


def test_lazy_grad_bytes_and_flops_by_hand():
    # 4 gradients of D = 2 over 3 rows: 4*(8+4) in, 2*3*(8+12) cache r/w
    assert work.lazy_grad_bytes(4, 3, 2) == 48 + 120
    assert work.lazy_grad_flops(4, 2) == 32


def test_ivf_work_by_hand():
    # 2 queries, 16 rows scored, 12 distinct, 4 centroids, D = 2, k = 3
    flops, nbytes = work.ivf_work(2, 16, 12, 4, 2, 3)
    assert flops == 2 * 2 * (2 * 4 + 16)
    assert nbytes == 4 * 8 + 12 * 12 + 2 * 8 + 2 * 3 * 8

