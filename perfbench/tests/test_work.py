"""(b) The ops/bytes function and the table of peaks."""

import pytest

from lib import peaks, work


@pytest.mark.parametrize("a,m,n,k", [(9, 4096, 1 << 24, 10), (9, 37, 5000, 3)])
def test_knn_work_is_the_algorithms(a, m, n, k):
    ops, nbytes = work.knn_search_work(a, m, n, k)
    assert ops == 3 * a * m * n
    assert nbytes == 4 * a * (n + m) + 8 * m * k


def test_compute_bounds_the_bulk_block_by_about_13x():
    peak = peaks.peak_for("TPU v5 lite")
    ops, nbytes = work.knn_search_work(9, 4096, 1 << 24, 10)
    t, term = work.least_time_s(ops, nbytes, peak)
    assert term == "compute"
    assert t == pytest.approx(ops / 197e12)
    assert 12 < (ops / 197e12) / (nbytes / 819e9) < 14
    # a single row is bound by reading the index once
    assert work.least_time_s(*work.knn_search_work(9, 1, 1 << 24, 10),
                             peak)[1] == "memory"


def test_v5e_row_and_unknown_kind():
    row = peaks.peak_for("TPU v5 lite")
    assert (row["bf16_flops"], row["int8_ops"], row["hbm_bytes_per_s"]) == \
        (197e12, 394e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak_for("TPU v9")
