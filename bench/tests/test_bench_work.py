"""FP64 work functions against hand counts, and the peak table."""

import pytest

from bench import harness
from bench.configs import cg_poisson7_ref, dgemm_ref


def test_dgemm_work_square_by_hand():
    # 2 x 3 times 3 x 4: 24 products and 24 additions; 6 + 12 + 8 values.
    flops, bytes_ = dgemm_ref.work(2, 3, 4)
    assert flops == 48
    assert bytes_ == 8 * 26


def test_dgemm_work_multi_rhs_by_hand():
    flops, bytes_ = dgemm_ref.work(8192, 8192, 8)
    assert flops == 2 * 8192 * 8192 * 8
    assert bytes_ == 8 * (8192 * 8192 + 8192 * 8 + 8192 * 8)


def test_cg_iteration_work_by_hand():
    # 8 rows, 7 slots: SpMV 2*56, dots 2*2*8, axpys 3*2*8; ELL 56*(8+4) bytes,
    # x, r, p read and written: 6*8*8 bytes.
    flops, bytes_ = cg_poisson7_ref.work(8, 7)
    assert flops == 112 + 32 + 48
    assert bytes_ == 56 * 12 + 384


def test_least_time_takes_the_larger_bound():
    peaks = harness.peaks_for("TPU v5 lite")
    ctx = harness.Context(None, 1, 2.0 * 8192 ** 3, 8.0 * 3 * 8192 ** 2, peaks)
    assert ctx.least_s() == pytest.approx(2.0 * 8192 ** 3 / 393e12)
    ctx = harness.Context(None, 1, 1e6, 819e9, peaks)
    assert ctx.least_s() == pytest.approx(1.0)


def test_peak_table_row():
    row = harness.peaks_for("TPU v5 lite")
    assert row["int8_ops_per_s"] == 393e12
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        harness.peaks_for("TPU v9 imaginary")
