"""Nominal work and peaks against hand arithmetic."""

import pytest

from bench import peaks
from bench.work import mlp_fleet, mlp_model

D6 = (561, 64, 6)
D1 = (42, 64, 2)


def test_d6_ops_per_row():
    # 2 * (561*64 + 64*6) = 2 * (35904 + 384)
    assert mlp_model.ops_per_row(D6) == 72576


def test_d1_ops_per_row():
    # 2 * (42*64 + 64*2) = 2 * (2688 + 128)
    assert mlp_model.ops_per_row(D1) == 5632


def test_d6_bytes_one_bulk_dispatch():
    ops, nbytes = mlp_model.work(D6, 16, rows=4096, calls=1)
    assert ops == 72576 * 4096
    # weights and biases (35904 + 64 + 384 + 6) * 2 B, rows (561 + 6) * 2 B
    assert nbytes == 36358 * 2 + 4096 * 567 * 2


def test_fleet_reads_every_member_once_per_call():
    ops, nbytes = mlp_fleet.work(D1, 16, rows=10, calls=2, members=32)
    assert ops == 5632 * 10
    params = (42 * 64 + 64 + 64 * 2 + 2) * 2
    assert nbytes == 2 * 32 * params + 10 * (42 + 2) * 2


def test_least_time_names_the_binding_roof():
    p = peaks.peaks("TPU v5 lite")
    t, bound = peaks.least_time(*mlp_model.work(D6, 16, 4096, 1), p)
    assert bound == "memory"
    assert t == pytest.approx((36358 * 2 + 4096 * 567 * 2) / 8.19e11)
    t, bound = peaks.least_time(3.93e14, 1.0, p)
    assert (t, bound) == (pytest.approx(1.0), "compute")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v9 imaginary")
