"""The SCD pass work count and the roofline's least time, on known shapes."""
import math

import pytest

from chipbench import harness, work

V5E = harness.peak_of("TPU v5 lite")


@pytest.mark.parametrize("n, k, edges", [(10_000_000, 10, 49),
                                         (65536, 10, 49), (1000, 3, 7)])
def test_scd_pass_counts(n, k, edges):
    ops, nbytes = work.scd_pass(n, k, edges)
    assert nbytes == 4 * (2 * n * k + k + k * edges + k * (edges + 1) + k)
    assert ops == n * k * (9 + math.ceil(math.log2(edges + 1)))


def test_table1_share_pass_is_bound_by_hbm():
    ops, nbytes = work.scd_pass(10_000_000, 10, 49)
    assert nbytes == 800_004_040
    assert ops == 1.5e9
    least, bound = work.least_seconds(ops, nbytes, V5E)
    assert bound == "hbm"
    assert least == pytest.approx(800_004_040 / 819e9, rel=1e-12)
    assert least == pytest.approx(9.768e-4, rel=1e-3)


def test_compute_bound_when_bytes_are_few():
    least, bound = work.least_seconds(1e15, 1.0, V5E)
    assert bound == "compute" and least == pytest.approx(1e15 / 197e12)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        harness.peak_of("TPU v9000")
