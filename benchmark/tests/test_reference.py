"""The plain references, the seeded inputs and the roofline arithmetic."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import roofline  # noqa: E402
import spec  # noqa: E402

p7 = spec.system("poisson7")


@pytest.mark.parametrize("n", [3, 5, 8])
def test_constant_operator_is_the_kronecker_sum(n):
    A, K = p7.operator(n), p7.kron_csr(n)
    assert abs(A - K).max() == 0
    assert (A.indptr == K.indptr).all() and (A.indices == K.indices).all()


def test_variable_operator_is_spd_and_reduces_to_the_constant():
    P = p7.Pattern(6)
    k = p7.lognormal_field(6, 0.5, 2**40 + 3, 1)
    A = P.csr(P.values(k))
    assert abs(A - A.T).max() < 1e-14
    assert np.linalg.eigvalsh(A.toarray()).min() > 0
    ones = P.csr(P.values(np.ones((6, 6, 6))))
    assert abs(ones - p7.kron_csr(6)).max() < 1e-14


@pytest.mark.parametrize("seed", [0, 2**31 + 11, -5, 2**70 + 1])
def test_inputs_are_a_function_of_the_seed(seed):
    conf = {}
    mix = {"rhs_pool": 3, "coefficients": {"kind": "lognormal",
                                           "sigma": 0.5}, "value_pool": 2}
    a = p7.build(conf, mix, seed, 4)
    b = p7.build(conf, mix, seed, 4)
    c = p7.build(conf, mix, seed + 1, 4)
    for u, v in zip(a.rhs + a.values, b.rhs + b.values):
        assert np.array_equal(u, v)
    assert not np.array_equal(a.rhs[0], c.rhs[0])
    assert not np.array_equal(a.rhs[0], a.rhs[1])


def test_peak_table_refuses_an_unknown_device():
    assert roofline.peak("TPU v5 lite").hbm_bytes_s == 819e9
    with pytest.raises(roofline.UnknownDevice):
        roofline.peak("TPU v9 imaginary")


def test_dia_bytes_and_share():
    assert roofline.dia_apply_bytes(2_097_152, 7, 4) == 2_097_152 * 9 * 4
    p = roofline.peak("TPU v5 lite")
    nbytes = roofline.dia_apply_bytes(1 << 20, 7, 4)
    share, bound = roofline.roofline_share(
        roofline.dia_apply_flops(1 << 20, 7), nbytes,
        2 * nbytes / p.hbm_bytes_s, p)
    assert bound == "hbm" and share == pytest.approx(0.5)
