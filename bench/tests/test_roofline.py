"""The candidate join's least work equals a brute-force count."""

import numpy as np
import pytest

from bench import roofline


def _brute(tenant, horizon, lo, hi, d):
    rows, flops = set(), 0
    for g in range(lo, hi):
        k = tenant[g]
        for j in range(g):
            if tenant[j] == k and g - j <= horizon[k]:
                flops += 2 * d
                if j < lo:
                    rows.add(j)
    return (len(rows) + hi - lo) * d * 4, flops


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_cand_work_matches_brute_force(seed):
    rng = np.random.default_rng(seed)
    tenant = rng.integers(0, 3, 600).astype(np.int32)
    horizon = np.array([37.5, 120.0, 401.2])
    batches = [(a, a + 16) for a in range(0, 592, 16)] + [(590, 597)]
    nbytes, flops = roofline.cand_work(tenant, horizon, batches, d=8)
    for (lo, hi), b, f in zip(batches, nbytes, flops):
        assert (b, f) == _brute(tenant, horizon, lo, hi, 8)


def test_least_time_names_its_bound():
    peak = roofline.peaks("TPU v5 lite")
    t, bound = roofline.least_time([819e9], [1.0], peak)
    assert bound == "hbm" and t[0] == pytest.approx(1.0)
    t, bound = roofline.least_time([1.0], [197e12 * 2], peak)
    assert bound == "mxu" and t[0] == pytest.approx(2.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
