"""The traffic generator is a pure function of the mix and the seed, keeps
its lengths inside their clips with medians near the target, and gives
every seed the same amount of work in another order."""

import numpy as np
import pytest

from chipbench_fixtures import CHIP  # noqa: F401  (sets the path)

import harness  # noqa: E402
import traffic  # noqa: E402

CAT = harness.Catalog()
SEED = 2**33 + 12345


@pytest.mark.parametrize("mix", ["chat", "docs"])
def test_lengths_clipped_and_near_median(mix):
    m = CAT.mix(mix)
    reqs = traffic.requests(m, 400, SEED, 151936)
    for key, get in (("prompt", lambda r: r.prompt.size),
                     ("output", lambda r: r.max_new)):
        xs = np.array([get(r) for r in reqs])
        spec = m[key]
        assert xs.min() >= spec["min"] and xs.max() <= spec["max"]
        assert abs(np.median(xs) / spec["median"] - 1) < 0.02
    assert all(0 <= r.prompt.min() and r.prompt.max() < 151936
               for r in reqs)


def test_same_seed_same_schedule_other_seed_same_work():
    m = CAT.mix("chat")
    a = traffic.open_loop(m, 4.0, 45, SEED, 151936)
    b = traffic.open_loop(m, 4.0, 45, SEED, 151936)
    c = traffic.open_loop(m, 4.0, 45, SEED + 1, 151936)
    assert len(a) == len(b) == len(c) == 180
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.due for r in a] != [r.due for r in c]
    # the same sizes, in another order
    assert sorted(r.prompt.size for r in a) == sorted(
        r.prompt.size for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    gaps = np.diff([r.due for r in a])
    assert a[0].due == 0.0 and a[-1].due < 45.0
    assert abs(np.mean(gaps) - 45 / 180) < 0.01


def test_calibration_from_seed():
    m = CAT.mix("prune")
    x = traffic.calibration(m, SEED, 151936)
    assert x.shape == (128, 2048) and x.dtype == np.int32
    assert np.array_equal(x, traffic.calibration(m, SEED, 151936))
    assert not np.array_equal(x, traffic.calibration(m, SEED + 1, 151936))


def test_unknown_distribution_fails():
    with pytest.raises(ValueError, match="unknown length distribution"):
        traffic.quantile_lengths({"dist": "pareto", "median": 1,
                                  "sigma": 1, "min": 1, "max": 2}, 4)


def test_seed_key_keeps_high_bits():
    import jax
    a = jax.random.key_data(harness.seed_key(5))
    b = jax.random.key_data(harness.seed_key(5 + 2**33))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
