"""The traffic generators: one seed gives one schedule, and the load is
the same work for every seed."""

import numpy as np
import pytest

from bench import cells

OPEN = cells.load_module(f"{cells.BENCH}/traffic/poisson_zipf.py", "pz")
CLOSED = cells.load_module(f"{cells.BENCH}/traffic/closed_bulk.py", "cb")
PARAMS = {"rate_per_s": 2000.0, "request_rows": 1, "zipf_s": 1.0}
BIG_SEED = 3_000_000_017


def test_one_seed_one_schedule():
    a = OPEN.schedule(PARAMS, BIG_SEED, 42000, 32, 10.0)
    b = OPEN.schedule(PARAMS, BIG_SEED, 42000, 32, 10.0)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    c = OPEN.schedule(PARAMS, BIG_SEED + 1, 42000, 32, 10.0)
    assert not np.array_equal(a["due"], c["due"])


@pytest.mark.parametrize("rate", [100.0, 2000.0, 7000.0])
def test_mean_rate_matches(rate):
    s = OPEN.schedule(dict(PARAMS, rate_per_s=rate), 5, 42000, 32, 10.0)
    due = s["due"]
    assert len(due) == round(rate * 10.0)
    assert np.all(np.diff(due) > 0) and due[0] == 0.0 and due[-1] < 10.0
    assert np.mean(np.diff(due)) == pytest.approx(1.0 / rate, rel=0.01)


def test_every_seed_gets_the_same_work():
    a = OPEN.schedule(PARAMS, 1, 42000, 32, 10.0)
    b = OPEN.schedule(PARAMS, 2, 42000, 32, 10.0)
    # The gaps, the last one up to the window's close, are one multiset.
    gaps = [np.sort(np.diff(np.append(s["due"], 10.0))) for s in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=0, atol=1e-9)
    assert sorted(np.bincount(a["member"], minlength=32)) == \
        sorted(np.bincount(b["member"], minlength=32))


def test_zipf_top_member_share():
    s = OPEN.schedule(PARAMS, 9, 42000, 32, 10.0)
    counts = np.bincount(s["member"], minlength=32)
    harmonic = sum(1.0 / r for r in range(1, 33))
    assert counts.max() / counts.sum() == pytest.approx(1 / harmonic, abs=1e-3)


def test_zipf_zero_or_one_member_is_plain_poisson():
    s = OPEN.schedule(dict(PARAMS, zipf_s=0.0), 3, 10299, 1, 2.0)
    assert set(s["member"].tolist()) == {0}
    counts = OPEN.zipf_counts(1000, 4, 0.0)
    assert counts.tolist() == [250, 250, 250, 250]


def test_closed_bulk_same_seed_same_requests():
    p = {"request_rows": 32768, "distinct_requests": 4}
    a = CLOSED.schedule(p, BIG_SEED, 10299)
    b = CLOSED.schedule(p, BIG_SEED, 10299)
    assert len(a["requests"]) == 4
    for x, y in zip(a["requests"], b["requests"]):
        assert x.shape == (32768,) and x.max() < 10299
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(a["order"], b["order"])
