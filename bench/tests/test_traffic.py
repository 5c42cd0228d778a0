"""The generator: deterministic per seed, with the Poisson and Zipf shapes
the mixes state."""
import numpy as np
import pytest

from harness.traffic import PairSource, open_schedule, rng_for

UNIFORM = {"rate_per_s": 5000, "pairs": "uniform",
           "levels": "uniform"}
ZIPF = dict(UNIFORM, pairs="zipf", zipf_a=1.0)
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("mix", [UNIFORM, ZIPF], ids=["uniform", "zipf"])
def test_same_seed_same_requests(mix):
    def draw(seed):
        rng = rng_for(seed, "window")
        due = open_schedule(mix, 2.0, rng)
        return (due,) + PairSource(mix, 1000, 5, seed).draw(rng, len(due))

    a, b, c = draw(BIG_SEED), draw(BIG_SEED), draw(BIG_SEED + 1)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert any(not np.array_equal(x, z) for x, z in zip(a, c))


def test_streams_do_not_share_draws():
    a = rng_for(7, "window").random(8)
    b = rng_for(7, "warmup").random(8)
    assert not np.allclose(a, b)


def test_poisson_schedule_shape():
    rng = rng_for(3, "window")
    due = open_schedule(UNIFORM, 10.0, rng)
    assert len(due) == 50000                   # exactly rate x seconds
    assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < 10.0
    gaps = np.diff(due)
    assert abs(gaps.mean() * 5000 - 1) < 0.02  # mean gap 1/rate
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05   # exponential: CV 1
    per_s = np.histogram(due, bins=10, range=(0, 10))[0]
    assert np.all(np.abs(per_s - 5000) < 5 * np.sqrt(5000))


def test_zipf_pairs_shape():
    V = 1000
    src = PairSource(ZIPF, V, 9, seed=11)
    s, t, w = src.draw(rng_for(11, "window"), 400000)
    counts = np.bincount(np.concatenate([s, t]), minlength=V)
    top = np.sort(counts)[::-1]
    harmonic = np.sum(1.0 / np.arange(1, V + 1))
    assert abs(top[0] / counts.sum() * harmonic - 1) < 0.05   # p(1) = 1/H_V
    assert abs(top[0] / top[1] - 2) < 0.2                      # p(1)/p(2) = 2
    # the popular vertices are a seeded permutation, not the low ids
    assert np.argmax(counts) == src._perm[0]
    assert PairSource(ZIPF, V, 9, seed=12)._perm[0] != src._perm[0] or \
        PairSource(ZIPF, V, 9, seed=13)._perm[0] != src._perm[0]
    assert w.min() == 0 and w.max() == 8


def test_uniform_pairs_shape():
    s, t, w = PairSource(UNIFORM, 100, 5, 1).draw(rng_for(1, "window"),
                                                  200000)
    counts = np.bincount(s, minlength=100)
    assert counts.min() > 1700 and counts.max() < 2300
    assert set(np.unique(w)) == set(range(5))
