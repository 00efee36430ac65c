"""The traffic generators: determined by their seed, with the stated
distributions, and the same work for every seed in another order."""
import numpy as np
import pytest

from bench.traffic import arrivals, fmnist

CHAT = {"arrivals": {"process": "poisson", "rate": 4.0},
        "prompt": {"dist": "lognormal", "median": 256, "sigma": 1.0,
                   "clip": [1, 1024], "round_up_to": [128, 256, 512, 1024]},
        "answer": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                   "clip": [16, 512]}}
BIG_SEED = 2 ** 31 + 12345


def test_schedule_is_determined_by_the_seed():
    a = arrivals.schedule(CHAT, 30.0, 1000, BIG_SEED)
    b = arrivals.schedule(CHAT, 30.0, 1000, BIG_SEED)
    c = arrivals.schedule(CHAT, 30.0, 1000, BIG_SEED + 1)
    assert [(t, p.tolist(), g) for t, p, g in a] == \
        [(t, p.tolist(), g) for t, p, g in b]
    assert [t for t, _, _ in a] != [t for t, _, _ in c]
    assert all(t < 30.0 for t, _, _ in a)
    assert all(int(p.max()) < 1000 for _, p, _ in a)


def test_every_seed_gets_the_same_sizes_in_another_order():
    rng1, rng2 = np.random.default_rng(1), np.random.default_rng(2)
    for spec in (CHAT["prompt"], CHAT["answer"]):
        x, y = arrivals.lengths(spec, 500, rng1), arrivals.lengths(spec, 500, rng2)
        assert sorted(x) == sorted(y) and list(x) != list(y)
    g1 = arrivals.arrival_times(CHAT["arrivals"], 500, rng1)
    g2 = arrivals.arrival_times(CHAT["arrivals"], 500, rng2)
    assert g1[-1] == pytest.approx(g2[-1])


def test_lognormal_lengths_follow_the_spec():
    v = arrivals.lengths(CHAT["answer"], 4001, np.random.default_rng(0))
    assert np.median(v) == 128
    assert v.min() >= 16 and v.max() <= 512
    # sigma 0.8: the quartiles sit at exp(+-0.6745 * 0.8) of the median
    q1, q3 = np.percentile(v, [25, 75])
    assert q1 == pytest.approx(128 * np.exp(-0.6745 * 0.8), rel=0.02)
    assert q3 == pytest.approx(128 * np.exp(0.6745 * 0.8), rel=0.02)
    p = arrivals.lengths(CHAT["prompt"], 4000, np.random.default_rng(0))
    assert set(np.unique(p)) <= {128, 256, 512, 1024}
    assert np.mean(p <= 256) == pytest.approx(0.5, abs=0.01)


def test_choice_lengths_follow_their_weights():
    spec = {"dist": "choice", "values": [1024, 2048], "weights": [1, 3]}
    v = arrivals.lengths(spec, 400, np.random.default_rng(0))
    assert np.sum(v == 1024) == 100 and np.sum(v == 2048) == 300


def test_poisson_gaps_are_exponential_at_the_rate():
    t = arrivals.arrival_times(CHAT["arrivals"], 4000, np.random.default_rng(3))
    gaps = np.diff(np.concatenate([[0.0], t]))
    assert gaps.mean() == pytest.approx(1 / 4.0, rel=0.01)
    assert np.median(gaps) == pytest.approx(np.log(2) / 4.0, rel=0.02)


def test_bursty_arrivals_run_at_three_times_the_base_in_bursts():
    spec = {"process": "bursty", "rate": 2.0, "burst_factor": 3.0,
            "burst_s": 2.0, "period_s": 10.0}
    t = arrivals.arrival_times(spec, 20000, np.random.default_rng(4))
    t = t[t < 9990.0]
    in_burst = (t % 10.0) < 2.0
    rate_burst = in_burst.sum() / (999 * 2.0)
    rate_calm = (~in_burst).sum() / (999 * 8.0)
    assert rate_burst / rate_calm == pytest.approx(3.0, rel=0.05)
    assert len(t) / 9990.0 == pytest.approx(2.0, rel=0.02)


def test_images_are_determined_by_the_seed_and_balanced():
    a = fmnist.make_images(2000, 500, BIG_SEED)
    b = fmnist.make_images(2000, 500, BIG_SEED)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert a["x_train"].shape == (2000, 28, 28, 1)
    assert a["x_train"].dtype == np.float32
    counts = np.bincount(a["y_train"], minlength=10)
    assert counts.min() > 150 and counts.max() < 250
    # pixel noise of the stated sigma around the prototypes
    assert a["x_train"].std() > 0.5


def test_iid_partition_covers_every_sample_once():
    parts = fmnist.partition_iid(60000, 100, BIG_SEED)
    assert [len(p) for p in parts] == [600] * 100
    assert np.array_equal(np.sort(np.concatenate(parts)), np.arange(60000))
