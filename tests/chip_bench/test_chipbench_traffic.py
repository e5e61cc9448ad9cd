"""The traffic generator and the latency arithmetic, without a chip."""
import math

import numpy as np
import pytest

from benchmarks.chip import images, traffic


def test_same_seed_same_images_and_arrivals():
    a, b = traffic.streams(2**31 + 17), traffic.streams(2**31 + 17)
    mnist = images.KINDS["mnist"][:3]
    np.testing.assert_array_equal(
        images.make_images("mnist", mnist, 8, a["pool"]),
        images.make_images("mnist", mnist, 8, b["pool"]))
    np.testing.assert_array_equal(traffic.due_times(500, 2, a["arrivals"]),
                                  traffic.due_times(500, 2, b["arrivals"]))
    c = traffic.streams(2**31 + 18)
    assert not np.array_equal(traffic.due_times(500, 2, a["arrivals"]),
                              traffic.due_times(500, 2, c["arrivals"]))


@pytest.mark.parametrize("kind", sorted(images.KINDS))
def test_images_have_the_geometry_and_range(kind):
    h, w, c, _ = images.KINDS[kind]
    x = images.make_images(kind, (h, w, c), 3, np.random.default_rng(0))
    assert x.shape == (3, h, w, c) and x.dtype == np.float32
    assert 0.0 <= x.min() and x.max() <= 1.0


@pytest.mark.parametrize("kind", sorted(images.KINDS))
def test_the_configuration_sets_the_size_and_the_kind_the_channels(kind):
    h, w, c, _ = images.KINDS[kind]
    x = images.make_images(kind, (2 * h, 3 * w, c), 3,
                           np.random.default_rng(0))
    assert x.shape == (3, 2 * h, 3 * w, c) and x.dtype == np.float32
    assert 0.0 <= x.min() and 0.5 < x.max() <= 1.0   # a template is drawn
    with pytest.raises(ValueError, match="channel"):
        images.make_images(kind, (h, w, c + 1), 1, np.random.default_rng(0))


@pytest.mark.parametrize("seed", [1, 2, 2**33 + 5])
def test_poisson_count_is_fixed_and_gaps_exponential(seed):
    rate, seconds = 2000.0, 5.0
    due = traffic.due_times(rate, seconds, traffic.streams(seed)["arrivals"])
    assert len(due) == 10_000                      # the same for every seed
    assert due[-1] == pytest.approx(seconds) and np.all(np.diff(due) >= 0)
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert gaps.mean() == pytest.approx(1.0 / rate)
    # an exponential's coefficient of variation is 1; 10k samples put
    # its standard error near 0.01
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)


def test_request_order_cycles_through_the_pool():
    order = traffic.request_order(10, 4, np.random.default_rng(3))
    assert len(order) == 10
    assert sorted(order[:4]) == [0, 1, 2, 3] == sorted(order[4:8])


def test_latency_counts_from_due_time_and_misses_are_infinite():
    due = [0.0, 0.010, 0.020, 0.030]
    done = [0.005, 0.050, math.nan, 0.031]
    lat = traffic.latencies_s(due, done)
    np.testing.assert_allclose(lat[[0, 1, 3]], [0.005, 0.040, 0.001])
    assert math.isinf(lat[2])
    assert traffic.percentile(lat, 50) == pytest.approx(0.005)
    assert math.isinf(traffic.percentile(lat, 99))


def test_percentile_is_nearest_rank():
    v = np.arange(1, 101, dtype=float)            # 1..100
    assert traffic.percentile(v, 99) == 99.0
    assert traffic.percentile(v, 50) == 50.0
    assert traffic.percentile(v[:10], 99) == 10.0


def test_mix_files_are_checked(tmp_path):
    (tmp_path / "bad.json").write_text('{"arrival": "burst", "warm": "all"}')
    with pytest.raises(ValueError, match="arrival"):
        traffic.load("bad", tmp_path)
    for name in ("backlog",):
        mix = traffic.load(name)
        assert mix["pool"] >= 1 and mix["warm"] in ("max_bucket", "all")
