import numpy as np

from harness import traffic


def test_open_schedule_is_reproducible_from_the_seed():
    a = traffic.open_schedule(traffic.streams(2**33 + 5, 2)[0], 5000, 2.0,
                              300)
    b = traffic.open_schedule(traffic.streams(2**33 + 5, 2)[0], 5000, 2.0,
                              300)
    c = traffic.open_schedule(traffic.streams(2**33 + 6, 2)[0], 5000, 2.0,
                              300)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])


def test_every_seed_offers_the_same_work():
    for seed in (0, 7, 2**31 + 3):
        due, rows = traffic.open_schedule(traffic.streams(seed, 1)[0],
                                          1234.0, 3.0, 50)
        assert len(due) == len(rows) == 3702
        assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < 3.0
        assert rows.min() >= 0 and rows.max() < 50


def test_sub_seeds_fit_a_jax_key_and_differ_by_purpose():
    s = [traffic.sub_seed(2**40 + 9, p) for p in range(4)]
    assert all(0 <= v < 2**31 for v in s)
    assert len(set(s)) == 4
    assert traffic.sub_seed(2**40 + 9, 1) == s[1]
