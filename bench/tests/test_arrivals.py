import numpy as np
import pytest

from bench.arrivals import arrival_times


def test_same_seed_same_schedule():
    a = arrival_times(500, 200.0, 2**31 + 9)
    b = arrival_times(500, 200.0, 2**31 + 9)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (500,) and np.all(np.diff(a) >= 0)
    assert not np.array_equal(a, arrival_times(500, 200.0, 2**31 + 10))


def test_copy_matches_the_program_schedule():
    from repro.serving.loadgen import LoadSpec
    from repro.serving.loadgen import arrival_times as program_times

    ours = arrival_times(300, 123.0, 17)
    theirs = program_times(LoadSpec(n_requests=300, qps=123.0, seed=17))
    np.testing.assert_array_equal(ours, theirs)


@pytest.mark.parametrize("n, rate", [(0, 1.0), (10, 0.0)])
def test_bad_schedule_raises(n, rate):
    with pytest.raises(ValueError):
        arrival_times(n, rate, 0)
