import numpy as np

from bench.datagen import clustered_vectors, seed_key


def _data(seed):
    x, q = clustered_vectors(seed_key(seed), 512, 32, 16)
    return np.asarray(x), np.asarray(q)


def test_same_seed_same_data():
    seed = 2**33 + 12345          # wider than 32 bits, as the driver's are
    for a, b in zip(_data(seed), _data(seed)):
        assert a.dtype == np.float32
        np.testing.assert_array_equal(a, b)


def test_seeds_that_share_low_bits_differ():
    assert not np.array_equal(_data(7)[0], _data(7 + 2**32)[0])
    assert not np.array_equal(_data(7)[0], _data(8)[0])


def test_copy_draws_what_the_program_draws():
    # one jitted call fuses centre + std * noise, so the last bit may round
    # otherwise than the program's eager ops
    from repro.data.synthetic import VectorDatasetSpec
    from repro.data.synthetic import clustered_vectors as program_vectors

    key = seed_key(99)
    ours = clustered_vectors(key, 300, 24, 10, 8, 0.5)
    theirs = program_vectors(key, VectorDatasetSpec("t", 300, 24, 10, 8, 0.5))
    for a, b in zip(ours, theirs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-6)
