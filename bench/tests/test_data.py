"""The benchmark's frozen inputs equal the program's generators today."""
import numpy as np
import pytest

from bench.data import digits, iris


@pytest.mark.parametrize("seed", [2023, 2**31 + 11])
def test_iris_equals_program_table(seed):
    from repro.data import iris as prog

    a, b = iris.load(seed=seed), prog.load(seed=seed)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


@pytest.mark.parametrize("seed,side,threshold", [
    (2023, 28, None), (2**31 + 11, 28, None),
    (2023, 7, None), (2**31 + 11, 7, None),
    (2**31 + 11, 28, 75 / 255), (2023, 7, 75 / 255),
])
def test_digits_equals_program_generator(seed, side, threshold):
    from repro.data import mnist as prog

    n = 40
    if threshold is None:
        a = digits.load(seed=seed, n_points=n, side=side)
        b = prog.load(seed=seed, n_points=n, side=side)
    else:
        a = digits.load(seed=seed, n_points=n, side=side, threshold=threshold)
        imgs, ys = prog.raw(n, seed, side)
        b = prog.booleanize(imgs, threshold), ys
    assert a[0].shape == (n, side * side) and a[0].dtype == bool
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    if threshold is not None:
        assert not np.array_equal(a[0], digits.load(seed, n, side)[0])
