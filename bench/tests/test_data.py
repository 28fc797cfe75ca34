"""The benchmark's frozen inputs equal the program's table today."""
import numpy as np
import pytest

from bench.data import iris


@pytest.mark.parametrize("seed", [2023, 2**31 + 11])
def test_iris_equals_program_table(seed):
    from repro.data import iris as prog

    a, b = iris.load(seed=seed), prog.load(seed=seed)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
