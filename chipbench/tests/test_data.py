"""The benchmark's data generator is the program's ``TabularTask`` math."""
import numpy as np
import pytest

import data


@pytest.mark.parametrize("seed", [0, 2 ** 33 + 7])
def test_slabs_match_the_programs_task(seed):
    from repro.data import TabularTask
    ours = data.Task(300, 7, 3, seed)
    theirs = TabularTask(300, 7, n_classes=3, seed=seed)
    for start in (0, 5, 37):
        xs, ys = ours.slab(start, 4, 16)
        tx, ty = theirs.batch_slab(start, 4, 16)
        np.testing.assert_array_equal(xs, tx)
        np.testing.assert_array_equal(ys, ty)
