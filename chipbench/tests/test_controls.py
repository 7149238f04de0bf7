"""The comparison that decides ``correct`` is seen to fail: the control
(the plain reference in the program's place, one guarantee broken) and
each planted fault of the timed path, in every tiny cell.

On the chip the same paths run at each cell's own size through
``python3 chipbench/controls.py``.
"""
from __future__ import annotations

import numpy as np
import pytest

from conftest import SECONDS, SEED, TINY_CELLS


@pytest.mark.parametrize("path", ["control", "answer_altered", "half_batch",
                                  "state_unchanged"])
@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_broken_path_is_not_correct(tiny_root, cell, path):
    from chipbench import controls, run

    wrap = controls.wrapper(TINY_CELLS[cell][1], path)
    res = run.run_cell(cell, SEED + 1, SECONDS, False, root=tiny_root,
                       wrap=wrap)
    assert res["correct"] is False
    assert max(c["value"] - c["limit"] for c in res["checks"].values()) > 0


def test_carryless_product_drops_only_the_carries():
    from chipbench import controls, reference

    rng = np.random.default_rng(3)
    a = rng.integers(0, 2 ** 32, (4, 8), dtype=np.uint64).astype(np.uint32)
    b = rng.integers(0, 2 ** 32, (4, 8), dtype=np.uint64).astype(np.uint32)
    got = controls.carryless_product(a, b)
    for row, (x, y) in enumerate(zip(a.astype(object), b.astype(object))):
        cols = [sum(int(x[i]) * int(y[k - i]) % 2 ** 32
                    for i in range(8) if 0 <= k - i < 8) % 2 ** 32
                for k in range(16)]
        assert got[row].tolist() == cols
    assert reference.mul_wrong(a, b, got) == 4
