"""The lower-precision control at a CPU size: it must fail the limits that
the program's own step passes (``test_faults.LIMITS``, set for this size).

S2FP8 cells: the reference put in the program's place with int4 at every
matrix product.  The bf16 cell: the program's own fp8 policy through the
whole harness.  ``control.py`` makes the same readings at the cells' own
size on the chip."""
from __future__ import annotations

import jax
import pytest

import tiny
import compare
import control
from test_faults import CELLS, LIMITS


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limits(cell):
    c = tiny.tiny_cell(cell)
    limits = LIMITS[c.entry["traffic"]]
    c.limits = limits
    rows = dict(control.readings(c, 2 ** 33 + 9, jax.devices()[:1]))
    for variant in ("control", "half_batch"):
        read = {k: (v, "") for k, v in rows[variant].items()}
        ok, _ = compare.judge(read, limits)
        assert not ok, (variant, rows[variant])
