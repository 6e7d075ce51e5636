"""The lower-precision control at a CPU size: it must fail the limits that
the program's own step passes (``test_faults.LIMITS``, set for this size).

S2FP8 cells: the reference put in the program's place with int4 at every
matrix product.  The bf16 cell: the program's own fp8 policy through the
whole harness.  A cell on several chips, in a process with as many virtual
CPU devices, the reference sharded over them, and the program with the
exchange of gradients left out besides.  ``control.py`` makes the same
readings at the cells' own size on the chip."""
from __future__ import annotations

import jax
import pytest

import tiny
import compare
import control
from test_faults import CELLS, LIMITS, MULTI_CHIP, run_on_chips


def _fails(rows, limits, variants):
    for variant in variants:
        read = {k: (v, "") for k, v in rows[variant].items()}
        ok, _ = compare.judge(read, limits)
        assert not ok, (variant, rows[variant])


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limits(cell):
    c = tiny.tiny_cell(cell)
    limits = LIMITS[c.entry["traffic"]]
    c.limits = limits
    rows = dict(control.readings(c, 2 ** 33 + 9, jax.devices()[:1]))
    _fails(rows, limits, ("control", "half_batch"))


@pytest.mark.parametrize("cell", MULTI_CHIP)
def test_control_on_chips_fails_the_limits(cell):
    rows = run_on_chips(cell, readings=True)
    _fails(rows, LIMITS[tiny.tiny_cell(cell).entry["traffic"]],
           ("control", "half_batch", "unsynced"))
