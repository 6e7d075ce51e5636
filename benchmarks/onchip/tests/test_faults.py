"""A whole run of a cell, at a CPU size, with the timed path broken under
the harness: ``correct`` must come out false for each fault a one-chip
training cell can have, and true for the unbroken step.

The faults wrap the program's step: a step that hands back its state
unchanged; one that drops the second half of the batch's rows and takes
the mean over the rest; and one whose answer is altered where it is made
(one weight matrix comes back without its update).  A one-chip cell has no
exchange between chips to leave out.

The limits here are for this size (the cells' own limits are for theirs):
above the unbroken step's readings at this size, below the faults'."""
from __future__ import annotations

import jax
import pytest

import tiny

LIMITS = {
    "train.s1024": {"loss_gap": 0.003, "grad_gap": 0.05, "change_gap": 0.02},
    "train-bf16.s1024": {"loss_gap": 5e-4, "grad_gap": 0.015,
                         "change_gap": 0.004},
}
CELLS = ["minicpm-2b.train.s1024", "minicpm-2b.train-bf16.s1024"]


def unchanged(step_fn):
    def step(*args):
        out = step_fn(*args)
        return (args[0], args[1]) + tuple(out[2:])
    return step


def half_batch(step_fn):
    def step(*args):
        batch = {k: v[: v.shape[0] // 2] for k, v in args[-2].items()}
        return step_fn(*args[:-2], batch, args[-1])
    return step


def one_leaf_not_updated(step_fn):
    def step(*args):
        out = step_fn(*args)
        params = dict(out[0])
        seg = dict(params["segments"][0])
        seg["wo"] = args[0]["segments"][0]["wo"]
        params["segments"] = [seg]
        return (params,) + tuple(out[1:])
    return step


def _run(cell_name, wrap):
    cell = tiny.tiny_cell(cell_name)
    cell.limits = LIMITS[cell.entry["traffic"]]
    return tiny.harness.run_cell(cell, 2 ** 35 + 17, 0.2, False,
                                 jax.devices()[:1], wrap_step=wrap)


@pytest.mark.parametrize("cell", CELLS)
def test_unbroken_step_is_correct(cell):
    out = _run(cell, None)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [unchanged, half_batch,
                                   one_leaf_not_updated])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_caught(cell, fault):
    out = _run(cell, fault)
    assert out["correct"] is False, out["checks"]
