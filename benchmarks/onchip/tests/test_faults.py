"""A whole run of a cell, at a CPU size, with the timed path broken under
the harness: ``correct`` must come out false for each fault the cell can
have, and true for the unbroken step.

The faults (``faults.py``) wrap the program's step: a step that hands back
its state unchanged; one that drops the second half of the batch's rows and
takes the mean over the rest; one whose answer is altered where it is made
(one weight matrix comes back without its update); and, where the cell runs
on several chips, one that leaves out the exchange of gradients between
them.  A one-chip cell has no exchange to leave out.  A cell on several
chips runs in a process of its own with as many virtual CPU devices
(``tiny.py``'s ``__main__``).

The limits here are for this size (the cells' own limits are for theirs):
above the unbroken step's readings at this size, below the faults'."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import pytest

import tiny
import faults

LIMITS = {
    "train.s1024": {"loss_gap": 0.003, "grad_gap": 0.05, "change_gap": 0.02},
    "train-bf16.s1024": {"loss_gap": 5e-4, "grad_gap": 0.015,
                         "change_gap": 0.004},
    "train-fsdpq.s1024": {"loss_gap": 0.003, "grad_gap": 0.05,
                          "change_gap": 0.02},
}
CELLS = ["minicpm-2b.train.s1024", "minicpm-2b.train-bf16.s1024"]
MULTI_CHIP = ["stablelm-12b.train.fsdpq.4chip"]
SEED = 2 ** 35 + 17


def _run(cell_name, wrap):
    cell = tiny.tiny_cell(cell_name)
    cell.limits = LIMITS[cell.entry["traffic"]]
    return tiny.harness.run_cell(cell, SEED, 0.2, False,
                                 jax.devices()[:1], wrap_step=wrap)


def run_on_chips(cell_name, fault=None, readings=False):
    """The last line a run of the tiny ``cell_name`` prints in a process
    with the cell's chips as virtual CPU devices: its result, or with
    ``readings`` those of ``control.readings``."""
    chips = tiny.tiny_cell(cell_name).chips
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    args = [sys.executable, str(tiny.ONCHIP / "tests" / "tiny.py"),
            cell_name, json.dumps(LIMITS[tiny.tiny_cell(cell_name)
                                         .entry["traffic"]]), str(SEED)]
    if fault:
        args += ["--fault", fault]
    if readings:
        args.append("--readings")
    p = subprocess.run(args, capture_output=True, text=True, env=env,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_unbroken_step_is_correct(cell):
    out = _run(cell, None)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [faults.unchanged, faults.half_batch,
                                   faults.one_leaf_not_updated])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_caught(cell, fault):
    out = _run(cell, fault)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", MULTI_CHIP)
def test_unbroken_step_on_chips_is_correct(cell):
    out = run_on_chips(cell)
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == tiny.tiny_cell(cell).chips
    assert out["attempted"] >= 1 and out["failed"] == 0


@pytest.mark.parametrize("fault", ["unsynced", "half_batch"])
@pytest.mark.parametrize("cell", MULTI_CHIP)
def test_fault_on_chips_is_caught(cell, fault):
    out = run_on_chips(cell, fault=fault)
    assert out["correct"] is False, out["checks"]
