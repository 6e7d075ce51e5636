#!/usr/bin/env python3
"""Readings of the lower-precision control and of the planted faults, for
setting and checking a training cell's limits.  The benchmark's own runs
never run this.

    python3 benchmarks/onchip/control.py --workload <cell> --seeds 1,2,3

For each seed it prints, as one JSON line each, the three numbers of
``compare.py`` for:

* ``control``: the step in the nearest precision below the cell's.  Where
  the program has such a path, the program with it switched on (the bf16
  cell: the program's own ``fp8`` policy, through the whole harness).
  Otherwise the reference put in the program's place, computed with int4
  operands and outputs at every matrix product (S2FP8 cells: int4 is the
  step below 8 bits).
* ``half_batch``: the reference put in the program's place with the
  second half of every batch's rows left out.
* ``unsynced`` (cells on several chips): the program through the whole
  harness with the exchange of gradients between its chips left out
  (``faults.unsynced``).

With ``--emulate`` it prints instead, for S2FP8 cells, the same numbers
for the reference with S2FP8 itself at the program's sites (paper Eq.
3-5, written in the reference, ``S2FP8``): ``s2fp8.fresh`` with
statistics taken afresh every step, ``s2fp8.delayed`` with them taken
every ``stats_refresh_every`` steps as the cell's job does, each with
its losses and the leaf where each gap is widest.

A state left unchanged reads 1 on ``change_gap`` by construction and
needs no run.  Like ``run.py``, it needs a TPU unless ``--cpu`` (tests).
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import compare  # noqa: E402
import faults  # noqa: E402
import generator  # noqa: E402
import run as harness  # noqa: E402

# the program's own path one precision below each policy, where it has one
PROGRAM_CONTROL = {"bf16": "fp8"}


def reference_readings(cell, seed: int, variants, devices) -> dict:
    """``{variant: numbers}``: the reference of each variant against the
    plain f32 reference, which runs once for them all."""
    ref, s, job = cell.reference, cell.sizes, cell.job
    key = generator.seed_key(seed, stream=0)
    make = generator.make_batch_fn(seed, s["vocab"], job["batch"],
                                   job["seq"], job["tokens"])
    batches = [make(i) for i in range(job["check_steps"])]

    def go(**kw):
        out = ref.train(s, job["optimizer"], key, batches, devices=devices,
                        **kw)
        out["grad_norms"] = compare.flatten(out["grad_norms"])
        out["change_norms"] = compare.flatten(out["change_norms"])
        return out

    kwargs = {
        "control": dict(num=ref.Int4()),
        "half_batch": dict(half_batch=True),
        "s2fp8.fresh": dict(num=ref.S2FP8(refresh_every=1)),
        "s2fp8.delayed": dict(num=ref.S2FP8(
            refresh_every=job["stats_refresh_every"])),
    }
    base = go()
    rows = {}
    for variant in variants:
        other = go(**kwargs[variant])
        read = compare.readings(other, base)
        out = {k: v for k, (v, _) in read.items()}
        if variant.startswith("s2fp8"):
            out.update({f"{k}.where": w for k, (_, w) in read.items()},
                       losses=other["losses"], f32_losses=base["losses"],
                       change_norms=other["change_norms"],
                       f32_change_norms=base["change_norms"])
        rows[variant] = out
    return rows


def program_readings(cell, seed: int, devices, wrap_step=None) -> dict:
    out = harness.run_cell(cell, seed, 1.0, False, devices,
                           wrap_step=wrap_step)
    return {k: v["value"] for k, v in out["checks"].items()}


def readings(cell, seed: int, devices, emulate: bool = False) -> list:
    if emulate:
        return list(reference_readings(
            cell, seed, ("s2fp8.fresh", "s2fp8.delayed"), devices).items())
    rows = []
    if cell.job["policy"] in PROGRAM_CONTROL:
        lower = copy.copy(cell)
        lower.job = dict(cell.job,
                         policy=PROGRAM_CONTROL[cell.job["policy"]])
        rows.append(("control", program_readings(lower, seed, devices)))
        rows += reference_readings(cell, seed, ("half_batch",),
                                   devices).items()
    else:
        rows += reference_readings(cell, seed, ("control", "half_batch"),
                                   devices).items()
    if len(devices) > 1:
        rows.append(("unsynced", program_readings(cell, seed, devices,
                                                  faults.unsynced)))
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--emulate", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.Cell(harness.load_json(harness.ROOT / "BENCHMARK.json"),
                        args.workload)
    import jax
    devices = (jax.devices()[:cell.chips] if args.cpu
               else harness.find_devices(cell.chips))
    harness.enable_compile_cache()
    sys.path.insert(0, str(harness.ROOT / "src"))
    for seed in (int(s) for s in args.seeds.split(",")):
        for variant, read in readings(cell, seed, devices, args.emulate):
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "variant": variant, **read}), flush=True)


if __name__ == "__main__":
    main()
