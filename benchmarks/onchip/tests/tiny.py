"""A benchmark cell cut to a size that the CPU runs in seconds, for the
harness's own tests: the sizes its configuration's reference gives for a
test (``tiny_sizes``), and the traffic of the named cell at 64 tokens a
sequence and 2 sequences a chip."""
from __future__ import annotations

import sys
from pathlib import Path

ONCHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ONCHIP))
sys.path.insert(0, str(ONCHIP.parents[1] / "src"))

import run as harness  # noqa: E402


def tiny_cell(workload: str):
    """The cell ``workload`` at its reference's test sizes."""
    cell = harness.Cell(harness.load_json(harness.ROOT / "BENCHMARK.json"),
                        workload)
    cell.sizes = cell.reference.tiny_sizes(cell.sizes)
    cell.cut = list(cell.sizes)
    cell.job = dict(cell.job, batch=2 * cell.chips, seq=64)
    if cell.job.get("shard_params") == "fsdp_q":
        # on the CPU ``auto`` resolves to XLA dots, which the payload
        # gathers cannot feed; on the chip it resolves to payload GEMMs
        cell.job["gemm_mode"] = "payload"
    return cell


def main(argv=None) -> None:
    """Run the tiny ``cell`` once on every device this process has (run it
    with as many virtual CPU devices as the cell has chips) and print the
    result as the last line; ``--fault`` plants a fault of ``faults.py``,
    ``--readings`` prints ``control.readings`` instead."""
    import argparse
    import json

    import jax

    import control
    import faults

    ap = argparse.ArgumentParser(description=main.__doc__.splitlines()[0])
    ap.add_argument("cell")
    ap.add_argument("limits", help="the limits, as JSON")
    ap.add_argument("seed", type=int)
    ap.add_argument("--fault")
    ap.add_argument("--readings", action="store_true")
    args = ap.parse_args(argv)
    cell = tiny_cell(args.cell)
    cell.limits = json.loads(args.limits)
    devices = jax.devices()
    if len(devices) != cell.chips:
        raise SystemExit(f"{cell.name} runs on {cell.chips} devices, this "
                         f"process has {len(devices)}")
    if args.readings:
        out = dict(control.readings(cell, args.seed, devices))
    else:
        wrap = getattr(faults, args.fault) if args.fault else None
        out = harness.run_cell(cell, args.seed, 0.2, False, devices,
                               wrap_step=wrap)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
