"""A benchmark cell cut to a size that the CPU runs in seconds, for the
harness's own tests: every width shrunk, the traffic that of the named
cell at 2 x 64 tokens."""
from __future__ import annotations

import sys
from pathlib import Path

ONCHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ONCHIP))
sys.path.insert(0, str(ONCHIP.parents[1] / "src"))

import run as harness  # noqa: E402

TINY = dict(d_model=128, n_heads=4, kv_heads=2, head_dim=32, d_ff=256,
            vocab=512, n_layers=2, rope_theta=10000.0,
            activation="silu_glu", norm="rms", remat=True)


def tiny_cell(workload: str):
    cell = harness.Cell(harness.load_json(harness.ROOT / "BENCHMARK.json"),
                        workload)
    cell.sizes = dict(TINY, tie_embeddings=cell.sizes["tie_embeddings"])
    cell.cut = list(TINY)
    cell.job = dict(cell.job, batch=2, seq=64)
    return cell
