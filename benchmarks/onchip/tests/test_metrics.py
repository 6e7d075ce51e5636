"""Each per-layer metric reader on a hand-made trace reduction: the
number it reads, and nothing where its kernels are absent."""
from __future__ import annotations

import json
import sys

import pytest

import tiny
import counts
import peaks
import trace_reduce as T

BENCH = json.loads((tiny.harness.ROOT / "BENCHMARK.json").read_text())
PEAK = peaks.peaks_for("TPU v5 lite")
SIZES = tiny.harness.load_module(tiny.ONCHIP / "configs" / "dense_decoder.py"
                                 ).sizes(json.loads(
                                     (tiny.ONCHIP / "configs"
                                      / "minicpm-2b.json").read_text()))
JOB = {"batch": 2, "seq": 1024}


def reader(name):
    return tiny.harness.reader(name)


def ctx(ops, steps=4, history=(), chips=1):
    red = T.Reduction(window_s=2.0, busy_s=1.5,
                      op_seconds={k: v for k, v in ops.items()},
                      op_counts={k: 1 for k in ops}, idle_gaps=[],
                      n_devices=chips)
    return {"trace": red, "sizes": SIZES, "job": JOB, "chips": chips,
            "peaks": PEAK, "steps": steps, "history": list(history),
            "counts": counts, "log": lambda msg: None}


def test_every_per_layer_metric_has_a_reader():
    for m in BENCH["per_layer"]:
        assert callable(reader(m["name"]).read)


def test_mfu_and_idle():
    c = ctx({})        # 4 steps of 2 x 1024 tokens in a 2 s traced window
    want = (100 * counts.model_flops_per_token(SIZES, JOB) * 4 * 2048 / 2.0
            / PEAK["bf16_flops"])
    assert reader("step_mfu.train").read(c) == pytest.approx(want)
    assert reader("device_idle_pct.train").read(c) == pytest.approx(25.0)


# device ops as a TPU trace names them: the HLO instruction's text
GEMM_OP = ("%s2fp8_matmul_pallas.131 = f32[2048,2304]{1,0:T(8,128)} "
           "custom-call(f32[1,1]{1,0:T(1,128)} %b.1, f8e5m2[2048,2560]{1,0} "
           "%p.1, f8e5m2[2560,2304]{1,0} %p.2), custom_call_target="
           "\"tpu_custom_call\"")
QFLASH_OP = ("%checkpoint.21 = f32[72,1024,128]{2,1,0:T(8,128)} custom-call("
             "f32[1,1]{1,0} %g.1, f8e5m2[72,1024,128]{2,1,0} %a, "
             "f8e5m2[72,1024,128]{2,1,0} %b, f8e5m2[72,1024,128]{2,1,0} %c, "
             "f8e5m2[72,1024,128]{2,1,0} %d, f32[72,1024,1]{2,1,0} %e), "
             "custom_call_target=\"tpu_custom_call\"")
QUANT_OP = ("%quant_apply_pallas.446 = f8e5m2[2304,2560]{1,0} custom-call("
            "f32[1,1]{1,0} %g, f32[2304,2560]{1,0} %x), custom_call_target="
            "\"tpu_custom_call\"")


@pytest.mark.parametrize("name,kernel,call", [
    ("gemm_roofline_pct", GEMM_OP, counts.gemm_call),
    ("qflash_roofline_pct", QFLASH_OP, counts.qflash_call),
])
def test_roofline(name, kernel, call):
    ideal = counts.ideal_seconds([call(kernel, SIZES, JOB)], PEAK)["seconds"]
    c = ctx({kernel: 2 * ideal, QUANT_OP: 1.0, "%while.3 = (...) while": 1})
    assert reader(name).read(c) == pytest.approx(100 / 2)
    assert reader(name).read(ctx({QUANT_OP: 1.0})) is None


def test_a_listed_metric_that_reads_nothing_fails_the_run():
    sys.path.insert(0, str(tiny.ONCHIP / "drivers"))
    cell = tiny.harness.Cell(BENCH, "minicpm-2b.train.s1024")
    c = ctx({QUANT_OP: 1.0})
    out = {"correct": True, "attempted": 1, "failed": 0, "layer_ctx": c,
           "memory_peak_bytes": 0, "checks": []}

    class Driver:
        @staticmethod
        def run(*a, **k):
            return out

    real = tiny.harness.load_module
    tiny.harness.load_module = lambda p: (Driver if p.parent.name ==
                                          "drivers" else real(p))
    try:
        with pytest.raises(SystemExit, match="gemm_roofline_pct"):
            tiny.harness.run_cell(cell, 1, 1.0, True, [FakeDevice()])
    finally:
        tiny.harness.load_module = real


class FakeDevice:
    platform, device_kind = "tpu", "TPU v5 lite"


def test_refresh_excess():
    hist = [{"step_s": 1.2, "stats_refreshed": 1.0}] + [
        {"step_s": 1.0, "stats_refreshed": 0.0}] * 3
    assert reader("refresh_excess_pct").read(
        ctx({}, history=hist)) == pytest.approx(20.0)
    assert reader("refresh_excess_pct").read(
        ctx({}, history=[{"step_s": 1.0}])) is None


# collectives as a TPU trace names them (self time over the chips)
COLLECTIVE_OPS = {
    "%all-gather-start.3 = (f32[4,8]{1,0}, f32[16,8]{1,0}) "
    "all-gather-start(f32[4,8]{1,0} %p.1), channel_id=1, dimensions={0}": 0.2,
    "%all-gather-done.3 = f32[16,8]{1,0} all-gather-done((f32[4,8]{1,0}, "
    "f32[16,8]{1,0}) %all-gather-start.3)": 0.1,
    "%reduce-scatter.7 = bf16[4,8]{1,0} reduce-scatter(bf16[16,8]{1,0} "
    "%convert.2), channel_id=2, dimensions={0}, to_apply=%add": 0.3,
    "%all-reduce-fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kOutput": 0.4,
}


def test_collective_exposed():
    # 1.0 s of collectives' self time over 4 chips x a 2 s window; a
    # fusion that reads a collective's output is no collective
    ops = dict(COLLECTIVE_OPS, **{
        "%fusion.2 = f32[16,8]{1,0} fusion(f32[16,8]{1,0} "
        "%all-gather-done.3), kind=kLoop": 5.0, QUANT_OP: 1.0})
    got = reader("collective_exposed_pct.train").read(ctx(ops, chips=4))
    assert got == pytest.approx(100 * 1.0 / (4 * 2.0))
    assert reader("collective_exposed_pct.train").read(
        ctx({QUANT_OP: 1.0}, chips=4)) is None
