"""The readers of what the program names and records about itself:
``quant_kernels_pct`` on hand-made trace reductions, ``step_trace_s`` on a
filled recorder, and both where the program has no ``repro.obs.spans``."""
from __future__ import annotations

import builtins

import pytest

import tiny
import counts
import trace_reduce as T

from repro.obs import spans


def reader(name):
    return tiny.harness.reader(name)


def ctx(ops, policy="s2fp8", n_devices=1, busy_s=2.0):
    red = T.Reduction(window_s=2.5, busy_s=busy_s,
                      op_seconds=dict(ops), op_counts={k: 1 for k in ops},
                      idle_gaps=[], n_devices=n_devices)
    return {"trace": red, "sizes": {}, "job": {"policy": policy},
            "chips": n_devices, "peaks": {}, "steps": 4, "history": [],
            "counts": counts, "log": lambda msg: None}


def op(name, n=7):
    """A Mosaic call as a TPU trace names it: its HLO instruction text."""
    return (f"%{name}.{n} = f32[2048,2304]{{1,0}} custom-call(f32[1,1]{{1,0}} "
            f"%a, f32[2048,2304]{{1,0}} %x), custom_call_target="
            f"\"tpu_custom_call\"")


GEMM = op(spans.GEMM)


def test_quant_kernels_pct_adds_the_quantization_kernels():
    ops = {op(spans.QUANT_APPLY, 1): 0.1, op(spans.QUANT_APPLY, 2): 0.05,
           op(spans.TRUNCATE_APPLY): 0.04, op(spans.STATS): 0.01,
           GEMM: 1.5, "%fusion.3 = f32[8] fusion(...)": 0.3}
    assert reader("quant_kernels_pct").read(ctx(ops)) == pytest.approx(
        100 * 0.2 / 2.0)
    # self time summed over chips, averaged: busy_s is a mean over chips
    assert reader("quant_kernels_pct").read(
        ctx(ops, n_devices=2, busy_s=1.0)) == pytest.approx(100 * 0.1 / 1.0)


def test_quant_kernels_pct_ignores_what_wraps_a_kernel():
    # a kernel still named after its remat or cond wrapper is not counted
    ops = {op("checkpoint"): 0.5, op("branch_0_fun"): 0.5,
           op(spans.DEQUANT): 0.2, GEMM: 1.0}
    assert reader("quant_kernels_pct").read(ctx(ops)) == pytest.approx(10.0)


def test_quant_kernels_pct_without_quantization_kernels():
    assert reader("quant_kernels_pct").read(ctx({GEMM: 1.0})) is None
    assert reader("quant_kernels_pct").read(
        ctx({"%fusion.1 = f32[8] fusion(...)": 1.0}, policy="bf16")) == 0.0


@pytest.fixture
def recorder():
    rec = spans.RECORDER
    rec.reset()
    yield rec
    rec.reset()


def _fill(rec, trace=3.0, lower=2.0, bank_ns=5_000_000_000):
    def my_step(*args):
        return args

    rec.register_step(my_step)
    rec.compile_event(spans.TRACE, "my_step", trace)
    rec.compile_event(spans.TRACE, "s2fp8_matmul_pallas", 0.5)   # nested
    rec.compile_event(spans.LOWER, "jit(my_step)", lower)
    rec.compile_event(spans.COMPILE, "jit(my_step)", 40.0)
    # the post-window ``.lower().compile()`` and the reference's jits
    rec.compile_event(spans.TRACE, "my_step", 0.01)
    rec.compile_event(spans.LOWER, "jit(my_step)", 1.5)
    rec.compile_event(spans.TRACE, "reference_step", 9.0)
    if bank_ns:
        with rec.span(spans.INIT_BANK) as s:
            pass
        rec.spans[spans.INIT_BANK].total_ns = bank_ns
        assert s.seconds >= 0


def test_step_trace_s_reads_init_bank_and_the_first_trace(recorder):
    _fill(recorder)
    assert reader("step_trace_s").read(ctx({})) == pytest.approx(10.0)


def test_step_trace_s_without_a_statsbank(recorder):
    _fill(recorder, bank_ns=0)
    assert reader("step_trace_s").read(ctx({})) == pytest.approx(5.0)


def test_step_trace_s_without_a_traced_step(recorder):
    assert reader("step_trace_s").read(ctx({})) is None
    recorder.register_step(lambda x: x)
    assert reader("step_trace_s").read(ctx({})) is None


@pytest.mark.parametrize("name", ["quant_kernels_pct", "step_trace_s"])
def test_readers_on_a_program_without_spans(monkeypatch, recorder, name):
    """A program older than ``repro.obs.spans`` (the parent of the change
    that added it) reads nothing, and does not raise."""
    _fill(recorder)
    real = builtins.__import__

    def no_spans(mod, globals=None, locals=None, fromlist=(), level=0):
        if mod == "repro.obs" and "spans" in (fromlist or ()):
            raise ModuleNotFoundError("No module named 'repro.obs.spans'")
        return real(mod, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_spans)
    assert reader(name).read(ctx({op(spans.QUANT_APPLY): 1.0})) is None
