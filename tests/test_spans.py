"""The span recorder of ``repro.obs.spans``: parents, bounds, counters, the
compile listener, and its clock against a profiler trace on the CPU."""
from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro.obs import sinks as obs_sinks
from repro.obs import spans
from repro.training.trainer import TrainLoop

MS = 1_000_000          # ns


def test_nested_spans_record_their_parent():
    rec = spans.Recorder()
    with rec.span("outer") as outer:
        with rec.span("inner") as inner:
            pass
        with rec.span("inner"):
            pass
    assert rec.spans["inner"].parent == "outer"
    assert rec.spans["outer"].parent is None
    assert rec.spans["inner"].count == 2 and rec.spans["outer"].count == 1
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert rec.spans["inner"].first_start_ns == inner.start_ns
    assert [(name, parent) for name, parent, _, _ in rec.recent] == [
        ("inner", "outer"), ("inner", "outer"), ("outer", None)]


def test_recorder_stays_bounded_over_many_spans():
    rec = spans.Recorder(ring=64, max_names=8)
    n = 100_000
    for i in range(n):
        with rec.span(f"s{i % 16}"):
            pass
    assert len(rec.recent) == 64
    assert rec.recent[-1][0] == f"s{(n - 1) % 16}"
    assert set(rec.spans) == {f"s{i}" for i in range(8)} | {spans.OTHER}
    assert rec.spans[spans.OTHER].count == n // 2
    assert sum(s.count for s in rec.spans.values()) == n


def test_counters_add_up_and_stay_bounded():
    rec = spans.Recorder(max_names=2)
    rec.counter("a")
    rec.counter("a", 4)
    rec.counter("b", 2)
    rec.counter("c")
    rec.counter("d", 3)
    assert rec.counters == {"a": 5, "b": 2, spans.OTHER: 4}
    assert {"kind": "counter", "name": "a", "value": 5} in list(rec.records())


def test_compile_listener_tells_the_step_from_nested_jits():
    spans.install_compile_listener()
    spans.install_compile_listener()        # once a process, however called
    rec = spans.RECORDER
    rec.reset()

    @jax.jit
    def inner_kernel(x):
        return x * 2.0

    def my_train_step(x):
        return inner_kernel(x) + 1.0

    rec.register_step(my_train_step)
    step = jax.jit(my_train_step)
    step(jnp.ones(3)).block_until_ready()
    first = {p: rec.first("my_train_step", p)
             for p in (spans.TRACE, spans.LOWER, spans.COMPILE)}
    assert all(v is not None and v > 0 for v in first.values()), first
    step(jnp.ones(3)).block_until_ready()
    step.lower(jnp.ones(3))
    assert {p: rec.first("my_train_step", p) for p in first} == first
    assert rec.compiles["my_train_step"][spans.TRACE].count >= 1
    # the nested jit is traced under its own name and lowered only inside
    # the step's module; its trace lies inside the step's
    assert rec.first("inner_kernel", spans.TRACE) <= first[spans.TRACE]
    assert set(rec.compiles["inner_kernel"]) == {spans.TRACE}
    assert rec.step_names == {"my_train_step"}
    steps = [r for r in rec.records() if r["kind"] == "compile"
             and r["fun_name"] == "my_train_step"]
    assert {r["phase"] for r in steps} == {spans.TRACE, spans.LOWER,
                                           spans.COMPILE}
    assert rec.total(spans.TRACE) >= first[spans.TRACE]
    # registering again starts its first events afresh, and keeps the
    # totals (two loops around one step function in one process)
    total = rec.total(spans.COMPILE)
    rec.register_step(my_train_step)
    assert rec.first("my_train_step", spans.TRACE) is None
    assert rec.total(spans.COMPILE) == total


def _trace_file(log_dir) -> str:
    paths = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    return paths[0]


def _host_events(profile, name):
    """(absolute start ns, absolute end ns) of each host event ``name``:
    the file holds them relative to the profile's start."""
    t0 = next(dict(p.stats)["profile_start_time"] for p in profile.planes
              if p.name == "Task Environment")
    return [(t0 + e.start_ns, t0 + e.end_ns)
            for p in profile.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events if e.name == name]


def test_span_lands_on_the_profiler_clock(tmp_path):
    rec = spans.Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("probe/clock") as s:
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    profile = ProfileData.from_file(_trace_file(tmp_path))
    [(start, end)] = _host_events(profile, "probe/clock")
    assert abs(start - s.start_ns) < MS
    assert abs(end - s.end_ns) < MS


def test_trainloop_steps_and_spans_show_in_a_profiler_trace(tmp_path):
    def train_step(params, opt_state, batch, step):
        return params, opt_state, {"loss": jnp.float32(1.0),
                                   "lr": jnp.float32(1e-3)}

    spans.RECORDER.reset()
    loop = TrainLoop(train_step, {"w": jnp.zeros((4,))},
                     {"m": jnp.zeros((4,))},
                     lambda s: {"x": jnp.zeros((2,))},
                     log_every=0, sink=obs_sinks.NullSink())
    jax.profiler.start_trace(str(tmp_path))
    try:
        loop.run(3)
    finally:
        jax.profiler.stop_trace()
    profile = ProfileData.from_file(_trace_file(tmp_path))
    steps = _host_events(profile, spans.STEP_TRACE)
    assert len(steps) == 3
    for name in (spans.TRAIN_DATA, spans.TRAIN_STEP):
        traced = _host_events(profile, name)
        recorded = [(a, b) for n, _, a, b in spans.RECORDER.recent
                    if n == name]
        assert len(traced) == len(recorded) == 3
        for (a, b), (ra, rb), (sa, sb) in zip(traced, recorded, steps):
            assert abs(a - ra) < MS and abs(b - rb) < MS
            assert sa - MS <= a and b <= sb + MS     # inside its step
    assert spans.RECORDER.spans[spans.TRAIN_STEP].count == 3
    assert spans.RECORDER.step_names == {"train_step"}


def test_gemm_plan_counters_count_each_traced_gemm():
    """``dispatch`` counts a payload GEMM's grid when it plans it: MACs
    over the padded grid, the padded part, the operand elements
    dequantized over the grid and the operands' own elements."""
    from repro.kernels import dispatch
    f8 = jnp.float8_e5m2
    a, b = jnp.zeros((100, 200), f8), jnp.zeros((200, 300), f8)
    spans.RECORDER.reset()
    jax.jit(lambda a, b: dispatch.qmatmul_nd(
        a, 1.0, 0.0, b, 1.0, 0.0, bm=128, bk=128, bn=128)).lower(a, b)
    # M 100 -> 104 rows, one 104-row block; K 200 -> 256; N 300 -> 384
    macs = 104 * 256 * 384
    gemm = {k: v for k, v in spans.RECORDER.counters.items()
            if k.startswith("gemm/")}
    assert gemm == {
        spans.GEMM_MACS: macs,
        spans.GEMM_PADDED_MACS: macs - 100 * 200 * 300,
        spans.GEMM_DEQUANT_ELEMS: macs // 128 + macs // 104,
        spans.GEMM_OPERAND_ELEMS: 100 * 200 + 200 * 300}
    # batched, B broadcast over A's 3 slices: each slice's grid counts
    spans.RECORDER.reset()
    jax.jit(lambda a, b: dispatch.qmatmul_batched_nd(
        a, 1.0, 0.0, b, 1.0, 0.0, bm=128, bk=128, bn=128)).lower(
        jnp.zeros((3, 100, 200), f8), jnp.zeros((1, 200, 300), f8))
    assert spans.RECORDER.counters[spans.GEMM_MACS] == 3 * macs
    assert spans.RECORDER.counters[spans.GEMM_OPERAND_ELEMS] == \
        3 * 100 * 200 + 200 * 300


def test_gemm_plan_counters_after_a_traced_training_step():
    """A payload-GEMM step's forward and both backward GEMMs are counted
    once per trace of the step, not per executed step."""
    from repro.core.policy import make_policy
    pol = make_policy("s2fp8", backend="pallas", gemm_mode="payload")
    x = jnp.ones((16, 256), jnp.float32)
    w = jnp.ones((256, 128), jnp.float32)
    step = jax.jit(jax.grad(lambda w, x: jnp.sum(pol.dot(x, w))))
    spans.RECORDER.reset()
    step(w, x).block_until_ready()
    step(w, x).block_until_ready()
    c = spans.RECORDER.counters
    # forward x W, dx = g W^T, dW = x^T g: 16 x 256 x 128 MACs each
    assert c[spans.GEMM_MACS] == 3 * 16 * 256 * 128
    assert c[spans.GEMM_PADDED_MACS] == 0
    assert c[spans.GEMM_OPERAND_ELEMS] == (
        (16 * 256 + 256 * 128) + (16 * 128 + 256 * 128)
        + (16 * 256 + 16 * 128))
    assert c[spans.GEMM_DEQUANT_ELEMS] >= c[spans.GEMM_OPERAND_ELEMS]
