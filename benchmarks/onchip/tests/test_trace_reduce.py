"""The trace reducer on hand-made events and on a small trace recorded on
the CPU (``data/cpu_window.xplane.pb``): three jitted ``tanh(x @ x)``
calls, each in a ``bench/train_step`` span and followed by 30 ms of sleep
in a ``bench/idle`` span, all inside ``bench/window``.  On the CPU the
XLA operations are host-thread events with an ``hlo_op`` stat; the test
hands those to the reducer as one device's operations."""
from __future__ import annotations

from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts the benchmark directory on the path)
import trace_reduce as T

DATA = Path(__file__).resolve().parent / "data" / "cpu_window.xplane.pb"


def test_union_and_gaps_by_hand():
    ops = {"/device:TPU:0": [T.Event("a", 10, 20), T.Event("b", 20, 30),
                             T.Event("a", 50, 60)]}
    spans = [T.Event(T.WINDOW_SPAN, 0, 100), T.Event("bench/data", 30, 50),
             T.Event("bench/train_step", 0, 100)]
    red = T.reduce(ops, spans)
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(30e-9)          # [10,30] + [50,60]
    assert red.op_seconds == pytest.approx({"a": 20e-9, "b": 10e-9})
    assert red.op_counts == {"a": 2, "b": 1}
    # the gaps: [0,10], [30,50] (inside bench/data), [60,100]
    assert red.idle_gaps == [("bench/train_step", pytest.approx(40e-9)),
                             ("bench/data", pytest.approx(20e-9)),
                             ("bench/train_step", pytest.approx(10e-9))]
    assert red.kernel_calls(lambda n: n == "a") == [
        ("a", pytest.approx(20e-9), 2)]
    assert red.top_ops(1) == [["a", pytest.approx(20e-9)]]


def test_nested_ops_count_their_own_time_once():
    """A TPU trace nests a loop's body ops inside the ``while`` op."""
    loop = "%while.3 = (f32[2]) while(f32[2] %t), body=%body"
    gemm = ("%s2fp8_matmul_pallas.131 = f32[8,8] custom-call(f8e5m2[8,8] "
            "%a), custom_call_target=\"tpu_custom_call\"")
    ops = {"/device:TPU:0": [T.Event(loop, 0, 100), T.Event(gemm, 10, 40),
                             T.Event(gemm, 50, 70), T.Event("%fusion.2", 90,
                                                            95)]}
    red = T.reduce(ops, [T.Event(T.WINDOW_SPAN, 0, 100)])
    assert red.busy_s == pytest.approx(100e-9)
    assert red.op_seconds[loop] == pytest.approx(45e-9)
    assert red.op_seconds[gemm] == pytest.approx(50e-9)
    assert sum(red.op_seconds.values()) == pytest.approx(red.busy_s)
    assert red.top_ops(2) == [["s2fp8_matmul_pallas", pytest.approx(50e-9)],
                              ["while", pytest.approx(45e-9)]]
    assert T.base_name(gemm) == "s2fp8_matmul_pallas"


def test_events_outside_the_window_are_clipped():
    ops = {"/device:TPU:0": [T.Event("a", -5, 5), T.Event("a", 95, 120)]}
    red = T.reduce(ops, [T.Event(T.WINDOW_SPAN, 0, 100)])
    assert red.busy_s == pytest.approx(10e-9)
    red = T.reduce(ops, [T.Event(T.WINDOW_SPAN, 0, 50)])
    assert red.busy_s == pytest.approx(5e-9)


def test_two_devices_average_busy_time():
    ops = {"/device:TPU:0": [T.Event("a", 0, 40)],
           "/device:TPU:1": [T.Event("a", 0, 20)]}
    red = T.reduce(ops, [T.Event(T.WINDOW_SPAN, 0, 100)])
    assert red.busy_s == pytest.approx(30e-9) and red.n_devices == 2


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError):
        T.reduce({}, [T.Event(T.WINDOW_SPAN, 0, 1)])


def _cpu_ops(profile):
    ops = []
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                ops += [T.Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if "hlo_op" in dict(e.stats)]
    return {"cpu:0": ops}


def test_recorded_cpu_trace():
    profile = T.load(str(DATA))
    assert T.device_ops(profile) == {}        # no TPU plane on the CPU
    spans = T.host_spans(profile)
    names = [s.name for s in spans]
    assert names.count("bench/train_step") == 3
    assert names.count("bench/idle") == 3
    ops = _cpu_ops(profile)
    red = T.reduce(ops, spans)
    lo, hi = T.window_of(spans)
    assert red.window_s == pytest.approx((hi - lo) * 1e-9)
    # three calls, each a dot and a tanh
    assert red.op_counts == {"dot_general.1": 3, "wrapped_tanh": 3}
    busy = sum(e.end - e.start for e in ops["cpu:0"]) * 1e-9
    assert red.busy_s == pytest.approx(busy)  # the ops do not overlap
    assert 0 < red.busy_s < red.window_s
    # the three longest gaps are the three sleeps of 30 ms
    longest = red.idle_gaps[:3]
    assert [n for n, _ in longest] == ["bench/idle"] * 3
    assert all(0.029 < s < 0.05 for _, s in longest)
    total_idle = sum(s for _, s in red.idle_gaps)
    assert total_idle + red.busy_s == pytest.approx(red.window_s)
