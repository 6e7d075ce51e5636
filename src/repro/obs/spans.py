"""Names the program gives its work in a profiler trace, and the in-process
recorder of its host spans, counters and compile times.

Every name lives here, so that the program and the readers of its traces
(``benchmarks/onchip/metrics``) agree on them:

* host spans, entered with :func:`span`: a ``jax.profiler.TraceAnnotation``
  on the profiler's host timeline, plus an entry in :data:`RECORDER`;
* device scopes, entered with ``jax.named_scope``: they land in the
  ``op_name`` metadata of every HLO operation traced inside them, so a
  device operation carries the phase it ran in (the backward pass of
  ``attn`` shows as ``transpose(jvp(attn))``).  They cost nothing at run
  time;
* kernel names, given to ``pl.pallas_call(name=...)``: the name is the
  innermost scope of the Mosaic custom call, so it becomes the call's HLO
  instruction name, which is what a TPU trace calls the kernel, whatever
  wraps it (``jax.checkpoint``, ``lax.cond``, a ``jax.jit``).

The recorder keeps, per span name, a count, the total time, the enclosing
span and the first start and end, plus a ring of the latest spans; per
counter a sum; and, fed by :func:`install_compile_listener`, the trace,
lowering and backend-compile (or persistent-cache read) durations JAX
reports, by function name.  All of it is bounded however long a process
runs.  Times are ``time.time_ns()``, the clock the profiler stamps host
events with: an ``.xplane.pb`` stores them relative to the
``profile_start_time`` stat of its ``Task Environment`` plane.
"""
from __future__ import annotations

import collections
import functools
import threading
import time
from typing import Dict, Iterator, Optional

import jax

# -- host spans --------------------------------------------------------------
INIT_BANK = "statsbank/init_bank"   # StatsBank site discovery (eval_shape)
TRAIN_DATA = "train/data"           # TrainLoop: make the step's batch
TRAIN_STEP = "train/step"           # TrainLoop: dispatch to block_until_ready
TRAIN_CKPT = "train/ckpt"           # TrainLoop: checkpoint save
STEP_TRACE = "train"                # TrainLoop's StepTraceAnnotation

# -- device scopes (jax.named_scope) -----------------------------------------
ATTN = "attn"
MLP = "mlp"
LM_HEAD = "lm_head"
OPTIMIZER = "optimizer"
STATSBANK_REFRESH = "statsbank_refresh"
S2FP8_QUANTIZE = "s2fp8_quantize"

# -- kernel names (pl.pallas_call(name=...)) ---------------------------------
# the payload GEMM and quant-apply names are also their jitted wrappers'
# names, which the benchmark's accepted readers match
GEMM = "s2fp8_matmul_pallas"
GEMM_BATCHED = "s2fp8_matmul_batched_pallas"
STATS = "stats_pallas"
QUANT_APPLY = "quant_apply_pallas"
DEQUANT = "dequant_pallas"
TRUNCATE_APPLY = "truncate_apply_pallas"
TRUNCATE_FUSED = "truncate_fused_pallas"
QFLASH_FWD = "qflash_fwd"
QFLASH_BWD_DQ = "qflash_bwd_dq"
QFLASH_BWD_DKDV = "qflash_bwd_dkdv"
FLASH = "flash_attention_pallas"
PAGED_DECODE = "paged_decode_attention"
SELECTIVE_SCAN = "selective_scan_pallas"
# the S2FP8 quantization passes: Eq. 3-4 statistics, the forward map to the
# payload, its inverse, and the Eq. 5 round trip
QUANT_KERNELS = (STATS, QUANT_APPLY, DEQUANT, TRUNCATE_APPLY, TRUNCATE_FUSED)
KERNELS = (GEMM, GEMM_BATCHED) + QUANT_KERNELS + (
    QFLASH_FWD, QFLASH_BWD_DQ, QFLASH_BWD_DKDV, FLASH, PAGED_DECODE,
    SELECTIVE_SCAN)

# -- counters and compile phases ---------------------------------------------
CACHE_HITS = "compile/cache_hits"
CACHE_MISSES = "compile/cache_misses"
TRACE, LOWER, COMPILE = "trace", "lower", "compile"
_PHASE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    # backend compile, or the read of the persistent cache that replaces it
    "/jax/core/compile/backend_compile_duration": COMPILE,
}
_COUNTER_OF_EVENT = {"/jax/compilation_cache/cache_hits": CACHE_HITS,
                     "/jax/compilation_cache/cache_misses": CACHE_MISSES}
OTHER = "<other>"      # names past a recorder's ``max_names`` pool here


class SpanStats:
    """Aggregate of one span name."""
    __slots__ = ("count", "total_ns", "parent", "first_start_ns",
                 "first_end_ns")

    def __init__(self, parent: Optional[str], start_ns: int, end_ns: int):
        self.count, self.total_ns, self.parent = 0, 0, parent
        self.first_start_ns, self.first_end_ns = start_ns, end_ns


class CompileStats:
    """Aggregate of one compile phase of one function; ``first_s`` is the
    first duration since the function was registered as a step."""
    __slots__ = ("count", "total_s", "first_s")

    def __init__(self):
        self.count, self.total_s, self.first_s = 0, 0.0, None


class Span:
    """One timed host span; ``seconds`` is its duration once it has ended."""
    __slots__ = ("recorder", "name", "parent", "start_ns", "end_ns",
                 "_annotation")

    def __init__(self, recorder: "Recorder", name: str):
        self.recorder, self.name = recorder, name
        self.parent = None
        self.start_ns = self.end_ns = 0

    def __enter__(self) -> "Span":
        stack = self.recorder._stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self.start_ns = time.time_ns()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._annotation.__exit__(*exc)
        self.end_ns = time.time_ns()
        self.recorder._stack().pop()
        self.recorder._add(self)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class Recorder:
    """Bounded in-memory record of host spans, counters and compile times.

    ``ring`` is how many of the latest spans ``recent`` keeps as
    ``(name, parent, start_ns, end_ns)``; ``max_names`` bounds the distinct
    span, counter and function names, past which they pool under
    :data:`OTHER`."""

    def __init__(self, ring: int = 256, max_names: int = 1024):
        self.max_names = max_names
        self.recent = collections.deque(maxlen=ring)
        self.spans: Dict[str, SpanStats] = {}
        self.counters: Dict[str, int] = {}
        self.compiles: Dict[str, Dict[str, CompileStats]] = {}
        self.step_names: set = set()
        self._lock = threading.Lock()
        self._local = threading.local()

    def reset(self) -> None:
        with self._lock:
            self.recent.clear()
            self.spans.clear()
            self.counters.clear()
            self.compiles.clear()
            self.step_names.clear()

    def _key(self, table: dict, name: str) -> str:
        return name if name in table or len(table) < self.max_names \
            else OTHER

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    # -- recording ---------------------------------------------------------
    def span(self, name: str) -> Span:
        """Context manager timing ``name`` on the profiler's host timeline
        and in this recorder; the span open around it is its parent."""
        return Span(self, name)

    def _add(self, s: Span) -> None:
        with self._lock:
            key = self._key(self.spans, s.name)
            agg = self.spans.get(key)
            if agg is None:
                agg = self.spans[key] = SpanStats(s.parent, s.start_ns,
                                                  s.end_ns)
            agg.count += 1
            agg.total_ns += s.end_ns - s.start_ns
            self.recent.append((s.name, s.parent, s.start_ns, s.end_ns))

    def counter(self, name: str, n: int = 1) -> None:
        with self._lock:
            key = self._key(self.counters, name)
            self.counters[key] = self.counters.get(key, 0) + n

    def compile_event(self, phase: str, fun_name: str, seconds: float
                      ) -> None:
        """One trace, lowering or compile of ``fun_name``; JAX names a
        lowered or compiled module ``jit(<fun_name>)``, which counts under
        the function's own name."""
        if fun_name.endswith(")") and "(" in fun_name:
            fun_name = fun_name[fun_name.index("(") + 1:-1]
        with self._lock:
            by_phase = self.compiles.setdefault(
                self._key(self.compiles, fun_name), {})
            agg = by_phase.get(phase)
            if agg is None:
                agg = by_phase[phase] = CompileStats()
            if agg.first_s is None:
                agg.first_s = seconds
            agg.count += 1
            agg.total_s += seconds

    def register_step(self, fn) -> None:
        """Mark ``fn``, a function about to be jitted, as a train step.
        Its trace, lowering and compile are recorded under its name, as
        JAX reports it (a ``functools.partial`` by its function's), apart
        from the nested kernel jits, recorded under their own names; from
        here ``first`` reads its first events after this registration."""
        while isinstance(fn, functools.partial):
            fn = fn.func
        fun_name = getattr(fn, "__name__", "<unnamed function>")
        with self._lock:
            self.step_names.add(fun_name)
            for agg in self.compiles.get(fun_name, {}).values():
                agg.first_s = None

    # -- reading -----------------------------------------------------------
    def first(self, fun_name: str, phase: str) -> Optional[float]:
        """Seconds of the first ``phase`` of ``fun_name`` (since its
        registration as a step), or None."""
        agg = self.compiles.get(fun_name, {}).get(phase)
        return None if agg is None else agg.first_s

    def total(self, phase: str) -> float:
        """Seconds of every ``phase`` event of every function."""
        return sum(by[phase].total_s for by in self.compiles.values()
                   if phase in by)

    def records(self) -> Iterator[Dict]:
        """Sink records (``repro.obs.sinks``): one ``span`` per span name,
        one ``counter`` per counter, one ``compile`` per phase of each
        registered step and, under ``fun_name`` ``*``, of all functions."""
        for name, s in sorted(self.spans.items()):
            yield {"kind": "span", "name": name, "parent": s.parent,
                   "count": s.count, "total_ms": s.total_ns * 1e-6,
                   "first_ms": (s.first_end_ns - s.first_start_ns) * 1e-6}
        for name, n in sorted(self.counters.items()):
            yield {"kind": "counter", "name": name, "value": n}
        for fun in sorted(self.step_names):
            for phase, c in sorted(self.compiles.get(fun, {}).items()):
                yield {"kind": "compile", "fun_name": fun, "phase": phase,
                       "count": c.count, "total_s": c.total_s,
                       "first_s": c.first_s}
        for phase in (TRACE, LOWER, COMPILE):
            yield {"kind": "compile", "fun_name": "*", "phase": phase,
                   "total_s": self.total(phase)}


RECORDER = Recorder()


def span(name: str) -> Span:
    """A span of the process's recorder, :data:`RECORDER`."""
    return RECORDER.span(name)


def counter(name: str, n: int = 1) -> None:
    RECORDER.counter(name, n)


def _on_time_span(event: str, start_s: float, end_s: float, **kw) -> None:
    phase = _PHASE_OF_EVENT.get(event)
    if phase is not None:
        RECORDER.compile_event(phase, str(kw.get("fun_name", "?")),
                               end_s - start_s)


def _on_event(event: str, **kw) -> None:
    name = _COUNTER_OF_EVENT.get(event)
    if name is not None:
        RECORDER.counter(name)


_installed = False


def install_compile_listener() -> None:
    """Feed JAX's compile events into :data:`RECORDER` (once a process)."""
    global _installed
    if not _installed:
        jax.monitoring.register_event_time_span_listener(_on_time_span)
        jax.monitoring.register_event_listener(_on_event)
        _installed = True
