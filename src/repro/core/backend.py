"""Numerics-backend registry: one interface, swappable engines.

Every S2FP8 operation the framework performs — stats, quantize, dequantize,
the Eq. 5 truncation that ``Policy`` wraps around each GEMM, and the
payload-domain GEMM (``qmatmul``: NN/NT/TN operand layouts, optional fused
Eq. 5 output epilogue, e5m2/e4m3 payloads; ``qdot_general`` maps restricted
higher-rank contractions onto it) — goes through a
:class:`NumericsBackend`.  Two engines ship:

  * ``"ref"``    — the pure-jnp implementation in core/s2fp8.py (today's
    semantics, the semantic ground truth, and the fast CPU path);
  * ``"pallas"`` — the fused Pallas kernels in kernels/ via the
    shape-generalizing dispatch layer (kernels/dispatch.py).  Its default
    stats mode computes (alpha, beta) with the same monolithic reduction
    the ref uses and fuses apply->FP8-RNE->inverse into one elementwise
    kernel — bitwise-identical outputs, two HBM passes instead of five.
    ``PallasBackend(stats_mode="fused")`` moves the stats reduction
    in-kernel as well (single two-phase ``pallas_call``; float-tolerance
    parity).

``"auto"`` resolves to ``"pallas"`` on TPU and ``"ref"`` elsewhere; the
``Policy`` dataclass carries the selection (core/policy.py), the launchers
expose it as ``--backend``, and ArchConfig carries a per-arch default.

Delayed-stats mode: every backend's ``truncate`` and ``quantize`` accept
precomputed ``stats=(alpha, beta)``.  The StatsBank subsystem
(core/statsbank.py) is the first-class consumer: a jit-carried, sharded,
checkpointable bank of per-site stats refreshed every k steps inside the
train step, plus ``HostStatsBank`` for eager callers (serving, checkpoint
compression).  :func:`truncate_delayed` remains the low-level functional
hook, and :class:`DelayedStatsCache` is a deprecated shim over the host
bank.  Tensor distributions drift slowly between adjacent steps (the
premise behind amortized scaling in FP8 training recipes), so stale-by-k
stats cost little accuracy while removing the stats reduction — the only
non-elementwise pass — from the hot loop.

Stats locality is explicit: ``compute_stats(x)`` reduces over the tensor
the caller holds (per-shard inside ``shard_map``), while
``compute_stats(x, axis_name=...)`` all-reduces the raw
``compute_stats_partials`` triplet for exact global stats.
"""
from __future__ import annotations

import functools
import string
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import s2fp8
from repro.core.s2fp8 import S2FP8Tensor
from repro.obs import spans

_TARGET_MAX = s2fp8.FMT_TARGET_MAX


def all_reduce_stats_partials(partials, axis_name: str):
    """Combine per-shard (log_sum, log_max, count) stats partials across a
    mapped/shard_map axis: sums and counts add, maxes max.  This is the one
    place global-stats semantics live — every caller (backend
    ``compute_stats(axis_name=...)``, the StatsBank refresh) reduces the
    same triplet, so global stats are exact, not shard-averaged."""
    log_sum, log_max, count = partials
    return (jax.lax.psum(log_sum, axis_name),
            jax.lax.pmax(log_max, axis_name),
            jax.lax.psum(count, axis_name))


class NumericsBackend:
    """Interface every numerics engine implements.

    ``stats`` arguments/returns are (alpha, beta) f32 scalar pairs;
    ``fmt`` selects the payload format ("e5m2" — the paper's — or "e4m3").

    Stats semantics are explicit: ``compute_stats(x)`` reduces over the
    tensor the caller holds (LOCAL — inside a ``shard_map`` body that is
    the shard); ``compute_stats(x, axis_name=...)`` all-reduces the raw
    partials across that mesh axis first (GLOBAL — every shard gets the
    stats of the logical tensor).  ``compute_stats_partials`` exposes the
    raw (sum, max, count) triplet for callers that combine shards
    themselves (the StatsBank refresh).
    """

    name = "abstract"

    def compute_stats(self, x: jnp.ndarray, *, fmt: str = "e5m2",
                      axis_name: Optional[str] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    def compute_stats_partials(self, x: jnp.ndarray
                               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    def quantize(self, x: jnp.ndarray, *, stats=None,
                 fmt: str = "e5m2") -> S2FP8Tensor:
        raise NotImplementedError

    def dequantize(self, t: S2FP8Tensor, dtype=jnp.float32) -> jnp.ndarray:
        raise NotImplementedError

    def truncate(self, x: jnp.ndarray, *, stats=None,
                 fmt: str = "e5m2") -> jnp.ndarray:
        raise NotImplementedError

    def qmatmul(self, a: S2FP8Tensor, b: S2FP8Tensor, *, layout: str = "nn",
                epilogue_stats=None, fmt: str = "e5m2") -> jnp.ndarray:
        """Payload-domain GEMM on 2-D payloads.

        ``layout`` selects transposed operand consumption ("nn"/"nt"/"tn",
        kernels/ref.py ``GEMM_CONTRACT``) — the backward GEMMs of
        core/qdot.py read the forward's saved payloads without
        materializing a transpose.  ``epilogue_stats=(alpha, beta)`` fuses
        the output site's Eq. 5 truncation into the GEMM epilogue
        (``fmt`` = the truncation's payload format)."""
        raise NotImplementedError

    def qmatmul_batched(self, a: S2FP8Tensor, b: S2FP8Tensor, *,
                        layout: str = "nn", out_batch: Optional[int] = None,
                        epilogue_stats=None, fmt: str = "e5m2") -> jnp.ndarray:
        """Batched payload-domain GEMM on 3-D payloads.

        ``a.payload`` is ``[Ga, ...]``, ``b.payload`` ``[Gb, ...]`` with a
        per-slice shape per ``layout`` (kernels/ref.py ``gemm_dims`` on the
        trailing two dims).  The combined batch is ``G = max(Ga, Gb)``;
        ``Ga`` and ``Gb`` must divide it, and the slice an operand
        contributes to combined step ``g`` is ``g % Gx`` — the
        trailing-aligned broadcast the MoE broadcast-on-B shapes
        (``becd,edf``) flatten to.  ``out_batch`` (default ``G``) < ``G``
        sums groups of ``G // out_batch`` adjacent-in-``g // out_batch``
        slices into one output slice — the weight-gradient reduction of a
        broadcast operand.  ``epilogue_stats`` fuses the output-site Eq. 5
        truncation exactly as in :meth:`qmatmul`."""
        raise NotImplementedError

    def qdot_general(self, a: S2FP8Tensor, b: S2FP8Tensor, dimension_numbers,
                     *, epilogue_stats=None, fmt: str = "e5m2") -> jnp.ndarray:
        """General-rank payload-domain contraction.

        Maps a ``lax.dot_general``-style contraction — single contracting
        dim at the boundary of each operand's free dims, batch dims (if
        any) leading and in order — onto the 2-D ``qmatmul`` or the
        batched ``qmatmul_batched`` via payload reshapes (1-byte moves).
        Raises ``ValueError`` for contractions outside that family;
        callers gate on :func:`qdot_general_supported`."""
        plan = plan_qdot_general(a.shape, b.shape, dimension_numbers)
        if plan is None:
            raise ValueError(
                f"qdot_general cannot map dimension_numbers "
                f"{dimension_numbers} on {a.shape} x {b.shape} onto a "
                f"payload GEMM; gate with qdot_general_supported()")
        return execute_qdot_plan(self, plan, a, b,
                                 epilogue_stats=epilogue_stats, fmt=fmt)

    def __repr__(self):
        return f"<NumericsBackend {self.name!r}>"


class QdotPlan(NamedTuple):
    """How one contraction maps onto the payload GEMM kernels.

    The first four fields keep the PR-3 tuple layout (layout, operand
    reshape targets, final output shape); ``batch`` / ``b_batch`` carry
    the batched extension.  ``batch == 1`` is a plain 2-D GEMM (the shapes
    are 2-D); ``batch > 1`` makes ``a2_shape`` a full-combined-batch 3-D
    ``(G, ., .)`` and ``b2_shape`` a ``(Gb, ., .)`` with ``Gb | G`` —
    ``Gb < G`` broadcasts B across the leading ``G // Gb`` groups (the
    ``becd,edf`` family)."""

    layout: str
    a2_shape: Tuple[int, ...]
    b2_shape: Tuple[int, ...]
    out_shape: Tuple[int, ...]
    batch: int = 1
    b_batch: int = 1


def execute_qdot_plan(backend_obj: NumericsBackend, plan: QdotPlan,
                      a: S2FP8Tensor, b: S2FP8Tensor, *,
                      epilogue_stats=None, fmt: str = "e5m2") -> jnp.ndarray:
    """Run a planned contraction on quantized operands: payload reshapes
    (1-byte moves), then the 2-D or batched kernel, then the output
    reshape."""
    qa, qb = a.reshape(plan.a2_shape), b.reshape(plan.b2_shape)
    if plan.batch == 1:
        y = backend_obj.qmatmul(qa, qb, layout=plan.layout,
                                epilogue_stats=epilogue_stats, fmt=fmt)
    else:
        y = backend_obj.qmatmul_batched(qa, qb, layout=plan.layout,
                                        epilogue_stats=epilogue_stats,
                                        fmt=fmt)
    return y.reshape(plan.out_shape)


def _prod(dims) -> int:
    p = 1
    for d in dims:
        p *= d
    return p


def _plan_from_parts(layout: str, batch_dims, b_batch_dims, m: int, k: int,
                     n: int, out_shape) -> Optional[QdotPlan]:
    """Assemble a QdotPlan from the decomposed contraction: combined batch
    dims (all of A's leading dims), B's stored batch dims (a trailing
    subset), per-slice (m, k, n), and the logical output shape."""
    g, gb = _prod(batch_dims), _prod(b_batch_dims)
    if 0 in (g, gb, m, k, n):
        return None                      # degenerate sizes: no kernel path
    if layout == "nn":
        a2, b2 = (m, k), (k, n)
    elif layout == "nt":
        a2, b2 = (m, k), (n, k)
    elif layout == "tn":
        a2, b2 = (k, m), (k, n)
    else:
        return None
    if g == 1:
        return QdotPlan(layout, a2, b2, tuple(out_shape))
    return QdotPlan(layout, (g,) + a2, (gb,) + b2, tuple(out_shape), g, gb)


def plan_qdot_general(a_shape, b_shape, dimension_numbers
                      ) -> Optional[QdotPlan]:
    """Map a dot_general onto a payload GEMM, or None when unsupported.

    Supported: a single contracting dim per operand positioned at the
    boundary of the free dims (first or last of the non-batch dims, so
    the rest flatten contiguously), and batch dims — if any — leading and
    in order on BOTH operands (the shape einsum lowering produces for the
    MoE/attention contractions).  The plan's output shape follows the
    dot_general convention ``batch + a_free + b_free``.  (first, last) on
    (a, b) — the "tt" case — has no kernel layout and returns None.
    """
    (ca, cb), (batch_a, batch_b) = dimension_numbers
    if len(ca) != 1 or len(cb) != 1:
        return None
    nb = len(batch_a)
    if tuple(batch_a) != tuple(range(nb)) or \
            tuple(batch_b) != tuple(range(nb)):
        return None
    if a_shape[:nb] != b_shape[:nb]:
        return None
    ca, cb = ca[0], cb[0]
    if ca not in (nb, len(a_shape) - 1) or cb not in (nb, len(b_shape) - 1):
        return None
    a_last = ca == len(a_shape) - 1
    b_first = cb == nb
    if not a_last and not b_first:
        return None                      # "tt": no layout variant
    k = a_shape[ca]
    if k != b_shape[cb]:
        return None
    a_rest = tuple(d for i, d in enumerate(a_shape) if i >= nb and i != ca)
    b_rest = tuple(d for i, d in enumerate(b_shape) if i >= nb and i != cb)
    layout = "nn" if (a_last and b_first) else ("nt" if a_last else "tn")
    return _plan_from_parts(layout, a_shape[:nb], a_shape[:nb],
                            _prod(a_rest), k, _prod(b_rest),
                            a_shape[:nb] + a_rest + b_rest)




def plan_einsum(spec: str, a_shape, b_shape) -> Optional[QdotPlan]:
    """Map a two-operand einsum onto a payload GEMM, or None.

    The supported family generalizes the PR-3 ``"...k,kn->...n"``
    whitelist to every contraction the batched kernels execute:

      * exactly one contracted label, sitting first or last among each
        operand's non-batch labels (no "tt", no multi-label contraction,
        no sum-over-free);
      * B's labels are ``shared-batch + free/contract``; A's are
        ``lead + shared-batch + free/contract`` where ``lead`` are free
        labels only (they broadcast B — the ``becd,edf`` family);
      * the output is exactly ``lead + shared + a_free + b_free`` — the
        order the batched GEMM produces, so the plan is pure reshapes.

    This covers the dense ``bsd,df->bsf`` family (empty batch), the MoE
    expert einsums ``ecd,edf->ecf`` / ``becd,edf->becf``, and the
    attention contractions ``bkgqd,bksd->bkgqs`` / ``bkgqs,bksd->bkgqd``.
    """
    if "->" not in spec:
        return None
    lhs, lo = spec.replace(" ", "").split("->")
    parts = lhs.split(",")
    if len(parts) != 2:
        return None
    la, lb = parts
    if "." in lb:
        return None                      # ellipsis rhs: ambiguous layout
    if "..." in la:
        # concretize "..." with fresh labels, shared between lhs and out
        n_ell = len(a_shape) - (len(la) - 3)
        if n_ell < 0 or "..." not in lo:
            return None
        fresh = "".join(c for c in string.ascii_letters
                        if c not in spec)[:n_ell]
        if len(fresh) != n_ell:
            return None
        la = la.replace("...", fresh)
        lo = lo.replace("...", fresh)
    if "." in la + lo or len(la) != len(a_shape) or len(lb) != len(b_shape):
        return None
    if len(set(la)) != len(la) or len(set(lb)) != len(lb) \
            or len(set(lo)) != len(lo):
        return None
    sa, sb, so = set(la), set(lb), set(lo)
    if not so <= (sa | sb):
        return None
    contract = (sa & sb) - so
    if len(contract) != 1:
        return None
    k_lab = contract.pop()
    if (sa - {k_lab}) - so or (sb - {k_lab}) - so:
        return None                      # sum-over-free: not a pure GEMM
    shared = "".join(c for c in la if c in sb and c != k_lab)
    if not lb.startswith(shared):
        return None
    rb = lb[len(shared):]
    if shared:
        i0 = la.index(shared[0])
        if la[i0:i0 + len(shared)] != shared:
            return None
        lead, ra = la[:i0], la[i0 + len(shared):]
        if any(c in sb for c in lead):
            return None                  # shared labels must be contiguous
    else:
        lead, ra = "", la
    fa = "".join(c for c in ra if c != k_lab)
    fb = "".join(c for c in rb if c != k_lab)
    if ra not in (fa + k_lab, k_lab + fa) or rb not in (k_lab + fb, fb + k_lab):
        return None
    if lo != lead + shared + fa + fb:
        return None
    a_last, b_first = ra.endswith(k_lab), rb.startswith(k_lab)
    if not a_last and not b_first:
        return None                      # "tt"
    dims = dict(zip(la, a_shape))
    for c, d in zip(lb, b_shape):
        if dims.setdefault(c, d) != d:
            return None
    layout = "nn" if (a_last and b_first) else ("nt" if a_last else "tn")
    return _plan_from_parts(
        layout, tuple(dims[c] for c in lead + shared),
        tuple(dims[c] for c in shared),
        _prod(dims[c] for c in fa), dims[k_lab], _prod(dims[c] for c in fb),
        tuple(dims[c] for c in lo))


def qdot_general_supported(a_shape, b_shape, dimension_numbers) -> bool:
    return plan_qdot_general(a_shape, b_shape, dimension_numbers) is not None


def _make_ref_truncate():
    # one jitted program over the existing oracle — no second fmt dispatch
    from repro.kernels import ref
    return jax.jit(ref.s2fp8_truncate_ref, static_argnames=("fmt",))


_ref_truncate = _make_ref_truncate()


class RefBackend(NumericsBackend):
    """Pure-jnp reference engine (core/s2fp8.py + kernels/ref.py).

    ``compute_stats`` and ``truncate`` each run as one jitted program —
    the execution shape every real caller (jitted train/eval steps) sees.
    This pins down ONE set of XLA fusion/FMA decisions per stage, which is
    what makes ref-vs-pallas bitwise parity well-defined: op-by-op eager
    dispatch of the same chain differs from any compiled version by 1-ulp
    FMA rounding.
    """

    name = "ref"

    def compute_stats(self, x, *, fmt: str = "e5m2", axis_name=None):
        if axis_name is not None:
            partials = all_reduce_stats_partials(
                self.compute_stats_partials(x), axis_name)
            return s2fp8.stats_from_reduction(*partials, _TARGET_MAX[fmt])
        return s2fp8.compute_stats_jit(x, target_max=_TARGET_MAX[fmt])

    def compute_stats_partials(self, x):
        return s2fp8.compute_stats_partials_jit(x)

    @jax.named_scope(spans.S2FP8_QUANTIZE)
    def quantize(self, x, *, stats=None, fmt: str = "e5m2"):
        return s2fp8.quantize(x, stats=stats, fmt=fmt)

    def dequantize(self, t, dtype=jnp.float32):
        return s2fp8.dequantize(t, dtype)

    @jax.named_scope(spans.S2FP8_QUANTIZE)
    def truncate(self, x, *, stats=None, fmt: str = "e5m2"):
        if stats is None:
            stats = self.compute_stats(x, fmt=fmt)
        return _ref_truncate(x, stats, fmt=fmt)

    def qmatmul(self, a, b, *, layout: str = "nn", epilogue_stats=None,
                fmt: str = "e5m2"):
        from repro.kernels import ref
        y = ref.s2fp8_matmul_ref(a.payload, a.alpha, a.beta,
                                 b.payload, b.alpha, b.beta, layout=layout)
        if epilogue_stats is not None:
            # the "epilogue" through this engine's pinned truncate program
            # — bitwise-comparable with a separate output truncation
            y = self.truncate(y, stats=epilogue_stats, fmt=fmt)
        return y

    def qmatmul_batched(self, a, b, *, layout: str = "nn", out_batch=None,
                        epilogue_stats=None, fmt: str = "e5m2"):
        from repro.kernels import ref
        y = ref.s2fp8_matmul_batched_ref(a.payload, a.alpha, a.beta,
                                         b.payload, b.alpha, b.beta,
                                         layout=layout, out_batch=out_batch)
        if epilogue_stats is not None:
            y = self.truncate(y, stats=epilogue_stats, fmt=fmt)
        return y


class PallasBackend(NumericsBackend):
    """Fused Pallas-kernel engine via kernels/dispatch.py.

    ``stats_mode``:
      * "exact" (default) — (alpha, beta) from the same monolithic jnp
        reduction the ref uses; truncation output is bitwise-identical to
        the ref backend (including under interpret mode off-TPU).
      * "fused"           — in-kernel blocked stats reduction (the
        two-phase single-kernel path); float-tolerance parity.
    ``interpret=None`` auto-detects the platform per call.
    """

    name = "pallas"

    def __init__(self, *, stats_mode: str = "exact",
                 interpret: Optional[bool] = None, block=None,
                 name: Optional[str] = None):
        if stats_mode not in ("exact", "fused"):
            raise ValueError(f"stats_mode must be 'exact' or 'fused', "
                             f"got {stats_mode!r}")
        from repro.kernels.s2fp8_quant import DEFAULT_BLOCK
        self.stats_mode = stats_mode
        self.interpret = interpret
        self.block = DEFAULT_BLOCK if block is None else block
        if name is not None:
            self.name = name

    def compute_stats(self, x, *, fmt: str = "e5m2", axis_name=None):
        from repro.kernels import dispatch
        if axis_name is not None:
            partials = all_reduce_stats_partials(
                self.compute_stats_partials(x), axis_name)
            return s2fp8.stats_from_reduction(*partials, _TARGET_MAX[fmt])
        if self.stats_mode == "exact":
            # Same compiled program as RefBackend — the bitwise-parity anchor.
            return s2fp8.compute_stats_jit(x, target_max=_TARGET_MAX[fmt])
        return dispatch.stats_nd(x, target_max=_TARGET_MAX[fmt],
                                 block=self.block, interpret=self.interpret)

    def compute_stats_partials(self, x):
        if self.stats_mode == "exact":
            return s2fp8.compute_stats_partials_jit(x)
        from repro.kernels import dispatch
        return dispatch.stats_partials_nd(x, block=self.block,
                                          interpret=self.interpret)

    @jax.named_scope(spans.S2FP8_QUANTIZE)
    def quantize(self, x, *, stats=None, fmt: str = "e5m2"):
        from repro.kernels import dispatch
        # exact mode: stats from the shared compiled reduction, so stored
        # (alpha, beta) match RefBackend.quantize and this backend's own
        # compute_stats bit-for-bit; fused mode keeps the reduction in-kernel
        if stats is None and self.stats_mode == "exact":
            stats = s2fp8.compute_stats_jit(x, target_max=_TARGET_MAX[fmt])
        payload, alpha, beta = dispatch.quant_nd(x, stats=stats, fmt=fmt,
                                                 block=self.block,
                                                 interpret=self.interpret)
        return S2FP8Tensor(payload=payload, alpha=alpha, beta=beta, fmt=fmt)

    def dequantize(self, t, dtype=jnp.float32):
        from repro.kernels import dispatch
        return dispatch.dequant_nd(t.payload, t.alpha, t.beta, dtype=dtype,
                                   block=self.block, interpret=self.interpret)

    @jax.named_scope(spans.S2FP8_QUANTIZE)
    def truncate(self, x, *, stats=None, fmt: str = "e5m2"):
        from repro.kernels import dispatch
        # stats=None + fused_stats=False -> truncate_nd's default branch
        # computes exact stats via the shared compute_stats_jit program
        return dispatch.truncate_nd(x, stats=stats, fmt=fmt,
                                    fused_stats=(self.stats_mode == "fused"),
                                    block=self.block, interpret=self.interpret)

    def qmatmul(self, a, b, *, layout: str = "nn", epilogue_stats=None,
                fmt: str = "e5m2"):
        from repro.kernels import dispatch
        return dispatch.qmatmul_nd(a.payload, a.alpha, a.beta,
                                   b.payload, b.alpha, b.beta,
                                   layout=layout, epilogue_stats=epilogue_stats,
                                   fmt=fmt, interpret=self.interpret)

    def qmatmul_batched(self, a, b, *, layout: str = "nn", out_batch=None,
                        epilogue_stats=None, fmt: str = "e5m2"):
        from repro.kernels import dispatch
        return dispatch.qmatmul_batched_nd(
            a.payload, a.alpha, a.beta, b.payload, b.alpha, b.beta,
            layout=layout, out_batch=out_batch,
            epilogue_stats=epilogue_stats, fmt=fmt, interpret=self.interpret)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, NumericsBackend] = {}


def register_backend(name: str, backend: NumericsBackend,
                     overwrite: bool = False) -> NumericsBackend:
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {name!r} already registered; "
                         f"pass overwrite=True to replace it")
    _REGISTRY[name] = backend
    return backend


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def default_backend_name() -> str:
    """Platform default: the fused kernels where they compile, ref elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def get_backend(name: Optional[str] = None) -> NumericsBackend:
    """Resolve a backend by name; ``None``/"auto" picks the platform default."""
    if name is None or name == "auto":
        name = default_backend_name()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown numerics backend {name!r}; "
                       f"registered: {available_backends()}") from None


register_backend("ref", RefBackend())
register_backend("pallas", PallasBackend())
register_backend("pallas_fused", PallasBackend(stats_mode="fused",
                                               name="pallas_fused"))


# ---------------------------------------------------------------------------
# differentiable truncations (paper Fig. 4 wiring), per backend
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def bidir_truncate(backend: Optional[str] = None, fmt: str = "e5m2"):
    """Backend-routed analogue of ``s2fp8.truncate_bidir``: Eq. 5 on the
    forward value AND on the cotangent.  Cached per (backend, fmt) so the
    returned callable is a stable object under repeated jit tracing; the
    NAME is cached, not the engine — resolution happens per call, so
    ``register_backend(..., overwrite=True)`` takes effect immediately."""

    @jax.custom_vjp
    def _trunc(x):
        return get_backend(backend).truncate(x, fmt=fmt)

    def _fwd(x):
        return get_backend(backend).truncate(x, fmt=fmt), None

    def _bwd(_, g):
        return (get_backend(backend).truncate(g, fmt=fmt),)

    _trunc.defvjp(_fwd, _bwd)
    return _trunc


# ---------------------------------------------------------------------------
# delayed stats
# ---------------------------------------------------------------------------

def truncate_delayed(x: jnp.ndarray, stats, *, refresh=False,
                     backend: Optional[str] = None, fmt: str = "e5m2"):
    """Functional delayed-stats truncation for jitted loops.

    Returns ``(truncated, stats_used)``.  Callers thread ``stats_used``
    into the next step; pass ``refresh=True`` (a Python bool, e.g.
    ``step % k == 0`` resolved outside jit or via two jitted branches)
    every k steps to recompute the reduction.  ``stats=None`` always
    refreshes.
    """
    be = get_backend(backend)
    if refresh or stats is None:
        stats = be.compute_stats(x, fmt=fmt)
    return be.truncate(x, stats=stats, fmt=fmt), stats


class DelayedStatsCache:
    """DEPRECATED shim over :class:`repro.core.statsbank.HostStatsBank`.

    There is one stats-caching story now — the StatsBank subsystem
    (core/statsbank.py): jit-carried banks for train steps, and
    ``HostStatsBank`` for eager callers (serving, checkpoint compression).
    This class keeps the old constructor/``truncate``/``clear`` surface
    (plus the ``_stats`` / ``_last_refresh`` views) and warns on use.
    """

    def __init__(self, backend: Optional[str] = None,
                 refresh_every: int = 16, fmt: str = "e5m2"):
        import warnings
        warnings.warn(
            "DelayedStatsCache is deprecated; use "
            "repro.core.statsbank.HostStatsBank (same semantics, shared "
            "with the jit-carried StatsBank)", DeprecationWarning,
            stacklevel=2)
        from repro.core import statsbank
        self._impl = statsbank.HostStatsBank(backend=backend,
                                             refresh_every=refresh_every,
                                             fmt=fmt)
        self.backend = backend
        self.refresh_every = refresh_every
        self.fmt = fmt

    def truncate(self, x: jnp.ndarray, key: str, step: int) -> jnp.ndarray:
        return self._impl.truncate(x, key, step)

    def clear(self):
        self._impl.clear()

    @property
    def _stats(self) -> Dict[str, Tuple[jnp.ndarray, jnp.ndarray]]:
        return {k: (e["alpha"], e["beta"]) for k, e in self._impl.bank.items()}

    @property
    def _last_refresh(self) -> Dict[str, int]:
        return {k: int(e["last"]) for k, e in self._impl.bank.items()}
