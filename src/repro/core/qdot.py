"""qdot_train: the differentiable payload-domain training GEMM.

The paper's §5 tensor processing engine consumes FP8 payloads plus
(alpha, beta) directly; this module makes that the *training* execution of
``Policy.dot`` (and friends) instead of the composed Fig. 4 chain of three
f32-in/f32-out truncation passes around an f32 GEMM.

Forward::

    qA = quantize(A, bank stats)      # elementwise, 1-byte HBM write
    qB = quantize(B, bank stats)
    Y  = qmatmul(qA, qB, epilogue_stats=out-site stats)
         #  payload tiles stream HBM->VMEM at 1 B/elt, dequant on the VPU,
         #  f32 MXU accumulation, Eq. 5 epilogue on the output tile in VMEM

and the residuals saved for backward are the *payloads* plus scalar stats
— a ~4x activation-residual cut vs the Fig. 4 chain's truncated-f32
operands.

Backward (paper Fig. 4's two transposed GEMMs, payload-domain)::

    qG = quantize(g, cotangent-site stats)        # truncate+store, 1 pass
    dA = qmatmul(qG, qB, layout="nt", epilogue_stats=a-site bwd stats)
    dB = qmatmul(qA, qG, layout="tn", epilogue_stats=b-site bwd stats)

The NT/TN layouts read the saved payloads through swapped BlockSpec index
maps — no transpose is materialized.  For non-"nn" forward layouts (the
attention-logits ``nt`` contraction) the backward pair comes from
``_BWD_GEMMS`` — the same table, re-oriented.

Batched contractions (MoE expert einsums, attention score/value products,
im2col'd convs) ride the same machinery through a
:class:`repro.core.backend.QdotPlan`: the operands reshape (1-byte moves)
onto a ``(G, ., .)`` batched payload GEMM — broadcast-on-B shapes like
``becd,edf`` keep B stored once at ``Gb < G`` and dB accumulates the
``G // Gb`` broadcast groups in-kernel (``out_batch``).  The six-direction
StatsBank node and the payload residuals are shape-agnostic, so a batched
node costs exactly what a dense node costs in stats state.

Numerics anchor: ``dequantize(quantize(x, s)) == truncate(x, s)``
elementwise, so with shared (bank) stats the payload-domain forward equals
the Fig. 4 chain *bitwise* — asserted ref-vs-pallas in
tests/test_qdot_train.py (dense) and tests/test_qdot_batched.py (batched).
Stale bank stats saturate at the format max inside quantize and the
epilogue (never inf).

Stats lifecycle: inside a StatsBank session each ``qdot_train`` call is
one bank node with six per-direction states (statsbank.GEMM_DIRS); all
refreshes run under ``lax.cond`` on the session cadence, so steady-state
steps execute ZERO stats reductions and exactly three payload GEMMs +
three elementwise quantizations per node.  Outside a session the exact
path quantizes with fresh per-call stats (eval / ad-hoc callers);
discovery traces route through that same exact path, so step-0 numerics
match every later step (site registration still happens first).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import backend as nbackend
from repro.core import collectives
from repro.core import s2fp8
from repro.core import statsbank
from repro.core.backend import QdotPlan
from repro.kernels import flash_attention as _fkern
from repro.obs import spans

# Backward GEMM table: forward layout -> ((dA lhs, dA rhs, dA layout),
# (dB lhs, dB rhs, dB layout)) with operands named from {"a", "b", "g"}
# (saved payloads + quantized cotangent).  Derivation: transpose the
# forward contraction; every entry reads the stored payloads through
# index-map swaps only.
_BWD_GEMMS = {
    "nn": (("g", "b", "nt"), ("a", "g", "tn")),
    "nt": (("g", "b", "nn"), ("g", "a", "tn")),
    "tn": (("b", "g", "nt"), ("a", "g", "nn")),
}


def _qmm(be, qx, qy, layout, *, out_batch=None, epilogue_stats=None,
         fmt="e5m2"):
    """Rank dispatch: 2-D payloads -> ``qmatmul``, 3-D -> the batched
    kernel (``out_batch`` reduces broadcast groups, e.g. dB of a
    broadcast weight)."""
    if qx.payload.ndim == 2:
        return be.qmatmul(qx, qy, layout=layout,
                          epilogue_stats=epilogue_stats, fmt=fmt)
    return be.qmatmul_batched(qx, qy, layout=layout, out_batch=out_batch,
                              epilogue_stats=epilogue_stats, fmt=fmt)


def _epilogue_qmatmul(qa, qb, layout, st, pred_f, step_f, cfg, fmt,
                      backend, target_max, out_batch=None):
    """Sited payload GEMM with fused output truncation.

    Steady state (no refresh due): ONE kernel launch — the Eq. 5 epilogue
    runs on each accumulated output tile in VMEM with the site's carried
    (alpha, beta).  Refresh steps take the other ``lax.cond`` branch: raw
    GEMM, stats refresh from the raw output, elementwise truncate
    (refresh-then-use, same cadence semantics as ``Session.truncate``).
    Returns (y, new_state).
    """
    be = nbackend.get_backend(backend)
    need = jnp.logical_or(pred_f > 0, st["last"] < 0)

    @jax.named_scope(spans.STATSBANK_REFRESH)
    def _refresh(_):
        y_raw = _qmm(be, qa, qb, layout, out_batch=out_batch, fmt=fmt)
        new = statsbank.refresh_state(
            y_raw, st, step_f, ema_decay=cfg.ema_decay,
            target_max=target_max, backend=backend, axis_name=cfg.axis_name, fmt=fmt)
        return be.truncate(y_raw, stats=(new["alpha"], new["beta"]),
                           fmt=fmt), new

    def _fused(_):
        y = _qmm(be, qa, qb, layout, out_batch=out_batch,
                 epilogue_stats=(st["alpha"], st["beta"]), fmt=fmt)
        return y, st

    return jax.lax.cond(need, _refresh, _fused, None)


def _gemm_structure(plan: Optional[QdotPlan]):
    """(fwd layout, dA/dB specs) for a plan; plan=None is the dense "nn"
    family.  Each backward spec is (lhs name, rhs name, layout,
    out_batch): out_batch reduces the broadcast groups when the
    differentiated operand is stored broadcast (Gb < G)."""
    layout = "nn" if plan is None else plan.layout
    (da_l, da_r, da_lay), (db_l, db_r, db_lay) = _BWD_GEMMS[layout]
    if plan is None or plan.batch == 1:
        a_ob = b_ob = None
    else:
        a_ob, b_ob = plan.batch, plan.b_batch
    return layout, (da_l, da_r, da_lay, a_ob), (db_l, db_r, db_lay, b_ob)


@functools.lru_cache(maxsize=None)
def _qdot_banked(backend: Optional[str], fmt: str, cfg: statsbank.StatsConfig,
                 plan: Optional[QdotPlan] = None,
                 fsdp: Optional[collectives.FSDPInfo] = None):
    """custom_vjp payload GEMM over (a2, b2, entry, pred_f, step_f); cached
    per (backend, fmt, cfg, plan, fsdp) so the callable is stable under
    jit tracing.  The bank entry is a differentiated argument whose
    cotangent is the refreshed entry (the StatsBank update idiom).

    With ``fsdp`` (the quantized-FSDP payload handoff), ``b`` is the
    owner's dim-0 SHARD of the logical B operand.  Its ``b.fwd`` stats
    refresh psums partials over ``cfg.axis_name`` exactly as in the
    replicated case — the shards partition the leaf over the fsdp axis,
    so the psum'd stats ARE the leaf-global (alpha, beta) and every owner
    quantizes coherently ("quantize-at-owner", once per refresh
    interval).  The 1-byte payload then all-gathers into the full GEMM B
    slot (never an f32/bf16 copy), and the backward reduce-scatters dB to
    the owner shard (psum over lead batch axes + psum_scatter over the
    fsdp axis; bf16 leg when the leaf routes compressed), so the b
    cotangent exits shard-shaped and pre-synced."""
    target_max = s2fp8.FMT_TARGET_MAX[fmt]
    layout, da_spec, db_spec = _gemm_structure(plan)

    def _fwd(a, b, entry, pred_f, step_f):
        be = nbackend.get_backend(backend)
        aa, ab, new_af = statsbank.maybe_refresh(
            a, entry["a.fwd"], pred_f, step_f, cfg, target_max, backend, fmt=fmt)
        ba, bb, new_bf = statsbank.maybe_refresh(
            b, entry["b.fwd"], pred_f, step_f, cfg, target_max, backend, fmt=fmt)
        qa = be.quantize(a, stats=(aa, ab), fmt=fmt)
        qb = be.quantize(b, stats=(ba, bb), fmt=fmt)
        if fsdp is not None:
            qb = collectives.payload_gather_axis(qb, fsdp.axis)
        y, new_of = _epilogue_qmatmul(qa, qb, layout, entry["out.fwd"],
                                      pred_f, step_f, cfg, fmt, backend,
                                      target_max)
        # Residuals: 1-byte payloads + scalar site states.  The f32
        # operands are NOT saved — asserted by shape inspection in
        # tests/test_qdot_train.py.
        res = (qa, qb, new_af, new_bf, new_of, entry["a.bwd"],
               entry["b.bwd"], entry["out.bwd"], pred_f, step_f)
        return y, res

    @jax.custom_vjp
    def qdot(a, b, entry, pred_f, step_f):
        return _fwd(a, b, entry, pred_f, step_f)[0]

    def _bwd(res, g):
        (qa, qb, new_af, new_bf, new_of, a_bwd, b_bwd, out_bwd,
         pred_f, step_f) = res
        be = nbackend.get_backend(backend)
        ga, gb, new_ob = statsbank.maybe_refresh(
            g, out_bwd, pred_f, step_f, cfg, target_max, backend, fmt=fmt)
        qg = be.quantize(g, stats=(ga, gb), fmt=fmt)
        ops = {"a": qa, "b": qb, "g": qg}
        dl, dr, dlay, dob = da_spec
        dA, new_ab = _epilogue_qmatmul(ops[dl], ops[dr], dlay, a_bwd,
                                       pred_f, step_f, cfg, fmt, backend,
                                       target_max, out_batch=dob)
        dl, dr, dlay, dob = db_spec
        dB, new_bb = _epilogue_qmatmul(ops[dl], ops[dr], dlay, b_bwd,
                                       pred_f, step_f, cfg, fmt, backend,
                                       target_max, out_batch=dob)
        if fsdp is not None:
            # full local dB -> owner shard: psum lead batch axes +
            # reduce-scatter over the fsdp axis.  The b cotangent leaves
            # jax.grad pre-synced; the trainer's replicated grad sync
            # skips this leaf.
            dB = collectives.param_scatter_axis(dB, fsdp)
        entry_cot = {"a.fwd": new_af, "a.bwd": new_ab, "b.fwd": new_bf,
                     "b.bwd": new_bb, "out.fwd": new_of, "out.bwd": new_ob}
        return (dA, dB, entry_cot,
                jnp.zeros_like(pred_f), jnp.zeros_like(step_f))

    qdot.defvjp(_fwd, _bwd)
    qdot.fwd_impl = _fwd      # residual-inspection hook (tests)
    return qdot


@functools.lru_cache(maxsize=None)
def _qdot_frozen(backend: Optional[str], fmt: str,
                 plan: Optional[QdotPlan] = None):
    """Frozen-stats serving variant (forward-only, no VJP): operands
    quantize with (alpha, beta) re-derived from the exported bank entry's
    carried moments (:func:`statsbank.frozen_stats` — pure scalar
    arithmetic), and the output truncates through the fused Eq. 5
    epilogue with the out site's frozen stats.  ZERO stats reductions by
    construction — no ``maybe_refresh``, no ``lax.cond`` — which is the
    serving invariant the engine tests assert by jaxpr inspection."""
    layout, _, _ = _gemm_structure(plan)

    def qdot(a, b, entry):
        be = nbackend.get_backend(backend)
        qa = be.quantize(a, stats=statsbank.frozen_stats(entry["a.fwd"], fmt),
                         fmt=fmt)
        qb = be.quantize(b, stats=statsbank.frozen_stats(entry["b.fwd"], fmt),
                         fmt=fmt)
        return _qmm(be, qa, qb, layout,
                    epilogue_stats=statsbank.frozen_stats(entry["out.fwd"],
                                                          fmt),
                    fmt=fmt)

    return qdot


@functools.lru_cache(maxsize=None)
def _qdot_exact(backend: Optional[str], fmt: str,
                plan: Optional[QdotPlan] = None):
    """Sessionless variant: fresh exact stats per call (one reduction per
    tensor, like the exact-stats Fig. 4 chain) but still payload-domain
    compute and payload residuals."""
    target_max = s2fp8.FMT_TARGET_MAX[fmt]
    layout, da_spec, db_spec = _gemm_structure(plan)

    def _fwd(a, b):
        be = nbackend.get_backend(backend)
        qa = be.quantize(a, fmt=fmt)
        qb = be.quantize(b, fmt=fmt)
        y_raw = _qmm(be, qa, qb, layout, fmt=fmt)
        so = be.compute_stats(y_raw, fmt=fmt)
        return be.truncate(y_raw, stats=so, fmt=fmt), (qa, qb)

    @jax.custom_vjp
    def qdot(a, b):
        return _fwd(a, b)[0]

    def _bwd(res, g):
        qa, qb = res
        be = nbackend.get_backend(backend)
        qg = be.quantize(g, fmt=fmt)
        ops = {"a": qa, "b": qb, "g": qg}
        grads = []
        for dl, dr, dlay, dob in (da_spec, db_spec):
            d = _qmm(be, ops[dl], ops[dr], dlay, out_batch=dob, fmt=fmt)
            grads.append(be.truncate(d, stats=be.compute_stats(d, fmt=fmt),
                                     fmt=fmt))
        return tuple(grads)

    qdot.defvjp(_fwd, _bwd)
    qdot.fwd_impl = _fwd
    return qdot


def qdot_train(a: jnp.ndarray, b: jnp.ndarray, *,
               plan: Optional[QdotPlan] = None,
               backend: Optional[str] = None, fmt: str = "e5m2"
               ) -> jnp.ndarray:
    """Differentiable payload-domain contraction.

    Without ``plan``: the dense ``[..., K] x [K, N] -> [..., N]`` family
    (every MLP/projection GEMM).  With a :class:`QdotPlan` (from
    ``backend.plan_einsum`` / ``backend.plan_qdot_general``): any planned
    contraction, including batched and broadcast-on-B shapes — the
    operands reshape onto the plan's payload layout (1-byte moves after
    quantization; the f32 reshapes here are views).

    Inside a StatsBank session this is one GEMM bank node (six
    per-direction states, zero steady-state reductions); outside — and in
    discovery traces — exact per-call stats.  Returns f32 (the caller
    casts, matching ``Policy.dot``).

    ``b`` may be a :class:`repro.core.collectives.FSDPPayloadParam` (the
    quantized-FSDP handoff, dense family only): the local shard quantizes
    with leaf-global bank stats and all-gathers as a 1-byte payload into
    the GEMM B slot — no f32/bf16 copy of the leaf is ever materialized —
    and the b gradient exits reduce-scattered to the owner shard.
    Requires an active (non-discovery) session whose StatsConfig
    ``axis_name`` covers the fsdp axis (the leaf-global stats contract).
    """
    fsdp = None
    if isinstance(b, collectives.FSDPPayloadParam):
        if plan is not None:
            raise ValueError("FSDP payload operands support the dense "
                             "[..., K] x [K, N] family only (planned/"
                             "batched contractions coerce through the "
                             "f32 gather in Policy)")
        fsdp = b.info
        b = b.shard
        k_full = b.shape[0] * fsdp.axis_size
        if b.ndim != 2 or a.ndim < 1 or a.shape[-1] != k_full:
            raise ValueError(f"qdot_train wants [..., K] x [K, N]; got "
                             f"{a.shape} x FSDP shard {b.shape} "
                             f"(full K = {k_full})")
        out_shape = a.shape[:-1] + (b.shape[-1],)
        a2_shape, b2_shape = (-1, a.shape[-1]), b.shape
    elif plan is None:
        if b.ndim != 2 or a.ndim < 1 or a.shape[-1] != b.shape[0]:
            raise ValueError(f"qdot_train wants [..., K] x [K, N]; got "
                             f"{a.shape} x {b.shape}")
        out_shape = a.shape[:-1] + (b.shape[-1],)
        a2_shape, b2_shape = (-1, a.shape[-1]), b.shape
    else:
        out_shape = plan.out_shape
        a2_shape, b2_shape = plan.a2_shape, plan.b2_shape
    # f32 at the custom_vjp boundary: quantization is f32-in anyway, and
    # the casts' own VJPs return bf16 cotangents to bf16 callers
    a2 = a.reshape(a2_shape).astype(jnp.float32)
    b2 = b.reshape(b2_shape).astype(jnp.float32)
    sess = statsbank.current_session()
    if fsdp is not None:
        if sess is None or sess.discovery:
            raise ValueError(
                "FSDP payload operands need an active StatsBank session "
                "(make_train_step(param_sharding='fsdp_q', stats=...)); "
                "discovery traces see full unwrapped params")
        axes = sess.cfg.axis_name
        axes = (axes,) if isinstance(axes, str) else tuple(axes or ())
        if fsdp.axis not in axes:
            raise ValueError(
                f"fsdp_q needs leaf-global stats: StatsConfig.axis_name "
                f"{axes!r} must include the fsdp axis {fsdp.axis!r}")
    if sess is None:
        y2 = _qdot_exact(backend, fmt, plan)(a2, b2)
    elif sess.discovery:
        # register the bank node, then run the SAME exact payload path a
        # sessionless call takes — step-0 (discovery-traced) numerics
        # match every later step instead of a raw untruncated f32 dot
        sess.qdot_site()
        y2 = _qdot_exact(backend, fmt, plan)(a2, b2)
    elif sess.frozen:
        # serving: frozen export-time stats, forward-only, zero reductions
        if fsdp is not None:
            raise ValueError("FSDP payload operands are a training-path "
                             "feature; frozen serving sessions see "
                             "replicated params")
        entry = sess.qdot_site()
        y2 = _qdot_frozen(backend, fmt, plan)(a2, b2, entry)
    else:
        entry = sess.qdot_site()
        y2 = _qdot_banked(backend, fmt, sess.cfg, plan, fsdp)(
            a2, b2, entry, sess.pred_f, sess.step_f)
    return y2.reshape(out_shape)


# ===========================================================================
# qflash_attention: the differentiable payload-domain flash attention node
# ===========================================================================
#
# Same contract as qdot_train, fused across the whole attention op: the
# forward consumes 1-byte Q/K/V payloads, keeps the [S, S] score/prob
# tensor in VMEM tiles only (never HBM), and applies the Eq. 5 epilogue to
# the output tile with the out site's bank stats.  The backward is the
# flash recompute schedule over PAYLOAD residuals: only the 1-byte
# Q/K/V/out payloads plus the rowwise logsumexp are saved, and the score
# tiles are rebuilt from the payloads — so attention residuals are
# ~4x smaller than the Fig. 4 flash chain's four truncated-f32 tensors,
# on top of the O(S^2) -> O(S) flash residual cut itself.


def _payload_flash_fwd(be, qq, qk, qv, causal, window, fmt, bq, bk,
                       out_stats):
    """Raw payload flash forward -> (out f32, lse [B,KV,G,Sq,1]).

    Pallas backend: the fused kernel (epilogue truncation in VMEM when
    ``out_stats`` is given).  Ref backend: dequantize + the pure-jnp
    grouped flash reference, then an elementwise truncate — same numerics
    by the truncate == dequant(quant) anchor.
    """
    b, kvh, g, sq, d = qq.payload.shape
    sk = qk.payload.shape[2]
    scale = 1.0 / math.sqrt(d)
    if isinstance(be, nbackend.PallasBackend):
        out, lse = _fkern.qflash_fwd_pallas(
            qq.payload.reshape(b * kvh * g, sq, d),
            qk.payload.reshape(b * kvh, sk, d),
            qv.payload.reshape(b * kvh, sk, d),
            (qq.alpha, qq.beta), (qk.alpha, qk.beta), (qv.alpha, qv.beta),
            g=g, causal=causal, window=window, scale=scale,
            out_stats=out_stats, fmt=fmt, bq=bq, bk=bk)
        return (out.reshape(b, kvh, g, sq, d),
                lse.reshape(b, kvh, g, sq, 1))
    out, lse = _fkern.flash_fwd_reference(
        be.dequantize(qq), be.dequantize(qk), be.dequantize(qv),
        causal=causal, window=window, q_chunk=bq, kv_chunk=bk)
    if out_stats is not None:
        out = be.truncate(out, stats=out_stats, fmt=fmt)
    return out, lse


def _payload_flash_bwd(be, qq, qk, qv, qg, lse, delta, causal, window,
                       fmt, bq, bk):
    """Raw payload flash backward -> (dq, dk, dv) f32, grouped layout.

    Score tiles are recomputed from the 1-byte payloads.  The pallas path
    runs the two-kernel schedule (dq; per-head dk/dv) and reduces the
    query-group axis here; the ref path is the pure-jnp recompute
    reference on dequantized payloads.
    """
    b, kvh, g, sq, d = qq.payload.shape
    sk = qk.payload.shape[2]
    scale = 1.0 / math.sqrt(d)
    if isinstance(be, nbackend.PallasBackend):
        dq, dkh, dvh = _fkern.qflash_bwd_pallas(
            qq.payload.reshape(b * kvh * g, sq, d),
            qk.payload.reshape(b * kvh, sk, d),
            qv.payload.reshape(b * kvh, sk, d),
            qg.payload.reshape(b * kvh * g, sq, d),
            (qq.alpha, qq.beta), (qk.alpha, qk.beta), (qv.alpha, qv.beta),
            (qg.alpha, qg.beta),
            lse.reshape(b * kvh * g, sq, 1),
            delta.reshape(b * kvh * g, sq, 1),
            g=g, causal=causal, window=window, scale=scale, bq=bq, bk=bk)
        dq = dq.reshape(b, kvh, g, sq, d)
        # the kernel emits per-head dk/dv (each output block written once);
        # the grouped-query reduction over g happens here
        dk = dkh.reshape(b, kvh, g, sk, d).sum(axis=2)
        dv = dvh.reshape(b, kvh, g, sk, d).sum(axis=2)
        return dq, dk, dv
    return _fkern.flash_bwd_reference(
        be.dequantize(qq), be.dequantize(qk), be.dequantize(qv),
        be.dequantize(qg), lse, delta,
        causal=causal, window=window, q_chunk=bq, kv_chunk=bk)


@functools.lru_cache(maxsize=None)
def _qflash_banked(backend: Optional[str], fmt: str,
                   cfg: statsbank.StatsConfig, causal: bool,
                   window: Optional[int], bq: int, bk: int):
    """custom_vjp payload flash attention over (q, k, v, entry, pred_f,
    step_f).  ``entry`` is one statsbank.FLASH_DIRS bank node; its
    cotangent is the refreshed entry (the StatsBank update idiom)."""
    target_max = s2fp8.FMT_TARGET_MAX[fmt]

    def _fwd(q, k, v, entry, pred_f, step_f):
        be = nbackend.get_backend(backend)
        qa, qb_, new_qf = statsbank.maybe_refresh(
            q, entry["q.fwd"], pred_f, step_f, cfg, target_max, backend, fmt=fmt)
        ka, kb, new_kf = statsbank.maybe_refresh(
            k, entry["k.fwd"], pred_f, step_f, cfg, target_max, backend, fmt=fmt)
        va, vb, new_vf = statsbank.maybe_refresh(
            v, entry["v.fwd"], pred_f, step_f, cfg, target_max, backend, fmt=fmt)
        qq = be.quantize(q, stats=(qa, qb_), fmt=fmt)
        qk = be.quantize(k, stats=(ka, kb), fmt=fmt)
        qv = be.quantize(v, stats=(va, vb), fmt=fmt)

        st = entry["out.fwd"]
        need = jnp.logical_or(pred_f > 0, st["last"] < 0)

        @jax.named_scope(spans.STATSBANK_REFRESH)
        def _refresh(_):
            raw, lse = _payload_flash_fwd(be, qq, qk, qv, causal, window,
                                          fmt, bq, bk, None)
            new = statsbank.refresh_state(
                raw, st, step_f, ema_decay=cfg.ema_decay,
                target_max=target_max, backend=backend,
                axis_name=cfg.axis_name, fmt=fmt)
            out = be.truncate(raw, stats=(new["alpha"], new["beta"]),
                              fmt=fmt)
            return out, lse, new["alpha"], new["beta"], new

        def _fused(_):
            out, lse = _payload_flash_fwd(be, qq, qk, qv, causal, window,
                                          fmt, bq, bk,
                                          (st["alpha"], st["beta"]))
            return out, lse, st["alpha"], st["beta"], st

        out, lse, oa, ob, new_of = jax.lax.cond(need, _refresh, _fused, None)
        # `out` is already in the out site's representable set, so this
        # quantization is its exact 1-byte payload — the residual the
        # backward dequantizes for the delta identity.
        qo = be.quantize(out, stats=(oa, ob), fmt=fmt)
        res = (qq, qk, qv, qo, lse, new_qf, new_kf, new_vf, new_of,
               entry["q.bwd"], entry["k.bwd"], entry["v.bwd"],
               entry["out.bwd"], pred_f, step_f)
        return out, res

    @jax.custom_vjp
    def qflash(q, k, v, entry, pred_f, step_f):
        return _fwd(q, k, v, entry, pred_f, step_f)[0]

    def _bwd(res, g):
        (qq, qk, qv, qo, lse, new_qf, new_kf, new_vf, new_of,
         q_bwd, k_bwd, v_bwd, out_bwd, pred_f, step_f) = res
        be = nbackend.get_backend(backend)
        g = g.astype(jnp.float32)
        ga, gb, new_ob = statsbank.maybe_refresh(
            g, out_bwd, pred_f, step_f, cfg, target_max, backend, fmt=fmt)
        qg = be.quantize(g, stats=(ga, gb), fmt=fmt)
        # flash-2 rowwise identity D = sum(dout * out) on the dequantized
        # payloads — the backward's single algorithmic reduction.
        delta = jnp.sum(be.dequantize(qg) * be.dequantize(qo),
                        axis=-1, keepdims=True)
        dq_raw, dk_raw, dv_raw = _payload_flash_bwd(
            be, qq, qk, qv, qg, lse, delta, causal, window, fmt, bq, bk)
        a, b, new_qb = statsbank.maybe_refresh(
            dq_raw, q_bwd, pred_f, step_f, cfg, target_max, backend, fmt=fmt)
        dq = be.truncate(dq_raw, stats=(a, b), fmt=fmt)
        a, b, new_kb = statsbank.maybe_refresh(
            dk_raw, k_bwd, pred_f, step_f, cfg, target_max, backend, fmt=fmt)
        dk = be.truncate(dk_raw, stats=(a, b), fmt=fmt)
        a, b, new_vb = statsbank.maybe_refresh(
            dv_raw, v_bwd, pred_f, step_f, cfg, target_max, backend, fmt=fmt)
        dv = be.truncate(dv_raw, stats=(a, b), fmt=fmt)
        entry_cot = {"q.fwd": new_qf, "q.bwd": new_qb,
                     "k.fwd": new_kf, "k.bwd": new_kb,
                     "v.fwd": new_vf, "v.bwd": new_vb,
                     "out.fwd": new_of, "out.bwd": new_ob}
        return (dq, dk, dv, entry_cot,
                jnp.zeros_like(pred_f), jnp.zeros_like(step_f))

    qflash.defvjp(_fwd, _bwd)
    qflash.fwd_impl = _fwd      # residual-inspection hook (tests)
    return qflash


@functools.lru_cache(maxsize=None)
def _qflash_frozen(backend: Optional[str], fmt: str, causal: bool,
                   window: Optional[int], bq: int, bk: int):
    """Frozen-stats serving flash attention (forward-only, mirrors
    ``_qdot_frozen``): Q/K/V quantize with the exported bank node's frozen
    stats, the fused kernel truncates the output tile with the frozen out
    stats — zero stats reductions (the softmax's own rowwise max/sum are
    algorithmic, present in the fp32 baseline too)."""

    def qflash(q, k, v, entry):
        be = nbackend.get_backend(backend)
        qq = be.quantize(q, stats=statsbank.frozen_stats(entry["q.fwd"], fmt),
                         fmt=fmt)
        qk = be.quantize(k, stats=statsbank.frozen_stats(entry["k.fwd"], fmt),
                         fmt=fmt)
        qv = be.quantize(v, stats=statsbank.frozen_stats(entry["v.fwd"], fmt),
                         fmt=fmt)
        out, _ = _payload_flash_fwd(
            be, qq, qk, qv, causal, window, fmt, bq, bk,
            statsbank.frozen_stats(entry["out.fwd"], fmt))
        return out

    return qflash


@functools.lru_cache(maxsize=None)
def _qflash_exact(backend: Optional[str], fmt: str, causal: bool,
                  window: Optional[int], bq: int, bk: int):
    """Sessionless variant: fresh exact stats per call, payload-domain
    compute and payload residuals (mirrors ``_qdot_exact``)."""
    target_max = s2fp8.FMT_TARGET_MAX[fmt]

    def _fwd(q, k, v):
        be = nbackend.get_backend(backend)
        qq = be.quantize(q, fmt=fmt)
        qk = be.quantize(k, fmt=fmt)
        qv = be.quantize(v, fmt=fmt)
        raw, lse = _payload_flash_fwd(be, qq, qk, qv, causal, window, fmt,
                                      bq, bk, None)
        so = be.compute_stats(raw, fmt=fmt)
        out = be.truncate(raw, stats=so, fmt=fmt)
        qo = be.quantize(out, stats=so, fmt=fmt)
        return out, (qq, qk, qv, qo, lse)

    @jax.custom_vjp
    def qflash(q, k, v):
        return _fwd(q, k, v)[0]

    def _bwd(res, g):
        qq, qk, qv, qo, lse = res
        be = nbackend.get_backend(backend)
        g = g.astype(jnp.float32)
        qg = be.quantize(g, fmt=fmt)
        delta = jnp.sum(be.dequantize(qg) * be.dequantize(qo),
                        axis=-1, keepdims=True)
        raws = _payload_flash_bwd(be, qq, qk, qv, qg, lse, delta, causal,
                                  window, fmt, bq, bk)
        return tuple(be.truncate(d, stats=be.compute_stats(d, fmt=fmt),
                                 fmt=fmt) for d in raws)

    qflash.defvjp(_fwd, _bwd)
    qflash.fwd_impl = _fwd
    return qflash


def qflash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                     causal: bool = True, window: Optional[int] = None,
                     backend: Optional[str] = None, fmt: str = "e5m2",
                     q_chunk: int = 512, kv_chunk: int = 512
                     ) -> jnp.ndarray:
    """Differentiable payload-domain flash attention.

    Layout matches models/flash.py: q ``[B, KV, G, Sq, d]``,
    k/v ``[B, KV, Sk, d]`` (grouped-query).  Inside a StatsBank session
    this is ONE bank node (eight per-direction states,
    statsbank.FLASH_DIRS) with zero steady-state stats reductions;
    outside — and in discovery traces — exact per-call stats.  Returns
    f32 (the caller casts, matching ``Policy`` conventions).
    """
    if q.ndim != 5 or k.ndim != 4 or v.ndim != 4:
        raise ValueError(f"qflash_attention wants q [B,KV,G,Sq,d], "
                         f"k/v [B,KV,Sk,d]; got {q.shape}, {k.shape}, "
                         f"{v.shape}")
    if (k.shape != v.shape or q.shape[:2] != k.shape[:2]
            or q.shape[-1] != k.shape[-1]):
        raise ValueError(f"inconsistent attention shapes: {q.shape}, "
                         f"{k.shape}, {v.shape}")
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    window = None if window is None else int(window)
    sess = statsbank.current_session()
    if sess is None:
        return _qflash_exact(backend, fmt, causal, window,
                             q_chunk, kv_chunk)(q, k, v)
    if sess.discovery:
        # register the bank node, then run the exact payload path so
        # step-0 (discovery-traced) numerics match later steps
        sess.qflash_site()
        return _qflash_exact(backend, fmt, causal, window,
                             q_chunk, kv_chunk)(q, k, v)
    if sess.frozen:
        # serving: frozen export-time stats, forward-only, zero reductions
        entry = sess.qflash_site()
        return _qflash_frozen(backend, fmt, causal, window,
                              q_chunk, kv_chunk)(q, k, v, entry)
    entry = sess.qflash_site()
    return _qflash_banked(backend, fmt, sess.cfg, causal, window,
                          q_chunk, kv_chunk)(q, k, v, entry,
                                             sess.pred_f, sess.step_f)
