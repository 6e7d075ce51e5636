"""StatsBank: first-class, jit-carried, sharded, checkpointable per-tensor
S2FP8 statistics.

The paper's mechanism is a pair of learnable statistics (shift beta,
squeeze alpha) per tensor, evolving across training steps (Fig. 5).  The
seed recomputed them from scratch inside every truncation; PR 1 amortized
that for eager callers with a host-side dict.  This module makes the
statistics *state*: a flat, keyed pytree — the **bank** — that is a
functional carry of the train step, refreshed *inside* jit every
``refresh_every`` steps, sharded like any other state under pjit, and
saved/restored by the checkpoint manager so a resumed run starts with
warm stats.

Bank layout (plain nested dicts — nothing to register, trivially
checkpointable)::

    bank = {
      "seg0:dense/attn/t0": {            # one entry per truncation site
         "fwd": {alpha, beta, ema_mu, ema_m, last},   # forward value stats
         "bwd": {alpha, beta, ema_mu, ema_m, last},   # cotangent stats
      },
      "seg0:dense/mlp/qt0": {            # one entry per payload-GEMM node
         "a.fwd": {...}, "a.bwd": {...},              # (core/qdot.py):
         "b.fwd": {...}, "b.bwd": {...},              # operand, output and
         "out.fwd": {...}, "out.bwd": {...},          # cotangent stats
      },
      ...
    }

``ema_mu`` / ``ema_m`` are EMAs of the *raw* log2-domain moments of paper
Eq. 3–4 (mean and max of log2|X| over nonzeros); (alpha, beta) are derived
from the EMAs at each refresh and stored so the bank literally carries the
paper's statistics.  ``last`` is the last-refresh step (f32; -1 = never —
forces a bootstrap refresh on first use so step 0 never truncates with
identity stats).  Sites inside a scanned layer segment hold [L]-shaped
leaves, one row per layer.

How state flows through jit (the part that makes this work under
``lax.scan`` over layers, ``jax.checkpoint`` remat, and pjit):

  * READS — a :func:`bind` context activates a :class:`Session` for the
    duration of the loss trace.  ``Policy``'s truncation wrappers route
    through ``session.truncate``, which resolves a stable site key from
    the active scope stack and pulls that entry out of the bank.  For
    scanned segments the model threads the per-layer entries through the
    scan's ``xs`` (``segment_sites`` + ``segment_ctx``), so each layer
    reads its own row.
  * WRITES — the bank is an extra *differentiated* argument of the loss,
    and each site's ``custom_vjp`` defines the cotangent of its entry to
    BE the refreshed entry (the delayed-scaling idiom from FP8 training
    systems).  ``jax.grad`` w.r.t. the bank therefore returns the new
    bank: scan transposition stacks per-layer rows back up, remat replays
    are deterministic, and no out-of-band state escapes the trace.
  * REFRESH — the Eq. 3–4 reduction runs under ``lax.cond`` on
    ``step % refresh_every == 0`` (or bootstrap), so non-refresh steps
    execute ZERO stats reductions — truncation is one elementwise pass.
    Under ``shard_map`` a session bound with ``axis_name`` all-reduces the
    raw (sum, max, count) partials so every shard refreshes with exact
    GLOBAL stats (``backend.compute_stats_partials`` +
    ``backend.all_reduce_stats_partials``).

``HostStatsBank`` is the eager, host-side view over the same per-site
state for serving/compression callers (it absorbs the deprecated
``DelayedStatsCache``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import backend as nbackend
from repro.core import s2fp8
from repro.obs import metrics as obs_metrics
from repro.obs import spans

STATE_FIELDS = ("alpha", "beta", "ema_mu", "ema_m", "last")

# Directions of a payload-domain GEMM node (core/qdot.py ``qdot_train``):
# operand sites ("a", "b"), the output site ("out"), each with forward-value
# and cotangent stats — the same six Fig. 4 sites the composed
# ``Policy.dot`` chain visits, keyed flat so bank plumbing (stacking,
# checkpointing, bookkeeping) is structure-agnostic.  The states are
# per-TENSOR scalars (paper Eq. 3–4), so one node covers any contraction
# shape the planner maps onto the kernels — dense, batched (MoE expert
# einsums, attention score/value products) and im2col'd convs cost the
# same six scalars.
GEMM_DIRS = ("a.fwd", "a.bwd", "b.fwd", "b.bwd", "out.fwd", "out.bwd")

# Directions of a payload-domain flash-attention node (core/qdot.py
# ``qflash_attention``): the q/k/v operands and the attention output, each
# with forward-value and cotangent stats.  Like GEMM_DIRS these are
# per-tensor scalars, so a fused attention node costs eight scalars
# regardless of sequence length; every direction has a "bwd" twin, which
# is what :func:`merge_updates` keys on.
FLASH_DIRS = ("q.fwd", "q.bwd", "k.fwd", "k.bwd", "v.fwd", "v.bwd",
              "out.fwd", "out.bwd")


@dataclasses.dataclass(frozen=True)
class StatsConfig:
    """Static StatsBank policy (carried by the session, not the bank).

    * ``refresh_every`` — recompute the Eq. 3–4 reduction every k steps;
      between refreshes truncation is a single elementwise pass.
    * ``ema_decay`` — EMA coefficient on the raw (mu, m) moments; 0.0
      means each refresh replaces them (pure delayed stats).
    * ``axis_name`` — when set, refreshes all-reduce the (sum, max, count)
      partials over that mapped axis (a name or tuple of names — psum
      accepts either): global stats inside shard_map.  Use
      :func:`for_mesh` to derive it from a mesh's batch axes.
    * ``telemetry`` — when True, site states carry the per-site FP8
      health-metric leaves (:data:`repro.obs.metrics.TELE_FIELDS`),
      updated inside the refresh ``lax.cond`` (steady steps stay
      reduction-free); drained by :mod:`repro.obs.telemetry`.
    """

    refresh_every: int = 16
    ema_decay: float = 0.0
    axis_name: Optional[Union[str, Tuple[str, ...]]] = None
    telemetry: bool = False

    def __post_init__(self):
        if self.refresh_every < 1:
            raise ValueError("refresh_every must be >= 1")
        if not (0.0 <= self.ema_decay < 1.0):
            raise ValueError("ema_decay must be in [0, 1)")
        if isinstance(self.axis_name, list):
            # keep the config hashable (it keys lru_caches in core/qdot.py)
            object.__setattr__(self, "axis_name", tuple(self.axis_name))


def for_mesh(cfg: StatsConfig, mesh) -> StatsConfig:
    """Mesh-aware refresh entry point: bind ``cfg``'s global-stats
    reduction to ``mesh``'s batch axes, so every refresh inside the
    mesh-native train step all-reduces the (sum, max, count) partials
    across the data shards — bank stats are stats of the GLOBAL batch,
    not the local shard.  ``mesh=None`` (or a mesh with no batch axes)
    clears ``axis_name``: single-device semantics."""
    if mesh is None:
        return dataclasses.replace(cfg, axis_name=None)
    from repro.parallel import sharding as shd
    axes = shd.mesh_batch_axes(mesh)
    if not axes:
        return dataclasses.replace(cfg, axis_name=None)
    return dataclasses.replace(
        cfg, axis_name=axes[0] if len(axes) == 1 else axes)


def init_site_state(length: Optional[int] = None,
                    telemetry: bool = False) -> Dict[str, jnp.ndarray]:
    """Fresh per-direction site state: identity stats, empty EMA,
    ``last = -1`` (bootstrap-refresh on first use).  ``telemetry=True``
    adds zeroed health-metric leaves (a cold site reports clean)."""
    shape = () if length is None else (length,)

    def full(v):
        return jnp.full(shape, v, jnp.float32)

    state = {"alpha": full(1.0), "beta": full(0.0), "ema_mu": full(0.0),
             "ema_m": full(0.0), "last": full(-1.0)}
    if telemetry:
        state.update(obs_metrics.init_tele_state(shape))
    return state


def refresh_state(x: jnp.ndarray, state: Dict[str, jnp.ndarray],
                  step_f: jnp.ndarray, *, ema_decay: float = 0.0,
                  target_max: float = s2fp8.TARGET_MAX_LOG2,
                  backend: Optional[str] = None,
                  axis_name: Optional[str] = None,
                  fmt: Optional[str] = None) -> Dict[str, jnp.ndarray]:
    """One unconditional refresh: raw moments of ``x`` folded into the
    EMAs, (alpha, beta) re-derived.  The single source of refresh numerics
    — the in-jit ``lax.cond`` branch, the shard_map global path and the
    eager :class:`HostStatsBank` all call this.

    A telemetry-enabled ``state`` (extra :data:`TELE_FIELDS
    <repro.obs.metrics.TELE_FIELDS>` leaves) additionally gets its health
    metrics recomputed here — measured against the PRE-refresh carried
    stats, i.e. how unhealthy the delayed stats had become by the time
    this refresh fired.  ``fmt`` names the payload format for the
    saturation threshold; when None it is reverse-derived from
    ``target_max``."""
    be = nbackend.get_backend(backend)
    log_sum, log_max, count = be.compute_stats_partials(x)
    if axis_name is not None:
        log_sum, log_max, count = nbackend.all_reduce_stats_partials(
            (log_sum, log_max, count), axis_name)
    has = count > 0
    mu_t = log_sum / jnp.maximum(count, 1.0)
    m_t = jnp.where(has, log_max, 0.0)
    # `last >= 0` doubles as "the EMA moments are valid": a refresh that
    # saw only zeros leaves BOTH untouched (last stays -1), so the site
    # keeps bootstrapping until real data arrives — the placeholder-zero
    # moments are never folded into a later EMA.
    first = state["last"] < 0
    d = jnp.where(first, 0.0, jnp.float32(ema_decay))
    ema_mu = jnp.where(has, d * state["ema_mu"] + (1.0 - d) * mu_t,
                       state["ema_mu"])
    ema_m = jnp.where(has, d * state["ema_m"] + (1.0 - d) * m_t,
                      state["ema_m"])
    # No moments yet at all (all-zero tensor on the bootstrap refresh):
    # stay on identity stats via the epilogue's empty-tensor convention.
    valid = jnp.logical_or(has, jnp.logical_not(first))
    alpha, beta = s2fp8.stats_from_reduction(
        ema_mu, ema_m, jnp.where(valid, 1.0, 0.0), target_max)
    new_last = jnp.where(has, jnp.float32(step_f), state["last"])
    new = {"alpha": alpha, "beta": beta, "ema_mu": ema_mu, "ema_m": ema_m,
           "last": new_last}
    if obs_metrics.has_telemetry(state):
        new.update(obs_metrics.health_update(
            x, state, new, mu_t, m_t, has, first, count,
            fmt=obs_metrics.resolve_fmt(fmt, target_max),
            backend=backend, axis_name=axis_name))
    return new


def maybe_refresh(x, state, pred_f, step_f, cfg: StatsConfig,
                  target_max: float, backend: Optional[str],
                  fmt: Optional[str] = None):
    """(alpha_used, beta_used, new_state) with the reduction under
    ``lax.cond`` — non-refresh steps run zero reductions.  Refresh steps
    truncate with the freshly derived stats (refresh-then-use), matching
    the host-side cadence semantics."""
    need = jnp.logical_or(pred_f > 0, state["last"] < 0)

    @jax.named_scope(spans.STATSBANK_REFRESH)
    def do(operand):
        x_, st = operand
        new = refresh_state(x_, st, step_f, ema_decay=cfg.ema_decay,
                            target_max=target_max, backend=backend,
                            axis_name=cfg.axis_name, fmt=fmt)
        return new["alpha"], new["beta"], new

    def keep(operand):
        _, st = operand
        return st["alpha"], st["beta"], st

    return jax.lax.cond(need, do, keep, (x, state))


# ---------------------------------------------------------------------------
# session (the trace-time object behind `bind`)
# ---------------------------------------------------------------------------

_ACTIVE = threading.local()


def current_session() -> Optional["Session"]:
    return getattr(_ACTIVE, "session", None)


def frozen_stats(state: Dict[str, jnp.ndarray], fmt: str
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(alpha, beta) re-derived from a site state's carried raw
    (ema_mu, ema_m) moments for ``fmt``'s target range, gradient-stopped.

    The single derivation shared by :meth:`Session.operand_stats`, the
    frozen serving session, and the serving KV-cache stats extraction —
    sharing it is what makes the serving engines' scalars bit-identical
    to the bank's.  The moments are format-agnostic, so a bank warmed
    under one format serves the other correctly (for the warming format
    the derivation reproduces the stored (alpha, beta) exactly).
    Never-refreshed sites (``last < 0``) fall through to identity stats
    via the empty-tensor convention of ``stats_from_reduction``."""
    alpha, beta = s2fp8.stats_from_reduction(
        state["ema_mu"], state["ema_m"],
        (state["last"] >= 0).astype(jnp.float32),
        s2fp8.FMT_TARGET_MAX[fmt])
    return jax.lax.stop_gradient(alpha), jax.lax.stop_gradient(beta)


class Session:
    """Trace-scoped view of a bank: resolves site keys, serves entries,
    and (in discovery mode) records the sites a model visits."""

    # Frozen (export-time) sessions override this; core/qdot.py branches
    # on it to pick the forward-only frozen-stats execution.
    frozen = False

    def __init__(self, bank: Optional[Dict[str, Any]], step,
                 cfg: StatsConfig, discovery: bool = False):
        self.bank = bank
        self.cfg = cfg
        self.discovery = discovery
        if not discovery:
            step = jnp.asarray(step, jnp.int32)
            self.step_f = step.astype(jnp.float32)
            self.pred_f = (step % cfg.refresh_every == 0).astype(jnp.float32)
        self._scopes: list = []
        self._counters: Dict[str, int] = {}
        self._segment: Optional[Tuple[str, Optional[Dict[str, Any]]]] = None
        # discovery outputs
        self.recorded: Dict[str, Dict[str, Any]] = {}
        self.segment_lengths: Dict[str, int] = {}

    # -- naming ---------------------------------------------------------
    def _site_key(self, kind: str) -> str:
        prefix = "/".join(self._scopes)
        ckey = f"{prefix}|{kind}"
        n = self._counters.get(ckey, 0)
        self._counters[ckey] = n + 1
        return f"{prefix}/{kind}{n}" if prefix else f"{kind}{n}"

    @contextlib.contextmanager
    def scope(self, name: str):
        self._scopes.append(name)
        try:
            yield
        finally:
            self._scopes.pop()

    # -- scanned segments ------------------------------------------------
    def segment_sites(self, name: str, length: int):
        """Stacked [L, ...] entries for every site under segment ``name``
        — the pytree the model threads through its layer scan's ``xs``.
        Returns None when the bank has no sites there (or in discovery)."""
        if self.discovery:
            self.segment_lengths[name] = length
            return None
        sites = {k: v for k, v in self.bank.items()
                 if k.startswith(name + "/")}
        if not sites:
            return None
        leaf = jax.tree_util.tree_leaves(sites)[0]
        if leaf.shape[:1] != (length,):
            raise ValueError(
                f"StatsBank segment {name!r} holds per-layer stats of "
                f"length {leaf.shape[:1]}, but the model scans {length} "
                f"layers — re-run statsbank.init_bank for this model")
        return sites

    @contextlib.contextmanager
    def segment_ctx(self, name: str, sliced_sites):
        """Inside a scan body: serve this layer's entry slices (pytree of
        scalars, one row of ``segment_sites``) to sites under ``name``."""
        if self._segment is not None:
            raise RuntimeError("StatsBank segments do not nest")
        self._segment = (name, sliced_sites)
        self._scopes.append(name)
        try:
            yield
        finally:
            self._scopes.pop()
            self._segment = None

    # -- entry resolution -------------------------------------------------
    def _lookup(self, key: str):
        if self._segment is not None:
            name, sites = self._segment
            entry = None if sites is None else sites.get(key)
        else:
            entry = self.bank.get(key)
        if entry is None:
            raise KeyError(
                f"truncation site {key!r} has no StatsBank entry — the "
                f"model structure changed since the bank was initialized; "
                f"re-run statsbank.init_bank")
        return entry

    # -- the two site kinds -----------------------------------------------
    def truncate(self, x: jnp.ndarray, *, fmt: str = "e5m2",
                 backend: Optional[str] = None) -> jnp.ndarray:
        """Bank-routed bidirectional truncation (paper Fig. 4): Eq. 5 on
        the forward value with the site's "fwd" stats and on the cotangent
        with its "bwd" stats; refreshed entries ride out as the bank
        argument's cotangent."""
        key = self._site_key("t")
        if self.discovery:
            self.recorded[key] = {"segment": self._segment[0] if self._segment
                                  else None, "dirs": ("fwd", "bwd")}
            return x
        entry = self._lookup(key)
        target_max = s2fp8.FMT_TARGET_MAX[fmt]
        cfg = self.cfg

        def routed(v, alpha, beta):
            return nbackend.get_backend(backend).truncate(
                v, stats=(alpha, beta), fmt=fmt)

        @jax.custom_vjp
        def t(x, fs, bs, pred_f, step_f):
            a, b, _ = maybe_refresh(x, fs, pred_f, step_f, cfg,
                                     target_max, backend, fmt=fmt)
            return routed(x, a, b)

        def t_fwd(x, fs, bs, pred_f, step_f):
            a, b, new_fs = maybe_refresh(x, fs, pred_f, step_f, cfg,
                                          target_max, backend, fmt=fmt)
            return routed(x, a, b), (new_fs, bs, pred_f, step_f)

        def t_bwd(res, g):
            new_fs, bs, pred_f, step_f = res
            a, b, new_bs = maybe_refresh(g, bs, pred_f, step_f, cfg,
                                          target_max, backend, fmt=fmt)
            # cotangents of (fs, bs) are the REFRESHED entries — this is
            # how the new bank leaves the trace (grad w.r.t. the bank).
            return (routed(g, a, b), new_fs, new_bs,
                    jnp.zeros_like(pred_f), jnp.zeros_like(step_f))

        t.defvjp(t_fwd, t_bwd)
        return t(x, entry["fwd"], entry["bwd"], self.pred_f, self.step_f)

    def qdot_site(self) -> Optional[Dict[str, Any]]:
        """Bank entry for a payload-domain GEMM node (core/qdot.py
        ``qdot_train``): six per-direction states keyed by
        :data:`GEMM_DIRS` — operand, output, and cotangent stats of one
        GEMM.  All six are differentiated through the node's custom_vjp,
        whose entry-cotangents are the refreshed states (the same
        bank-update idiom as :meth:`truncate`).  Returns None in
        discovery mode (after recording the site)."""
        key = self._site_key("qt")
        if self.discovery:
            self.recorded[key] = {"segment": self._segment[0] if self._segment
                                  else None, "dirs": GEMM_DIRS}
            return None
        return self._lookup(key)

    def qflash_site(self) -> Optional[Dict[str, Any]]:
        """Bank entry for a payload-domain flash-attention node
        (core/qdot.py ``qflash_attention``): eight per-direction states
        keyed by :data:`FLASH_DIRS`.  Same custom_vjp bank-update idiom as
        :meth:`qdot_site` — entry cotangents are the refreshed states.
        Returns None in discovery mode (after recording the site)."""
        key = self._site_key("qf")
        if self.discovery:
            self.recorded[key] = {"segment": self._segment[0] if self._segment
                                  else None, "dirs": FLASH_DIRS}
            return None
        return self._lookup(key)

    def operand_stats(self, x: jnp.ndarray, *, fmt: str = "e5m2"
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Read-only (alpha, beta) for a payload-domain GEMM operand
        (``Policy.qdot``).  Forward-only consumers (serving) keep the bank
        warm through :class:`HostStatsBank`; no update flows from here.

        The read is gradient-stopped: under a differentiated (banked
        train) step these entries would otherwise receive the mathematical
        dLoss/dalpha cotangent instead of a refreshed entry.  With the
        stop, their cotangent is zero and :func:`merge_updates` carries
        the old entry forward.

        (alpha, beta) are re-derived from the site's carried raw
        (ema_mu, ema_m) moments with THIS caller's ``fmt`` target — the
        moments are format-agnostic, so a bank warmed under one format
        serves the other correctly (for the warming format the derivation
        reproduces the stored scalars exactly).  Never-refreshed sites
        fall through to identity stats."""
        key = self._site_key("q")
        if self.discovery:
            self.recorded[key] = {"segment": self._segment[0] if self._segment
                                  else None, "dirs": ("fwd",)}
            return jnp.float32(1.0), jnp.float32(0.0)
        return frozen_stats(self._lookup(key)["fwd"], fmt)


class FrozenSession(Session):
    """Read-only serving session over an exported bank: every site serves
    (alpha, beta) re-derived from its carried raw moments
    (:func:`frozen_stats`) and NOTHING refreshes — no ``lax.cond``, no
    stats reduction, no custom_vjp.  This is the inference contract of the
    paper's delayed-stats idiom: a trained bank's statistics are frozen at
    export and prefill/decode run pure elementwise quantization around the
    payload kernels (the zero-reduction property the serving tests assert
    by jaxpr inspection).

    ``core/qdot.py`` dispatches on ``session.frozen`` to forward-only
    frozen-stats GEMM/flash execution; :meth:`truncate` here is the
    forward-only analogue of the banked truncation site."""

    frozen = True

    def __init__(self, bank: Dict[str, Any], cfg: StatsConfig = StatsConfig()):
        super().__init__(bank, 0, cfg)
        # never consumed on the frozen paths; zeroed so any accidental
        # maybe_refresh ride-along would still deselect the reduction
        self.pred_f = jnp.float32(0.0)
        self.step_f = jnp.float32(0.0)

    def truncate(self, x: jnp.ndarray, *, fmt: str = "e5m2",
                 backend: Optional[str] = None) -> jnp.ndarray:
        entry = self._lookup(self._site_key("t"))
        alpha, beta = frozen_stats(entry["fwd"], fmt)
        return nbackend.get_backend(backend).truncate(
            x, stats=(alpha, beta), fmt=fmt)


# ---------------------------------------------------------------------------
# module-level conveniences (no-ops without an active session)
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def bind(bank: Dict[str, Any], step, cfg: StatsConfig = StatsConfig()):
    """Activate a session over ``bank`` for the current trace.  Use inside
    the function being differentiated; pass ``bank`` as a differentiated
    argument and read the refreshed bank out of its gradient."""
    if current_session() is not None:
        raise RuntimeError("a StatsBank session is already active")
    sess = Session(bank, step, cfg)
    _ACTIVE.session = sess
    try:
        yield sess
    finally:
        _ACTIVE.session = None


@contextlib.contextmanager
def freeze(bank: Dict[str, Any], cfg: StatsConfig = StatsConfig()):
    """Activate a :class:`FrozenSession` over an exported bank for the
    current trace — the serving engines' entry point.  Unlike :func:`bind`
    the bank is NOT a differentiated argument: nothing flows back.  Use
    inside the jitted prefill/decode function so the bank entries fold
    into the compiled program as constants."""
    if current_session() is not None:
        raise RuntimeError("a StatsBank session is already active")
    sess = FrozenSession(bank, cfg)
    _ACTIVE.session = sess
    try:
        yield sess
    finally:
        _ACTIVE.session = None


@contextlib.contextmanager
def scope(name: str):
    sess = current_session()
    if sess is None:
        yield
        return
    with sess.scope(name):
        yield


def segment_sites(name: str, length: int):
    sess = current_session()
    if sess is None:
        return None
    return sess.segment_sites(name, length)


@contextlib.contextmanager
def segment_ctx(name: str, sliced_sites):
    sess = current_session()
    if sess is None:
        yield
        return
    with sess.segment_ctx(name, sliced_sites):
        yield


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------

def init_bank(loss_fn: Callable, params, batch, policy,
              cfg: StatsConfig = StatsConfig()) -> Dict[str, Any]:
    """Discover the model's truncation sites and return a zero-initialized
    bank matching them.

    ``loss_fn(params, batch, policy) -> (loss, aux)`` is the same callable
    the trainer uses.  Discovery runs under ``jax.eval_shape`` (no FLOPs,
    no memory) with a recording session: each ``Policy`` truncation site
    reports its key and whether it sits inside a scanned layer segment;
    segment sites get [L]-stacked state rows.  Site keys are a function of
    Python execution order, which is identical between this abstract trace
    and the jitted train step.  The discovery is timed as the host span
    ``statsbank/init_bank`` (``repro.obs.spans``).
    """
    if current_session() is not None:
        raise RuntimeError("cannot run discovery inside an active session")
    sess = Session(None, 0, cfg, discovery=True)

    def probe(p, b):
        _ACTIVE.session = sess
        try:
            loss, _ = loss_fn(p, b, policy)
        finally:
            _ACTIVE.session = None
        return loss

    with spans.span(spans.INIT_BANK):
        jax.eval_shape(probe, params, batch)
    bank: Dict[str, Any] = {}
    for key, info in sess.recorded.items():
        length = (sess.segment_lengths.get(info["segment"])
                  if info["segment"] else None)
        bank[key] = {d: init_site_state(length, telemetry=cfg.telemetry)
                     for d in info["dirs"]}
    if not bank:
        raise ValueError(
            "no truncation sites found — StatsBank requires an s2fp8-mode "
            f"policy (got mode={getattr(policy, 'mode', policy)!r})")
    return bank


def merge_updates(bank: Dict[str, Any], updates: Dict[str, Any]
                  ) -> Dict[str, Any]:
    """Assemble the next-step bank from the loss gradient w.r.t. the bank.

    Sites with any cotangent-carrying direction — truncation sites
    ("bwd") and payload-GEMM nodes (every :data:`GEMM_DIRS` state) — emit
    their refreshed entry as their cotangent: take ``updates``.  Read-only
    operand-stats sites ("fwd"-only entries, gradient-stopped reads) have
    zero cotangents — carry the old entry forward unchanged."""
    return {k: updates[k] if any("bwd" in d for d in bank[k]) else bank[k]
            for k in bank}


def force_refresh(bank: Dict[str, Any]) -> Dict[str, Any]:
    """Host-side forced refresh: set ``last = -1`` on every
    cotangent-carrying site so each bootstrap-refreshes (EMA re-seeded
    with d=0 — exactly the reset wanted after numeric distress) on its
    next use.  Read-only operand-stats sites are left alone:
    :func:`merge_updates` carries their INPUT entry forward, so a -1
    planted there would never clear and the trainer's cold-start probe
    would report a refresh every step.  The escalation ladder's rung 2
    (training/guard.py docstring) calls this between steps."""
    out = {}
    for site, entry in bank.items():
        if any("bwd" in d for d in entry):
            out[site] = {d: dict(st, last=jnp.full_like(st["last"], -1.0))
                         for d, st in entry.items()}
        else:
            out[site] = entry
    return out


def bookkeeping_last(bank: Dict[str, Any]) -> jnp.ndarray:
    """Every site-direction's last-refresh scalar, concatenated — the
    trainer's O(n_sites) cold-start probe (``min < 0`` => some site still
    bootstraps this step).  Structure-agnostic over plain truncation
    entries, read-only operand sites, and GEMM nodes."""
    return jnp.concatenate([jnp.ravel(st["last"])
                            for e in bank.values() for st in e.values()])


# ---------------------------------------------------------------------------
# jaxpr inspection: prove non-refresh steps run zero stats reductions
# ---------------------------------------------------------------------------

REDUCE_PRIMS = ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                "argmax", "argmin")


from jax.extend.core import ClosedJaxpr as _ClosedJaxpr, Jaxpr as _Jaxpr


def _extract_jaxprs(v):
    out = []
    if isinstance(v, (_Jaxpr, _ClosedJaxpr)):
        out.append(v)
    elif isinstance(v, (tuple, list)):
        for item in v:
            out.extend(_extract_jaxprs(item))
    return out


def count_reductions(jaxpr, include_cond: bool = True,
                     prims: Tuple[str, ...] = REDUCE_PRIMS) -> int:
    """Count reduction primitives in a (closed) jaxpr, recursing into
    sub-jaxprs (scan/pjit/remat/custom_vjp).  ``include_cond=False`` skips
    ``lax.cond`` branches — code that does NOT execute on steps where the
    predicate deselects it.  A StatsBank train step keeps every stats
    reduction inside cond branches, so its ``include_cond=False`` count
    equals the numerics-free (fp32) baseline's."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in prims:
            n += 1
        for pname, pval in eqn.params.items():
            if (eqn.primitive.name == "cond" and pname == "branches"
                    and not include_cond):
                continue
            for sub in _extract_jaxprs(pval):
                n += count_reductions(sub, include_cond, prims)
    return n


# ---------------------------------------------------------------------------
# host-side bank (absorbs DelayedStatsCache)
# ---------------------------------------------------------------------------

class HostStatsBank:
    """Eager, host-side keyed bank for non-jit callers (serving loops,
    checkpoint compression).  Same per-site state and refresh numerics as
    the jit-carried bank — ``refresh_state`` is shared — with the refresh
    decision taken on the host: ``truncate(x, key, step)`` refreshes when
    the key is new or ``step - last >= refresh_every``, else it is a
    single elementwise pass reusing the stored (alpha, beta)."""

    def __init__(self, backend: Optional[str] = None,
                 refresh_every: int = 16, ema_decay: float = 0.0,
                 fmt: str = "e5m2"):
        if refresh_every < 1:
            raise ValueError("refresh_every must be >= 1")
        self.backend = backend
        self.refresh_every = refresh_every
        self.ema_decay = ema_decay
        self.fmt = fmt
        self.bank: Dict[str, Dict[str, jnp.ndarray]] = {}

    def stats(self, key: str) -> Optional[Tuple[jnp.ndarray, jnp.ndarray]]:
        st = self.bank.get(key)
        return None if st is None else (st["alpha"], st["beta"])

    def _site(self, x, key: str, step: int):
        """The site's state, refreshed when the key is new or stale."""
        st = self.bank.get(key)
        if st is None or step - float(st["last"]) >= self.refresh_every:
            st = refresh_state(
                x, st if st is not None else init_site_state(),
                jnp.float32(step), ema_decay=self.ema_decay,
                target_max=s2fp8.FMT_TARGET_MAX[self.fmt],
                backend=self.backend)
            self.bank[key] = st
        return st

    def truncate(self, x: jnp.ndarray, key: str, step: int) -> jnp.ndarray:
        st = self._site(x, key, step)
        be = nbackend.get_backend(self.backend)
        return be.truncate(x, stats=(st["alpha"], st["beta"]), fmt=self.fmt)

    def quantize(self, x: jnp.ndarray, key: str, step: int):
        """Bank-stats quantization to S2FP8 storage (compression callers)."""
        st = self._site(x, key, step)
        be = nbackend.get_backend(self.backend)
        return be.quantize(x, stats=(st["alpha"], st["beta"]), fmt=self.fmt)

    def clear(self):
        self.bank.clear()
