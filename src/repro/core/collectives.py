"""S2FP8-compressed data-parallel gradient synchronization (beyond-paper).

The paper never discusses distribution; this extends its format to the DP
gradient all-reduce, which at pod scale is ICI-bound.  Key numerics fact:
S2FP8 is a *nonlinear* code (log-domain affine), so summation does NOT
commute with encoding — you cannot all-reduce payloads directly.  We
therefore split the all-reduce into its two data-movement-asymmetric legs:

    all_reduce(g)  ==  all_gather(reduce_scatter(g))

  * reduce-scatter leg: arithmetic — runs in bf16 (additive-safe, 2 bytes/elt)
  * all-gather leg: pure data movement — each device S2FP8-encodes its
    *reduced* shard (1 byte/elt + 8 bytes stats) and gathers payloads.

ICI bytes per element: f32 all-reduce ~ 2*(n-1)/n * 4B; compressed version
~ (n-1)/n * (2B + 1B) — a ~2.7x traffic cut with the paper's own format
carrying the gather leg.

Two API levels:

  * **axis level** (``grad_sync_axis`` / ``compressed_allreduce_axis``) —
    plain functions over ``lax`` collectives that run INSIDE an existing
    ``shard_map`` body.  This is what the mesh-native train step
    (training/trainer.py ``make_train_step(mesh=...)``) composes: the
    gradient pytree is synced leaf-by-leaf with a per-leaf routing
    decision (:func:`leaf_sync_route`) — small, integer, 0-d or
    non-divisible leaves bypass compression through a plain ``psum``.
  * **mesh level** (``compressed_grad_sync`` / ``compressed_allreduce_1d``)
    — self-contained wrappers that build their own ``shard_map`` over a
    replicated input; kept for standalone callers and as the numerics
    test surface (tests/test_collectives.py).

The schedule is explicit ``lax`` collectives so it is inspectable in HLO;
the dry-run roofline counts the bytes (see also ``modeled_ici_bytes`` in
benchmarks/kernel_bench.py).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Optional, Sequence, Tuple, \
    Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import backend as nbackend
from repro.core.s2fp8 import S2FP8Tensor

AxisName = Union[str, Tuple[str, ...]]


def _encode_local(x: jnp.ndarray, backend: Optional[str] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-shard S2FP8 encode through the numerics-backend registry —
    LOCAL stats by the backend-interface convention (``compute_stats``
    without ``axis_name`` reduces over the tensor the caller holds; inside
    a shard_map body that is the shard).  Still one (a, b) pair per
    tensor-shard, 8 bytes against megabytes of payload; on TPU pods the
    registry resolves to the fused Pallas kernels for the encode pass."""
    t = nbackend.get_backend(backend).quantize(x)
    return t.payload, t.alpha, t.beta


def _decode_local(payload, alpha, beta, backend: Optional[str] = None
                  ) -> jnp.ndarray:
    return nbackend.get_backend(backend).dequantize(
        S2FP8Tensor(payload=payload, alpha=alpha, beta=beta))


# ---------------------------------------------------------------------------
# per-leaf routing
# ---------------------------------------------------------------------------

def leaf_sync_route(shape: Sequence[int], dtype, axis_size: int,
                    min_size: int = 1 << 16) -> str:
    """Routing decision for one gradient leaf: ``"compressed"`` (S2FP8
    all-gather leg) or ``"plain"`` (f32 psum).  Pure function of the
    leaf's static shape/dtype, so the decision is trace-free and
    unit-testable (tests/test_mesh_train.py).

    A leaf bypasses compression when any of:

      * non-float dtype — integer/bool leaves (step counters, masks) have
        no log2 image; summation must stay exact;
      * 0-d scalar — nothing to scatter, and the 8-byte stats would
        outweigh the payload;
      * fewer than ``min_size`` elements — below ~64k the per-tensor
        stats reduction and kernel launches dominate the 3-byte/elt win;
      * length not divisible by ``axis_size`` — the tiled
        psum_scatter/all_gather legs need equal shards (padding a grad
        leaf would perturb its stats).
    """
    size = 1
    for d in shape:
        size *= d
    if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return "plain"
    if len(shape) == 0:
        return "plain"
    if size < min_size:
        return "plain"
    if size % axis_size != 0:
        return "plain"
    return "compressed"


# ---------------------------------------------------------------------------
# axis level: composable inside an existing shard_map body
# ---------------------------------------------------------------------------

def compressed_allreduce_axis(flat: jnp.ndarray, axis_name: str,
                              axis_size: int,
                              backend: Optional[str] = None) -> jnp.ndarray:
    """SUM-all-reduce a 1-D f32 leaf across a mapped axis with the
    S2FP8-compressed all-gather leg.  Must run inside a ``shard_map`` (or
    other mapped context) where ``axis_name`` is bound; ``flat`` is the
    local value (len % axis_size == 0).  ``backend`` selects the numerics
    engine for the encode/decode legs (None/"auto": platform default —
    fused Pallas kernels on TPU, ref jnp elsewhere)."""
    red = jax.lax.psum_scatter(flat.astype(jnp.bfloat16), axis_name,
                               scatter_dimension=0, tiled=True)
    payload, alpha, beta = _encode_local(red.astype(jnp.float32), backend)
    payloads = jax.lax.all_gather(payload, axis_name, tiled=True)
    alphas = jax.lax.all_gather(alpha[None], axis_name)
    betas = jax.lax.all_gather(beta[None], axis_name)
    chunks = payloads.reshape(axis_size, flat.shape[0] // axis_size)
    dec = jax.vmap(functools.partial(_decode_local, backend=backend))(
        chunks, alphas[:, 0], betas[:, 0])
    return dec.reshape(-1)


def grad_sync_axis(grads, axis_name: AxisName, axis_sizes: Dict[str, int],
                   *, mode: str = "s2fp8", min_size: int = 1 << 16,
                   backend: Optional[str] = None, skip=None):
    """SUM-reduce a gradient pytree across mapped mesh axes, inside an
    existing ``shard_map`` body.

    This is the mesh-native train step's gradient synchronizer: the step
    scales its local loss by ``1 / global_batch_shards`` before
    differentiation, so the per-shard gradients are *contributions* to the
    global mean and the sync is a pure sum (no trailing division — the
    1-device and N-device backward pipelines then see identical
    per-element cotangent values).

    * ``mode="f32"``  — every leaf is a plain ``psum`` (float leaves
      promoted to f32 for the wire, cast back after).
    * ``mode="s2fp8"`` — leaves routed per :func:`leaf_sync_route`:
      compressible leaves take the bf16-reduce-scatter + S2FP8-all-gather
      legs, the rest fall back to plain psum.

    ``axis_name`` may be a tuple (e.g. ``("pod", "data")``): the
    compressed legs run over the LAST axis (the largest, innermost data
    axis by the mesh conventions in launch/mesh.py) and a plain f32 psum
    folds the leading axes first.

    ``skip``: optional bool pytree matching ``grads`` — True leaves are
    returned untouched.  The FSDP train step uses this for param leaves
    whose gradients exit ``jax.grad`` already reduce-scattered to the
    owner shard by the gather custom_vjp's backward.
    """
    if mode not in ("f32", "s2fp8"):
        raise ValueError(f"grad_sync mode must be 'f32' or 's2fp8', "
                         f"got {mode!r}")
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    inner = axes[-1]

    def plain(g):
        if jnp.issubdtype(g.dtype, jnp.floating):
            return jax.lax.psum(g.astype(jnp.float32), axes).astype(g.dtype)
        return jax.lax.psum(g, axes)

    def sync(g):
        if mode == "f32" or leaf_sync_route(
                g.shape, g.dtype, axis_sizes[inner], min_size) == "plain":
            return plain(g)
        flat = g.reshape(-1).astype(jnp.float32)
        if len(axes) > 1:
            flat = jax.lax.psum(flat, axes[:-1])
        out = compressed_allreduce_axis(flat, inner, axis_sizes[inner],
                                        backend)
        return out.reshape(g.shape).astype(g.dtype)

    if skip is not None:
        return jax.tree_util.tree_map(
            lambda g, s: g if s else sync(g), grads, skip)
    return jax.tree_util.tree_map(sync, grads)


# ---------------------------------------------------------------------------
# FSDP param axis: gather-on-use / scatter-on-grad
# ---------------------------------------------------------------------------
#
# The grad machinery above syncs REPLICATED leaves; this section is the
# param-axis counterpart for leaves *sharded* over the mesh's ``fsdp``
# axis (dim 0, ZeRO-3 style).  Two wire formats for the gather leg:
#
#   * f32   — ``param_gather_axis``: a tiled all-gather of the owner
#     shards (4 bytes/elt), wrapped in a custom_vjp whose backward is the
#     grad reduce-scatter, so grads leave ``jax.grad`` already summed AND
#     sharded back to the owner (the trainer skips its replicated sync
#     for these leaves).
#   * payload — ``payload_gather_axis``: each owner quantizes its shard
#     with the leaf-GLOBAL (alpha, beta) from the StatsBank (the
#     partials psum over the batch axes makes every shard agree on the
#     stats; refresh cadence == quantize-at-owner cadence) and gathers
#     1-byte payloads.  The result is a full-size ``S2FP8Tensor`` that
#     ``qdot_train`` feeds straight into the payload GEMM operand slot —
#     no f32/bf16 copy of the leaf ever crosses the wire or lands in HBM.
#
# ``FSDPPayloadParam`` is the handoff contract: the trainer wraps each
# payload-eligible shard in it, the wrapper flows through the user's
# loss_fn as a pytree leaf, and ``Policy.dot`` / ``qdot_train`` unwrap it
# at the GEMM.  Any OTHER consumption (embedding lookups, norms, ...)
# degrades safely: ``__jax_array__`` coerces through the f32 gather with
# the same reduce-scatter backward.

class FSDPInfo(NamedTuple):
    """Static (hashable) description of one FSDP-sharded leaf: how to
    gather it and how to return its gradient.  ``lead_axes`` are the
    OTHER mapped batch axes (e.g. ``("pod",)``) whose contributions must
    psum before the reduce-scatter over ``axis``.  ``gather_f32`` is the
    per-train-step custom_vjp f32 gather (shared so ``__jax_array__``
    fallbacks get the identical grad path)."""
    axis: str
    axis_size: int
    lead_axes: Tuple[str, ...]
    grad_mode: str
    grad_min_size: int
    grad_backend: Optional[str]
    gather_f32: Optional[Callable] = None


def param_scatter_axis(g: jnp.ndarray, info: FSDPInfo) -> jnp.ndarray:
    """Reduce a full-size grad leaf back to the owner's shard: psum over
    the lead batch axes, then reduce-scatter over the fsdp axis (dim 0).
    This is the sharded half of ``all_reduce == all_gather(reduce_scatter)``
    — FSDP grads only need to exist at the owner, so the compressed path
    keeps just the arithmetic (bf16 reduce-scatter) leg and drops the
    payload all-gather leg entirely."""
    if info.lead_axes:
        g = jax.lax.psum(g, info.lead_axes)
    if info.axis_size == 1:
        return g
    route = ("compressed" if info.grad_mode == "s2fp8" and leaf_sync_route(
        g.shape, g.dtype, info.axis_size, info.grad_min_size) == "compressed"
        else "plain")
    wire = jnp.bfloat16 if route == "compressed" else jnp.float32
    red = jax.lax.psum_scatter(g.astype(wire), info.axis,
                               scatter_dimension=0, tiled=True)
    return red.astype(g.dtype)


def make_param_gather(info: FSDPInfo) -> Callable:
    """custom_vjp f32 gather for one FSDP leaf config: forward is a tiled
    all-gather over dim 0 (shard -> full leaf), backward is
    :func:`param_scatter_axis` (full cotangent -> owner shard).  Build
    ONCE per train-step factory so the custom_vjp identity is stable
    across traces."""
    @jax.custom_vjp
    def gather(p_shard):
        return jax.lax.all_gather(p_shard, info.axis, tiled=True)

    def fwd(p_shard):
        return gather(p_shard), None

    def bwd(_, g):
        return (param_scatter_axis(g, info),)

    gather.defvjp(fwd, bwd)
    return gather


def param_gather_axis(p_shard: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Plain (non-differentiable-boundary) tiled f32 gather of an FSDP
    shard over dim 0 — 4 bytes/elt on the wire.  For the in-step gather
    use :func:`make_param_gather` (this is the forward leg only)."""
    return jax.lax.all_gather(p_shard, axis_name, tiled=True)


def payload_gather_axis(q_local: S2FP8Tensor, axis_name: str) -> S2FP8Tensor:
    """All-gather an S2FP8-quantized FSDP shard into the full-size
    payload tensor: 1 byte/elt on the wire, stats scalars ride along
    unchanged (every shard quantized with the same leaf-global (alpha,
    beta), so the gathered tensor is a single coherent S2FP8Tensor).
    FP8 payloads move as bitcast u8 — all_gather is pure data movement
    and some backends reject sub-byte-exponent float element types."""
    u8 = jax.lax.bitcast_convert_type(q_local.payload, jnp.uint8)
    full = jax.lax.all_gather(u8, axis_name, tiled=True)
    payload = jax.lax.bitcast_convert_type(full, q_local.payload.dtype)
    return S2FP8Tensor(payload=payload, alpha=q_local.alpha,
                       beta=q_local.beta, fmt=q_local.fmt)


class FSDPPayloadParam:
    """Pytree marker carrying one payload-eligible FSDP shard into the
    loss function.  Child: the local f32 shard (dim 0 = full / axis_size);
    static aux: the :class:`FSDPInfo`.  ``qdot_train`` consumes it
    directly (quantize-at-owner -> payload all-gather -> payload GEMM B
    slot -> grad reduce-scatter); every other consumption coerces via
    ``__jax_array__`` through the f32 gather custom_vjp, which keeps the
    gradient contract identical."""

    def __init__(self, shard, info: FSDPInfo):
        self.shard = shard
        self.info = info

    # --- array-like surface (full LOGICAL leaf, not the shard) ---
    @property
    def shape(self):
        return (self.shard.shape[0] * self.info.axis_size,) \
            + tuple(self.shard.shape[1:])

    @property
    def ndim(self):
        return self.shard.ndim

    @property
    def dtype(self):
        return self.shard.dtype

    def __jax_array__(self):
        if self.info.gather_f32 is None:
            return param_gather_axis(self.shard, self.info.axis)
        return self.info.gather_f32(self.shard)

    def astype(self, dtype):
        return self.__jax_array__().astype(dtype)

    def __getitem__(self, idx):
        return self.__jax_array__()[idx]

    @property
    def T(self):
        # e.g. tied-embedding lm heads (`params["embed"].T`): a transposed
        # B slot can't stream the row-sharded payload, so it takes the f32
        # gather like any other non-GEMM consumption
        return self.__jax_array__().T

    def __repr__(self):
        return (f"FSDPPayloadParam(shard={self.shard.shape}, "
                f"full={self.shape}, axis={self.info.axis!r}"
                f"x{self.info.axis_size})")


jax.tree_util.register_pytree_node(
    FSDPPayloadParam,
    lambda p: ((p.shard,), p.info),
    lambda info, children: FSDPPayloadParam(children[0], info))


# ---------------------------------------------------------------------------
# mesh level: self-contained wrappers over replicated inputs
# ---------------------------------------------------------------------------

def compressed_allreduce_1d(g: jnp.ndarray, mesh: Mesh, axis: str = "data",
                            backend: Optional[str] = None):
    """All-reduce a replicated-per-shard gradient across ``axis`` with an
    S2FP8-compressed all-gather leg.  g must be 1-D with len % axis_size == 0
    (caller flattens/pads; see ``compressed_grad_sync``).  Builds its own
    ``shard_map``; the body is :func:`compressed_allreduce_axis`."""
    n = mesh.shape[axis]
    body = functools.partial(compressed_allreduce_axis, axis_name=axis,
                             axis_size=n, backend=backend)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=P(), out_specs=P(), check_vma=False)(g)


def compressed_grad_sync(grads, mesh: Mesh, axis: str = "data",
                         min_size: int = 1 << 16,
                         backend: Optional[str] = None):
    """Apply the compressed all-reduce to every leaf :func:`leaf_sync_route`
    deems compressible (small / integer / 0-d / non-divisible leaves go
    through a plain f32 psum — stats overhead dominates below ~64k
    elements, and non-float leaves must sum exactly).  Leaves are averaged
    over ``axis``."""
    n = mesh.shape[axis]

    def sync_leaf(g):
        if leaf_sync_route(g.shape, g.dtype, n, min_size) == "plain":
            if jnp.issubdtype(g.dtype, jnp.integer):
                # integer/bool leaves stay in their own dtype: psum the n
                # replicated copies and divide back exactly (the sum is a
                # multiple of n, so floor-division is the true mean) — an
                # f32 round-trip would truncate and drop bits past 2^24
                def plain_int(x):
                    return jax.lax.psum(x, axis) // n
                return jax.shard_map(plain_int, mesh=mesh, in_specs=P(),
                                     out_specs=P(), check_vma=False)(g)

            def plain(x):
                return jax.lax.psum(x, axis) / n
            return jax.shard_map(plain, mesh=mesh, in_specs=P(),
                                 out_specs=P(), check_vma=False)(
                g.astype(jnp.float32)).astype(g.dtype)
        flat = g.reshape(-1).astype(jnp.float32)
        out = compressed_allreduce_1d(flat, mesh, axis, backend) / n
        return out.reshape(g.shape).astype(g.dtype)

    return jax.tree_util.tree_map(sync_leaf, grads)
