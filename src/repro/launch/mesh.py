"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init — dryrun.py sets
XLA_FLAGS before importing anything else).

Axis contract: the ``data`` axis carries both the batch AND (under the
launchers' ``--shard-params`` FSDP modes) the param/optimizer shards —
``parallel/sharding.py``'s TRAIN_RULES maps the logical ``fsdp`` axis to
``data``, so every mesh built here supports ZeRO-3 sharding with no extra
axis.  Multi-host meshes additionally need ``jax.distributed.initialize``
before any builder runs (ROADMAP: multi-host FSDP remainder).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    # Auto axes: the models place activations with logical-axis sharding
    # constraints (parallel/sharding.py), which jax refuses on the
    # Explicit axes jax.make_mesh now defaults to
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has — for smoke runs (usually 1 device).

    All devices go on the ``data`` axis, so the mesh-native train step
    (training/trainer.py) data-parallelizes a multi-device host (e.g.
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` CPU smoke
    runs) out of the box."""
    n = len(jax.devices())
    return _make_mesh((n, 1), ("data", "model"))


def make_mesh_from_spec(spec: str):
    """``"DxT"`` mesh specs for the launchers' ``--mesh`` flag.

    Two ints (``"8x1"``) build a ``("data", "model")`` mesh; three
    (``"2x8x1"``) a ``("pod", "data", "model")`` one.  The product must
    match the visible device count (``jax.make_mesh`` enforces it)."""
    try:
        dims = tuple(int(p) for p in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"mesh spec {spec!r} is not of the form 'DxT' "
                         f"or 'PxDxT' (e.g. '8x1')") from None
    if len(dims) == 2:
        axes = ("data", "model")
    elif len(dims) == 3:
        axes = ("pod", "data", "model")
    else:
        raise ValueError(f"mesh spec {spec!r}: want 2 (DxT) or 3 (PxDxT) "
                         f"factors, got {len(dims)}")
    return _make_mesh(dims, axes)


def axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
