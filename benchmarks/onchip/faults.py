"""Faults planted in the program's train step, for checking that the
comparison catches them (``tests/test_faults.py`` at a CPU size,
``control.py`` at a cell's own size).  The benchmark's own runs never plant
one.

Each wraps the step that ``make_train_step`` returns; the harness hands the
wrapped step to the same ``TrainLoop`` and window (``run.run_cell``'s
``wrap_step``).  The step's arguments are (params, opt_state[, bank],
batch, step) and its outputs (params, opt_state[, bank], metrics)."""
from __future__ import annotations

import contextlib


def unchanged(step_fn):
    """A step that hands back its weights and optimizer state unchanged."""
    def step(*args):
        out = step_fn(*args)
        return (args[0], args[1]) + tuple(out[2:])
    return step


def half_batch(step_fn):
    """A step that drops the second half of the batch's rows and takes the
    mean over the rest."""
    def step(*args):
        batch = {k: v[: v.shape[0] // 2] for k, v in args[-2].items()}
        return step_fn(*args[:-2], batch, args[-1])
    return step


def one_leaf_not_updated(step_fn):
    """A step whose answer is altered where it is made: one weight matrix
    comes back without its update."""
    def step(*args):
        out = step_fn(*args)
        params = dict(out[0])
        seg = dict(params["segments"][0])
        seg["wo"] = args[0]["segments"][0]["wo"]
        params["segments"] = [seg]
        return (params,) + tuple(out[1:])
    return step


@contextlib.contextmanager
def _patched(module, **attrs):
    old = {k: getattr(module, k) for k in attrs}
    for k, v in attrs.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(module, k, v)


def unsynced(step_fn):
    """The exchange of gradients between chips left out: each chip keeps
    its own share of the gradient of its own rows (a sharded leaf's
    reduce-scatter and a replicated leaf's all-reduce become local), so
    each chip applies its own quarter's gradient.  The program's
    collectives (``repro.core.collectives``) are replaced while the step
    is traced."""
    import jax
    from repro.core import collectives

    def own_share(g, info):
        n = g.shape[0] // info.axis_size
        return jax.lax.dynamic_slice_in_dim(
            g, jax.lax.axis_index(info.axis) * n, n, 0)

    def step(*args):
        with _patched(collectives, param_scatter_axis=own_share,
                      grad_sync_axis=lambda grads, *a, **k: grads):
            return step_fn(*args)
    return step
