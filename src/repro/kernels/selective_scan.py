"""Pallas TPU kernel: Mamba-1 selective scan with VMEM-resident state.

WHY (EXPERIMENTS.md §Perf, falcon cell): the XLA lax.scan formulation
round-trips the state h [B,di,n] plus per-step [di,n] temporaries through
HBM every timestep — the static analysis shows ~2.2 PB/device/step for
falcon-mamba train_4k (memory term ~2600s).  Unrolling helps 1.5x; the SSD
chunk factorization that fixes Mamba-2 is numerically UNSTABLE for Mamba-1
(matrix A: exp(±cum) factors overflow f32 for fast-decaying channels — the
exact reason Mamba-2 moved to scalar decay).  The TPU-native answer is a
kernel that pins h and the dA temporaries in VMEM/VREGs and streams only
x/dt/B/C/y through HBM:

    traffic = (3*[B,S,di] + 2*[B,S,n] streams) ~ 4 bytes/elt each
    vs ~ 2*[B,di,n]*S state round-trips + per-step temporaries.

Grid: (B, di/bd).  Each program owns a [bd, n] state slab and walks the
whole sequence with fori_loop; x/dt/y tiles [S, bd] and B/C tiles [S, n]
live in VMEM for the program's lifetime (S=4096, bd=256, n=16:
~3 * 4096*256*4 + 2 * 4096*16*4 + 256*16*4 bytes ~= 13 MiB — fits v5e VMEM;
halve bd for longer S).

Validated against ref.selective_scan_ref in interpret mode
(tests/test_kernels.py); the dry-run graphs keep the lax.scan form (the
CPU backend can't lower pallas), so EXPERIMENTS.md reports this kernel's
roofline analytically next to the XLA-sim numbers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import auto_interpret
from repro.obs import spans


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
                 y_ref, hout_ref, *, seq_len):
    a = a_ref[...]                       # [bd, n]
    dskip = d_ref[0]                     # [bd]
    h0 = jnp.zeros(a.shape, jnp.float32)

    def step(t, h):
        xt = x_ref[0, t, :]              # [bd]
        dtt = dt_ref[0, t, :]            # [bd]
        bt = b_ref[0, t, :]              # [n]
        ct = c_ref[0, t, :]              # [n]
        da = jnp.exp(dtt[:, None] * a)   # [bd, n] — in-register
        h = h * da + (dtt * xt)[:, None] * bt[None, :]
        y = jnp.sum(h * ct[None, :], axis=-1) + dskip * xt
        y_ref[0, t, :] = y
        return h

    h = jax.lax.fori_loop(0, seq_len, step, h0)
    hout_ref[0] = h


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def selective_scan_pallas(x, dt, bmat, cmat, a, d_skip, *,
                          block_d: int = 256, interpret: bool | None = None):
    """x, dt: [B,S,di]; bmat, cmat: [B,S,n]; a: [di,n]; d_skip: [di].
    Returns (y [B,S,di], h_final [B,di,n])."""
    if interpret is None:
        interpret = auto_interpret()
    b, s, di = x.shape
    n = bmat.shape[-1]
    bd = min(block_d, di)
    assert di % bd == 0
    grid = (b, di // bd)
    y, hout = pl.pallas_call(
        functools.partial(_scan_kernel, seq_len=s),
        name=spans.SELECTIVE_SCAN,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s, bd), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, s, bd), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, s, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, s, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((bd, n), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bd), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, s, bd), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, bd, n), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, di), jnp.float32),
            jax.ShapeDtypeStruct((b, di, n), jnp.float32),
        ],
        interpret=interpret,
    )(x.astype(jnp.float32), dt.astype(jnp.float32),
      bmat.astype(jnp.float32), cmat.astype(jnp.float32),
      a.astype(jnp.float32), d_skip.reshape(1, -1).astype(jnp.float32))
    return y, hout
