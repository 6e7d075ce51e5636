"""Operations and bytes of a dense decoder's training step, from shapes.

``sizes`` are a configuration's sizes (``sizes()`` of its reference):
d_model, n_heads, kv_heads, head_dim, d_ff, vocab, n_layers.  ``job`` is a
traffic file: batch and seq.  Nothing here is read from the program.

* :func:`model_flops_per_token` is what model FLOP/s utilization counts:
  6 x the matmul weights (the LM head included) plus the attention score
  and value products over the causal half, forward and backward, with no
  recompute.
* :func:`gemm_call` and :func:`qflash_call` give the work of one kernel
  call, read from the HLO text by which a TPU trace names it, at the
  S2FP8 interface (1-byte operands, f32 output) and at the published
  sizes: a kernel that pads an operand to its blocks (2304 to 2560, the
  head dim 64 to 128) does not count the padding as work.  Every call in
  the trace is counted as it ran, so recompute under remat and the extra
  calls of a StatsBank refresh step count too.
"""
from __future__ import annotations

import re
from typing import Dict, List, NamedTuple

# the padding a kernel may add to a published size (its largest block)
MAX_PAD = 512
_SHAPE = re.compile(r"\b(f8e5m2|f8e4m3fn|bf16|f32)\[([\d,]*)\]")


class Call(NamedTuple):
    name: str        # what the call computes
    flops: float     # operations of one call
    bytes: float     # bytes one call must move at least
    count: int       # how many such calls


def _layer_linears(s: Dict) -> List[tuple]:
    d, hd, ff = s["d_model"], s["head_dim"], s["d_ff"]
    q, kv = s["n_heads"] * hd, s["kv_heads"] * hd
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
            ("w_gate", d, ff), ("w_up", d, ff), ("w_down", ff, d)]


def matmul_weights(s: Dict) -> int:
    """Weights that take part in a matmul, per token: every layer linear
    and the LM head (the embedding lookup is a gather, not a matmul)."""
    per_layer = sum(k * n for _, k, n in _layer_linears(s))
    return s["n_layers"] * per_layer + s["d_model"] * s["vocab"]


def model_flops_per_token(s: Dict, job: Dict) -> float:
    attn = s["n_layers"] * 6 * s["n_heads"] * s["head_dim"] * job["seq"]
    return 6.0 * matmul_weights(s) + attn


def _op_shapes(op: str):
    """(output shapes, operand shapes) of an HLO instruction's text, each
    a list of (dtype, dims); the (1, 1) scalars of S2FP8 statistics are
    left out of the operands."""
    head, _, rest = op.partition("custom-call(")
    operands = rest.split("custom_call_target", 1)[0]

    def shapes(text):
        return [(t, tuple(int(x) for x in dims.split(",") if x))
                for t, dims in _SHAPE.findall(text)]

    return (shapes(head.split(" = ", 1)[-1]),
            [sh for sh in shapes(operands) if sh[1] != (1, 1)])


def published(dim: int, s: Dict, job: Dict) -> int:
    """The published size that a kernel padded to ``dim``: the largest of
    the step's sizes not above it and within ``MAX_PAD`` of it."""
    sizes = {s["d_model"], s["n_heads"] * s["head_dim"],
             s["kv_heads"] * s["head_dim"], s["d_ff"], s["vocab"],
             job["batch"] * job["seq"]}
    fit = [c for c in sizes if dim - MAX_PAD < c <= dim]
    if not fit:
        raise ValueError(f"no size of the step pads to {dim}: {sorted(sizes)}")
    return max(fit)


def gemm_call(op: str, s: Dict, job: Dict, count: int = 1) -> Call:
    """One payload GEMM call, C[M, N] = A B with A and B stored as [M, K]
    or [K, M] and [K, N] or [N, K], from its HLO text; a batched call has
    one more leading dimension on all three."""
    outs, ins = _op_shapes(op)
    (_, c), = outs
    (_, a), (_, b) = ins
    batch = c[0] if len(c) == 3 else 1
    (om, on), (a0, a1), (b0, b1) = c[-2:], a[-2:], b[-2:]
    # A [M, K] or [K, M]; B [K, N] or [N, K]; C unpadded or padded
    for m, k, kb, n in ((a0, a1, b0, b1), (a0, a1, b1, b0),
                        (a1, a0, b0, b1)):
        if k == kb and 0 <= m - om < MAX_PAD and 0 <= n - on < MAX_PAD:
            break
    else:
        raise ValueError(f"not a GEMM of two matrices: {op[:200]}")
    m, k, n = (published(x, s, job) for x in (m, k, n))
    return Call(f"gemm[{batch},{m},{k},{n}]", 2.0 * batch * m * k * n,
                batch * (m * k + k * n + 4 * m * n), count)


def qflash_call(op: str, s: Dict, job: Dict, count: int = 1) -> Call:
    """One payload flash-attention call over [B*H, S, d] operands, from
    its HLO text: the forward (outputs the attention and the row
    log-sum-exp), dq (one output) or dk/dv (two full outputs).  The
    causal half of the (query, key) pairs, the published head dim, K and
    V at the key-value heads' count."""
    outs, ins = _op_shapes(op)
    bh = max(dims[0] for _, dims in ins if len(dims) == 3)   # Q's B*H
    sq = ins[0][1][1]
    hd = s["head_dim"]
    pairs = bh * sq * sq / 2.0
    q_b = bh * sq * hd                         # q, dO, out or dq elements
    kv_b = q_b * s["kv_heads"] / s["n_heads"]  # k or v elements
    row = bh * sq * 4                          # one f32 per query row
    if len(outs) == 2 and outs[1][1][-1] == 1:
        # QK^T and PV
        return Call("qflash.fwd", 4 * pairs * hd,
                    q_b + 2 * kv_b + 4 * q_b + row, count)
    if len(outs) == 1:
        # QK^T recomputed, dO V^T, dS K
        return Call("qflash.dq", 6 * pairs * hd,
                    2 * q_b + 2 * kv_b + 2 * row + 4 * q_b, count)
    # QK^T recomputed, P^T dO, dO V^T, dS^T Q
    return Call("qflash.dkdv", 8 * pairs * hd,
                2 * q_b + 2 * kv_b + 2 * row + 2 * 4 * q_b, count)


def ideal_seconds(calls: List[Call], peaks: Dict) -> Dict[str, float]:
    """Least time of ``calls`` on a chip with ``peaks``: for each call the
    larger of operations over the bf16 peak and bytes over the HBM
    bandwidth.  Also how much of it each bound sets."""
    total = compute = memory = 0.0
    for c in calls:
        tc = c.flops / peaks["bf16_flops"]
        tm = c.bytes / peaks["hbm_bytes_per_s"]
        total += c.count * max(tc, tm)
        if tc >= tm:
            compute += c.count * tc
        else:
            memory += c.count * tm
    return {"seconds": total, "compute_bound_s": compute,
            "memory_bound_s": memory}
