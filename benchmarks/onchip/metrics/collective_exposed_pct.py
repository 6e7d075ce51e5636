"""collective_exposed_pct.train (%), beside ``train_tokens_per_s``: the
share of the chips' time in the traced window in which a collective ran on
a chip and no other operation of that chip covered it.  That is each
collective's self time (``trace_reduce.self_times``: the time in which it is
the innermost operation on its chip's op line, so that a loop or a
conditional around it does not hide it and an operation inside or after it
does), for every all-gather, all-reduce, reduce-scatter, collective-permute
and all-to-all, their asynchronous ``-start`` and ``-done`` halves
included, summed over the chips, over chips x window.  A TPU trace names an
operation by its HLO text: a collective is told by its opcode, or by an
instruction name that starts with one (a fusion around it).  Nothing where
the trace holds no collective."""

import re
from collections import defaultdict

import trace_reduce

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
# the opcode of the instruction, not an operand's name (``%all-gather.3``)
_OPCODE = re.compile(r"(?:^|\s)(%s)(?:-start|-done)?\("
                     % "|".join(COLLECTIVES))


def kind(op):
    """The collective ``op`` runs, or None."""
    name = trace_reduce.base_name(op)
    for c in COLLECTIVES:
        if name.startswith(c):
            return c
    m = _OPCODE.search(op)
    return m.group(1) if m else None


def read(ctx):
    red = ctx["trace"]
    found = red.kernel_calls(lambda op: kind(op) is not None)
    if not found:
        top = ", ".join(n for n, _ in red.top_ops(20))
        ctx["log"](f"collective_exposed_pct: no collective in the trace; "
                   f"its largest ops: {top}")
        return None
    by_kind, calls = defaultdict(float), defaultdict(int)
    for op, t, n in found:
        by_kind[kind(op)] += t
        calls[kind(op)] += n
    secs = sum(by_kind.values())
    each = ", ".join(f"{k} {by_kind[k]:.6g} s in {calls[k]} ops"
                     for k in sorted(by_kind))
    ctx["log"](f"collective_exposed_pct: {secs:.6g} s exposed over "
               f"{red.n_devices} chips x {red.window_s:.6g} s ({each}) in "
               f"{ctx['steps']} steps")
    return 100.0 * secs / (red.n_devices * red.window_s)
