"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against a limit of its own (``limits/<cell>.json``):

* ``loss_gap``: the relative gap between the program's loss and the
  reference's at the first step, before any update: the forward pass
  through every layer at the cell's sizes.  (The later steps' losses
  follow the updates, which ``change_gap`` reads.  Under S2FP8 with
  delayed statistics they part from the f32 reference's by design: the
  first step's statistics, kept for the next, saturate activations that
  the first update made larger; ``control.py --emulate`` shows it.)
* ``grad_gap``: the first gradient as the optimizer got it, by the worst
  leaf: | |g_prog| - |g_ref| | over the larger of |g_ref| for that leaf
  and the median leaf's |g_ref|.
* ``change_gap``: the weights' change after the checked steps, by the
  worst leaf, in the same measure.  Leaves whose reference gradient is
  under a thousandth of the median leaf's move under Adam by round-off
  alone and are left out.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Tuple

NEGLIGIBLE_GRAD = 1e-3


def flatten(tree) -> Dict[str, float]:
    """``{"segments/0/mlp/w_gate": value, ...}`` of a tree of scalars."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out[key] = float(leaf)
    return out


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep: Optional[List[str]] = None) -> Tuple[float, str]:
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))}")
    floor = statistics.median(ref.values())
    worst, where = 0.0, ""
    for k in (keep if keep is not None else ref):
        gap = abs(prog[k] - ref[k]) / max(ref[k], floor)
        if worst != worst:              # a NaN is the worst gap of all
            break
        if not gap <= worst:
            worst, where = gap, k
    return worst, where


def readings(prog: Dict, ref: Dict) -> Dict[str, Tuple[float, str]]:
    """The three numbers, each with where it was read."""
    loss = abs(prog["losses"][0] - ref["losses"][0]) / abs(ref["losses"][0])
    g_ref = ref["grad_norms"]
    floor = statistics.median(g_ref.values())
    moved = [k for k, g in g_ref.items() if g >= NEGLIGIBLE_GRAD * floor]
    return {
        "loss_gap": (loss, "step 0"),
        "grad_gap": worst_leaf_gap(prog["grad_norms"], g_ref),
        "change_gap": worst_leaf_gap(prog["change_norms"],
                                     ref["change_norms"], moved),
    }


def judge(read: Dict[str, Tuple[float, str]], limits: Dict[str, float]):
    """(correct, [(name, value, limit, where), ...]) over the numbers that
    have a limit.  A cell's limits file leaves out a number that no
    control or fault separates from sound runs (PERF.md names it)."""
    rows = [(k, v, limits[k], where) for k, (v, where) in read.items()
            if k in limits]
    return all(v <= lim for _, v, lim, _ in rows), rows
