"""The comparison that decides `correct`: the program's readings against the
plain reference's, each number with a limit of its own (chipbench/limits/).
Shared by the harness, the control (chipbench/control.py) and the tests.
"""

from __future__ import annotations

import statistics


def worst_leaf_gap(prog, ref, keep=None):
    """Worst leaf of |‖prog‖ − ‖ref‖| over max(‖ref‖ of that leaf, ‖ref‖ of
    the median leaf): the gap between the norms, not the norm of the
    difference.  `keep` leaves out leaves by a rule on the reference."""
    median = statistics.median(ref.values())
    worst, where = 0.0, None
    for name, r in ref.items():
        if keep is not None and not keep[name]:
            continue
        gap = abs(prog[name] - r) / max(r, median, 1e-30)
        if gap >= worst:
            worst, where = gap, name
    return worst, where


def moved_leaves(ref_grad_norms):
    """Leaves whose reference gradient is a thousandth of the median leaf's
    or more: the others move under Adam by round-off alone and are left out
    of the comparison of the parameters' change."""
    median = statistics.median(ref_grad_norms.values())
    return {k: v >= 1e-3 * median for k, v in ref_grad_norms.items()}


def training(prog, ref, limits):
    """prog and ref: {"losses": [..], "grad_norms": {leaf: norm},
    "change_norms": {leaf: norm}}.  Returns {name: {"value", "limit"}}."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    if len(prog["losses"]) != len(ref["losses"]):
        loss_gap = float("inf")
    grad_gap, grad_leaf = worst_leaf_gap(prog["grad_norms"],
                                         ref["grad_norms"])
    change_gap, change_leaf = worst_leaf_gap(
        prog["change_norms"], ref["change_norms"],
        moved_leaves(ref["grad_norms"]))
    values = {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
              "change_norm_gap": change_gap}
    out = {k: {"value": v, "limit": limits[k]} for k, v in values.items()
           if k in limits}              # a limit of None: reading only
    for key, leaf in (("grad_norm_gap", grad_leaf),
                      ("change_norm_gap", change_leaf)):
        if key in out:
            out[key]["leaf"] = leaf
    return out


def widest_logit_gap(ref_logits, tokens):
    """Widest gap by which a token's reference logit lies below the
    reference's best, over rows of `ref_logits` [n, vocab] (numpy)."""
    import numpy as np

    ref_logits = np.asarray(ref_logits, np.float32)
    best = ref_logits.max(-1)
    got = ref_logits[np.arange(len(tokens)), np.asarray(tokens)]
    gaps = best - got
    return float(gaps.max()), int(gaps.argmax())
