"""The comparison that decides ``correct``: the program's first training
steps against the plain reference's, on the same weights and rows.

Three numbers, each with a limit of its own in ``limits/<cell>.json``:

* ``loss_gap``: the largest |loss - reference loss| over the steps;
* ``grad_norm_gap``: over the leaves of the first gradient as the
  optimizer takes it, the largest gap between the program's norm and
  the reference's, as a share of the larger of that leaf's reference
  norm and the median leaf's;
* ``update_norm_gap``: the same for each leaf's change over the steps.
  Leaves whose reference gradient is under a thousandth of the median
  leaf's (nought but rounding, as a key's bias is under softmax) move by
  round-off alone and are left out of it.
"""

from __future__ import annotations

import math
import statistics

QUIET_LEAF = 1e-3


def leaf_gaps(prog: dict, ref: dict, leaves) -> dict:
    """{leaf: |program norm - reference norm| / max(reference norm,
    median leaf's reference norm)}."""
    floor = statistics.median(ref[k] for k in leaves)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], floor) for k in leaves}


def moving_leaves(ref: dict) -> list:
    g = ref["grad_norms"]
    floor = statistics.median(g.values())
    return [k for k in g if g[k] >= QUIET_LEAF * floor]


def worst(gaps: dict, n: int = 3) -> list:
    return sorted(gaps.items(), key=lambda kv: -kv[1])[:n]


def numbers(prog: dict, ref: dict) -> dict:
    if set(prog["grad_norms"]) != set(ref["grad_norms"]):
        raise ValueError("program and reference leaves differ")
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"],
                                              strict=True))
    grad = leaf_gaps(prog["grad_norms"], ref["grad_norms"],
                     list(ref["grad_norms"]))
    change = leaf_gaps(prog["change_norms"], ref["change_norms"],
                       moving_leaves(ref))
    return {"loss_gap": loss_gap, "grad_norm_gap": max(grad.values()),
            "update_norm_gap": max(change.values())}


def describe(prog: dict, ref: dict) -> list:
    """Lines that name the leaves behind each number, for the log."""
    grad = leaf_gaps(prog["grad_norms"], ref["grad_norms"],
                     list(ref["grad_norms"]))
    change = leaf_gaps(prog["change_norms"], ref["change_norms"],
                       moving_leaves(ref))
    quiet = sorted(set(ref["grad_norms"]) - set(moving_leaves(ref)))
    return [f"losses {prog['losses']} reference {ref['losses']}",
            f"grad_norm_gap worst leaves {worst(grad)}",
            f"update_norm_gap worst leaves {worst(change)}",
            f"left out of update_norm_gap (quiet gradient): {quiet}"]


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(every number finite and within its limit, {name: {value, limit}})."""
    checks = {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
