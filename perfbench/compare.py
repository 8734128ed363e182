"""The numbers that decide ``correct`` for a training cell.

Each is a gap between what the program's timed path produced and what the
float32 reference computes from the same weights and rows:

- ``loss_gap``: the largest relative gap of a checked step's loss;
- ``grad_gap``: the first gradient as the optimizer got it, by the worst
  leaf: the gap between the program's norm and the reference's, over the
  larger of the reference's norm of that leaf and of the median leaf;
- ``update_gap``: the same for the change of the parameters after the
  checked steps, over the leaves the reference's gradient moves.

A leaf is one layer's slice of a weight stacked by layer, or a weight that
is not (the reference's ``leaf_norms`` gives a list or one number).
"""
from __future__ import annotations

import statistics

NEGLIGIBLE_GRAD = 1e-3  # of the median leaf's gradient norm


def _flat(norms: dict) -> dict:
    out = {}
    for name, v in norms.items():
        if isinstance(v, list):
            out.update({(name, i): float(x) for i, x in enumerate(v)})
        else:
            out[(name, None)] = float(v)
    return out


def worst_leaf_gap(prog: dict, ref: dict, leaves=None) -> tuple:
    """(gap, leaf) of the worst leaf among ``leaves`` (default: all)."""
    p, r = _flat(prog), _flat(ref)
    keys = sorted(r if leaves is None else leaves, key=str)
    median = statistics.median(r[k] for k in keys)
    worst, where = 0.0, None
    for k in keys:
        gap = abs(p[k] - r[k]) / max(r[k], median, 1e-30)
        if gap >= worst:
            worst, where = gap, k
    return worst, where


def moved_leaves(ref_grad: dict) -> list:
    """Leaves whose reference gradient is more than round-off."""
    r = _flat(ref_grad)
    median = statistics.median(r.values())
    return [k for k, v in r.items() if v >= NEGLIGIBLE_GRAD * median]


def gaps(prog: dict, ref: dict) -> dict:
    """prog/ref: {"losses": [...], "grad_norms": {...}, "update_norms": {}}."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                   ref["losses"]))
    grad, grad_at = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    upd, upd_at = worst_leaf_gap(prog["update_norms"], ref["update_norms"],
                                 moved_leaves(ref["grad_norms"]))
    return {"loss_gap": loss, "grad_gap": grad, "update_gap": upd,
            "grad_gap_leaf": _label(grad_at), "update_gap_leaf": _label(upd_at)}


def _label(key) -> str:
    name, layer = key
    return name if layer is None else f"{name}[{layer}]"


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit)]): every number with a limit must be
    finite and at most its limit; one without a limit is reported only."""
    rows, ok = [], True
    for name in ("loss_gap", "grad_gap", "update_gap"):
        value = numbers[name]
        limit = limits.get(name, {}).get("limit")
        rows.append((name, value, limit))
        if limit is not None and not value <= limit:  # NaN fails too
            ok = False
    if all(limit is None for _, _, limit in rows):
        ok = False
    return ok, rows
