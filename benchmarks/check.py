"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, each number beside a limit of its own."""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Sequence


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float]):
    """Largest gap between the program's norm and the reference's over the
    leaves, each measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger. Returns (gap, leaf)."""
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))[:4]}")
    floor = statistics.median(ref.values())
    worst, at = -1.0, None
    for k, r in ref.items():
        g = abs(prog[k] - r) / max(r, floor, 1e-30)
        if not math.isfinite(g):
            g = math.inf
        if g > worst:
            worst, at = g, k
    return worst, at


def compared(name: str, value: float, limit: float, note: str = "") -> dict:
    ok = bool(math.isfinite(value) and value <= limit)
    return {"name": name, "value": value, "limit": limit, "ok": ok, "note": note}


ZERO_GRADIENT = 1e-4  # of the median leaf's gradient norm


def train_readings(prog: dict, ref: dict) -> Dict[str, float]:
    """The numbers compared for a training cell. ``prog`` is the side in the
    program's place: {"losses", "grad_norms", "delta_norms"}; ``ref`` the
    reference's, which also holds ``grad_diff_norms``, the norm per leaf of
    the difference between the two sides' first gradients.

    A leaf whose reference gradient is all but zero (a key bias, which the
    softmax cancels) moves by rounding noise divided by itself under Adam, so
    its change is left out of ``delta_norm_gap``; its gradient stays in the
    other two, held against the median leaf as every small leaf is."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))
    grad_gap, grad_leaf = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    floor = statistics.median(ref["grad_norms"].values())
    live = [k for k, g in ref["grad_norms"].items() if g >= ZERO_GRADIENT * floor]
    delta_gap, delta_leaf = worst_leaf_gap({k: prog["delta_norms"][k] for k in live},
                                           {k: ref["delta_norms"][k] for k in live})
    diff = {k: d / max(ref["grad_norms"][k], floor) for k, d in ref["grad_diff_norms"].items()}
    diff_leaf = max(diff, key=diff.get)
    return {"loss_rel_gap": loss_gap, "grad_norm_gap": grad_gap, "delta_norm_gap": delta_gap,
            "grad_diff_gap": diff[diff_leaf], "_grad_leaf": grad_leaf,
            "_delta_leaf": delta_leaf, "_diff_leaf": diff_leaf}


def train_checks(prog: dict, ref: dict, limits: dict, window_losses: Sequence[float]) -> List[dict]:
    if len(prog["losses"]) != len(ref["losses"]):
        raise ValueError("the program and the reference walked different numbers of steps")
    r = train_readings(prog, ref)
    out = [
        compared("loss_rel_gap", r["loss_rel_gap"], limits["loss_rel_gap"],
                 f"program {prog['losses']} reference {ref['losses']}"),
        compared("grad_diff_gap", r["grad_diff_gap"], limits["grad_diff_gap"], r["_diff_leaf"]),
        compared("grad_norm_gap", r["grad_norm_gap"], limits["grad_norm_gap"], r["_grad_leaf"]),
        compared("delta_norm_gap", r["delta_norm_gap"], limits["delta_norm_gap"], r["_delta_leaf"]),
    ]
    bad = sum(1 for x in window_losses if not math.isfinite(x))
    out.append(compared("nonfinite_window_losses", float(bad), 0.0, f"of {len(window_losses)}"))
    return out


def gap_sigmas(logits, tokens):
    """Per position: how far the given token's logit lies below the row's
    best, in standard deviations of the row. ``logits`` [n, vocab] numpy."""
    import numpy as np

    rows = np.asarray(logits, np.float64)
    picked = rows[np.arange(len(tokens)), np.asarray(tokens)]
    return (rows.max(-1) - picked) / rows.std(-1)


def print_checks(checks: List[dict]) -> None:
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['ok'] else 'FAILED'} {c['note']}", flush=True)
