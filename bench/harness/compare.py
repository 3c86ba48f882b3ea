"""The comparison that decides ``correct``.

The timed trainer's first rounds, taken in set-up through the window's own
``train()`` call, against the reference's first rounds from the same seed.
Three numbers, each with a limit of its own (``bench/limits/<cell>.json``):

* ``loss``: per round, the gap between the Lagrangian ``train()`` reports
  and the reference's, over the reference's; the worst round.
* ``first_update``: per leaf (W_l, Z_l, U), the gap between the norms of
  the two sides' first-round change, ‖S₁−S₀‖, over the larger of the
  reference's norm of that leaf and of the median leaf; the worst leaf.
* ``change``: the same for the change over all the compared rounds,
  ‖S_k−S₀‖.

A leaf the reference leaves unmoved to rounding, its change under a
thousandth of the median moving leaf's, is left out of that number: the
first round starts from a consistent point (Z = forward(W), U = 0), where
the gradients of W_l and of the hidden Z_l are zero.
"""
from __future__ import annotations

import math

import numpy as np

MOVED_SHARE = 1e-3


def leaves(state: dict) -> dict:
    out = {f"W{i + 1}": w for i, w in enumerate(state["w"])}
    out.update({f"Z{i + 1}": z for i, z in enumerate(state["z"])})
    out["U"] = state["u"]
    return out


def change_norms(before: dict, after: dict) -> dict:
    a, b = leaves(before), leaves(after)
    return {k: float(np.linalg.norm(np.asarray(b[k], np.float64)
                                    - np.asarray(a[k], np.float64)))
            for k in a}


def norm_gap(program: dict, reference: dict) -> tuple[float, dict]:
    """Worst leaf of |‖Δ_prog‖ − ‖Δ_ref‖| / max(‖Δ_ref‖, median ‖Δ_ref‖),
    over the leaves the reference moves; also the per-leaf gaps."""
    moving = [v for v in reference.values() if v > 0]
    med = float(np.median(moving)) if moving else 0.0
    gaps = {}
    for k, ref in reference.items():
        if ref <= 0 or ref < MOVED_SHARE * med:
            continue
        gaps[k] = abs(program[k] - ref) / max(ref, med)
    return (max(gaps.values()) if gaps else math.nan), gaps


def numbers(prog_states: list, prog_losses: list, ref_states: list,
            ref_losses: list) -> tuple[dict, dict]:
    """``*_states``: S₀ … S_k on the host; ``*_losses``: the Lagrangian
    after rounds 1 … k.  Returns (numbers, per-leaf detail)."""
    loss = max(abs(p - r) / max(abs(r), 1e-30)
               for p, r in zip(prog_losses, ref_losses))
    first, first_leaves = norm_gap(change_norms(prog_states[0], prog_states[1]),
                                   change_norms(ref_states[0], ref_states[1]))
    change, change_leaves = norm_gap(
        change_norms(prog_states[0], prog_states[-1]),
        change_norms(ref_states[0], ref_states[-1]))
    return ({"loss": float(loss), "first_update": float(first),
             "change": float(change)},
            {"first_update": first_leaves, "change": change_leaves})


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """Correct when every number is finite and within its limit.  Returns
    (correct, {name: {"value", "limit"}}) in the limits' order."""
    out, ok = {}, True
    for name, limit in limits.items():
        v = values.get(name, math.nan)
        out[name] = {"value": v, "limit": limit}
        if not (math.isfinite(v) and v <= limit):
            ok = False
    return ok, out
