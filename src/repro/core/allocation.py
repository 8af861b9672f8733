"""P2 — computational & communication resource allocation (paper §IV-D).

    min_{b, E}  K_ε(E) · cost(t)     s.t. (22a)-(22f)

The paper hands this mixed-integer non-convex program to Ipopt.  Our solver
exploits its structure instead (DESIGN.md §7):

* For fixed E, Σ a_m b_m = 1 makes the ρ·R_co term constant, so the
  continuous subproblem reduces to min-max of the uplink epigraph
      min_b max_m { E·Q_C,m + (S_m + ωd)/(b_m B) }
  whose optimum equalizes finish times:  b_m(τ) = (S_m+ωd)/(B(τ − E·Q_C,m)).
  Σ b_m(τ) = 1 is monotone in τ ⇒ bisection gives the exact optimum, then
  the b_min box constraint is enforced by clipping + renormalising over the
  unclipped set (standard waterfilling).
* E is swept over {1..E_max}; the paper's guard E ← min(Ê, E_last) keeps the
  deadline feasible.

The sweep is one batched solve (`solve_bandwidths`): the bisection runs on
an ``(n_E, |A_t|)`` array, one bracket per E, and eq. 22 is evaluated for
every row at once.  Each row computes the same element-wise expressions as
a one-E solve and sums along the contiguous last axis, as numpy sums a
vector, so every row is bitwise the one-E result.  The bisection stops at
the first step that moves no row's bracket, capped at 200 steps.  That
returns the τ the full 200 steps would: a step that changes neither ``lo``
nor ``hi`` hands the next step the same ``mid`` and so the same outcome,
and every later step is the same no-op.  In float64 the bracket stops
moving after about 60 steps.  `bisect_steps` counts the steps taken.

`solve_bandwidth` is verified against brute force in tests/test_allocation.py.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

from repro.core.cost import SystemParams, k_eps, schedule_metrics

MAX_BISECT_STEPS = 200

# bisection steps taken by every solve in this process (the campaign
# planner counts a plan's share as ``p2_bisect_steps``)
bisect_steps = 0


def _clip_to_b_min(bs: np.ndarray, b_min: float) -> np.ndarray:
    """Enforce b_min by clip + renormalise the rest (waterfilling step)."""
    for _ in range(len(bs)):
        low = bs < b_min
        if not low.any():
            break
        fixed = np.sum(np.where(low, b_min, 0.0))
        free = ~low
        if fixed >= 1.0 or not free.any():
            bs = np.full(len(bs), 1.0 / len(bs))
            break
        bs = np.where(low, b_min, bs * (1.0 - fixed) / np.sum(bs[free]))
    return bs


def solve_bandwidths(a: np.ndarray, Es: Sequence[int], sp: SystemParams
                     ) -> np.ndarray:
    """Exact min-max bandwidth split of the selected set for each E in
    ``Es``: row i of the ``(len(Es), M)`` result is the split for ``Es[i]``.

    A client's achievable rate is ``b_m B G_m`` (``G_m`` = channel gain,
    all-ones in the static model), so a faded client needs a larger share
    for the same finish time — dividing its payload by ``G_m`` folds the
    fade into the same equalization, exactly."""
    global bisect_steps
    Es = np.asarray(Es)
    sel = np.where(a > 0)[0]
    b = np.zeros((len(Es), sp.M))
    if len(sel) == 0:
        return b
    size = (sp.S_m[sel] + sp.omega * sp.d_model_bits) / sp.G_m[sel]  # bits
    offs = Es[:, None] * sp.Q_C[sel]                      # s, (n_E, |sel|)

    def excess(tau: np.ndarray) -> np.ndarray:
        denom = np.maximum(tau[:, None] - offs, 1e-12)
        return (size / (sp.B * denom)).sum(axis=1) - 1.0

    lo = np.max(offs, axis=1) + 1e-9
    hi = lo + float(np.sum(size)) / sp.B + 1.0
    grow = excess(hi) > 0
    while grow.any():
        hi = np.where(grow, hi * 2.0, hi)
        grow = excess(hi) > 0
    for steps in range(1, MAX_BISECT_STEPS + 1):
        mid = 0.5 * (lo + hi)
        up = excess(mid) > 0
        moved = np.where(up, mid != lo, mid != hi)
        lo = np.where(up, mid, lo)
        hi = np.where(up, hi, mid)
        if not moved.any():          # the bracket's fixed point
            break
    bisect_steps += steps
    bs = size / (sp.B * np.maximum(hi[:, None] - offs, 1e-12))
    for i in np.flatnonzero((bs < sp.b_min).any(axis=1)):
        bs[i] = _clip_to_b_min(bs[i], sp.b_min)
    b[:, sel] = bs / bs.sum(axis=1, keepdims=True)
    return b


def solve_bandwidth(a: np.ndarray, E: int, sp: SystemParams) -> np.ndarray:
    """Exact min-max bandwidth split for the selected set (fixed E): the
    one-row call of `solve_bandwidths`."""
    return solve_bandwidths(a, [E], sp)[0]


def solve_p2(a: np.ndarray, E_last: int, sp: SystemParams
             ) -> Tuple[np.ndarray, int, float]:
    """Sweep integer E, exact bandwidth per E; apply the paper's guard
    E ← Ê only if Ê ≤ E_last.  Returns (b, E, objective)."""
    Es = np.arange(1, sp.E_max + 1)
    b = solve_bandwidths(a, Es, sp)
    # eq. 22 per row: schedule_metrics' cost is round_cost's, row for row
    cost = schedule_metrics(np.broadcast_to(a, b.shape), b, Es, sp)[1]
    vals = k_eps(Es, sp.eps) * cost
    i = int(np.argmin(vals))     # the first minimum, as a strict `<` sweep
    if Es[i] > E_last:           # guard (paper §IV-D): never increase E
        i = E_last - 1
    return b[i], int(Es[i]), float(vals[i])
