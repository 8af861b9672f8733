"""The comparison that decides ``correct``: one campaign of the program
against the plain reference of ``reference.py``.

Each number is a reading, compared against its limit in the cell's
configuration file (``limits``).  The numbers:

* ``schedule_mismatch`` -- rounds whose selected set A_t or local-update
  count E_t differ from the reference planner's (exact: limit 0);
* ``loss_gap`` -- the widest gap of a round's phase loss, over seeds, rounds
  and phases, against the reference's loss of that round or the median of
  the seed's losses, whichever is larger;
* ``loss_gap_first`` and ``loss_gap_early`` -- the same over the first
  round and over the first ``EARLY`` rounds only: where rounding sends two
  sound trajectories apart later (FedAvg on one-class-per-RIC data), the
  first rounds still agree;
* ``loss_gap_early_median`` -- the median of the gaps over seeds, phases
  and the first ``EARLY`` rounds: where a widest gap over many seeds
  swings with the seed that parts first (FedAvg), the median stays with
  the bulk of them;
* ``loss_gap_mean`` -- the mean of those gaps over seeds, phases and the
  rounds after the first ``EARLY`` (all rounds, where there are no more):
  steady from seed to seed, where the widest gap is set by the first
  rounds' rounding (SplitMe's first rounds train one client for 20
  steps), and moved by a fault that shifts every later round (half of
  each cohort left out);
* ``acc_gap`` -- the widest gap, over seeds, between the final accuracy
  the program reports and the reference's eval of the program's own final
  parameters: the eval path (Step 4, the Gram kernel, the test forward)
  checked apart from the training trajectory;
* ``param_gap`` -- for each seed and each parameter leaf, the gap between
  the norms of the program's and the reference's change over the campaign,
  against the reference's norm of that leaf or the median leaf, whichever
  is larger; the worst leaf.  Leaves the reference moves by less than a
  thousandth of the median leaf move by round-off alone and are left out.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("schedule_mismatch", "loss_gap", "loss_gap_mean",
           "loss_gap_first", "loss_gap_early", "loss_gap_early_median",
           "acc_gap", "param_gap")
EARLY = 3


def _leaves(tree):
    import jax
    return [np.asarray(l, np.float64) for l in jax.tree.leaves(tree)]


def readings(prog: dict, ref: dict, ref_acc) -> dict:
    """``prog``: the program's campaign as host arrays (``a``, ``E``,
    ``losses`` (S, R, P), ``acc`` (R, S), ``params`` stacked over seeds).
    ``ref``: ``reference.run_campaign``'s result; ``ref_acc`` (S,): the
    reference's accuracy of the program's final parameters
    (``reference.accuracy``)."""
    a_p, e_p = np.asarray(prog["a"]), np.asarray(prog["E"])
    mismatch = int(np.sum(np.any(a_p != ref["a"], axis=1)
                          | (e_p != ref["E"])))
    lp, lr = np.asarray(prog["losses"], np.float64), ref["losses"]
    med = np.median(np.abs(lr), axis=(1, 2), keepdims=True)
    rel = np.abs(lp - lr) / np.maximum(np.abs(lr), med)
    acc_gap = float(np.max(np.abs(np.asarray(prog["acc"])[-1]
                                  - np.asarray(ref_acc))))
    init = _leaves(ref["init"])
    fin_p, fin_r = _leaves(prog["params"]), _leaves(ref["params"])
    gaps = []
    for s in range(lp.shape[0]):
        dp = np.array([np.linalg.norm(p[s] - i[s])
                       for p, i in zip(fin_p, init)])
        dr = np.array([np.linalg.norm(r[s] - i[s])
                       for r, i in zip(fin_r, init)])
        med_leaf = np.median(dr)
        keep = dr >= 1e-3 * med_leaf
        gaps.append(np.max(np.abs(dp - dr)[keep]
                           / np.maximum(dr[keep], med_leaf)))
    return {"schedule_mismatch": mismatch, "loss_gap": float(np.max(rel)),
            "loss_gap_mean": float(np.mean(
                rel[:, EARLY:] if rel.shape[1] > EARLY else rel)),
            "loss_gap_first": float(np.max(rel[:, :1])),
            "loss_gap_early": float(np.max(rel[:, :EARLY])),
            "loss_gap_early_median": float(np.median(rel[:, :EARLY])),
            "acc_gap": acc_gap, "param_gap": float(np.max(gaps))}


def judge(values: dict, limits: dict) -> tuple:
    """(correct, [[name, value, limit], ...]) for the numbers that have a
    limit; a value that is not finite fails."""
    rows, ok = [], True
    for name in NUMBERS:
        if name not in limits:
            continue
        v, lim = values[name], limits[name]
        ok &= bool(np.isfinite(v) and v <= lim)
        rows.append([name, v, lim])
    return ok, rows
