"""The numbers that decide ``correct``.

The program's first three rounds against the plain reference's, per
seed:

- ``loss_gap``: the widest relative gap of the eval loss after each of
  the three rounds, |L - L_ref| / |L_ref|;
- ``moment_gap`` (Adam only): the optimizer's first moments after
  round 1, per parameter leaf over all users;
- ``update_gap``: the parameters' change over the three rounds, all
  leaves as one vector.

A gap is the gap between the program's norm and the reference's,
|‖p‖ - ‖r‖|.  ``moment_gap`` takes it per leaf, against the larger of
the reference's norm of that leaf and of the median leaf, and is the
worst leaf of any seed.  ``update_gap`` takes it over the whole change,
against the reference's norm of it, and is the worst seed: per leaf it
would follow one small leaf's round-off, since Adam's first step moves
every parameter by the learning rate times the sign of its gradient,
and a bias gradient within rounding of nought flips that sign.  Leaves
whose first gradient in the reference is nought to rounding (under a
thousandth of the median leaf's, as a convolution's bias before
batch-norm) are left out: they move by round-off alone.
"""
from __future__ import annotations

import jax
import numpy as np

NOUGHT = 1e-3


def _norms(tree):
    return np.array([float(np.linalg.norm(np.asarray(l, np.float64)))
                     for l in jax.tree.leaves(tree)])


def leaf_gap(prog, ref, keep) -> float:
    p, r = _norms(prog)[keep], _norms(ref)[keep]
    floor = max(float(np.median(r)), 1e-30)
    return float(np.max(np.abs(p - r) / np.maximum(r, floor)))


def total_gap(prog, ref, keep) -> float:
    p, r = _norms(prog)[keep], _norms(ref)[keep]
    r_all = max(float(np.sqrt(np.sum(r * r))), 1e-30)
    return abs(float(np.sqrt(np.sum(p * p))) - r_all) / r_all


def readings(prog: dict, ref: list, theta0, adam: bool) -> dict:
    """prog: {"losses" [3, S], "m1" (leaves [S, C, M, ...]) or None,
    "theta3" (leaves [S, ...])}; ref: per seed (losses, opt1, grad1,
    theta3) from `reference.Round.run`; theta0: leaves [S, ...]."""
    loss_gap, mom, upd = 0.0, 0.0, 0.0
    pick = lambda tree, s: jax.tree.map(lambda a: np.asarray(a)[s], tree)
    for s, (losses, opt1, g1, th3) in enumerate(ref):
        lr = np.asarray(losses, np.float64)
        lp = np.asarray(prog["losses"], np.float64)[:, s]
        loss_gap = max(loss_gap, float(np.max(np.abs(lp - lr)
                                              / np.abs(lr))))
        gn = _norms(g1)
        keep = gn >= NOUGHT * np.median(gn)
        if adam:
            mom = max(mom, leaf_gap(pick(prog["m1"], s), opt1["m"], keep))
        t0 = pick(theta0, s)
        d_prog = jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b,
                              pick(prog["theta3"], s), t0)
        d_ref = jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b,
                             th3, t0)
        upd = max(upd, total_gap(d_prog, d_ref, keep))
    out = {"loss_gap": loss_gap, "update_gap": upd}
    if adam:
        out["moment_gap"] = mom
    return out


def judge(readings: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for every number the cell's limits
    file names; a number with no limit, or a limit with no number, is
    an error."""
    if set(readings) != set(limits):
        raise KeyError(f"readings {sorted(readings)} do not match the "
                       f"limits {sorted(limits)}")
    return {k: {"value": readings[k], "limit": float(limits[k])}
            for k in sorted(readings)}
