"""The comparison that decides ``correct`` for a training cell.

Both sides are readings of the same first steps from the same seed and the
same host batches: the program's (taken by the runner from the object the
window then drives) and the plain reference's.  Compared, each against a
limit of its own from the cell's traffic file:

  ``loss<k>_gap``      |program - reference| / |reference| of step k's loss;
  ``stats_gap``        the worst normalisation statistic's distance after
                       the first step (the norm of the difference of the
                       two running means or variances, over the norm of
                       the reference's move from the starting value): the
                       forward pass layer by layer, and the number that
                       fails the lower-precision control;
  ``grad_norm_gap``    the worst leaf's gap between the two norms of the
                       first gradient as the optimizer kept it;
  ``change_norm_gap``  the median leaf's gap between the two norms of the
                       parameters' change after the steps;
  ``change_worst_gap`` the worst leaf's.

A leaf's gap is the distance between the program's norm and the
reference's (for ``stats_gap`` the norm of their difference), over the
reference's norm of that leaf or of the median leaf, whichever is larger.
Leaves whose raw gradient in the reference is under a thousandth of the
median leaf's are left out of the change: they move by round-off alone.
``change_worst_gap`` takes only the leaves whose natural-gradient direction
is well determined (the reference's
``optim.well_determined``: no preconditioned axis with fewer rows than its
factors' rank; PERF.md section 2 names the leaves this leaves out and
shows them the worst).  A limit of null means the number is printed and
not compared (PERF.md says why).
"""

from __future__ import annotations

import math
import statistics

import jax
import numpy as np


def _leaves(tree):
    return [float(v) for v in jax.tree.leaves(tree)]


def diff_norms(program, reference):
    """Per leaf, the norm of the difference of two trees of host arrays."""
    prog, ref = jax.tree.leaves(program), jax.tree.leaves(reference)
    if len(prog) != len(ref):
        raise ValueError(f"{len(prog)} leaves against {len(ref)}")
    return [float(np.linalg.norm((np.asarray(p, np.float64)
                                  - np.asarray(r, np.float64)).ravel()))
            for p, r in zip(prog, ref)]


def leaf_gaps(program, reference, keep=None, distance=None):
    """[(leaf index, gap)] of the leaves that count; ``distance`` is each
    leaf's distance where it is not the gap between the two norms."""
    prog, ref = _leaves(program), _leaves(reference)
    if len(prog) != len(ref):
        raise ValueError(f"{len(prog)} leaves against {len(ref)}")
    floor = statistics.median(ref)
    out = []
    for i, (p, r) in enumerate(zip(prog, ref)):
        if keep is None or keep[i]:
            d = abs(p - r) if distance is None else distance[i]
            gap = d / max(r, floor, 1e-30)
            out.append((i, gap if math.isfinite(gap) else math.inf))
    return out


def worst_leaf_gap(program, reference, keep=None, distance=None):
    """(gap, leaf index) of the leaf that is farthest off."""
    at, gap = max(leaf_gaps(program, reference, keep, distance),
                  key=lambda g: g[1], default=(-1, 0.0))
    return gap, at


def stats_gap(program, reference, start) -> float:
    """The worst statistic's distance between two trees of running
    statistics, over the reference's move from ``start``."""
    if jax.tree.structure(program) != jax.tree.structure(reference):
        raise ValueError("the reference's statistics are not the program's: "
                         f"{jax.tree.structure(reference)} vs "
                         f"{jax.tree.structure(program)}")
    moved = diff_norms(reference, start)
    return worst_leaf_gap(moved, moved, None,
                          diff_norms(program, reference))[0]


def compare(program: dict, reference: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} in a fixed order, and nothing else."""
    out = {}
    for k, (p, r) in enumerate(zip(program["loss"], reference["loss"]), 1):
        gap = abs(p - r) / max(abs(r), 1e-30)
        out[f"loss{k}_gap"] = gap if math.isfinite(gap) else math.inf
    if jax.tree.leaves(reference.get("stats", {})):
        out["stats_gap"] = stats_gap(program["stats"], reference["stats"],
                                     reference["stats_start"])
    well = [bool(w) for w in jax.tree.leaves(reference["well_determined"])]
    out["grad_norm_gap"], _ = worst_leaf_gap(program["grad_norm"],
                                             reference["grad_norm"])
    raw = _leaves(reference["raw_grad_norm"])
    moved = [g >= 1e-3 * statistics.median(raw) for g in raw]
    gaps = leaf_gaps(program["change_norm"], reference["change_norm"], moved)
    out["change_norm_gap"] = statistics.median(g for _, g in gaps)
    out["change_worst_gap"] = max(g for i, g in gaps if well[i])
    return {name: {"value": value, "limit": limits.get(name)}
            for name, value in out.items()}


def verdict(compared: dict) -> bool:
    """Every compared number at or under its limit; at least one compared."""
    held = [c for c in compared.values() if c["limit"] is not None]
    return bool(held) and all(c["value"] <= c["limit"] for c in held)
