"""PageRank: the engine's program, and what its answers are held to.

The number compared is ``rank_l1``: the L1 distance between the ranks a
timed job produced and the float64 power iteration on the same arcs. A
converged job stops once the blocks' residuals sum under ``t2``, so its
distance to the fixpoint is small but not zero; the bfloat16 control
sits an order of magnitude further off.
"""
from __future__ import annotations

import numpy as np

from bench import reference as R

SOURCED = False


def make(params: dict, source=None):
    from repro.core import algorithms as A
    return A.pagerank(float(params["damping"]))


def compare(n, src, dst, w, params: dict, answers: list, low=False) -> dict:
    """``{"rank_l1": worst L1 distance}`` over the ``(source, values)``
    answers, against the float64 reference; ``low`` puts the bfloat16
    reference in the program's place."""
    d = float(params["damping"])
    want = R.pagerank(n, src, dst, d)
    if low:
        answers = [(None, R.pagerank(n, src, dst, d, low=True))]
    worst = 0.0
    for _, got in answers:
        got = np.asarray(got, np.float64)
        err = float(np.abs(got - want).sum()) if np.all(np.isfinite(got)) \
            else float("inf")
        worst = max(worst, err)
    return {"rank_l1": worst}
