"""Single-source shortest paths: the engine's program, and what its
answers are held to.

The number compared is ``dist_gap``: over the sampled answers, the
largest relative gap between a reachable vertex's distance and
scipy's float64 Dijkstra on the same arcs (parallel arcs keep their
lightest copy); a vertex whose reachability differs reads infinite. The
engine sums float32 weights along the same paths, so sound answers sit
at float32 rounding; the bfloat16 control rounds every path sum.
"""
from __future__ import annotations

import numpy as np

from bench import reference as R

SOURCED = True


def make(params: dict, source=None):
    from repro.core import algorithms as A
    return A.sssp(int(source))


def compare(n, src, dst, w, params: dict, answers: list, low=False) -> dict:
    """``{"dist_gap": worst gap}`` over the ``(source, values)`` answers;
    ``low`` puts the bfloat16 reference in the program's place."""
    sources = [int(s) for s, _ in answers]
    want = R.dijkstra(n, src, dst, w, sources)
    worst = 0.0
    for k, (s, got) in enumerate(answers):
        if low:
            got = R.sssp_low(n, src, dst, w, int(s))
        worst = max(worst, R.dist_gap(got, want[k]))
    return {"dist_gap": worst}
