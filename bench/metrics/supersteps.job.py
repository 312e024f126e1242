"""Supersteps per analytics job: the fused superstep loop's
``Metrics.iterations``, averaged over the jobs of the window."""
import numpy as np


def read(run):
    if not run.jobs:
        return None
    return float(np.mean([j["iterations"] for j in run.jobs]))
