"""Edge visits per arc per analytics job: the fused superstep loop's
``Metrics.edges_processed`` over the graph's arcs, averaged over the jobs
of the window. A converged job visits each arc at least once."""
import numpy as np


def read(run):
    if not run.jobs or not run.arcs:
        return None
    return float(np.mean([j["edges_processed"] for j in run.jobs])) / run.arcs
