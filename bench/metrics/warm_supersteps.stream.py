"""Supersteps of warm reconvergence per edit batch: the streaming
layer's ``StreamBatchReport.iterations``, averaged over the batches of
the window."""
import numpy as np


def read(run):
    if not run.batches:
        return None
    return float(np.mean([b["iterations"] for b in run.batches]))
