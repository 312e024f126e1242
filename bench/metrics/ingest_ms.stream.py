"""Host ingest time per edit batch, in ms: the streaming layer's
``StreamBatchReport.ingest_time_s`` (a host-clock span inside
``StreamingEngine.ingest``: storage mutation, delete resets, device
commits), averaged over the batches of the window."""
import numpy as np


def read(run):
    if not run.batches:
        return None
    return 1e3 * float(np.mean([b["ingest_s"] for b in run.batches]))
