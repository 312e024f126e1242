"""MB of edge tile rows written into the device pool per out-of-core
job: the ``bytes`` of the tier's demand admissions (``ooc.admit``) and
boundary prefetches (``ooc.prefetch``) inside each window job's
``engine.run`` span, as ``bench/kinds/paged_jobs.py`` sums them,
averaged over the jobs. Nothing is returned where no job was paged or
the recorder dropped an event."""
import numpy as np


def read(run):
    paging = getattr(run, "paging", None)
    if not paging:
        return None
    return float(np.mean([j["bytes"] for j in paging])) / 1e6
