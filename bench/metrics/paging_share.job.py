"""Share of an out-of-core job's wall time, in percent, that the host
spends inside the tier's spans (``ooc.admit``, ``ooc.spill_evict``,
``ooc.prefetch``, ``ooc.compact``; nested spans counted once) over the
job's ``engine.run`` span, as ``bench/kinds/paged_jobs.py`` sums them,
averaged over the window's jobs. Nothing is returned where no job was
paged or the recorder dropped an event."""
import numpy as np


def read(run):
    paging = getattr(run, "paging", None)
    if not paging:
        return None
    return 100.0 * float(np.mean([j["tier_s"] / j["wall_s"]
                                  for j in paging]))
