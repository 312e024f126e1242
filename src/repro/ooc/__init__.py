"""Out-of-core block tier + epoch persistence.

Three pieces, layered under the existing engines:

  * :mod:`repro.ooc.store` — :class:`SpillStore`: the device holds a pool
    of ``EngineConfig.resident_rows`` edge tile rows, each resident
    block's run contiguous in it at a traced row start; cold blocks'
    rows live in a host cache / npz disk segments and are placed back
    before the schedule can touch them, so a budget-constrained run is
    bitwise-identical to the fully resident one.
  * :mod:`repro.ooc.prefetch` — the activity-directed policy: the PSD
    priority queue predicts the next superstep's schedule (the host
    scheduler twin is property-tested decision-identical to the fused
    device select), demand sets are protected, and retired/calm blocks —
    the paper's cold partition — are the eviction candidates.
  * :mod:`repro.ooc.snapshot` — :class:`GraphCheckpoint`: epoch
    persistence on top of :class:`repro.ckpt.manager.CheckpointManager`,
    serializing the EdgeStore truth, tile rows, fixpoint values,
    PSD/calm state and the partition plan; ``StreamingEngine.save_epoch``
    / ``StreamingEngine.restore`` warm-start a restarted service from the
    last fixpoint instead of paying cold reconvergence.
"""
from repro.ooc.snapshot import GraphCheckpoint
from repro.ooc.store import SpillStore

__all__ = ["GraphCheckpoint", "SpillStore"]
