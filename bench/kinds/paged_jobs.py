"""Analytics jobs under an out-of-core budget: the ``jobs`` kind, run
with the program's span recorder installed, so that the readers see what
the out-of-core tier did in each job.

``bench/kinds/jobs.py`` runs as it is (loaded by name, not copied)
inside ``repro.obs.trace.recording`` with ``timeline=False``: spans only,
so the jobs run the same untraced chunks as in the resident cells. After
the window each window job's tier spans — the ``ooc.*`` spans inside
that job's ``engine.run`` span — are summed into ``run.paging``, one
dict per job:

- ``bytes``: bytes written into the device pool, the ``bytes`` of the
  demand admissions (``ooc.admit``) and boundary prefetches
  (``ooc.prefetch``);
- ``tier_s``: host seconds inside ``ooc.admit``, ``ooc.spill_evict``,
  ``ooc.prefetch`` and ``ooc.compact`` (nested spans counted once);
- ``wall_s``: the job's ``engine.run`` span.

``run.paging`` is ``None`` when the recorder dropped an event: the sums
would then leave part of a job out.
"""
from __future__ import annotations

from bench import harness

# spans recorded per superstep of a paged job are a handful; this bounds
# the recorder far above a 60 s window's worth, and the ring allocates
# only what it holds
CAPACITY = 1 << 22
TIER = ("admit", "spill_evict", "prefetch", "compact")
PLACED = ("admit", "prefetch")  # the spans whose bytes are pool writes


def run(run: harness.Run, graph, seed: int, seconds: float, traced: bool,
        counter: harness.CompileCounter, t_start: float, devs) -> None:
    from repro.obs import trace as obs_trace
    jobs = harness.load_module("kinds", "jobs", run.cell.bench)
    with obs_trace.recording(CAPACITY, timeline=False) as rec:
        jobs.run(run, graph, seed, seconds, traced, counter, t_start, devs)
    run.paging = None if rec.dropped else paging(rec.events,
                                                 len(run.jobs))


def paging(events, n_jobs: int) -> list[dict]:
    """Per job of the last ``n_jobs`` ``engine.run`` spans (the window's;
    the warm-up job runs before them): its tier bytes and seconds."""
    spans = [e for e in events if e["type"] == "span"]
    runs = sorted((e for e in spans
                   if e["cat"] == "engine" and e["name"] == "run"),
                  key=lambda e: e["ts"])[-n_jobs:] if n_jobs else []
    tier = [e for e in spans if e["cat"] == "ooc" and e["name"] in TIER]
    out = []
    for r in runs:
        lo, hi = r["ts"], r["ts"] + r["dur"]
        inside = [e for e in tier if lo <= e["ts"] and e["ts"] + e["dur"]
                  <= hi]
        out.append({
            "bytes": sum(int(e["args"].get("bytes", 0)) for e in inside
                         if e["name"] in PLACED),
            "tier_s": union_seconds(inside),
            "wall_s": r["dur"]})
    return out


def union_seconds(spans) -> float:
    """Seconds covered by the union of the spans' intervals."""
    total, end = 0.0, float("-inf")
    for e in sorted(spans, key=lambda e: e["ts"]):
        lo, hi = e["ts"], e["ts"] + e["dur"]
        if hi > end:
            total += hi - max(lo, end)
            end = hi
    return total
