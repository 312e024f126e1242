"""Structure-aware observability: span tracing + superstep timelines.

Two halves:

  * device side — ``engine.run(trace=True)`` grows the fused while_loop
    carry with a bounded per-superstep history buffer (counter deltas,
    dispatch width, retirements, PSD stats) flushed at the existing
    repartition-boundary sync and surfaced as ``RunResult.timeline``;
  * host side — :class:`TraceRecorder` collects nested spans (``run``,
    ``chunk``, ``sync``, ``boundary``, ``repartition``, ``ingest``,
    ``apply``, ``commit``, ``reconverge``, ``spill_evict``/``prefetch``,
    ``snapshot``, ``query_batch``) from engine/stream/serve/ooc into a
    ring buffer, exported as Chrome-trace/Perfetto JSON
    (:mod:`repro.obs.export`) and rendered by ``python -m repro.obs``.
    Each span is also a profiler ``TraceAnnotation`` named
    ``<cat>.<name>``, so a ``jax.profiler`` trace holds them on the
    device's clock; :mod:`repro.obs.scopes` names the device operations
    by the ``jax.named_scope`` of the superstep part they ran in.

Typical capture::

    from repro.obs import trace, export
    with trace.recording() as rec:
        service.run_pending()          # spans auto-attach
    export.write(rec, "results/trace_serve.json")

or ``python -m benchmarks.run --trace`` for whole bench suites.
"""
from repro.obs.trace import (TraceRecorder, current, install,  # noqa: F401
                             recording, span, uninstall)
from repro.obs.export import to_chrome, validate, write  # noqa: F401
