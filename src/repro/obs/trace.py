"""Host-side span tracer: nested spans into a bounded ring buffer.

The paper's thesis is that SCHEDULING decisions drive runtime — so the
interesting question about any run is *when* things happened (when a
block retired, when a re-arm wave fired, which ingest stalled a serve
batch), not just the end-of-run totals the ``Metrics`` classes carry.
This module is the host half of the observability layer:

  * :class:`TraceRecorder` — structured events (spans and counter rows)
    appended to a ``deque`` ring buffer; overflow drops the OLDEST
    events and counts them (``dropped``), so a long-lived service can
    keep a recorder installed forever at bounded memory.
  * module-level ``install()`` / ``current()`` / ``recording()`` — the
    engines look the recorder up per call.
  * :func:`span` — nested-span context manager. The yielded handle
    carries ``t0``/``t1`` (seconds, relative to the recorder epoch) and
    ``set(**args)`` for results only known at exit (e.g. whether a
    repartition boundary actually fired).

Every span is also a ``jax.profiler.TraceAnnotation`` named
``<cat>.<name>`` (``engine.chunk``, ``stream.ingest``), entered whether
or not a recorder is installed: under a profiler session the spans land
on the profiler's host plane, on the same clock as the device's
operations, so a device gap can be attributed to the host step it sits
in. With no profiler session the annotation is a no-op; with no
recorder installed either, a span costs that no-op and one global read.

All clock reads live HERE, not at the instrumented call sites: the
schedule-affecting modules (``ooc/store.py`` and friends) are under the
RA004 no-clocks lint rule, and routing their spans through this module
keeps them clock-free while still timestamping their events. Nothing in
this module touches device state (it imports only ``jax.profiler``) —
recording a span can never perturb a trajectory (bitwise parity with
tracing on is property-tested in ``tests/test_obs.py``).

Timestamps are ``time.perf_counter()`` deltas (monotonic) against the
recorder's construction epoch; the Chrome-trace exporter
(:mod:`repro.obs.export`) converts to microseconds.
"""
from __future__ import annotations

import time
from collections import deque
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

DEFAULT_CAPACITY = 65536  # events kept before the ring starts dropping


class SpanHandle:
    """Mutable view of an open span: ``set(**kw)`` attaches result args;
    ``t0``/``t1`` expose the measured window after the ``with`` exits
    (the engine interpolates per-superstep counter timestamps from
    them)."""

    __slots__ = ("name", "cat", "args", "t0", "t1")

    def __init__(self, name: str, cat: str, args: dict):
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = 0.0
        self.t1 = 0.0

    def set(self, **kw) -> None:
        self.args.update(kw)


class _NullSpan:
    """Shared do-nothing handle for the tracing-off path."""

    __slots__ = ()
    t0 = 0.0
    t1 = 0.0

    def set(self, **kw) -> None:
        pass


class _Span:
    """A span's context: the profiler annotation around the recorder's
    span (``inner``), or around the shared no-op handle when no recorder
    is installed."""

    __slots__ = ("annotation", "inner")

    def __init__(self, annotation: TraceAnnotation, inner):
        self.annotation = annotation
        self.inner = inner

    def __enter__(self):
        self.annotation.__enter__()
        if self.inner is None:
            return _NULL_SPAN
        return self.inner.__enter__()

    def __exit__(self, *exc):
        try:
            if self.inner is not None:
                self.inner.__exit__(*exc)
        finally:
            self.annotation.__exit__(*exc)
        return False


_NULL_SPAN = _NullSpan()


class TraceRecorder:
    """Ring buffer of structured trace events.

    Event shapes (plain dicts, exporter-agnostic):
      ``{"type": "span", "name", "cat", "ts", "dur", "depth", "args"}``
      ``{"type": "counter", "name", "cat", "ts", "values"}``
    ``ts``/``dur`` are seconds relative to the recorder epoch.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 timeline: bool = True):
        self.capacity = int(capacity)
        # whether engines that find this recorder installed also record
        # the device's per-superstep timeline (the traced chunk variant);
        # spans are recorded either way
        self.timeline = bool(timeline)
        self.events: deque = deque(maxlen=self.capacity)
        self.dropped = 0  # oldest events evicted by the ring
        self._epoch = time.perf_counter()
        self._depth = 0

    def now(self) -> float:
        return time.perf_counter() - self._epoch

    def _push(self, ev: dict) -> None:
        if len(self.events) == self.capacity:
            self.dropped += 1
        self.events.append(ev)

    @contextmanager
    def span(self, name: str, cat: str = "", **args):
        h = SpanHandle(name, cat, dict(args))
        h.t0 = self.now()
        self._depth += 1
        try:
            yield h
        finally:
            self._depth -= 1
            h.t1 = self.now()
            self._push({"type": "span", "name": name, "cat": cat,
                        "ts": h.t0, "dur": h.t1 - h.t0,
                        "depth": self._depth, "args": h.args})

    def counter_rows(self, name: str, rows: list, t0: float, t1: float,
                     cat: str = "engine") -> None:
        """Emit one counter event per row, timestamps interpolated
        UNIFORMLY across ``[t0, t1]``. This is how the fused engine's
        per-superstep timeline (exact counters, flushed once per chunk at
        the existing boundary sync) lands on the time axis: the counter
        VALUES are exact, their placement within the chunk's wall window
        is interpolated — the device does not timestamp supersteps."""
        k = len(rows)
        if k == 0:
            return
        step = (t1 - t0) / k
        for i, row in enumerate(rows):
            self._push({"type": "counter", "name": name, "cat": cat,
                        "ts": t0 + i * step,
                        "values": {k2: v for k2, v in row.items()
                                   if isinstance(v, (int, float))
                                   and not isinstance(v, bool)}})


# -- module-level installation ----------------------------------------------
_CURRENT: TraceRecorder | None = None


def install(recorder: TraceRecorder) -> TraceRecorder:
    """Make ``recorder`` the process-wide target of :func:`span`.
    Returns it (chaining convenience)."""
    global _CURRENT
    _CURRENT = recorder
    return recorder


def uninstall() -> None:
    global _CURRENT
    _CURRENT = None


def current() -> TraceRecorder | None:
    return _CURRENT


@contextmanager
def recording(capacity: int = DEFAULT_CAPACITY, timeline: bool = True):
    """Install a fresh recorder for the duration of the block (restoring
    whatever was installed before): the test/bench-friendly entry point.
    ``timeline=False`` records spans only: engines keep their untraced
    chunks (no per-superstep history buffers).

    >>> with recording() as rec:
    ...     engine.run()
    >>> export.write(rec, "results/trace_run.json")
    """
    global _CURRENT
    prev = _CURRENT
    rec = TraceRecorder(capacity, timeline)
    _CURRENT = rec
    try:
        yield rec
    finally:
        _CURRENT = prev


def span(name: str, cat: str = "", **args):
    """Span against the installed recorder, annotated for the profiler as
    ``<cat>.<name>``; with no recorder installed the handle is a shared
    no-op (the instrumented hot paths call this unconditionally)."""
    rec = _CURRENT
    return _Span(TraceAnnotation(f"{cat}.{name}" if cat else name),
                 None if rec is None else rec.span(name, cat, **args))
