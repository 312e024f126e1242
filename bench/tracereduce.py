"""Reduce a profiler trace of one run to the numbers the readers need.

The harness wraps the traced part of its window in a
``jax.profiler.TraceAnnotation`` named ``window``, and each call into the
program in a span of its own (``job``, ``ingest``, ``warmup``). From the
``.xplane.pb`` file the profiler writes, :func:`reduce_trace` takes

- the window: the ``window`` span on the host's clock;
- busy time: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:`` plane), inside the
  window, averaged over the devices;
- self time per device operation, by instruction name (the TPU names an
  event by its HLO text, ``%fusion.7 = f32[...] fusion(...)``; a ``while``
  holds its body's operations, so its self time is what they leave),
  and the calls and time of the named kernel (an operation whose name,
  up to its first dot, is the kernel's: ``block_sweep.3``), keyed by the
  event's full instruction text, which carries its operands' shapes;
- the idle gaps: the stretches of the window in which no operation ran,
  each labelled with the innermost benchmark span the host was in at the
  gap's midpoint (``none`` outside every span).
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np

WINDOW = "window"
DEVICE_OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # averaged over devices
    devices: int
    op_s: dict  # operation name -> self seconds, summed over devices
    # the named kernel's events, keyed by their compiled instruction text
    # (one text per dispatch bucket that ran it): calls and seconds
    kernel_calls: dict
    kernel_s: dict
    gaps: list  # [(label, seconds)], the longest ten, longest first
    idle_by_span: dict  # label -> idle seconds

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def find_trace(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(event_name: str) -> str:
    """``fusion.7`` from ``%fusion.7 = f32[...] fusion(...)``; other names
    as they are."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def _self_times(s: np.ndarray, e: np.ndarray) -> np.ndarray:
    """Each interval's length less the intervals nested in it."""
    order = np.lexsort((-(e - s), s))
    own = e - s
    stack: list = []
    for i, st, en in zip(order.tolist(), s[order].tolist(),
                         e[order].tolist()):
        while stack and e[stack[-1]] <= st:
            stack.pop()
        if stack:
            own[stack[-1]] -= en - st
        stack.append(i)
    return own


def _union(starts: np.ndarray, ends: np.ndarray):
    """Merged [start, end) intervals of a set of intervals."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.r_[True, s[1:] > reach[:-1]]
    idx = np.flatnonzero(new)
    return s[idx], np.r_[reach[idx[1:] - 1], reach[-1]]


def reduce_trace(path: Path, kernel: str, spans: tuple) -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    host = []  # (name, start, end) of the benchmark's own spans
    dev_lines = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW or ev.name in spans:
                        host.append((ev.name, ev.start_ns, ev.end_ns))
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    dev_lines.append(line)
    windows = [(s, e) for name, s, e in host if name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace {path}")
    w0, w1 = windows[0]
    op_s: dict = {}
    kernel_calls: dict = {}
    kernel_s: dict = {}
    busy = 0.0
    gap_s, gap_e = [], []
    for line in dev_lines:
        texts, starts, ends = [], [], []
        for ev in line.events:
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e > s:
                texts.append(ev.name)
                starts.append(s)
                ends.append(e)
        s, e = np.asarray(starts, float), np.asarray(ends, float)
        own = _self_times(s, e) * 1e-9
        for text, d, o in zip(texts, ((e - s) * 1e-9).tolist(),
                              own.tolist()):
            name = op_name(text)
            op_s[name] = op_s.get(name, 0.0) + o
            if name.split(".")[0] == kernel:
                kernel_calls[text] = kernel_calls.get(text, 0) + 1
                kernel_s[text] = kernel_s.get(text, 0.0) + d
        us, ue = _union(s, e)
        busy += float((ue - us).sum()) * 1e-9
        gs, ge = np.r_[w0, ue], np.r_[us, w1]
        gap_s.append(gs[ge > gs])
        gap_e.append(ge[ge > gs])
    devices = max(len(dev_lines), 1)
    gs = np.concatenate(gap_s) if gap_s else np.zeros(0)
    ge = np.concatenate(gap_e) if gap_e else np.zeros(0)
    # the benchmark's spans follow one another on one thread: label each
    # gap by the span that holds its midpoint
    inner = sorted((h for h in host if h[0] != WINDOW), key=lambda h: h[1])
    labels = ["none"] + [h[0] for h in inner]
    sp_s = np.asarray([h[1] for h in inner], float)
    sp_e = np.asarray([h[2] for h in inner], float)
    mid = (gs + ge) / 2
    at = np.searchsorted(sp_s, mid, side="right") - 1
    held = (at >= 0) & (mid <= sp_e[np.maximum(at, 0)] if inner else False)
    lab = np.where(held, at + 1, 0)
    sec = (ge - gs) * 1e-9
    idle_by_span: dict = {}
    per_label = np.bincount(lab, weights=sec, minlength=len(labels))
    for name, v in zip(labels, per_label.tolist()):
        idle_by_span[name] = idle_by_span.get(name, 0.0) + v / devices
    top = np.argsort(-sec, kind="stable")[:10]
    gaps = [(labels[lab[i]], float(sec[i])) for i in top]
    return TraceSummary(window_s=(w1 - w0) * 1e-9,
                        busy_s=busy / devices, devices=len(dev_lines),
                        op_s=op_s, kernel_calls=kernel_calls,
                        kernel_s=kernel_s, gaps=gaps,
                        idle_by_span=idle_by_span)
