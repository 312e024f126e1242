"""The program's own spans and named scopes in a profiler trace.

The program (``repro.obs``) writes each of its spans as a
``jax.profiler.TraceAnnotation`` named ``<cat>.<name>`` (``engine.chunk``,
``engine.sync``, ``stream.apply``), and wraps the parts of its chunk
program in ``jax.named_scope``; ``StructureAwareEngine.op_scopes()`` maps
each compiled device operation, keyed as a trace event prints it, to its
scope. From the ``.xplane.pb`` file of a traced run (the benchmark's
``window`` span around it), :func:`reduce_program` takes

- the window and the device's busy time, as :mod:`bench.tracereduce` does;
- self time per device operation by its full instruction text, which
  :func:`scope_seconds` maps to the program's scopes;
- the program's spans inside the window: how many of each name, the
  device idle time inside each name, and the idle time by the innermost
  program span the host was in (``none`` outside every one).

The per-layer numbers these give are :func:`gather_share`,
:func:`post_share`, :func:`boundary_idle_ms` and :func:`apply_idle`.
On a program without the spans or scopes each reads as nothing.

    python3 bench/spans.py TRACE.xplane.pb [--scopes SCOPES.json]

prints the reduction, and the time by scope where a map is given.
"""
from __future__ import annotations

import argparse
import dataclasses
import gzip
import json
import sys
import tempfile
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # as bench/run.py: import as the package ``bench``, the program from src
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench import tracereduce  # noqa: E402

# categories of the program's spans (``repro.obs.trace``)
PROGRAM_CATS = ("engine", "stream", "ooc", "serve")


@dataclasses.dataclass
class ProgramTrace:
    window_s: float
    busy_s: float  # averaged over devices
    devices: int
    op_text_s: dict  # full instruction text -> self seconds, over devices
    span_count: dict  # program span name -> spans in the window
    idle_in_span: dict  # name -> device idle seconds inside its spans
    idle_by_program_span: dict  # innermost span name ("none") -> seconds


def _overlap(s: np.ndarray, e: np.ndarray, bs: np.ndarray,
             be: np.ndarray) -> float:
    """Total length that the intervals [s, e) share with the disjoint,
    sorted intervals [bs, be)."""
    if s.size == 0 or bs.size == 0:
        return 0.0
    cum = np.r_[0.0, np.cumsum(be - bs)]

    def below(x):  # measure of the b intervals below x
        k = np.searchsorted(bs, x, side="right") - 1
        kk = np.maximum(k, 0)
        part = np.clip(x - bs[kk], 0.0, be[kk] - bs[kk])
        return np.where(k >= 0, cum[kk] + part, 0.0)

    return float((below(e) - below(s)).sum())


def _innermost(spans: list) -> dict:
    """name -> (starts, ends): the disjoint stretches in which each span
    name is the innermost open one (the one opened last)."""
    # at one instant: ends first, then starts, the longer span first
    edges = sorted([(s, 1, s - e, i) for i, (_, s, e) in enumerate(spans)]
                   + [(e, 0, 0, i) for i, (_, _, e) in enumerate(spans)])
    out: dict = {}
    open_: list = []
    last = None
    for t, is_start, _, i in edges:
        if open_ and last is not None and t > last:
            out.setdefault(spans[open_[-1]][0], []).append((last, t))
        if is_start:
            open_.append(i)
        else:
            open_.remove(i)
        last = t
    return {k: tuple(np.asarray(v, float).T) for k, v in out.items()}


def reduce_program(path: Path) -> ProgramTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    windows, program, dev_lines = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == tracereduce.WINDOW:
                        windows.append((ev.start_ns, ev.end_ns))
                    elif ev.name.split(".", 1)[0] in PROGRAM_CATS:
                        program.append((ev.name, ev.start_ns, ev.end_ns))
        elif plane.name.startswith("/device:"):
            dev_lines += [line for line in plane.lines
                          if line.name == tracereduce.DEVICE_OPS_LINE]
    if not windows:
        raise ValueError(f"no {tracereduce.WINDOW!r} span in the trace "
                         f"{path}")
    w0, w1 = windows[0]
    op_text_s: dict = {}
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
        own = tracereduce._self_times(s, e) * 1e-9
        for text, o in zip(texts, own.tolist()):
            op_text_s[text] = op_text_s.get(text, 0.0) + o
        us, ue = tracereduce._union(s, e)
        busy += float((ue - us).sum()) * 1e-9
        gs, ge = np.r_[w0, ue], np.r_[us, w1]
        gap_s.append(gs[ge > gs])
        gap_e.append(ge[ge > gs])
    devices = max(len(dev_lines), 1)
    gs = np.concatenate(gap_s) if gap_s else np.zeros(0)
    ge = np.concatenate(gap_e) if gap_e else np.zeros(0)
    # the program's spans, clipped to the window
    program = [(n, max(s, w0), min(e, w1)) for n, s, e in program
               if min(e, w1) > max(s, w0)]
    span_count: dict = {}
    idle_in_span: dict = {}
    for name in {n for n, _, _ in program}:
        ps = np.asarray([s for n, s, _ in program if n == name], float)
        pe = np.asarray([e for n, _, e in program if n == name], float)
        span_count[name] = int(ps.size)
        us, ue = tracereduce._union(ps, pe)
        idle_in_span[name] = _overlap(gs, ge, us, ue) * 1e-9 / devices
    idle_by_program_span: dict = {}
    for name, (ps, pe) in _innermost(program).items():
        idle_by_program_span[name] = _overlap(gs, ge, ps, pe) * 1e-9 / devices
    idle_by_program_span["none"] = max(
        0.0, float((ge - gs).sum()) * 1e-9 / devices
        - sum(idle_by_program_span.values()))
    return ProgramTrace(window_s=(w1 - w0) * 1e-9, busy_s=busy / devices,
                        devices=len(dev_lines), op_text_s=op_text_s,
                        span_count=span_count, idle_in_span=idle_in_span,
                        idle_by_program_span=idle_by_program_span)


def scope_seconds(tr: ProgramTrace, scopes: dict) -> dict:
    """Device self seconds by the program's named scope (summed over
    devices), ``scopes`` mapping an operation's key to its scope as the
    engine's ``op_scopes()`` gives it; an operation it does not name is
    ``unscoped``."""
    from repro.obs.scopes import UNSCOPED, op_key
    out: dict = {}
    for text, sec in tr.op_text_s.items():
        scope = scopes.get(op_key(text), UNSCOPED)
        out[scope] = out.get(scope, 0.0) + sec
    return out


def _busy_share(tr: ProgramTrace, scopes: dict | None, keep) -> float | None:
    if not scopes or tr.busy_s <= 0:
        return None
    by_scope = scope_seconds(tr, scopes)
    return 100.0 * sum(v for k, v in by_scope.items() if keep(k)) \
        / (tr.busy_s * tr.devices)


def gather_share(tr: ProgramTrace, scopes: dict | None) -> float | None:
    """Share of the device's busy time in the block sweep's gathers, in
    percent: self time under a ``sweep_gather_*`` scope (source values,
    their aux values, the chunk's tile rows) over busy time."""
    return _busy_share(tr, scopes, lambda k: k.startswith("sweep_gather_"))


def post_share(tr: ProgramTrace, scopes: dict | None) -> float | None:
    """Share of the device's busy time in the staleness coupling's post
    step (the bump that re-arms downstream blocks, and the calm
    counters), in percent."""
    return _busy_share(tr, scopes, lambda k: k == "post")


def boundary_idle_ms(tr: ProgramTrace) -> float | None:
    """Device idle time per chunk boundary of the fused superstep loop, in
    ms: idle inside ``engine.sync`` (the chunk's blocking reads) and
    ``engine.boundary`` (host work up to the next dispatch), over the
    count of ``engine.boundary`` spans."""
    n = tr.span_count.get("engine.boundary", 0)
    if not n:
        return None
    return 1e3 * (tr.idle_in_span.get("engine.sync", 0.0)
                  + tr.idle_in_span.get("engine.boundary", 0.0)) / n


def apply_idle(tr: ProgramTrace) -> float | None:
    """Device idle inside the streaming ingest's ``stream.apply`` spans
    (storage mutation, tile edits, device commits), in percent of the
    window."""
    if not tr.span_count.get("stream.apply") or tr.window_s <= 0:
        return None
    return 100.0 * tr.idle_in_span.get("stream.apply", 0.0) / tr.window_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="an .xplane.pb file (or .xplane.pb.gz)")
    ap.add_argument("--scopes", help="a JSON file holding op_scopes()'s "
                    "map, or an object with it under 'scopes'")
    args = ap.parse_args(argv)
    path = Path(args.trace)
    with tempfile.TemporaryDirectory() as tmp:
        if path.suffix == ".gz":
            raw = Path(tmp) / path.stem
            raw.write_bytes(gzip.decompress(path.read_bytes()))
            path = raw
        tr = reduce_program(path)
    out = dataclasses.asdict(tr)
    del out["op_text_s"]
    out["boundary_idle_ms"] = boundary_idle_ms(tr)
    out["apply_idle"] = apply_idle(tr)
    if args.scopes:
        scopes = json.loads(Path(args.scopes).read_text())
        scopes = scopes.get("scopes", scopes)
        out["busy_by_scope_s"] = scope_seconds(tr, scopes)
        out["gather_share"] = gather_share(tr, scopes)
        out["post_share"] = post_share(tr, scopes)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
