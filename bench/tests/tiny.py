"""Cells of the benchmark cut to a size the CPU runs in seconds.

The configuration keeps every setting but its scale; the run goes through
``harness.execute``, the same set-up, window and comparison as on the
chip, with the look for a chip left out and the CPU's peaks borrowed from
the v5e's (no CPU number is ever reported as a device metric).
"""
from __future__ import annotations

import time

from bench import harness, roofline

TINY = {"scale": 10, "side": 16}


def cell(name: str, root=harness.ROOT, **config):
    c = harness.resolve(name, root)
    c.config.update({k: v for k, v in TINY.items() if k in c.config})
    c.config.update(config)
    return c


def execute(c, seed: int = 7, seconds: float = 0.3, traced: bool = False,
            monkeypatch=None) -> dict:
    import jax
    if monkeypatch is not None:
        monkeypatch.setitem(roofline.PEAKS, jax.devices()[0].device_kind,
                            roofline.PEAKS["TPU v5 lite"])
    return harness.execute(c, seed, seconds, traced, jax.devices(),
                           time.perf_counter())
