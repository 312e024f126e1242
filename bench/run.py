"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout; everything it names (configuration, traffic mix, per-layer
metrics, generator) is found by name under ``bench/``. The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``). The run exits non-zero, and prints no result, when JAX
finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program under {ROOT / 'src'}: run from a whole checkout",
              file=sys.stderr)
        return 2
    # the script's own directory would shadow modules of the standard
    # library; the benchmark imports as the package ``bench``
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    return harness.main(argv, t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
