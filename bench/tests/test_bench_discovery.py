"""A configuration, a traffic mix of a new kind and a per-layer metric
added as new files and new ``BENCHMARK.json`` entries are found by name,
with no edit to a file that is there; and the command refuses to run
anywhere but a TPU."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bench import harness
from bench.tests import tiny

NEW_METRIC = '''"""Jobs finished in the window."""


def read(run):
    return float(len(run.jobs)) if run.jobs else None
'''

NEW_KIND = '''"""Analytics jobs, as ``jobs`` runs them, each with a marker."""
from bench import harness


def run(run, graph, seed, seconds, traced, counter, t_start, devs):
    jobs = harness.load_module("kinds", "jobs", run.cell.bench)
    jobs.run(run, graph, seed, seconds, traced, counter, t_start, devs)
    for j in run.jobs:
        j["marked"] = True
'''


def copy_benchmark(dst):
    shutil.copy(harness.ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(harness.BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    copy_benchmark(tmp_path)
    b = tmp_path / "bench"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    before[tmp_path / "BENCHMARK.json"] = \
        (tmp_path / "BENCHMARK.json").read_bytes()
    cfg = json.loads((b / "configs" / "g500-s16.json").read_text())
    cfg.update(name="g500-s9", scale=9)
    (b / "configs" / "g500-s9.json").write_text(json.dumps(cfg))
    (b / "traffic" / "pagerank-again.json").write_text(
        json.dumps({"kind": "marked-jobs", "program": "pagerank"}))
    (b / "kinds" / "marked-jobs.py").write_text(NEW_KIND)
    (b / "metrics" / "jobs_done.job.py").write_text(NEW_METRIC)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "g500-s9", "source": "test",
                            "file": "bench/configs/g500-s9.json",
                            "reduced": ["scale"], "why": "test"})
    cell = "g500-s9.pagerank-again"
    spec["workloads"].append({"name": cell, "config": "g500-s9",
                              "traffic": "pagerank-again", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "converge_s" in (m["name"], m.get("moves")) and "workloads" in m:
            m["workloads"].append(cell)
    spec["per_layer"].append({"name": "jobs_done.job", "unit": "jobs",
                              "better": "higher", "source": "host_clock",
                              "layer": "fused superstep loop",
                              "moves": "converge_s", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    c = harness.resolve(cell, root=tmp_path)
    assert c.bench == b and c.config["scale"] == 9
    assert "jobs_done.job" in {m["name"] for m in c.per_layer}
    line = tiny.execute(c, traced=True, monkeypatch=monkeypatch)
    run = harness.execute_run(c, 3, 0.2, False, jax.devices(), 0.0)
    assert run.jobs and all(j["marked"] for j in run.jobs)
    assert line["correct"], line["checks"]
    assert line["metrics"]["jobs_done.job"]["value"] == line["attempted"]
    assert line["metrics"]["supersteps.job"]["value"] > 0
    # nothing that was there before changed, BENCHMARK.json aside
    changed = [p for p, data in before.items()
               if p.name != "BENCHMARK.json" and p.read_bytes() != data]
    assert changed == []


def run_command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500-s16.pagerank",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_unknown_kind_is_refused(tmp_path):
    copy_benchmark(tmp_path)
    (tmp_path / "bench" / "traffic" / "pagerank.json").write_text(
        json.dumps({"kind": "no-such-kind", "program": "pagerank"}))
    with pytest.raises(ValueError, match="names no kind"):
        harness.resolve("g500-s16.pagerank", root=tmp_path)


def test_no_tpu_no_result():
    r = run_command(harness.ROOT)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert "correct" not in r.stdout


def test_benchmark_files_alone_are_not_enough(tmp_path):
    copy_benchmark(tmp_path)
    r = run_command(tmp_path)
    assert r.returncode != 0
    assert "correct" not in r.stdout
