"""Whole runs with the timed path broken underneath: ``correct`` must come
out false for each fault a cell can have.

The runs go through ``harness.execute`` (the look for a chip left out) on
cells cut to a CPU size. The faults are planted in the program's
classes, where the timed path produces its answers:

- a step that returns its state unchanged: a job hands back its start
  values; an ingest applies nothing and leaves the values as they were;
- half of the batch left out: an ingest applies half of its edits;
- an answer altered where it is produced: two vertices' values swapped.

The cells run on one chip, so there is no exchange between chips to
leave out.
"""
import numpy as np
import pytest

from bench.tests import tiny


def swap_two(values):
    """Swap the largest finite value with the smallest nonzero one."""
    v = np.array(values)
    ok = np.flatnonzero(np.isfinite(v) & (v < 1e17) & (v > 0))
    hi, lo = ok[np.argmax(v[ok])], ok[np.argmin(v[ok])]
    v[hi], v[lo] = v[lo], v[hi]
    return v


def fault_jobs(monkeypatch, kind):
    from repro.core.engine import StructureAwareEngine
    orig = StructureAwareEngine.run

    def run(self, *a, warm=None, **k):
        res = orig(self, *a, warm=warm, **k)
        if kind == "unchanged":
            start = warm.values if warm is not None else self.values0
            res.values = np.asarray(start)[self.plan.inv]
        else:
            res.values = swap_two(res.values)
        return res

    monkeypatch.setattr(StructureAwareEngine, "run", run)


def fault_stream(monkeypatch, kind):
    from repro.stream import DeltaBatch, StreamingEngine
    orig = StreamingEngine.ingest

    def ingest(self, batch):
        if kind == "unchanged":
            return orig(self, DeltaBatch.empty())
        if kind == "half":
            h, d = batch.n_inserts // 2, batch.n_deletes // 2
            batch = DeltaBatch(ins_src=batch.ins_src[:h],
                               ins_dst=batch.ins_dst[:h],
                               ins_w=batch.ins_w[:h],
                               del_src=batch.del_src[:d],
                               del_dst=batch.del_dst[:d])
        rep = orig(self, batch)
        if kind == "altered":
            self._values = swap_two(self._values)
        return rep

    monkeypatch.setattr(StreamingEngine, "ingest", ingest)


def test_sound_runs_are_correct(monkeypatch):
    for name in ("g500-s16.pagerank", "g500-s16.stream"):
        line = tiny.execute(tiny.cell(name), monkeypatch=monkeypatch)
        assert line["correct"] and line["attempted"] > 0, line


@pytest.mark.parametrize("name,kind", [
    ("g500-s16.pagerank", "unchanged"),
    ("g500-s16.pagerank", "altered"),
    ("road-grid128.sssp", "unchanged"),
    ("road-grid128.sssp", "altered"),
    ("g500-s16.stream", "unchanged"),
    ("g500-s16.stream", "half"),
    ("g500-s16.stream", "altered"),
])
def test_fault_is_not_correct(monkeypatch, name, kind):
    c = tiny.cell(name)
    if c.traffic["kind"] == "edits":
        fault_stream(monkeypatch, kind)
    else:
        fault_jobs(monkeypatch, kind)
    line = tiny.execute(c, monkeypatch=monkeypatch)
    assert line["attempted"] > 0
    assert line["correct"] is False, line["checks"]
    # the result line names each number compared beside its limit, last
    assert list(line)[-1] == "checks"
    assert all(set(v) == {"value", "limit"} for v in line["checks"].values())


def test_result_line_keys(monkeypatch):
    line = tiny.execute(tiny.cell("road-grid128.sssp"), monkeypatch=monkeypatch)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "checks"}
    assert set(line["metrics"]) == {"setup_s", "converge_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])


@pytest.mark.parametrize("name", ["g500-s16.pagerank", "road-grid128.sssp",
                                  "g500-s16.stream", "g500-s16.sssp"])
def test_control_in_the_programs_place_is_not_correct(monkeypatch, name):
    """The control (the reference in bfloat16) put in the program's place
    on the run's own answers reads ``correct`` false through the result
    line."""
    import jax

    from bench import harness, roofline
    monkeypatch.setitem(roofline.PEAKS, jax.devices()[0].device_kind,
                        roofline.PEAKS["TPU v5 lite"])
    c = tiny.cell(name)
    devs = jax.devices()
    run = harness.execute_run(c, 5, 0.3, False, devs, 0.0)
    assert harness.result_line(run, False, devs)["correct"]
    prog, args = run.compared
    run.checks = {k: (v, run.checks[k][1])
                  for k, v in prog.compare(*args, low=True).items()}
    line = harness.result_line(run, False, devs)
    assert line["correct"] is False, line["checks"]
