"""The program's spans beside the device's ops (``chipbench.lib.spans``):
the clock offset, the gap labels, the idle seconds under spans, and the
``resume.compile_s`` reader, on small made-up traces and on recorded v5e
traces."""

import json
import math
import types

import pytest

from chipbench.lib import harness, spans, trace

FIXTURES = harness.BENCH / "fixtures"


def _prog(*events, line=0):
    return [[n, float(s), float(d), line] for n, s, d in events]


def test_clock_offset_from_the_profiled_stretch():
    """The profile holds a stretch of what the tracer recorded; the offset
    is the median over the pairs it matches, whatever stretch it is."""
    names = ["train.step", "train.batch", "train.dispatch", "train.wait"]
    records = []
    for k in range(40):  # obs clock, us; steps of uneven length, as on the chip
        t = 1000.0 * k + (37 * k * k) % 500
        records += [{"name": names[0], "ts_us": t, "dur_us": 900.0},
                    {"name": names[1], "ts_us": t + 1, "dur_us": 10.0},
                    {"name": names[2], "ts_us": t + 12, "dur_us": 30.0},
                    {"name": names[3], "ts_us": t + 43, "dur_us": 850.0}]
    off = 7.25e9  # profiler ns = obs ns + off, within 2 us of jitter
    jitter = [0, 300, -200, 1500, -1800]
    program = [[r["name"], r["ts_us"] * 1e3 + off + jitter[i % 5], r["dur_us"] * 1e3, 3]
               for i, r in enumerate(records[40:60])]  # steps 10-14 only
    got, diffs = spans.clock_offset(program, records, 3)
    assert len(diffs) == 20
    assert got == pytest.approx(off, abs=300)
    none, pairs = spans.clock_offset(program, records, 4)  # no span on that line
    assert math.isnan(none) and pairs == []


def test_labels_name_the_innermost_span_covering_most_of_a_gap():
    t = {"host": [[trace.WINDOW, 0, 1000], ["chipbench.save", 100, 200]],
         "devices": {"/device:TPU:0": [["a", 0, 100], ["b", 300, 100], ["c", 500, 100],
                                       ["d", 900, 100]]}}
    program = _prog(("train.step", 0, 410), ("manager.save", 100, 200),
                    ("save.stage", 110, 180), ("train.step", 420, 500),
                    ("train.batch", 420, 40), ("train.dispatch", 460, 70),
                    ("train.wait", 600, 300), ("save.shard", 0, 1000))
    program[-1][3] = 1  # another thread's span names nothing here
    gaps = spans.named_gaps(t, program, 0)
    assert gaps == [
        ["host: outside any benchmark phase > train.wait", pytest.approx(300e-9)],
        ["chipbench.save > save.stage", pytest.approx(200e-9)],
        # [400, 500): train.batch and train.dispatch cover 40 each, the
        # step 80: the innermost span covering more than half is the step
        ["host: outside any benchmark phase > train.step", pytest.approx(100e-9)],
    ]
    # the second step starting later: nothing covers more than half of [400, 500)
    program[3][1:3] = [460.0, 460.0]
    assert spans.named_gaps(t, program, 0)[2][0] == (
        f"host: outside any benchmark phase > {spans.NO_SPAN}")
    # the gap seconds and order are reduce's
    assert [g[1] for g in gaps] == [g[1] for g in trace.reduce(t)["idle_gaps"]]
    bare = spans.named_gaps(t, [], 0)
    assert [g[0] for g in bare][1] == "chipbench.save > no program span"


def test_idle_under_spans():
    t = {"host": [[trace.WINDOW, 0, 1000]],
         "devices": {"/device:TPU:0": [["a", 0, 200], ["b", 250, 50], ["c", 700, 300]]}}
    # idle: [200, 250), [300, 700)
    program = _prog(("train.step", 0, 690), ("train.wait", 180, 100),
                    ("train.wait", 400, 100), ("train.batch", 600, 20))
    assert spans.idle_in_s(t, program, 0, ("train.wait",)) == pytest.approx(150e-9)
    assert spans.idle_outside_s(t, program, 0) == pytest.approx(10e-9)  # [690, 700)
    assert spans.first_wait_idle_s(t, program, 0) == pytest.approx(50e-9)
    assert spans.first_wait_idle_s(t, [], 0) is None


def test_resume_compile_reads_the_jit_spans_of_the_training_thread():
    rec = lambda name, ts, dur, tid=1: {"name": name, "ts_us": ts, "dur_us": dur, "tid": tid}
    read = harness.load_module("metrics", "resume.compile_s").read
    one = [rec("train.step", 500, 400), rec("jit.trace", 510, 100), rec("jit.lower", 620, 50),
           rec("jit.compile", 680, 100), rec("jit.trace", 530, 20),  # nested in the first
           rec("jit.compile", 700, 1000, tid=2)]  # another thread
    two = [dict(r, ts_us=r["ts_us"] + 10_000) for r in one]
    ctx = types.SimpleNamespace(spans=one + two, resumes=[(0, 5000), (10_000, 15_000)])
    assert read(ctx) == pytest.approx(250e-6)
    ctx.spans = [r for r in ctx.spans if not r["name"].startswith("jit.")]
    assert read(ctx) is None  # a program that records no jit span


def test_gap_seconds_on_the_older_fixture_are_reduce_s():
    """On the benchmark's older recorded trace, which holds no program span,
    the gaps keep ``reduce``'s seconds and order and name no span."""
    fx = json.loads((FIXTURES / "trace-tpu-v5-lite.json").read_text())["trace"]
    want = trace.reduce(fx)["idle_gaps"]
    got = spans.named_gaps(fx, [], 0)
    assert [g[1] for g in got] == [g[1] for g in want]
    assert [g[0] for g in got] == [f"{w[0]} > {spans.NO_SPAN}" for w in want]


def test_readings_on_a_recorded_save():
    """A recorded stretch of the save cell around its save, program spans
    included: the interval arithmetic against idle seconds and gap labels
    read on a raster (``chipbench/attribute.py``), and every gap of 10 ms or
    more named after a program span or ``no program span``."""
    fx = json.loads((FIXTURES / "spans-tpu-v5-lite-save.json").read_text())
    t, prog, want = fx["trace"], fx["program"], fx["expected"]
    tol = want["edges"] * want["raster_ns"] * 1e-9
    assert sum(b - a for a, b in spans.idle(t)) / 1e9 == pytest.approx(want["idle_s"], abs=tol)
    assert spans.idle_in_s(t, prog, 0, ("train.wait",)) == pytest.approx(
        want["train.wait_idle_s"], abs=tol)
    assert spans.idle_outside_s(t, prog, 0) == pytest.approx(want["train.host_gap_s"], abs=tol)
    assert spans.first_wait_idle_s(t, prog, 0) == pytest.approx(
        want["first_wait_idle_s"], abs=tol)
    got = spans.named_gaps(t, prog, 0)
    long = [i for i, s in enumerate(want["gap_s"]) if s >= 0.01]
    assert len(long) == 4
    assert [got[i][0] for i in long] == [want["gap_labels"][i] for i in long]
    assert [got[i][1] for i in long] == pytest.approx([want["gap_s"][i] for i in long], abs=tol)
    assert [g[1] for g in got] == [g[1] for g in trace.reduce(t)["idle_gaps"]]


def test_attribute_keeps_the_program_spans_of_a_traced_run(tmp_path, capsys):
    """``attribute.py`` on the CPU at the tiny size: the traced run's profile
    holds the training thread's mirrored spans, the clock offset matches
    them to the tracer's records, and the stash is written."""
    import gzip

    from chipbench import attribute

    assert attribute.run("smollm-360m.save_async", [7], 3.0, rehearse=True,
                         out_dir=tmp_path) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = line["attribution"]
    assert line["result"]["correct"]
    assert got["clock_offset_pairs"] >= 4 * 4  # four step spans a step, several steps
    assert got["clock_offset_range_ns"] < 100e3
    with gzip.open(line["stash"], "rt") as f:
        stash = json.load(f)
    names = {n for n, *_ in stash["program"]}
    assert {"train.step", "train.batch", "train.dispatch", "train.wait"} <= names
    assert trace.compact is not None and trace.compact.__name__ == "compact"
