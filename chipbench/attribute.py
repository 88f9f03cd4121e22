#!/usr/bin/env python3
"""Name the device's idle time after the program's spans (on the chip).

::

    python3 chipbench/attribute.py --workload <cell> --seeds <n>[,<n>...] [--seconds 30]
    python3 chipbench/attribute.py --stash <file.json.gz> [--fixture <out.json> --cut <a>,<b>]

The first form makes the cell's traced run (``--trace 1``) once per seed,
as ``run.py`` makes it, and keeps what the traced stretch's profile holds
beside the benchmark's own phases: the ``repro.obs`` spans on the
profiler's clock, and the tracer's records (``chipbench.lib.spans``).  It
prints one JSON line per run: the run's result line under ``result``, and
under ``attribution`` the offset between the tracer's clock and the
profiler's with its spread over the matched pairs, the idle gaps named
``<benchmark phase> > <program span>``, and the device idle seconds inside
``train.wait``, outside every program span, and inside a resume's first
``train.wait``.  Each run's stash (compacted trace, program events,
records) is written to ``chiprun_out/attribute/<cell>-<seed>.json.gz``.
``--rehearse`` makes the runs on the CPU at ``rehearse.py``'s tiny size
(no device ops: the plumbing only).

The second form reads a stash back without the chip; with ``--fixture`` it
writes the stretch ``[a, b)`` seconds into the traced window as a test
fixture, with the expected idle seconds read on a raster.
"""

from __future__ import annotations

import argparse
import gzip
import json
import shutil
import statistics
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

RASTER_NS = 50  # the fixture's expected values are read on this raster
MERGE_NS = 1000  # device-op holes shorter than this are closed in a fixture


def attribution(stash: dict) -> dict:
    from chipbench.lib import spans

    trace, records = stash["trace"], stash["records"]
    program = list(stash["program"])
    line = spans.training_line(program)
    if line is None:
        return {"error": "no train.step span in the profile"}
    mine = spans.training_records(records)
    offset, diffs = spans.clock_offset(program, mine, line)
    program += spans.placed(mine, offset, line)
    q = statistics.quantiles(diffs, n=4) if len(diffs) > 1 else [offset] * 3
    return {
        "clock_offset_ns": offset,
        "clock_offset_pairs": len(diffs),
        "clock_offset_iqr_ns": q[2] - q[0],
        "clock_offset_range_ns": (max(diffs) - min(diffs)) if diffs else None,
        "idle_s": sum(b - a for a, b in spans.idle(trace)) / 1e9,
        "idle_gaps": spans.named_gaps(trace, program, line),
        "train.wait_idle_s": spans.idle_in_s(trace, program, line, ("train.wait",)),
        "train.host_gap_s": spans.idle_outside_s(trace, program, line),
        "first_wait_idle_s": spans.first_wait_idle_s(trace, program, line),
        "jit_s_in_stretch": spans.overlap([spans.window(trace)],
                                           spans.spans_of(program, line, spans.JIT)) / 1e9,
    }


def run(workload: str, seeds: list[int], seconds: float, rehearse: bool,
        out_dir: Path = ROOT / "chiprun_out" / "attribute") -> int:
    import repro.obs as obs
    from chipbench.lib import harness, spans, trace

    out_dir.mkdir(parents=True, exist_ok=True)
    kw = {}
    if rehearse:
        from chipbench.rehearse import tiny

        kw = {"overrides": tiny, "require_tpu": False, "declared": False}
    kept: list[tuple[dict, Path, object]] = []
    compact = trace.compact

    def keeping(profile_dir):
        # The driver removes the profile after the run: keep a copy of it,
        # and the tracer whose records it is matched with.
        got = compact(profile_dir)
        copy = Path(tempfile.mkdtemp(prefix="chipbench-attribute-"))
        shutil.copytree(profile_dir, copy, dirs_exist_ok=True)
        kept.append((got, copy, obs.active()))
        return got

    rc = 0
    trace.compact = keeping
    try:
        for seed in seeds:
            kept.clear()
            args = harness.parse(["--workload", workload, "--seed", str(seed),
                                  "--seconds", str(seconds), "--trace", "1"])
            code, result = harness.execute(args, **kw)
            if code != 0 or not kept:
                print(json.dumps({"workload": workload, "seed": seed, "exit": code,
                                  "result": result}), flush=True)
                rc = rc or code or 1
                continue
            got, copy, tracer = kept[0]
            stash = {"workload": workload, "seed": seed, "trace": got,
                     "program": spans.program_events(copy),
                     "records": tracer.span_records() if tracer is not None else []}
            shutil.rmtree(copy, ignore_errors=True)
            path = out_dir / f"{workload}-{seed}.json.gz"
            with gzip.open(path, "wt") as f:
                json.dump(stash, f)
            print(json.dumps({"workload": workload, "seed": seed, "result": result,
                              "attribution": attribution(stash), "stash": str(path)}),
                  flush=True)
    finally:
        trace.compact = compact
    return rc


def raster_expected(trace: dict, program: list, line: int) -> dict:
    """The fixture's idle seconds and gap labels read on a ``RASTER_NS``
    raster, apart from the interval arithmetic the tests check."""
    import numpy as np

    from chipbench.lib.spans import NO_SPAN, OUTSIDE
    from chipbench.lib.trace import WINDOW

    (w0, w1), = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW]
    cell = lambda t: int(round((min(max(t, w0), w1) - w0) / RASTER_NS))
    n = cell(w1)

    def mask(intervals):
        m = np.zeros(n, bool)
        for s, d in intervals:
            m[cell(s):cell(s + d)] = True
        return m

    ops = trace["devices"][sorted(trace["devices"])[0]]
    idle = ~mask([(s, d) for _, s, d in ops])
    mine = [(nm, s, d) for nm, s, d, ln in program if ln == line]
    waits = sorted((s, d) for nm, s, d in mine if nm == "train.wait")
    sec = RASTER_NS / 1e9
    # idle runs, longest first, each named by the cells its phase and spans cover
    edges = np.flatnonzero(np.diff(np.concatenate([[0], idle.astype(np.int8), [0]])))
    runs = sorted(zip(edges[::2], edges[1::2]), key=lambda r: r[0] - r[1])[:10]
    phases = [(nm, s, d) for nm, s, d in trace["host"] if nm != WINDOW]
    labels = []
    for a, b in runs:
        cover = lambda s, d: min(b, cell(s + d)) - max(a, cell(s))
        best = max(phases, key=lambda p: cover(p[1], p[2]), default=None)
        phase = best[0] if best and cover(best[1], best[2]) > 0 else OUTSIDE
        most = [(d, nm) for nm, s, d in mine if 2 * cover(s, d) > b - a]
        labels.append(f"{phase} > {min(most)[1] if most else NO_SPAN}")
    return {
        "raster_ns": RASTER_NS,
        "edges": 2 * (len(ops) + len(mine)),
        "idle_s": float(idle.sum() * sec),
        "train.wait_idle_s": float((idle & mask(waits)).sum() * sec),
        "train.host_gap_s": float((idle & ~mask([(s, d) for _, s, d in mine])).sum() * sec),
        "first_wait_idle_s": float((idle & mask(waits[:1])).sum() * sec),
        "gap_s": [float((b - a) * sec) for a, b in runs],
        "gap_labels": labels,
    }


def fixture(stash: dict, out: Path, a_s: float, b_s: float) -> None:
    """Write ``[a_s, b_s)`` seconds into the traced window of ``stash`` as a
    fixture: device ops merged into busy intervals, the benchmark phases,
    the training line's program spans (``jit.*`` placed), the expected
    readings."""
    from chipbench.lib import spans
    from chipbench.lib.trace import WINDOW, _union

    trace, program = stash["trace"], list(stash["program"])
    line = spans.training_line(program)
    mine = spans.training_records(stash["records"])
    offset, _ = spans.clock_offset(program, mine, line)
    program += spans.placed(mine, offset, line)
    w0, _ = spans.window(trace)
    c0, c1 = w0 + a_s * 1e9, w0 + b_s * 1e9
    dev = sorted(trace["devices"])[0]
    merged: list[list[float]] = []
    for s, e in _union([(max(s, c0), min(s + d, c1)) for _, s, d in trace["devices"][dev]
                        if min(s + d, c1) > max(s, c0)]):
        if merged and s - merged[-1][1] < MERGE_NS:
            merged[-1][1] = e
        else:
            merged.append([s, e])
    cut = {
        "devices": {dev: [["busy", s, e - s] for s, e in merged]},
        "host": [[WINDOW, c0, c1 - c0]] + [
            [n, max(s, c0), min(s + d, c1) - max(s, c0)] for n, s, d in trace["host"]
            if n != WINDOW and min(s + d, c1) > max(s, c0)],
    }
    prog = [[n, s, d, 0] for n, s, d, ln in program
            if ln == line and min(s + d, c1) > max(s, c0)]
    doc = {
        "about": (f"{b_s - a_s:.2f} s of the traced stretch of {stash['workload']} on one TPU v5 "
                  f"lite (--trace 1, seed {stash['seed']}), cut to [{a_s}, {b_s}) s of it: the "
                  "first device's ops as busy intervals (holes under "
                  f"{MERGE_NS} ns closed), the benchmark phases, and the training thread's "
                  "repro.obs spans on the profiler's clock (jit.* placed by the clock offset) "
                  "as line 0; expected readings taken on a raster (chipbench/attribute.py)"),
        "trace": cut, "program": prog, "line": 0,
        "expected": raster_expected(cut, prog, 0),
    }
    out.write_text(json.dumps(doc, separators=(",", ":")) + "\n")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload")
    p.add_argument("--seeds", default="")
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--stash")
    p.add_argument("--fixture")
    p.add_argument("--cut", default="")
    a = p.parse_args(argv)
    if a.stash:
        with gzip.open(a.stash, "rt") as f:
            stash = json.load(f)
        print(json.dumps(attribution(stash)))
        if a.fixture:
            lo, hi = (float(x) for x in a.cut.split(","))
            fixture(stash, Path(a.fixture), lo, hi)
        return 0
    if a.rehearse:
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return run(a.workload, [int(x) for x in a.seeds.split(",") if x], a.seconds, a.rehearse)


if __name__ == "__main__":
    sys.exit(main())
