"""The program's own spans beside the device's ops, on the profiler's clock.

While a ``repro.obs`` tracer is enabled, every span it records is also a
``jax.profiler`` host annotation of the same name on the same thread, so
the profile holds them beside the device's op events.  Spans that JAX
reports after the fact (``jit.trace``, ``jit.lower``, ``jit.compile``)
cannot be annotated; they are placed on the profiler's clock by an offset
read from the annotated spans themselves (:func:`clock_offset`).

All arithmetic works on plain lists, the form :func:`program_events`
reads and ``chipbench/fixtures/`` records:

* ``trace`` — :func:`chipbench.lib.trace.compact` (device op events and
  the benchmark's ``chipbench.*`` phases);
* program events — ``[name, start_ns, duration_ns, line]``: a span of the
  ``repro.obs`` catalog on host thread line ``line``.

Device idle time is the holes, inside the traced window, in the union of
the first device's op intervals: the gaps ``chipbench.lib.trace.reduce``
reports, whose seconds and order this module leaves as they are.
"""

from __future__ import annotations

import bisect
import glob
import statistics
from pathlib import Path

from chipbench.lib.trace import WINDOW, _union

JIT = ("jit.trace", "jit.lower", "jit.compile")
NO_SPAN = "no program span"
OUTSIDE = "host: outside any benchmark phase"


def catalog() -> set[str]:
    """Every span name ``repro.obs`` records (its catalog's span tables)."""
    from repro.obs import catalog as c

    return set(c.SPANS) | set(c.TIMED) | set(c.JIT_SPANS)


def program_events(profile_dir: str | Path) -> list[list]:
    """The ``repro.obs`` spans among a profile's host events; ``line``
    numbers the host threads in the order the profile lists them."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(profile_dir) / "**" / "*.xplane.pb"), recursive=True))
    names = catalog()
    out, line = [], 0
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name in names:
                    out.append([e.name, float(e.start_ns), float(e.duration_ns), line])
            line += 1
    return out


def training_line(program: list) -> int | None:
    """The host thread line that holds the ``train.step`` spans."""
    lines = {ln for n, _, _, ln in program if n == "train.step"}
    return min(lines) if lines else None


def training_records(records: list[dict]) -> list[dict]:
    """The obs span records of the thread that ran ``train.step``."""
    tids = {r["tid"] for r in records if r["name"] == "train.step"}
    return [r for r in records if r["tid"] in tids]


def clock_offset(program: list, records: list[dict], line: int,
                 tol_ns: float = 1e5) -> tuple[float, list[float]]:
    """(offset, per-pair offsets) in ns: profiler start − obs start of the
    same span, over the pairs matched by name and order on one thread.

    The profile covers a stretch of what the tracer recorded; the stretch's
    place among the records is the one under which most annotated spans
    find a record of their name within ``tol_ns``, and then the one whose
    pairs agree the most."""
    starts: dict[str, list[float]] = {}
    for r in records:
        starts.setdefault(r["name"], []).append(r["ts_us"] * 1e3)
    for v in starts.values():
        v.sort()
    evs = sorted((s, n) for n, s, _, ln in program if ln == line and n in starts)
    if not evs:
        return float("nan"), []

    def pairs(c: float) -> list[float]:
        out = []
        for s, n in evs:
            ts = starts[n]
            i = bisect.bisect_left(ts, s - c)
            near = [t for t in ts[max(i - 1, 0): i + 1] if abs(s - c - t) <= tol_ns]
            if near:
                out.append(s - min(near, key=lambda t: abs(s - c - t)))
        return out

    def fit(d: list[float]) -> tuple[int, float]:  # most pairs, then the tightest
        m = statistics.median(d) if d else 0.0
        return len(d), -sum(abs(x - m) for x in d)

    s0, n0 = evs[0]
    best = max((pairs(s0 - t) for t in starts[n0]), key=fit)
    return (statistics.median(best) if best else float("nan")), best


def placed(records: list[dict], offset_ns: float, line: int, names=JIT) -> list[list]:
    """Obs records named in ``names`` as program events on the profiler's
    clock (for spans that were never annotated)."""
    return [[r["name"], r["ts_us"] * 1e3 + offset_ns, r["dur_us"] * 1e3, line]
            for r in records if r["name"] in names]


def window(trace: dict) -> tuple[float, float] | None:
    wins = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW]
    return wins[0] if wins else None


def idle(trace: dict) -> list[tuple[float, float]]:
    """The first device's idle intervals inside the traced window."""
    w = window(trace)
    if w is None or not trace["devices"]:
        return []
    w0, w1 = w
    events = trace["devices"][sorted(trace["devices"])[0]]
    u = _union([(max(s, w0), min(s + d, w1)) for _, s, d in events
                if min(s + d, w1) > max(s, w0)])
    edges = [w0] + [x for ab in u for x in ab] + [w1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def overlap(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def spans_of(program: list, line: int, names=None) -> list[tuple[float, float]]:
    """The union of the line's spans (those named in ``names``, or all)."""
    return _union([(s, s + d) for n, s, d, ln in program
                   if ln == line and (names is None or n in names)])


def idle_in_s(trace: dict, program: list, line: int, names) -> float:
    """Device idle seconds while the line is inside a span named in ``names``."""
    return overlap(idle(trace), spans_of(program, line, names)) / 1e9


def idle_outside_s(trace: dict, program: list, line: int) -> float:
    """Device idle seconds while the line is inside no program span."""
    gaps = idle(trace)
    return (sum(b - a for a, b in gaps) - overlap(gaps, spans_of(program, line))) / 1e9


def first_wait_idle_s(trace: dict, program: list, line: int) -> float | None:
    """Device idle seconds inside the line's first ``train.wait``."""
    waits = sorted((s, s + d) for n, s, d, ln in program if ln == line and n == "train.wait")
    return overlap(idle(trace), waits[:1]) / 1e9 if waits else None


def label(a: float, b: float, phases: list, program: list, line: int) -> str:
    """``<benchmark phase> > <program span>``: the phase covering most of
    the gap ``[a, b)`` (as ``reduce`` names it), then the innermost span of
    the line that covers more than half of the gap, or ``no program span``."""
    phase, cover = OUTSIDE, 0.0
    for n, s, e in phases:
        c = min(b, e) - max(a, s)
        if c > cover:
            phase, cover = n, c
    most = [(d, n) for n, s, d, ln in program
            if ln == line and min(b, s + d) - max(a, s) > (b - a) / 2]
    return f"{phase} > {min(most)[1] if most else NO_SPAN}"


def named_gaps(trace: dict, program: list, line: int, top: int = 10) -> list[list]:
    """The ``top`` longest idle gaps, longest first, each ``[label, s]``."""
    phases = [(n, s, s + d) for n, s, d in trace["host"] if n != WINDOW]
    gaps = sorted(idle(trace), key=lambda g: g[0] - g[1])[:top]
    return [[label(a, b, phases, program, line), (b - a) / 1e9] for a, b in gaps]
