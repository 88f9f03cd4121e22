"""From a ``jax.profiler`` trace to device busy time, idle share and the
breakdown, and from ``repro.obs`` span records to self times.

A profile is first cut down to a small plain dict (:func:`compact`): the
device planes' op events, and the host annotations whose names start with
``chipbench.`` (the benchmark's own phases, on the profiler's clock).  All
arithmetic works on that dict, so it is checked on a recorded fixture
(``chipbench/fixtures/``) without a chip.

The traced window is the host annotation ``chipbench.traced``.  Busy time
is, per device, the union of the op intervals clipped to that window,
averaged over the devices; idle gaps are the holes in that union on the
first device, each named by the benchmark phase that covers most of it.
The per-op breakdown counts only ops that hold no other op (a ``while``
and the ops of its body share the device's op line).
"""

from __future__ import annotations

import glob
from pathlib import Path

WINDOW = "chipbench.traced"
OPS_LINE = "XLA Ops"  # the device plane's line of executed HLO ops


def compact(profile_dir: str | Path) -> dict:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(profile_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(files[-1])
    out = {"devices": {}, "host": [], "lines": {}}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            out["lines"][plane.name] = sorted(lines)
            ops = lines.get(OPS_LINE)
            if ops is not None:
                out["devices"][plane.name] = [
                    [op_name(e.name), float(e.start_ns), float(e.duration_ns)]
                    for e in ops.events
                ]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("chipbench."):
                        out["host"].append([e.name, float(e.start_ns), float(e.duration_ns)])
    return out


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _leaves(events: list) -> list:
    """The ops that hold no other op: a ``while`` and its body's ops share
    the line, and only the body's ops are counted per op."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    parent = [False] * len(order)
    stack: list[int] = []
    for i, (_, s, d) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(order, parent) if not p]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def reduce(trace: dict) -> dict | None:
    """``busy_s``, ``window_s``, ``idle_share`` and the breakdown, or None
    when the trace holds no window or no device op."""
    wins = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW]
    if not wins or not trace["devices"]:
        return None
    w0, w1 = wins[0]
    busy, per_op, gaps = [], {}, []
    for k, dev in enumerate(sorted(trace["devices"])):
        events = trace["devices"][dev]
        u = _union([(max(s, w0), min(s + d, w1)) for _, s, d in events
                    if min(s + d, w1) > max(s, w0)])
        for name, s, d in _leaves(events):
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                per_op[name] = per_op.get(name, 0.0) + (b - a)
        busy.append(sum(b - a for a, b in u))
        if k == 0:
            edges = [w0] + [x for ab in u for x in ab] + [w1]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    if not busy or max(busy) <= 0:
        return None
    phases = [(n, s, s + d) for n, s, d in trace["host"] if n != WINDOW]

    def label(a, b):
        best, cover = "host: outside any benchmark phase", 0.0
        for n, s, e in phases:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = n, c
        return best

    window_s = (w1 - w0) / 1e9
    busy_s = sum(busy) / len(busy) / 1e9
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": [[n, t / 1e9 / len(busy)] for n, t in top_ops],
        "idle_gaps": [[label(a, b), (b - a) / 1e9] for a, b in top_gaps],
    }


def self_time_s(records: list[dict], name: str, t0_us: float | None = None,
                t1_us: float | None = None) -> list[float]:
    """Self time of each ``name`` span starting in ``[t0_us, t1_us)``: its
    duration less the part of it that its child spans cover."""
    kids: dict[int, list[tuple[float, float]]] = {}
    for r in records:
        if r["parent_id"] is not None:
            kids.setdefault(r["parent_id"], []).append((r["ts_us"], r["ts_us"] + r["dur_us"]))
    out = []
    for r in records:
        if r["name"] != name:
            continue
        if t0_us is not None and r["ts_us"] < t0_us:
            continue
        if t1_us is not None and r["ts_us"] >= t1_us:
            continue
        a, b = r["ts_us"], r["ts_us"] + r["dur_us"]
        clipped = [(max(x, a), min(y, b)) for x, y in kids.get(r["span_id"], [])
                   if min(y, b) > max(x, a)]
        covered = sum(y - x for x, y in _union(clipped))
        out.append((r["dur_us"] - covered) / 1e6)
    return out
