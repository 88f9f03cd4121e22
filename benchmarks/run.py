"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and (with ``--json``) writes the
same rows as machine-readable JSON so the perf trajectory records across
PRs.  Run as::

    PYTHONPATH=src python -m benchmarks.run [--only save_cost,...] \
        [--sizes small,medium] [--json BENCH_checkpointing.json] \
        [--trace trace.json]

``--trace`` records the whole run under an obs tracer (memory-only while
the benches run — the file census in bench_checkpointing counts every
byte under its roots, so nothing may stream to disk mid-bench), exports
the Chrome trace to PATH at the end, and attaches per-family derived
columns to the JSON rows: the fraction of shard-write worker time spent
in fsync and the engine handle-cache hit rate.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def _obs_derived(tracer, counters_before, nspans_before) -> dict:
    """fsync fraction + cache hit rate over one bench family's slice of
    the trace (records appended since the family started).

    The fsync fraction divides by summed per-shard worker time, not the
    parent save's wall time: shard writes overlap on the pool, so summed
    child durations can exceed the parent span and only the same clock
    domain (``save.shard``/``drain.shard``, where the fsync children
    live) yields a true fraction."""
    spans = tracer.span_records()[nspans_before:]
    shard_us = sum(
        r["dur_us"] for r in spans if r["name"] in ("save.shard", "drain.shard")
    )
    fsync_us = sum(r["dur_us"] for r in spans if r["name"] == "save.fsync")
    after = tracer.counters()
    delta = lambda k: after.get(k, 0) - counters_before.get(k, 0)
    hits, misses = delta("engine.handle.hit"), delta("engine.handle.miss")
    out = {}
    if shard_us:
        out["fsync_fraction"] = round(fsync_us / shard_us, 4)
    if hits + misses:
        out["cache_hit_rate"] = round(hits / (hits + misses), 4)
    return out


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default="", help="comma-separated bench names")
    p.add_argument(
        "--sizes", default="",
        help="comma-separated model sizes (small,medium,large) for the "
        "benches that take a size ladder; empty = each bench's default",
    )
    p.add_argument(
        "--json", default="", metavar="PATH",
        help="also write rows as JSON: "
        '[{"bench","name","us_per_call","derived"}, ...]',
    )
    p.add_argument(
        "--trace", default="", metavar="PATH",
        help="record an obs trace of the run; export as Chrome trace-event "
        "JSON at PATH and attach derived obs columns to --json rows",
    )
    args = p.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    tracer = None
    if args.trace:
        import repro.obs as obs

        tracer = obs.enable()

    from . import bench_checkpointing as B
    from . import bench_fanout as F

    benches = {
        "save_cost": B.bench_save_cost,               # paper Fig. 11
        "transform_load": B.bench_transform_load,     # paper Fig. 12
        "hot_tier": B.bench_hot_tier,                 # beyond-paper hot tier
        "delta": B.bench_delta,                       # beyond-paper delta saves
        "codec": B.bench_codec,                       # beyond-paper shard codec
        "fanout": F.bench_fanout,                     # beyond-paper serving fan-out
        "conversion_scaling": B.bench_conversion_scaling,  # §3.2 Table 2
        "correctness": B.bench_correctness,           # Fig. 6/7, Table 3
        "codec_equiv": B.bench_codec_equiv,           # nightly loss-curve gate
    }
    # accept sizes=...
    sized = {"save_cost", "transform_load", "hot_tier", "delta", "codec", "fanout"}
    sizes = tuple(s for s in args.sizes.split(",") if s)
    only = {s for s in args.only.split(",") if s}
    print("name,us_per_call,derived")
    records: list[dict] = []
    failed = False
    for name, fn in benches.items():
        if only and name not in only:
            continue
        if tracer is not None:
            counters_before = tracer.counters()
            nspans_before = len(tracer.span_records())
        family: list[dict] = []
        try:
            rows = fn(sizes=sizes) if sizes and name in sized else fn()
            for row, us, derived in rows:
                print(f"{row},{us:.0f},{derived}", flush=True)
                family.append(
                    {"bench": name, "name": row, "us_per_call": us,
                     "derived": derived}
                )
        except Exception:
            failed = True
            traceback.print_exc()
            print(f"{name},NaN,ERROR", flush=True)
            family.append(
                {"bench": name, "name": name, "us_per_call": None,
                 "derived": "ERROR"}
            )
        if tracer is not None and family:
            extra = _obs_derived(tracer, counters_before, nspans_before)
            if extra:
                for rec in family:
                    rec["obs"] = extra
        records.extend(family)
    if tracer is not None:
        import repro.obs as obs

        obs.disable(tracer)
        obs.write_chrome_trace(args.trace, tracer)
        print(f"trace: {len(tracer.span_records())} spans -> {args.trace}",
              file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(
                {"schema": "repro-bench/v1", "recorded_at": time.time(),
                 "rows": records},
                f, indent=1,
            )
            f.write("\n")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
