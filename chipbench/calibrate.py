#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (run on the chip).

::

    python3 chipbench/calibrate.py --workload <cell> --seeds 11,12,... \\
        [--control-seeds 21,22,23] [--seconds 8]

Training cells (``save_async``), in one process, for every seed in
``--seeds``: the program's set-up steps through the cell's own trainer at
the cell's own sizes, and three readings against the float32 reference —
the program's (the lower reading), the control's (the reference computed
in int8 put in the program's place) and the planted half-batch fault's
(the reference over half of the rows).  A state left unchanged reads 1 by
construction and needs no run.

Every cell, for each seed in ``--control-seeds``: a whole run of the cell
(``--seconds`` long) with the program's own lower-precision save path on
(the shard codec ``int8:b256`` on the optimizer moments), which the
checkpoint comparisons must fail.

One JSON line per reading on standard output, also appended to
``chiprun_out/calibrate-<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

CONTROL_CODEC = "int8:b256"


def emit(out: Path, rec: dict) -> None:
    line = json.dumps(rec, default=str)
    print(line, flush=True)
    with out.open("a") as f:
        f.write(line + "\n")


def training_readings(workload: str, seeds: list[int], out: Path) -> None:
    import jax

    from chipbench.drivers import save_async as D
    from chipbench.lib import compare, model
    from chipbench.lib.harness import Run, parse, resolve
    from chipbench.lib.reference import Reference

    _, cell, wl, raw = resolve(workload, declared=False)
    args = parse(["--workload", workload, "--seed", str(seeds[0]), "--seconds", "0"])
    r = Run(args, cell, wl, raw)
    try:
        trainer = r.trainer(wl["mesh"], None, None)
        shapes = model.param_shapes(trainer)
        refs = {m: Reference(raw, m) for m in ("f32", "int8")}
        n = wl["setup_steps"]
        for seed in seeds:
            t0 = time.perf_counter()
            r.seed = seed
            feed = r.feed()
            trainer.batch = feed
            prog, state = D.first_steps(r, trainer, seed, n)
            del state
            ref = D.reference(r, shapes, feed, n, refs["f32"])
            t_ref = time.perf_counter()
            ctrl = D.reference(r, shapes, feed, n, refs["int8"])
            half = D.reference(r, shapes, feed, n, refs["f32"], half=True)
            for what, got in (("program", prog), ("control_int8", ctrl), ("fault_half_batch", half)):
                g = compare.training_gaps(got, ref)
                emit(out, {"workload": workload, "seed": seed, "reading": what,
                           **{k: g[k] for k in ("loss_gap", "grad_gap", "change_gap",
                                                "grad_gap_leaf", "change_gap_leaf")},
                           "still_leaves": len(g["still_leaves"]),
                           "losses": got["losses"], "reference_losses": ref["losses"]})
            emit(out, {"workload": workload, "seed": seed, "reading": "timing",
                       "reference_s": time.perf_counter() - t_ref,
                       "seed_s": time.perf_counter() - t0, "device": jax.devices()[0].device_kind})
    finally:
        r.cleanup()


def whole_runs(workload: str, seeds: list[int], seconds: float, variant: dict,
               reading: str, out: Path) -> None:
    """A whole run of the cell per seed, with ``variant`` planted: its
    checks, beside their limits, are the reading."""
    from chipbench.lib.harness import execute, parse

    for seed in seeds:
        args = parse(["--workload", workload, "--seed", str(seed), "--seconds", str(seconds)])
        code, result = execute(args, variant=variant)
        emit(out, {"workload": workload, "seed": seed, "reading": reading, "exit": code,
                   "correct": result and result["correct"],
                   "checks": result and result["checks"]})


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=8.0)
    a = p.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    from chipbench.lib.harness import ROOT, enable_cache, resolve

    out = ROOT / "chiprun_out" / f"calibrate-{a.workload}.jsonl"
    out.parent.mkdir(exist_ok=True)
    enable_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU; readings are taken on the chip", file=sys.stderr)
        return 2
    _, _, wl, _ = resolve(a.workload, declared=False)
    if ints(a.seeds) and wl["driver"] == "save_async":
        training_readings(a.workload, ints(a.seeds), out)
    if ints(a.control_seeds):
        whole_runs(a.workload, ints(a.control_seeds), a.seconds, {"codec": CONTROL_CODEC},
                   f"control_codec_{CONTROL_CODEC}", out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
