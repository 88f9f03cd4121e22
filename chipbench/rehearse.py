#!/usr/bin/env python3
"""Rehearse a cell on the CPU at a tiny size, without a chip.

::

    python3 chipbench/rehearse.py --workload <cell> [--seconds 3] [--seed 7]
        [--trace 0|1] [--variant '{"codec": "int8:b256"}']

Runs the cell's own driver end to end — set-up, window, the comparison
that decides ``correct`` — on four virtual CPU devices, with the model cut
to two layers of tiny widths and the batch to a few short rows.  What it
prints is a rehearsal: every number comes from the CPU, so none is a
device metric; the line carries them under ``cpu_readings``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

TINY_MODEL = {
    "dense": {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab_size": 256},
    "ssm": {"num_layers": 2, "d_model": 64, "vocab_size": 256,
            "ssm": {"d_state": 16, "d_conv": 4, "expand": 2, "head_dim": 16,
                    "n_groups": 1, "chunk": 16}},
}
TINY_TRAFFIC = {"batch": 4, "seq": 32, "save_interval": 4, "window_start_step": 3}


def tiny(workload: dict, config: dict) -> None:
    config["model"].update(TINY_MODEL[config["model"]["family"]])
    for k, v in TINY_TRAFFIC.items():
        if k in workload:
            workload[k] = v


def rehearse(workload: str, *, seed: int = 7, seconds: float = 3.0, trace: int = 0,
             variant: dict | None = None, overrides=tiny) -> dict:
    """One run of ``workload`` on the CPU; returns its result dict."""
    from chipbench.lib.harness import execute, parse

    args = parse(["--workload", workload, "--seed", str(seed),
                  "--seconds", str(seconds), "--trace", str(trace)])
    code, result = execute(args, variant=variant, overrides=overrides, require_tpu=False,
                           declared=False)
    if code != 0 or result is None:
        raise RuntimeError(f"rehearsal of {workload} exited {code}")
    result["cpu_readings"] = result.pop("metrics")
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--seconds", type=float, default=3.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--variant", default="{}")
    a = p.parse_args(argv)
    result = rehearse(a.workload, seed=a.seed, seconds=a.seconds, trace=a.trace,
                      variant=json.loads(a.variant))
    print(json.dumps({"rehearsal": True, **result}), flush=True)
    return 0


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4").strip()
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    sys.exit(main())
