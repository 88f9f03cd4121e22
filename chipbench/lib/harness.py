"""The harness: one cell, one run, one result line.

Everything a cell is made of is found by name:

* ``BENCHMARK.json`` (checkout root) declares the cell, its metrics and
  which cells report each;
* ``chipbench/workloads/<cell>.json`` names the traffic kind (``driver``),
  the configuration, the mesh, the batch and the traffic's parameters, and
  the limits of the comparison that decides ``correct``;
* ``chipbench/configs/<config>.json`` is the model configuration as run;
* ``chipbench/drivers/<driver>.py`` runs set-up, window and check, and
  returns a :class:`Outcome`;
* ``chipbench/metrics/<metric>.py`` reads one per-layer metric from the
  traced run's context (``read(ctx) -> float | None``).

A later cell, traffic mix, driver or metric is a new file; no file here
changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import shutil
import signal
import sys
import tempfile
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> Any:
    return json.loads(Path(path).read_text())


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def log(phase: str, **rec) -> None:
    print(json.dumps({"phase": phase, **rec}, default=str), flush=True)


def dir_bytes(root: Path) -> int:
    return sum(p.stat().st_size for p in Path(root).rglob("*") if p.is_file())


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back: end-to-end values by metric name, the
    counts, and the context the per-layer readers read."""

    e2e: dict[str, float]
    attempted: int
    failed: int
    ctx: Any = None
    trace: dict | None = None  # lib.trace.reduce() of the traced window


class Run:
    """One run of one cell: its arguments, files, scratch space and checks.

    ``variant`` (never set by ``run.py``) plants a control or a fault for
    ``calibrate.py`` and the CPU tests: ``codec`` saves under a lossy shard
    codec, ``step`` = ``"unchanged"`` returns the state unchanged from every
    step, ``"half_batch"`` feeds the step half of its rows; ``side``, where
    given, confines the step fault to the trainers a driver builds for that
    side (``"resume"``: the resumed ones, not the one that saved)."""

    def __init__(self, args, cell: dict, workload: dict, config: dict,
                 variant: dict | None = None):
        self.seed, self.seconds, self.traced = args.seed, args.seconds, bool(args.trace)
        self.cell, self.wl, self.raw = cell, workload, config
        self.variant = dict(variant or {})
        self.checks: list[Check] = []
        self.memory_peak = 0
        self._scratch: list[Path] = []

    # ----------------------------------------------------------- scratch
    def scratch(self, what: str) -> Path:
        """A fresh directory under ``TMPDIR``, removed when the run ends."""
        p = Path(tempfile.mkdtemp(prefix=f"chipbench-{what}-"))
        self._scratch.append(p)
        return p

    def cleanup(self) -> None:
        for p in self._scratch:
            shutil.rmtree(p, ignore_errors=True)
        self._scratch.clear()

    # ------------------------------------------------------------ checks
    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append(Check(name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)

    def phase(self, name: str):
        """A host annotation in the profiler's trace (traced runs only)."""
        import contextlib

        import jax

        return jax.profiler.TraceAnnotation(name) if self.traced else contextlib.nullcontext()

    # ----------------------------------------------------------- devices
    def read_memory_peak(self, devices) -> None:
        """The fullest chip's peak: its buffers' peak plus the peak that its
        programs reserved for their temporaries, which the TPU runtime
        counts apart (``peak_bytes_reserved``), not in ``peak_bytes_in_use``."""
        stats = [d.memory_stats() or {} for d in devices]
        peaks = [m.get("peak_bytes_in_use", 0) + m.get("peak_bytes_reserved", 0) for m in stats]
        self.memory_peak = max([self.memory_peak, *peaks])

    # ----------------------------------------------------------- builders
    def model_config(self):
        from .model import model_config

        return model_config(self.raw)

    def trainer(self, mesh: str, ckpt_dir: str | None, policy):
        from repro.launch.mesh import make_mesh_from_string
        from repro.train.trainer import Trainer

        from .model import parallel_config, train_config

        return Trainer.create(
            self.model_config(), parallel_config(self.raw),
            train_config(self.raw, self.seed), make_mesh_from_string(mesh),
            batch_size=self.wl["batch"], seq_len=self.wl["seq"],
            ckpt_dir=ckpt_dir, policy=policy,
        )

    def _faulty(self, side: str) -> str | None:
        v = self.variant
        return v.get("step") if v.get("side", side) == side else None

    def feed(self, side: str = "setup"):
        from .model import Feed

        return Feed(self.seed, self.raw["model"]["vocab_size"], self.wl["batch"],
                    self.wl["seq"], half=self._faulty(side) == "half_batch")

    def plant(self, trainer, side: str = "setup") -> None:
        """Install the variant's step fault, if any, under the trainer."""
        if self._faulty(side) != "unchanged":
            return
        import jax
        import jax.numpy as jnp

        step = trainer.step_fn

        def unchanged(state, batch):
            _, metrics = step(jax.tree.map(jnp.copy, state), batch)
            return state, metrics

        trainer.step_fn = unchanged


def parse(argv):
    import argparse

    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def resolve(name: str, declared: bool = True) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, its cell entry, the workload file, the config file).

    ``declared=False`` (rehearsals only) also takes a workload file that
    ``BENCHMARK.json`` does not declare, as a cell of its own."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    wl = load_json(BENCH / "workloads" / f"{name}.json")
    if name not in cells:
        if declared:
            raise KeyError(f"no cell {name!r} in BENCHMARK.json; known: {sorted(cells)}")
        cells[name] = {"name": name, "config": wl["config"], "chips": wl["chips"]}
    cell = cells[name]
    raw = load_json(BENCH / "configs" / f"{cell['config']}.json")
    if wl["config"] != cell["config"] or wl["chips"] != cell["chips"]:
        raise ValueError(f"{name}: workload file disagrees with BENCHMARK.json")
    return spec, cell, wl, raw


def enable_cache() -> None:
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    # Every program this benchmark compiles, down to the small ones, is
    # found again by the cell's next run.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _sigterm(*_):
    raise SystemExit(143)


def execute(args, *, variant: dict | None = None, overrides=None,
            require_tpu: bool = True, declared: bool = True) -> tuple[int, dict | None]:
    """Run one cell; return (exit code, result dict or None).

    ``overrides`` (rehearsals and tests only) edits the workload and the
    configuration before the run: ``overrides(workload, config)``."""
    spec, cell, wl, raw = resolve(args.workload, declared)
    if overrides is not None:
        overrides(wl, raw)
    enable_cache()
    import jax

    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            print(f"chipbench: no TPU (JAX platform {devices[0].platform!r}); "
                  "nothing measured", file=sys.stderr)
            return 2, None
        if len(devices) < cell["chips"]:
            print(f"chipbench: {args.workload} needs {cell['chips']} chips, "
                  f"found {len(devices)}", file=sys.stderr)
            return 2, None
    from .peaks import peaks

    kind = devices[0].device_kind
    peak = peaks(kind) if require_tpu else None

    r = Run(args, cell, wl, raw, variant)
    r.peak = peak
    r.devices = devices[: cell["chips"]]
    old = signal.signal(signal.SIGTERM, _sigterm)
    try:
        out: Outcome = load_module("drivers", wl["driver"]).run(r)
    finally:
        r.cleanup()
        signal.signal(signal.SIGTERM, old)

    metrics: dict[str, dict] = {}
    if not r.traced:
        for m in spec["end_to_end"]:
            if applies(m, cell["name"]):
                metrics[m["name"]] = {"value": out.e2e[m["name"]], "unit": m["unit"]}
    else:
        for m in spec["per_layer"]:
            if applies(m, cell["name"]):
                v = load_module("metrics", m["name"]).read(out.ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {
        "platform": devices[0].platform, "kind": kind, "count": len(devices),
        "memory_peak_bytes": int(r.memory_peak),
    }
    result: dict[str, Any] = {
        "correct": r.correct, "attempted": out.attempted, "failed": out.failed,
        "metrics": metrics, "device": device,
    }
    if r.traced and out.trace is not None:
        device["busy_s"] = out.trace["busy_s"]
        device["window_s"] = out.trace["window_s"]
        result["breakdown"] = {"device_ops": out.trace["device_ops"],
                               "idle_gaps": out.trace["idle_gaps"]}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in r.checks}
    for c in r.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    return 0, result


def main(argv=None) -> int:
    args = parse(argv)
    code, result = execute(args)
    if result is not None:
        print(json.dumps(result), flush=True)
    return code
