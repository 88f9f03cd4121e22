#!/usr/bin/env bash
# Fast-fail CI for the repo (run by .github/workflows/ci.yml).
#
# Stage 1 — import smoke: import every module under src/repro.  A missing
# module (the failure mode that once broke the whole suite at collection)
# fails here in seconds instead of deep inside pytest.
# Stage 2 — the test suite.  The full suite exceeds 2 minutes, so the
# default lane for iteration is `--fast`: it deselects tests marked `slow`
# (multi-second subprocess/e2e/property tests).  The tier-1 gate
# (ROADMAP.md) remains the FULL suite — run ci.sh without --fast before
# shipping (the main/nightly CI lane does).
# Stage 3 — obs smoke: run elastic-resume phase 1 with --trace and
# validate the exported Chrome trace-event file (schema, event/containment
# invariants, non-trivial span count) via repro.obs.validate_chrome_trace,
# so a broken exporter or an instrumentation path that stops emitting
# fails the PR lane, not the next person opening Perfetto.
#
# Stage 4 — chaos smoke (opt-in, --chaos-smoke): three fixed seeds through
# the deterministic fault-injection harness (scripts/chaos_sweep.py), so a
# regression in the recovery ladder fails the PR lane in seconds; the
# nightly lane runs the full bounded sweep separately.
#
# Stage 0 — lint (opt-in, --lint): the project-invariant static analyzer
# (repro.analysis — lock/clock/decode/catalog/except discipline plus the
# PR 5/7 regression pins, DESIGN.md §11).  Pure stdlib, imports no model
# code, runs in under a second — so it goes first and a broken invariant
# fails before anything heavyweight starts.
#
# Usage: scripts/ci.sh [--fast] [--lint] [--chaos-smoke] [extra pytest args...]
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

stage="setup"
smoke_trace=""
cleanup() {
    if [[ -n "$smoke_trace" ]]; then rm -f "$smoke_trace"; fi
}
on_err() { echo "ci.sh: FAILED during stage: $stage" >&2; }
trap cleanup EXIT
trap on_err ERR

PYTEST_ARGS=()
chaos_smoke=0
lint=0
while [[ $# -gt 0 ]]; do
    case "$1" in
        --fast) PYTEST_ARGS+=(-m "not slow"); shift ;;
        --chaos-smoke) chaos_smoke=1; shift ;;
        --lint) lint=1; shift ;;
        *) break ;;
    esac
done

stage="tracked-bytecode-guard"
# Committed .pyc files churn on every run and bloat diffs; they were purged
# once (git rm -r --cached) and must never come back.
if git ls-files | grep -E '(^|/)__pycache__/|\.py[cod]$' >/dev/null; then
    echo "ci.sh: tracked __pycache__/.pyc entries found:" >&2
    git ls-files | grep -E '(^|/)__pycache__/|\.py[cod]$' >&2
    exit 1
fi

if [[ "$lint" == 1 ]]; then
    stage="lint"
    python -m repro.analysis src/repro
fi

stage="import-smoke"
python - <<'PY'
import importlib
import pkgutil
import sys

import repro

mods = ["repro"]
for m in pkgutil.walk_packages(repro.__path__, prefix="repro."):
    mods.append(m.name)

# Subsystem packages the walk must have discovered — a packaging mistake
# (missing __init__.py, renamed dir) would otherwise shrink the walk
# silently and the smoke would "pass" while covering less.
for required in ("repro.core", "repro.ckpt", "repro.hot", "repro.serve"):
    assert required in mods, f"import-smoke: {required} not discovered"

failed = []
for name in sorted(mods):
    try:
        importlib.import_module(name)
    except Exception as e:  # noqa: BLE001 - report every import failure
        failed.append(name)
        print(f"IMPORT FAIL {name}: {type(e).__name__}: {e}")
print(f"import-smoke: {len(mods) - len(failed)}/{len(mods)} modules importable")
if failed:
    sys.exit(1)
PY

stage="pytest"
python -m pytest -x -q "${PYTEST_ARGS[@]}" "$@"

stage="obs-smoke"
smoke_trace="$(mktemp /tmp/obs_smoke.XXXXXX.json)"
python examples/elastic_resume.py --phase 1 --trace "$smoke_trace" >/dev/null
python - "$smoke_trace" <<'PY'
import json
import sys

from repro.obs import validate_chrome_trace

doc = json.load(open(sys.argv[1]))
assert doc.get("otherData", {}).get("schema") == "repro-trace/v1", doc.get("otherData")
n = validate_chrome_trace(doc)
# phase 1 does 10 train steps and 2 sync saves; a healthy trace has far
# more than a handful of events — a near-empty one means instrumentation
# silently stopped emitting.
assert n >= 50, f"obs-smoke: only {n} trace events (instrumentation broken?)"
names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
for required in ("train.step", "ckpt.save", "save.fsync", "ckpt.commit"):
    assert required in names, f"obs-smoke: no {required} spans in {sorted(names)}"
print(f"obs-smoke: {n} trace events ok")
PY

if [[ "$chaos_smoke" == 1 ]]; then
    stage="chaos-smoke"
    python scripts/chaos_sweep.py --seed-list 0,1,2 --events 8
fi

stage="done"
