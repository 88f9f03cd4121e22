"""Elastic resume end-to-end: the paper's Fig. 1 scenario, plus the
beyond-paper hot tier.

Phases 1–2: a training job runs on 8 (simulated) chips as DP=4 × TP=2.
Two chips "fail"; the elastic planner proposes a 4-chip mesh, and the job
resumes from the last distributed checkpoint THROUGH UCP — different
mesh, different parallelism, same loss curve, same data order.  Each of
these phases is a separate launcher process (device counts are fixed at
jax init), exactly like a restarted job on a shrunken cluster.

Phase 3: the *hot* path — the process survives a peer-rank loss, so
recovery never needs the restart at all.  Training checkpoints into the
in-memory tier (peer-replicated snapshots every few steps), ranks "fail",
and `hot_recover` restores from the surviving replicas in memory:
HOT_DIRECT onto the same layout, HOT_RESHARD onto a different one — both
without reading a single checkpoint byte from disk, and bit-identical to
what the disk path would have produced.

::

    PYTHONPATH=src python examples/elastic_resume.py            # all phases
    PYTHONPATH=src python examples/elastic_resume.py --phase 1 \
        --trace /tmp/phase1-trace.json                          # obs smoke

``--phase`` runs one phase standalone (1 trains to a checkpoint and
needs nothing; 2 needs the phase-1 checkpoint, so standalone runs both
launches; 3 is fully in-process).  ``--trace`` forwards to the train
launcher, which exports its obs trace as Chrome trace-event JSON — this
is what CI's obs-smoke stage validates.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def launch(ndev: int, mesh: str, steps: int, ckpt: str,
           trace: str = "") -> list[dict]:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    cmd = [
        sys.executable, "-m", "repro.launch.train",
        "--arch", "smollm-360m", "--reduced",
        "--host-devices", str(ndev), "--mesh", mesh,
        "--steps", str(steps), "--batch", "8", "--seq", "32",
        "--ckpt-dir", ckpt, "--save-interval", "5", "--sync-save",
        "--log-json",
    ]
    if trace:
        cmd += ["--trace", trace]
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=900)
    if out.returncode != 0:
        sys.exit(out.stderr[-2000:])
    return [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]


def hot_tier_demo() -> None:
    """Phase 3: in-process rank loss, recovered from in-memory replicas."""
    import tempfile

    import jax
    import numpy as np

    from repro.configs import ParallelismConfig, get_config, reduced
    from repro.core.layout import MeshSpec
    from repro.ckpt.manager import CheckpointManager
    from repro.ckpt.policy import CheckpointPolicy
    from repro.dist.sharding import make_plan, vocab_multiple
    from repro.elastic.resume import ElasticEvent, hot_recover
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.train.optimizer import init_state

    cfg = reduced(get_config("smollm-360m"))
    parallel = ParallelismConfig()
    mesh = MeshSpec.from_dict({"data": 2, "model": 2})
    lm = build_model(cfg, vocab_multiple=vocab_multiple(parallel, mesh))
    plan = make_plan(cfg, lm.registry, parallel, mesh)
    state = init_state(lm.init(jax.random.PRNGKey(0)))
    jmesh = make_mesh((1, 1), ("data", "model"))

    with tempfile.TemporaryDirectory() as tmp:
        mgr = CheckpointManager(
            f"{tmp}/job", plan,
            policy=CheckpointPolicy(
                hot_interval=1, save_interval=4,  # hot every step, disk every 4th
                hot_replication=1, async_save=False,
            ),
        )
        for step in (1, 2, 3):  # three hot snapshots, nothing on disk yet
            mgr.save(state, step)
        mgr.wait()
        print(f"  hot ring: {[s.step for s in mgr.hot.snapshots()]}, "
              f"disk steps: {mgr.steps()} (drain due at step 4)")

        print("\n*** simulated failure: ranks {0, 3} lose their host memory ***")
        event = ElasticEvent(healthy_devices=2, reason="failure",
                             failed_ranks=(0, 3))
        restored, info = hot_recover(mgr, event, jmesh, verify=True)
        print(f"  recovered @ step {info.step} mode={info.mode.value} "
              f"({info.reason}) in {info.wall_time_s:.3f}s — zero disk reads")

        # reshard onto the shrunken 2-chip layout, still from memory
        mesh2 = MeshSpec.from_dict({"data": 2, "model": 1})
        lm2 = build_model(cfg, vocab_multiple=vocab_multiple(parallel, mesh2))
        plan2 = make_plan(cfg, lm2.registry, parallel, mesh2)
        restored2, info2 = hot_recover(mgr, event, jmesh, target_plan=plan2)
        print(f"  resharded @ step {info2.step} mode={info2.mode.value} "
              f"({info2.reason})")

        assert info.mode.value == "hot_direct" and info2.mode.value == "hot_reshard"
        for a, b in zip(jax.tree.leaves(restored.params),
                        jax.tree.leaves(state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("  restored state is bit-identical to the checkpointed state")
        mgr.close()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--phase", choices=("1", "2", "3", "all"), default="all",
                    help="run one phase standalone (default: all)")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="forward to the train launcher: export its obs "
                    "trace as Chrome trace-event JSON at PATH (phases 1/2; "
                    "phase 2 traces the resume launch)")
    args = ap.parse_args()

    if args.phase == "3":
        print("phase 3: hot-tier recovery — the process survives, so the "
              "surviving ranks' MEMORY is the checkpoint")
        hot_tier_demo()
        return

    with tempfile.TemporaryDirectory() as tmp:
        ckpt = f"{tmp}/job"
        print("phase 1: 8 chips, mesh data=4,model=2 — train to step 10")
        phase1_trace = args.trace if args.phase in ("1", "all") else ""
        for r in launch(8, "data=4,model=2", 10, ckpt, trace=phase1_trace):
            if r.get("event") == "step":
                print(f"  step {r['step']:3d} loss {r['loss']:.4f}")
        if args.phase == "1":
            if args.trace:
                print(f"  trace written to {args.trace}")
            return

        print("\n*** simulated failure: 4 chips lost — planner proposes a "
              "4-chip mesh (data=2,model=2) ***\n")
        sys.path.insert(0, os.path.join(REPO, "src"))
        from repro.configs import get_config, reduced
        from repro.elastic.planner import propose_mesh

        mesh = propose_mesh(reduced(get_config("smollm-360m")), 4, max_model=2)
        mesh_str = ",".join(f"{a}={s}" for a, s in mesh.axes)
        print(f"planner: {mesh_str}")

        print("\nphase 2: resume on 4 chips — UCP reconfigures the checkpoint")
        phase2_trace = args.trace if args.phase == "2" else ""
        for r in launch(4, mesh_str, 16, ckpt, trace=phase2_trace):
            if r.get("event") == "restored":
                print(f"  restored @ step {r['step']} mode={r['mode']} "
                      f"({r['reason']}) in {r['load_s']}s")
            elif r.get("event") == "step":
                print(f"  step {r['step']:3d} loss {r['loss']:.4f}")
        print("\ntraining continued seamlessly on the shrunken cluster.")

        if args.phase == "2":
            return
        print("\nphase 3: hot-tier recovery — the process survives, so the "
              "surviving ranks' MEMORY is the checkpoint")
        hot_tier_demo()


if __name__ == "__main__":
    main()
