"""System-level behaviour: the full UCP life-cycle in one process, plus the
elastic-capacity planner and serve path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Multi-second subprocess/e2e tests: excluded from `scripts/ci.sh --fast`.
pytestmark = pytest.mark.slow

from repro.configs import ParallelismConfig, TrainConfig, get_config, reduced
from repro.core.layout import MeshSpec
from repro.core.plan import ResumeMode
from repro.ckpt.manager import CheckpointManager
from repro.ckpt.policy import CheckpointPolicy
from repro.dist.sharding import make_plan, vocab_multiple
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.models import decode as D
from repro.train.trainer import Trainer


def _mk_trainer(tmp, **parallel_kw):
    cfg = reduced(get_config("smollm-360m"))
    jmesh = make_mesh((1, 1), ("data", "model"))
    parallel = ParallelismConfig(**parallel_kw)
    tcfg = TrainConfig(warmup_steps=2, total_steps=50)
    return Trainer.create(
        cfg, parallel, tcfg, jmesh, batch_size=4, seq_len=24,
        ckpt_dir=str(tmp / "ck"),
        policy=CheckpointPolicy(save_interval=4, async_save=False),
    )


def test_train_checkpoint_resume_same_layout(tmp_path):
    t = _mk_trainer(tmp_path)
    state, info = t.init_or_restore()
    assert info is None
    state, hist = t.run(state, 0, 8)
    assert len(hist) == 8
    # fresh trainer object == crashed-and-restarted process
    t2 = _mk_trainer(tmp_path)
    state2, info2 = t2.init_or_restore()
    assert info2 is not None and info2.mode == ResumeMode.DIRECT and info2.step == 8
    state2, hist2 = t2.run(state2, 8, 2)
    assert hist2[0]["step"] == 9


def test_resume_under_new_zero_stage_matches_losses(tmp_path):
    t = _mk_trainer(tmp_path)
    state, _ = t.init_or_restore()
    state, hist_a = t.run(state, 0, 8)  # saves at 4 and 8

    # continue WITHOUT reconfig to get reference losses for steps 9..10
    state, ref = t.run(state, 8, 2)

    # new trainer with different ZeRO staging resumes from step 8 by
    # streaming the checkpoint straight into the new layout
    t2 = _mk_trainer(tmp_path, zero=1, fsdp=False)
    state2, info2 = t2.init_or_restore()
    assert info2 is not None and info2.mode == ResumeMode.RESHARD_STREAM
    state2, hist_b = t2.run(state2, 8, 2)
    for r, b in zip(ref, hist_b):
        assert abs(r["loss"] - b["loss"]) < 2e-2


def test_elastic_planner_proposes_valid_meshes():
    from repro.elastic.planner import propose_mesh

    cfg = get_config("gemma3-27b")
    # full pod healthy
    m = propose_mesh(cfg, 256)
    assert m.size <= 256 and m.axis_size("model") >= 1
    # 16 chips died → planner finds the biggest usable sub-mesh
    m2 = propose_mesh(cfg, 240)
    assert m2.size <= 240
    assert {a for a, _ in m2.axes} == {"data", "model"}
    # memory feasibility: bytes per chip under the HBM budget
    from repro.elastic.planner import state_bytes_per_chip

    assert state_bytes_per_chip(cfg, m2) < 16e9


def test_serve_batched_decode(tmp_path):
    cfg = reduced(get_config("gemma3-12b"))
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    b = 4
    cache = D.init_cache(lm, b, 64)
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, 4), 0, cfg.vocab_size)
    logits, cache = D.prefill(lm, params, cache, toks)
    outs = []
    step = jax.jit(lambda p, c, t: D.decode_step(lm, p, c, t))
    cur = jnp.argmax(logits, -1)[:, None]
    for _ in range(8):
        lg, cache = step(params, cache, cur)
        cur = jnp.argmax(lg[:, -1], -1)[:, None]
        outs.append(cur)
    seq = jnp.concatenate(outs, 1)
    assert seq.shape == (b, 8)
    assert int(cache["pos"][0]) == 12
