"""CheckpointPolicy: the consolidated checkpointing-knob object.

Covers validation in ``__post_init__``, the codec-tag shorthand, and
``policy=`` as the one spelling on both ``CheckpointManager`` and
``Trainer.create``: a loose checkpoint keyword, an old knob name or a typo,
is Python's own ``TypeError``.
"""

import pytest

from repro.ckpt import CheckpointManager, CheckpointPolicy
from repro.configs import ParallelismConfig, TrainConfig, get_config, reduced
from repro.core.codec import CodecPolicy
from repro.core.layout import MeshSpec
from repro.dist.sharding import make_plan, vocab_multiple
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.train.trainer import Trainer


@pytest.fixture(scope="module")
def plan():
    cfg = reduced(get_config("smollm-360m"))
    mesh = MeshSpec.from_dict({"data": 1, "model": 1})
    parallel = ParallelismConfig()
    lm = build_model(cfg, vocab_multiple=vocab_multiple(parallel, mesh))
    return make_plan(cfg, lm.registry, parallel, mesh)


# ---------------------------------------------------------------- validation
def test_defaults_validate():
    p = CheckpointPolicy()
    assert p.save_mode == "dedup"
    assert p.codec is None
    assert p.effective_disk_interval == p.save_interval


@pytest.mark.parametrize(
    "kw",
    [
        {"save_mode": "sometimes"},
        {"keep_last": 0},
        {"save_interval": 0},
        {"full_interval": 0},
        {"hot_interval": 0},
        {"disk_interval": 0},
        {"max_pending_saves": 0},
        {"hot_replication": -1},
    ],
)
def test_bad_values_raise(kw):
    with pytest.raises(ValueError):
        CheckpointPolicy(**kw)


def test_effective_disk_interval_override():
    p = CheckpointPolicy(save_interval=5, disk_interval=20, hot_interval=5)
    assert p.effective_disk_interval == 20


# -------------------------------------------------------------- codec field
def test_codec_tag_shorthand_codes_moments_only():
    p = CheckpointPolicy(codec="int8:b128")
    assert isinstance(p.codec, CodecPolicy)
    assert p.codec.params == "raw"
    assert p.codec.exp_avg == "int8:b128"
    assert p.codec.exp_avg_sq == "int8:b128"


def test_codec_policy_passthrough_and_all_raw_normalizes_to_none():
    cp = CodecPolicy(exp_avg="fp8:e4m3:b256")
    assert CheckpointPolicy(codec=cp).codec is cp
    assert CheckpointPolicy(codec=CodecPolicy()).codec is None
    assert CheckpointPolicy(codec="raw").codec is None


def test_codec_wrong_type_raises():
    with pytest.raises(TypeError):
        CheckpointPolicy(codec=42)


def test_lossy_params_require_opt_in():
    with pytest.raises(ValueError):
        CheckpointPolicy(codec=CodecPolicy(params="int8:b256"))
    p = CheckpointPolicy(
        codec=CodecPolicy(params="int8:b256", allow_lossy_params=True)
    )
    assert p.codec.params == "int8:b256"


# ----------------------------------------------------- manager integration
def test_manager_accepts_policy(tmp_path, plan):
    pol = CheckpointPolicy(
        keep_last=2, save_mode="delta", full_interval=4, codec="int8:b256",
        async_save=False,
    )
    mgr = CheckpointManager(tmp_path / "ck", plan, policy=pol)
    try:
        assert mgr.policy is pol
        assert mgr.keep_last == 2
        assert mgr.save_mode == "delta"
        assert mgr.full_interval == 4
        assert isinstance(mgr.codec, CodecPolicy)
        assert mgr._async is None
    finally:
        mgr.close()


@pytest.mark.parametrize(
    "make,name",
    [
        (CheckpointManager, "kep_last"),
        (CheckpointManager, "keep_last"),
        (Trainer.create, "keep_last"),
    ],
    ids=["manager-typo", "manager-old-knob", "trainer-old-knob"],
)
def test_manager_rejects_unknown_kwarg(tmp_path, plan, make, name):
    if make is CheckpointManager:
        args, kw = (tmp_path / "ck", plan), {}
    else:
        args = (
            reduced(get_config("smollm-360m")), ParallelismConfig(),
            TrainConfig(total_steps=10), make_mesh((1, 1), ("data", "model")),
        )
        kw = dict(batch_size=2, seq_len=16, ckpt_dir=str(tmp_path / "ck"))
    with pytest.raises(TypeError, match=f"unexpected keyword argument '{name}'"):
        make(*args, **kw, **{name: 2})


def test_manager_default_policy(tmp_path, plan):
    mgr = CheckpointManager(tmp_path / "ck", plan)
    try:
        assert mgr.policy == CheckpointPolicy()
    finally:
        mgr.close()


# ----------------------------------------------------- trainer integration
def test_trainer_accepts_policy(tmp_path):
    cfg = reduced(get_config("smollm-360m"))
    tcfg = TrainConfig(total_steps=10)
    jmesh = make_mesh((1, 1), ("data", "model"))
    pol = CheckpointPolicy(save_interval=4, save_mode="delta", async_save=False)
    tr = Trainer.create(
        cfg, ParallelismConfig(), tcfg, jmesh,
        batch_size=2, seq_len=16, ckpt_dir=str(tmp_path / "a"), policy=pol,
    )
    assert tr.manager.policy is pol
    assert tr.manager.save_interval == 4
    tr.manager.close()
