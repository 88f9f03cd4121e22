"""The SSM family's plain reference (``chipbench/references/ssm.py``)
against the program's Mamba-2 block, at the rehearsal's tiny SSM size on
the CPU, through the cell ``mamba2-130m.save_async``'s own driver and
limits.

The program runs at float32 compute here.  At the tiny widths bfloat16
rounding alone reads gaps of the size of the full-size limits (a few
tokens and channels average little of it away), so the equations are
compared without it: a sound program then reads gaps near 1e-6, far
inside every limit, and a program with one term of the block missing
reads gaps far outside them.  The full-size, bfloat16 readings that set
the limits are taken on the chip (``chipbench/calibrate.py``).
"""

import functools

import pytest

from chipbench.drivers import save_async as D
from chipbench.lib import compare, model
from chipbench.lib.harness import Run, parse, resolve
from chipbench.lib.reference import Reference
from chipbench.rehearse import rehearse, tiny

CELL = "mamba2-130m.save_async"
TRAINING = ("loss_gap", "grad_gap", "change_gap")


@pytest.fixture
def float32_program(monkeypatch):
    import jax.numpy as jnp

    import repro.train.trainer as trainer
    from repro.models import build_model

    monkeypatch.setattr(trainer, "build_model",
                        functools.partial(build_model, compute_dtype=jnp.float32))


@pytest.fixture
def without_d_skip(monkeypatch):
    """The planted fault: the SSD's ``D · x`` term dropped in the program."""
    import jax.numpy as jnp

    from repro.models.lm import LM

    mamba = LM._mamba

    def dropped(self, p, x, **kw):
        return mamba(self, {**p, "d_skip": jnp.zeros_like(p["d_skip"])}, x, **kw)

    monkeypatch.setattr(LM, "_mamba", dropped)


def _gaps(seed: int = 3) -> tuple[dict, dict]:
    """The program's set-up steps against the float32 reference."""
    _, c, wl, raw = resolve(CELL, declared=False)
    tiny(wl, raw)
    r = Run(parse(["--workload", CELL, "--seed", str(seed), "--seconds", "0"]), c, wl, raw)
    trainer = r.trainer(wl["mesh"], None, None)
    feed = r.feed()
    trainer.batch = feed
    n = wl["setup_steps"]
    prog, _ = D.first_steps(r, trainer, seed, n)
    ref = D.reference(r, model.param_shapes(trainer), feed, n)
    return compare.training_gaps(prog, ref), wl["limits"]


@pytest.mark.parametrize("seed", [3, 4100000007])
def test_program_equals_the_reference(float32_program, seed):
    gaps, limits = _gaps(seed)
    for k in TRAINING:
        assert gaps[k] <= limits[k] / 20, (k, gaps)


def test_dropped_d_term_fails_a_limit(float32_program, without_d_skip):
    gaps, limits = _gaps()
    over = {k: gaps[k] for k in TRAINING if not gaps[k] <= limits[k]}
    assert over, (gaps, limits)


def test_int8_control_fails_a_limit():
    _, c, wl, raw = resolve(CELL, declared=False)
    tiny(wl, raw)
    r = Run(parse(["--workload", CELL, "--seed", "3", "--seconds", "0"]), c, wl, raw)
    shapes = model.param_shapes(r.trainer(wl["mesh"], None, None))
    feed = r.feed()
    n = wl["setup_steps"]
    ref = D.reference(r, shapes, feed, n)
    control = D.reference(r, shapes, feed, n, Reference(raw, "int8"))
    gaps = compare.training_gaps(control, ref)
    assert {k: gaps[k] for k in TRAINING if gaps[k] > wl["limits"][k]}, (gaps, wl["limits"])


@pytest.mark.parametrize("fault", [None, "without_d_skip"])
def test_whole_run(float32_program, request, fault):
    """The cell end to end (set-up, window with a save, the checkpoint read
    back, the reference): correct when sound, not with the fault."""
    if fault:
        request.getfixturevalue(fault)
    res = rehearse(CELL, seconds=1.0)
    assert res["correct"] == (fault is None), res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
