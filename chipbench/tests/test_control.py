"""The control of each training cell — the plain reference computed in
int8, the precision below the configuration's bfloat16 compute, put in the
program's place — fails at least one of the cell's limits (here at a tiny
size on the CPU; on the chip at the cell's own size through
``chipbench/calibrate.py``)."""

import pytest

from chipbench.drivers import save_async as D
from chipbench.lib import compare, model
from chipbench.lib.harness import Run, parse, resolve
from chipbench.lib.reference import Reference
from chipbench.rehearse import tiny


@pytest.mark.parametrize("cell", ["smollm-360m.save_async"])
def test_int8_control_fails_a_limit(cell):
    _, c, wl, raw = resolve(cell, declared=False)
    tiny(wl, raw)
    r = Run(parse(["--workload", cell, "--seed", "3", "--seconds", "0"]), c, wl, raw)
    trainer = r.trainer(wl["mesh"], None, None)
    feed = r.feed()
    shapes = model.param_shapes(trainer)
    n = wl["setup_steps"]
    ref = D.reference(r, shapes, feed, n)
    control = D.reference(r, shapes, feed, n, Reference(raw, "int8"))
    gaps = compare.training_gaps(control, ref)
    over = {k: gaps[k] for k in wl["limits"] if gaps[k] > wl["limits"][k]}
    assert over, (gaps, wl["limits"])
