"""Whole runs of each cell on the CPU at a tiny size, past the harness's
look for a chip: sound, they come out correct; with the timed path broken
underneath — or with the control switched on — ``correct`` comes out false.

Faults a training cell can have: a step that returns its state unchanged;
half of the batch left out, the mean over the rest; an answer altered
where it is produced (the saved state, through the program's own lossy
shard codec — also the control of every checkpoint comparison).  A resume
cell's answer is the state it reads back, which the codec alters, and the
step it then takes: the step faults are planted on the resumed side only,
so that set-up's step, which the resume is held to, stays sound."""

import pytest

from chipbench.rehearse import rehearse

CODEC = {"codec": "int8:b256"}


@pytest.mark.parametrize("cell", ["smollm-360m.save_async", "smollm-360m.resume_direct"])
def test_sound_run_is_correct(cell):
    res = rehearse(cell, seconds=1.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("cell,variant", [
    ("smollm-360m.save_async", {"step": "unchanged"}),
    ("smollm-360m.save_async", {"step": "half_batch"}),
    ("smollm-360m.save_async", CODEC),
    ("smollm-360m.resume_direct", CODEC),
    ("smollm-360m.resume_direct", {"step": "unchanged", "side": "resume"}),
    ("smollm-360m.resume_direct", {"step": "half_batch", "side": "resume"}),
])
def test_broken_run_is_not_correct(cell, variant):
    res = rehearse(cell, seconds=1.0, variant=variant)
    assert not res["correct"], res["checks"]


def test_traced_run_reports_its_layers():
    res = rehearse("smollm-360m.save_async", seconds=1.0, trace=1)
    assert {"train.step_s", "save.stage_s", "save.write_s", "save.fsync_share"} <= set(res["cpu_readings"])
    res = rehearse("smollm-360m.resume_direct", seconds=1.0, trace=1)
    assert {"restore.read_s", "restore.place_s", "resume.first_step_s"} <= set(res["cpu_readings"])
