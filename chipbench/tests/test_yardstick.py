"""The benchmark's fixed parts: the declaration, the lookups by name, the
FLOP count, the peaks table and the reduction from a recorded TPU trace."""

import json
import re

import pytest

from chipbench.lib import harness, model, trace
from chipbench.lib.peaks import peaks

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def test_every_name_resolves_to_its_files():
    for cell in SPEC["workloads"]:
        spec, c, wl, raw = harness.resolve(cell["name"])
        assert (harness.BENCH / "drivers" / f"{wl['driver']}.py").is_file()
        assert raw["name"] == cell["config"]
    for m in SPEC["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])


def test_flop_count_matches_the_program_parameters():
    from repro.configs import get_config
    from repro.models import build_model

    cfgs = [model.model_config(json.loads((harness.ROOT / c["file"]).read_text()))
            for c in SPEC["configs"]]
    for cfg in cfgs + [get_config("mamba2-130m")]:  # the SSM family's count too
        assert model._n_params(cfg) == build_model(cfg).registry.num_params()


def test_step_time_leaves_out_the_step_a_save_stalls():
    import types

    # Trainer.run closes the span of step s, then saves step s; the stall
    # shows in the span of step s + 1.
    spans = [{"name": "train.step", "ts_us": 10 * s, "dur_us": 1e6, "attrs": {"step": s}}
             for s in range(48, 54)]
    spans[3]["dur_us"] = 3e6  # step 51, after the save at 50
    ctx = types.SimpleNamespace(spans=spans, window_us=(0, 1e9), save_steps={50})
    assert harness.load_module("metrics", "train.step_s").read(ctx) == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks("cpu")


def test_trace_reduction_on_a_recorded_trace():
    fixture = json.loads((harness.BENCH / "fixtures" / "trace-tpu-v5-lite.json").read_text())
    got = trace.reduce(fixture["trace"])
    want = fixture["expected"]
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
    assert got["busy_s"] == pytest.approx(want["busy_s"], abs=1e-6)  # the raster's 10 ns steps
    assert got["idle_share"] == pytest.approx(1 - got["busy_s"] / got["window_s"])
    assert got["device_ops"][0][0] == want["top_op"]
    assert got["idle_gaps"][0][1] == pytest.approx(want["longest_gap_s"])
    assert len(got["idle_gaps"]) <= 10 and len(got["device_ops"]) <= 10
    # leaf ops only: the per-op sum never exceeds the busy time
    assert sum(t for _, t in got["device_ops"]) <= got["busy_s"] * (1 + 1e-9)


def test_self_time_leaves_out_children():
    rec = lambda i, p, name, ts, dur: {"span_id": i, "parent_id": p, "name": name,
                                       "ts_us": ts, "dur_us": dur}
    spans = [rec(1, None, "restore.prefetch", 0, 100), rec(2, 1, "x", 10, 30),
             rec(3, 1, "y", 20, 30), rec(4, None, "restore.prefetch", 200, 50)]
    assert trace.self_time_s(spans, "restore.prefetch") == [60e-6, 50e-6]
    assert trace.self_time_s(spans, "restore.prefetch", 150, 300) == [50e-6]


def test_union_idle_and_gap_labels():
    t = {"host": [[trace.WINDOW, 0, 100], ["chipbench.save", 40, 30]],
         "devices": {"/device:TPU:0": [["a", 0, 40], ["b", 30, 10], ["c", 80, 20]]}}
    got = trace.reduce(t)
    assert got["busy_s"] == pytest.approx(60e-9)
    assert got["idle_gaps"] == [["chipbench.save", pytest.approx(40e-9)]]


def test_fsync_share_over_summed_worker_time():
    import types

    rec = lambda name, dur: {"name": name, "dur_us": dur}
    ctx = types.SimpleNamespace(save_steps={50}, spans=[
        rec("save.shard", 100), rec("save.fsync", 30), rec("save.shard", 300),
        rec("save.fsync", 10), rec("save.async_job", 250)])
    assert harness.load_module("metrics", "save.fsync_share").read(ctx) == pytest.approx(10.0)
    ctx.spans = []
    assert harness.load_module("metrics", "save.fsync_share").read(ctx) is None
