"""``restore.place_s``: per resume in the window, the self time of its
``restore.materialize`` spans (``make_array_from_callback`` placement),
averaged over resumes."""

from chipbench.lib.harness import load_module


def read(ctx):
    return load_module("metrics", "restore.read_s")._per_resume(ctx, "restore.materialize")
