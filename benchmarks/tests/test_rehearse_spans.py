"""The metrics that read the program's sections (``fit/*``, ``build/*``,
``pipeline/next_batch``), at the rehearsal's sizes on the CPU. Run by hand
with the other harness tests (``python3 -m pytest benchmarks/tests -q``); not
tier-1. A rehearsal prints no time, so of the five only the count shows in
its line; the readers of the times are driven directly."""

from __future__ import annotations

import pytest

from test_harness import _manifest, _run     # same directory; sets sys.path

import run                                   # noqa: E402  (benchmarks/run.py)

NEW = {"input_wait_ms": "examples_per_s", "call_overhead_ms": "examples_per_s",
       "host_syncs_per_step": "examples_per_s", "setup_build_s": "setup_s",
       "setup_compile_s": "setup_s"}


def test_the_new_entries_are_the_last_five_and_have_their_files():
    last = _manifest()["per_layer"][-5:]
    assert {m["name"]: m["moves"] for m in last} == NEW
    for m in last:
        assert callable(run.load_module("metrics", m["name"]).read)


@pytest.mark.parametrize("cell,epochs_between_syncs", [
    ("resnet50.train", None), ("bert_base.finetune", 1)])
def test_rehearsal_prints_host_syncs_per_step(cell, epochs_between_syncs):
    p, result = _run(["--workload", cell, "--seed", "2147483999",
                      "--seconds", "1", "--trace", "1", "--rehearse"])
    assert p.returncode == 0, p.stderr[-2000:]
    # of the new metrics a rehearsal reads the counter alone
    assert set(result["metrics"]) & set(NEW) == {"host_syncs_per_step"}
    got = result["metrics"]["host_syncs_per_step"]
    assert got["unit"] == "count"
    if epochs_between_syncs is None:    # ComputationGraph.fit never syncs
        assert got["value"] == 0
    else:                               # SameDiff.fit: once an epoch, exactly
        _, _, mix = run.load_cell(_manifest(), cell, rehearse=True)
        assert got["value"] == pytest.approx(1.0 / mix["batches"], abs=0)


def test_readers_on_a_program_without_the_sections_return_nothing():
    """What the parent commit gives: no ``fit/`` or ``build/`` section, so
    each reader of one leaves its metric out, and none raises."""
    from deeplearning4j_tpu.common.profiler import OpProfiler

    prof = OpProfiler.get()
    prof.reset()
    with prof.time_section("pipeline/dispatch"):
        pass
    ctx = {"steps": 4, "fit_calls": 1}
    for name in ("input_wait_ms", "call_overhead_ms", "host_syncs_per_step",
                 "setup_build_s"):
        reader = run.load_module("metrics", name)
        reader.start(ctx)
        if hasattr(reader, "stop"):
            reader.stop(ctx)
        assert reader.read(ctx) is None, name


def test_readers_divide_the_window_s_share_by_steps_and_calls():
    from deeplearning4j_tpu.common.profiler import OpProfiler

    prof = OpProfiler.get()
    prof.reset()

    def emit(section, n):
        for _ in range(n):
            with prof.time_section(section):
                pass

    emit("build/init", 1)
    emit("fit/enter", 1)        # before the window: not counted
    emit("fit/sync", 3)
    readers = {n: run.load_module("metrics", n) for n in NEW}
    ctx = {"steps": 8, "fit_calls": 2,
           "meter": type("M", (), {"snapshot": lambda self: {
               "compile_s": 1.5}})()}
    for r in readers.values():
        r.start(ctx)
    emit("fit/enter", 2)
    emit("fit/exit", 2)
    emit("fit/sync", 2)
    emit("pipeline/next_batch", 10)
    emit("build/init", 1)       # inside the window: not set-up
    for r in readers.values():
        if hasattr(r, "stop"):
            r.stop(ctx)
    stats = prof.get_statistics()
    assert readers["host_syncs_per_step"].read(ctx) == 2 / 8
    assert readers["setup_compile_s"].read(ctx) == 1.5
    assert 0 < readers["setup_build_s"].read(ctx) < stats["build/init"]["total_s"]
    assert readers["input_wait_ms"].read(ctx) == pytest.approx(
        stats["pipeline/next_batch"]["total_s"] / 8 * 1e3)
    assert 0 < readers["call_overhead_ms"].read(ctx) < (
        stats["fit/enter"]["total_s"] + stats["fit/exit"]["total_s"]) / 2 * 1e3
