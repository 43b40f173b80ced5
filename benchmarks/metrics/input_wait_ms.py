"""Layer: input pipeline. Host time the ``fit`` loop spent blocked on its
next batch — the program's ``pipeline/next_batch`` section (``OpProfiler``;
``data/pipeline.timed_iter``, which ``ComputationGraph.fit`` and
``SameDiff.fit`` both feed through) — total over the window / steps. For
``SameDiff.fit`` the section holds the batch's binding to device arrays too.
A program path without that section gives nothing to read."""

SECTION = "pipeline/next_batch"


def _total():
    from deeplearning4j_tpu.common.profiler import OpProfiler

    return OpProfiler.get().get_statistics().get(SECTION, {}).get("total_s")


def start(ctx):
    ctx["input_wait_s_before"] = _total() or 0.0


def stop(ctx):
    ctx["input_wait_s_after"] = _total()


def read(ctx):
    total = ctx["input_wait_s_after"]
    if total is None or not ctx["steps"]:
        return None
    return (total - ctx["input_wait_s_before"]) / ctx["steps"] * 1e3
