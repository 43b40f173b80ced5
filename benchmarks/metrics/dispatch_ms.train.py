"""Layer: entry points. Host time inside the program's ``pipeline/dispatch``
section (``OpProfiler``; ``nn/graph.py:_dispatch_one``), total over the
window / steps. A program path without that section gives nothing to read."""

SECTION = "pipeline/dispatch"


def _total():
    from deeplearning4j_tpu.common.profiler import OpProfiler

    return OpProfiler.get().get_statistics().get(SECTION, {}).get("total_s")


def start(ctx):
    ctx["dispatch_s_before"] = _total() or 0.0


def stop(ctx):
    ctx["dispatch_s_after"] = _total()


def read(ctx):
    total = ctx["dispatch_s_after"]
    if total is None or not ctx["steps"]:
        return None
    return (total - ctx["dispatch_s_before"]) / ctx["steps"] * 1e3
