"""Layer: entry points. What one ``fit`` call costs at its two ends, on the
host's clock: the program's ``fit/enter`` section (resume cursor, updater
state, step build; in ``SameDiff.fit`` every variable from the host to the
device) plus its ``fit/exit`` section (``SameDiff.fit``: every variable back
to the host, leaf by leaf; ``ComputationGraph.fit`` has none), total over the
window / ``fit`` calls. A program without the sections gives nothing to
read."""

SECTIONS = ("fit/enter", "fit/exit")


def _total():
    from deeplearning4j_tpu.common.profiler import OpProfiler

    stats = OpProfiler.get().get_statistics()
    if SECTIONS[0] not in stats:
        return None
    return sum(stats.get(s, {}).get("total_s", 0.0) for s in SECTIONS)


def start(ctx):
    ctx["call_overhead_s_before"] = _total() or 0.0


def stop(ctx):
    ctx["call_overhead_s_after"] = _total()


def read(ctx):
    total = ctx["call_overhead_s_after"]
    if total is None or not ctx["fit_calls"]:
        return None
    return (total - ctx["call_overhead_s_before"]) / ctx["fit_calls"] * 1e3
