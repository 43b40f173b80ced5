"""Layer: entry points. How often ``fit`` itself blocks on a value from the
device: executions of the program's ``fit/sync`` section in the window /
steps (``SameDiff.fit`` reads the epoch's loss once an epoch;
``ComputationGraph.fit`` never does on its default path, so it reads 0). A
count of the program's own, which repeats exactly, so a rehearsal reads it
too. A program that has no ``fit/`` sections at all gives nothing to read."""

SECTION = "fit/sync"
ANY = "fit/enter"       # every fit call of a program with the sections


def _count():
    from deeplearning4j_tpu.common.profiler import OpProfiler

    stats = OpProfiler.get().get_statistics()
    if ANY not in stats:
        return None
    return stats.get(SECTION, {}).get("count", 0)


def start(ctx):
    ctx["host_syncs_before"] = _count() or 0


def stop(ctx):
    ctx["host_syncs_after"] = _count()


def read(ctx):
    count = ctx["host_syncs_after"]
    if count is None or not ctx["steps"]:
        return None
    return (count - ctx["host_syncs_before"]) / ctx["steps"]
