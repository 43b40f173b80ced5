"""Layer: compiled step. Device time a step, self time, of the step's phase
``update``: every device op whose name stack (the trace's ``tf_op``) begins
``jit(step)/update`` — gradient normalisation, the updater, the stochastic
rounding of bf16 state (``sr``) and the constraints, AS FAR AS THEY ARE OPS OF
THEIR OWN. XLA books a fusion to its dot or convolution, so a leaf's update
that rides its weight-gradient product is in ``backward`` (``PERF.md``
section 3, step 0 (d)). What is left here is ``sr`` and the leaves the
compiler did not fuse: the cells listed are the three whose bf16 state is
rounded, where ``sr`` is most of the number.

This file also makes the reading that the other ``scope_*`` metrics share.
``run.py`` deletes its trace before any ``read`` and hands metrics a
reduction without the ops' stats, so ``stop`` puts ONE one-epoch ``fit``
call (the warm-up's own, so nothing compiles), fenced, under the program's
``OpProfiler.trace``, has the program read its own trace
(``OpProfiler.scope_times``: ``common/xprof.py``), joins the model's
``scope_kinds()`` and keeps the table in ``ctx["scope_table"]``. It is
``stop`` and not ``start`` because the ``scope_*`` entries are the last of
``per_layer``: the window has closed, every other reader has taken its last
reading of the program's sections and counters, and the benchmark's traced
call comes next — so the steps read here are the epoch before the ones that
``step_device_ms`` reads. The call adds rows to the routed layers'
``expert_load``, so the reading that ``moe_gmm_roofline_share`` took "before
the traced call" is taken again, by that file's own ``stop``. A program from
before these scopes has no such reader: nothing runs, the table stays ``None``
and every ``scope_*`` metric reads nothing. The sibling files take ``stop``
and ``total`` from here, and the profile runs once."""

import importlib.util
import os
import shutil
import tempfile
import time


def _sibling(name):
    spec = importlib.util.spec_from_file_location(
        "bench_metrics_" + name.replace(".", "_"),
        os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def stop(ctx):
    if "scope_table" in ctx:        # a sibling's stop came first
        return
    ctx["scope_table"] = None
    from deeplearning4j_tpu.common.profiler import OpProfiler

    prof = OpProfiler.get()
    if not hasattr(prof, "scope_times"):
        return
    t0 = time.perf_counter()
    job = ctx["job"]
    logdir = tempfile.mkdtemp(prefix="bench_scopes_")
    try:
        with prof.trace(logdir):
            job.fit(ctx["data"], epochs=1)
            job.fence()
        table = prof.scope_times(step_program=ctx["conf"].STEP_PROGRAM)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    if "moe_rows_before_trace" in ctx:  # this call's rows are not the traced
        _sibling("moe_gmm_roofline_share").stop(ctx)    # call's
    model = getattr(job, "model", None)     # a job without one: phases only
    kinds = model.scope_kinds() if hasattr(model, "scope_kinds") else {}
    for r in table["rows"]:
        r["kind"] = kinds.get(r["vertex"], "")
    ctx["scope_table"] = table

    def ms_by(key):     # the run's phase line: the table in two cuts
        sums = {}
        for r in table["rows"]:
            sums[key(r)] = sums.get(key(r), 0.0) + r["ms"]
        return {k: round(v, 3)
                for k, v in sorted(sums.items(), key=lambda kv: -kv[1])}

    ctx["phase"]("scope_table", seconds=round(time.perf_counter() - t0, 2),
                 steps=table["steps"], step_ms=round(table["step_ms"], 3),
                 unattributed_ms=round(table["unattributed_ms"], 3),
                 by_phase=ms_by(lambda r: r["phase"]),
                 by_kind=ms_by(lambda r: r["kind"] or f"({r['phase']})"))


def total(ctx, keep):
    """ms a step of the table's rows that ``keep``; None where there is no
    table or no such row."""
    table = ctx.get("scope_table")
    if not table:
        return None
    return sum(r["ms"] for r in table["rows"] if keep(r)) or None


def read(ctx):
    return total(ctx, lambda r: r["phase"] == "update")
