"""Layer: kernels. The grouped expert products' share of the matrix unit's
peak: the least time the chip could take for the products that the rows a step
REALLY routed to the held experts require (``conf.expert_flops`` of those
rows: nine grouped products a row — three forward, three input gradients,
three weight gradients — compute-bound at these widths) over the device time a
step of the trace events whose name starts ``moe_gmm`` (the Pallas calls'
``name``; forward, input-gradient and weight-gradient kernels carry the one
name, so ``trace_reduce``'s ten-name cut hands them over whole or not at all).

Rows and seconds are of the SAME steps, the traced call's: the held experts'
load grows while the cell trains (``PERF.md`` section 6, PR 32), so the
window's rows would not do for the traced call's seconds. The rows are the
model's own ``expert_load`` (``job.buffers()``), read in ``stop`` — the window
has closed behind its fence and the traced call comes next — and once more
after the traced call: ``run.py`` has no hook there, so the configuration's
``Job.free`` keeps its last reading (``last_buffers``) and ``read`` takes it
from the job that ``stop`` remembered.

Under per-vertex rematerialisation the three forward products run twice a step
and are counted once, so a perfect kernel reads 75%. Where the XLA path ran
(``lax.ragged_dot``: no event of that name), where the name is not among the
ten, or where the job has no such state, there is nothing to read."""

PREFIX = "moe_gmm"


def _held_rows(ctx, loads):
    """Tokens that selected a held expert, summed over the routed layers,
    since the job's reset."""
    import numpy as np

    first, end = ctx["sizes"]["experts_held"]
    return float(sum(np.asarray(leaves["expert_load"])[first:end].sum()
                     for leaves in loads.values()))


def stop(ctx):
    job = ctx["job"]
    if hasattr(job, "buffers") and "experts_held" in ctx["sizes"]:
        ctx["moe_job"] = job
        ctx["moe_rows_before_trace"] = _held_rows(ctx, job.buffers())


def read(ctx):
    t = ctx["trace"]
    count = getattr(ctx["conf"], "expert_flops", None)
    after = getattr(ctx.get("moe_job"), "last_buffers", None)
    if not t or not t.get("step_executions") or count is None or not after:
        return None
    seconds = sum(s for name, s in t.get("device_ops", ())
                  if name.startswith(PREFIX))
    rows = _held_rows(ctx, after) - ctx["moe_rows_before_trace"]
    if not seconds or not rows > 0:
        return None
    least = (count(ctx["cfg"], ctx["sizes"], rows / t["step_executions"])
             / ctx["chips"] / ctx["peaks"]["flops_per_s"])
    return 100.0 * least / (seconds / t["step_executions"])
