"""Layer: kernels. The matrix unit's roofline: the least time the step's
matrix products could take (``mxu_flops`` per step / peak FLOP/s; they are
compute-bound at these shapes) over the device time per step of the trace
events that hold a convolution or a dot. ``jax.profiler.ProfileData`` does
not expose the profiler's ``hlo_category``, so those events are selected by
their own HLO text (``trace_reduce.is_mxu``, decided after reading the first
traces of PR 25 by hand): a fusion of ``kind=kOutput`` (on a TPU a
convolution or dot with the ops fused onto its output) or an unfused
``convolution``/``dot``. Fused epilogues (a BatchNorm statistic or Adam's
update riding on a product) count in the denominator, so the share is a
lower bound of the products' own; Pallas kernels are custom calls and are
never counted."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t.get("step_executions") or not t.get("mxu_s"):
        return None
    per_step = (ctx["conf"].mxu_flops(ctx["cfg"], ctx["sizes"], ctx["mix"])
                * ctx["examples"] / ctx["steps"] / ctx["chips"])
    least = per_step / ctx["peaks"]["flops_per_s"]
    return 100.0 * least / (t["mxu_s"] / t["step_executions"])
