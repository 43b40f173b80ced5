"""Layer: kernels. The attention forward's share of the matrix unit's peak:
the least time the chip could take for the products the forward requires
(``conf.attention_fwd_flops``: per score map the causal half or the window's
band, scores once, values at their own width; compute-bound at these shapes)
over the device time a step of the trace events whose name starts
``flash_attention_fwd`` (the Pallas call's ``name``). Forward only, so that
the number means the same whether the backward is a kernel or XLA loops. Under
per-vertex rematerialisation the forward runs twice a step and the FLOPs are
counted once, so the kernel's own share is up to twice the reading. Where the
name is not among the ten most expensive that ``trace_reduce`` keeps, or the
program has no such kernel, there is nothing to read."""

PREFIX = "flash_attention_fwd"


def read(ctx):
    t = ctx["trace"]
    count = getattr(ctx["conf"], "attention_fwd_flops", None)
    if not t or not t.get("step_executions") or count is None:
        return None
    seconds = sum(s for name, s in t.get("device_ops", ())
                  if name.startswith(PREFIX))
    if not seconds:
        return None
    least = (count(ctx["cfg"], ctx["sizes"], ctx["mix"])
             * ctx["examples"] / ctx["steps"] / ctx["chips"]
             / ctx["peaks"]["flops_per_s"])
    return 100.0 * least / (seconds / t["step_executions"])
