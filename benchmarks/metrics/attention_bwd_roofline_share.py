"""Layer: kernels. The attention backward's share of the matrix unit's peak:
the least time the chip could take for the four products the backward
requires (dV and dP at the value's width, dQ and dK at the head's: twice
``conf.attention_fwd_flops``, over the causal half or the window's band;
compute-bound at these shapes) over the device time a step of the trace
events whose name starts ``flash_attention_bwd`` (the Pallas call's
``name``). A real backward also recomputes the scores from the saved
log-sum-exp, a fifth product that is not required and not counted, so a
kernel at the forward's own efficiency reads about 4/5 of what the forward
reads per call. The backward runs once a step (rematerialisation repeats the
forward only). Where the name is not among the ten most expensive that
``trace_reduce`` keeps, or the program has no such kernel (the backward as
XLA loops), there is nothing to read."""

PREFIX = "flash_attention_bwd"


def read(ctx):
    t = ctx["trace"]
    count = getattr(ctx["conf"], "attention_fwd_flops", None)
    if not t or not t.get("step_executions") or count is None:
        return None
    seconds = sum(s for name, s in t.get("device_ops", ())
                  if name.startswith(PREFIX))
    if not seconds:
        return None
    least = (2.0 * count(ctx["cfg"], ctx["sizes"], ctx["mix"])
             * ctx["examples"] / ctx["steps"] / ctx["chips"]
             / ctx["peaks"]["flops_per_s"])
    return 100.0 * least / (seconds / t["step_executions"])
