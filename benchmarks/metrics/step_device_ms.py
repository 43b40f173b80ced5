"""Layer: compiled step. Device trace: time in which an operation ran on the
device inside the executions of the step program (the configuration's
``STEP_PROGRAM``), over the number of those executions in the trace."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t.get("step_executions"):
        return None
    return t["step_busy_s"] / t["step_executions"] * 1e3
