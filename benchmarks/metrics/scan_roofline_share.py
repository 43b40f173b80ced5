"""Layer: kernels. The selective scan's share of its bandwidth roofline: the
least time the chip could take to move what the scans of a step have to read
and write (``conf.scan_bytes``: u, dt, B, C, y and their cotangents at
bfloat16, forward and backward, the state never leaving the chip; no
recomputation) at the peak bytes/s, over the device time a step of the trace
events whose name starts ``selective_scan`` (the Pallas calls' ``name``, which
becomes the HLO instruction's name). Forward and backward kernel carry that one
name, so ``trace_reduce``, which keeps the ten most expensive names only,
hands over the scan whole or not at all: a share of whichever kernel passed
the cut would change its meaning with no change of code. The scan is bound by
``exp`` and the vector unit, not by bandwidth, and under per-vertex
rematerialisation its forward runs twice a step, so expect single digits to
about 20%: the number says how far the kernel is from being free, not how well
it uses what bounds it. Where the name is not among the ten, or the program
has no such kernel, there is nothing to read."""

PREFIX = "selective_scan"


def read(ctx):
    t = ctx["trace"]
    count = getattr(ctx["conf"], "scan_bytes", None)
    if not t or not t.get("step_executions") or count is None:
        return None
    seconds = sum(s for name, s in t.get("device_ops", ())
                  if name.startswith(PREFIX))
    if not seconds:
        return None
    least = (count(ctx["cfg"], ctx["sizes"], ctx["mix"])
             * ctx["examples"] / ctx["steps"] / ctx["chips"]
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / t["step_executions"])
