"""Layer: kernels. The state-space dual scan's share of its roofline: the
least time the chip could take for the SSD scans of a step — the greater of
``conf.ssd_flops`` at the peak FLOP/s and ``conf.ssd_bytes`` at the peak
bytes/s (forward and backward, the forward that rematerialisation runs again
not counted) — over the device time a step of the kernels named
``ssd_scan`` (the Pallas calls' ``name``, which becomes the HLO
instruction's). Forward and backward carry that one name, so the reader sees
the scan whole or not at all.

Where the seconds come from: the traced call's events whose name starts
``ssd_scan``, as ``trace_reduce`` hands them over. It keeps the ten most
expensive names only, and this cell's step is mostly the products of XLA's
own fusions, so where the name is not among the ten the seconds are read
from the scope table (``scope_ms.update``'s profiled call, one epoch of the
same program) as the rows whose op is the kernel's: the same events, timed
in a call of their own, so the share is there whichever way the cut falls.
Where the program has no such kernel (an older tree, a CPU run) there is
nothing to read."""

PREFIX = "ssd_scan"


def _seconds_a_step(ctx):
    t = ctx["trace"]
    if t and t.get("step_executions"):
        seconds = sum(s for name, s in t.get("device_ops", ())
                      if name.startswith(PREFIX))
        if seconds:
            return seconds / t["step_executions"]
    table = ctx.get("scope_table")
    if table:
        ms = sum(r["ms"] for r in table["rows"]
                 if r["op"].startswith(PREFIX))
        if ms:
            return ms / 1e3
    return None


def read(ctx):
    conf = ctx["conf"]
    flops = getattr(conf, "ssd_flops", None)
    nbytes = getattr(conf, "ssd_bytes", None)
    seconds = _seconds_a_step(ctx)
    if flops is None or nbytes is None or not seconds:
        return None
    per_step = ctx["examples"] / ctx["steps"] / ctx["chips"]
    args = (ctx["cfg"], ctx["sizes"], ctx["mix"])
    least = per_step * max(flops(*args) / ctx["peaks"]["flops_per_s"],
                           nbytes(*args) / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
