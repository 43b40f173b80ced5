"""Layer: kernels. How many times the SSD scan or the attention took its
plain XLA path in place of its Pallas kernel while the step was traced: the
program's counters ``seq/ssd_fallback`` + ``seq/attn_fallback`` +
``seq/attn_bwd_fallback`` as the window closes (bumped when a step is
traced, so the count is of call sites in the compiled programs, not of
executions). 0 on the chip: nine Mamba-2 layers' scans and the attention
layer's forward and backward all on their kernels. In a rehearsal it is what
the CPU path took. A program without the counters gives nothing to read."""

COUNTERS = ("seq/ssd_fallback", "seq/attn_fallback", "seq/attn_bwd_fallback")
ANY = ("seq/ssd_kernel",) + COUNTERS


def stop(ctx):
    try:
        from deeplearning4j_tpu.common.profiler import OpProfiler

        counters = OpProfiler.get().get_counters()
    except Exception:       # noqa: BLE001 - a program without the profiler
        counters = {}
    ctx["ssd_kernel_fallbacks"] = (
        sum(counters.get(c, 0) for c in COUNTERS)
        if any(c in counters for c in ANY) else None)


def read(ctx):
    return ctx.get("ssd_kernel_fallbacks")
