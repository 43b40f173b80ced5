"""Layer: kernels. How many times a grouped expert product or the attention
took its plain XLA path in place of its Pallas kernel while the step was
traced: the program's counters ``moe/gmm_fallback`` + ``seq/attn_fallback`` +
``seq/attn_bwd_fallback`` as the window closes (they are bumped when a step is
traced, so the count is of call sites in the compiled programs, not of
executions). 0 on the chip; in a rehearsal it is what the CPU path took. A
program without the counters gives nothing to read."""

COUNTERS = ("moe/gmm_fallback", "seq/attn_fallback", "seq/attn_bwd_fallback")
ANY = ("moe/gmm_kernel", "seq/attn_kernel", "seq/attn_bwd_kernel") + COUNTERS


def stop(ctx):
    try:
        from deeplearning4j_tpu.common.profiler import OpProfiler

        counters = OpProfiler.get().get_counters()
    except Exception:       # noqa: BLE001 - a program without the profiler
        counters = {}
    ctx["moe_kernel_fallbacks"] = (
        sum(counters.get(c, 0) for c in COUNTERS)
        if any(c in counters for c in ANY) else None)


def read(ctx):
    return ctx.get("moe_kernel_fallbacks")
