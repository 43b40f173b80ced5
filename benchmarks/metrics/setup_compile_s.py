"""Layer: compiled step. The part of set-up that JAX itself reports as
tracing, lowering and compiling (or loading from the persistent cache) —
``run.Meter``'s ``compile_s`` as the window starts, so every program of the
first steps and the warm-up is in it and nothing of the window. Warm it is
tracing plus cache loads; cold it is the compiler."""


def start(ctx):
    ctx["setup_compile_s"] = ctx["meter"].snapshot()["compile_s"]


def read(ctx):
    return ctx["setup_compile_s"]
