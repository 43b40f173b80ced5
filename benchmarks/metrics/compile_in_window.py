"""Layer: entry points. Backend compilations plus persistent-cache misses that
JAX reported during the window (``run.Meter``). Has to read 0: the harness
fails a run in which it does not."""


def read(ctx):
    return ctx["compile_in_window"]
