"""End to end, host clock: process start to the start of the window —
imports, model build or import, data and weights from the seed, the first
steps (compile or cache load) and warm-up."""


def read(ctx):
    return ctx["setup_s"]
