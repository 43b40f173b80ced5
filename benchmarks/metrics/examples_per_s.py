"""End to end, host clock: every example dispatched in the window over the
whole window (clock started after warm-up and a fence, stopped after a fence
on the final parameters). Not a median of chunks."""


def read(ctx):
    return ctx["examples"] / ctx["window_s"]
