"""Layer: compiled step. The whole step's share of the chip's peak:
``model_flops`` (analytic, from the configuration's shapes: forward and
backward, no recomputation; never XLA's count) x examples in the window /
window / (chips x peak FLOP/s). Host clock and shapes only, so it also bounds
the kernels' rooflines when one of them leaves the path."""


def read(ctx):
    flops = ctx["conf"].model_flops(ctx["cfg"], ctx["sizes"], ctx["mix"]) * ctx["examples"]
    peak = ctx["chips"] * ctx["peaks"]["flops_per_s"]
    return 100.0 * flops / ctx["window_s"] / peak
