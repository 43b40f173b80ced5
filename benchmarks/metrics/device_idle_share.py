"""Layer: device. 1 - (union of the device-op intervals / traced window),
averaged over the chips used. The traced window is one ``fit`` call put under
the profiler after the measured window: of ``epochs_per_call`` epochs, or of
the mix's ``traced_epochs`` where it names fewer. What a call costs at its
ends (weights to the host and back, the first dispatch) is then a larger share
of the traced call than of the window's own calls: with ``traced_epochs`` = n
the share read here is about ``epochs_per_call / n`` times the window's where
the idle time is at the ends of the call, and the same where it is between
steps. ``breakdown.idle_gaps`` says which."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t.get("busy_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
