"""Layer: kernels. ``trinity_scope_ms.window_attention``'s reading (taken
from that file, not copied) for the attention vertex of the cell's full
layer (``full_attention``: every earlier key, no rotation): device time a
step, self time, every phase, the forward kernel and the backward's XLA loops
included."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metrics_trinity_scope_ms_window_attention",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "trinity_scope_ms.window_attention.py"))
_window = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_window)

stop = _window.stop


def read(ctx):
    return _window.attention_ms(ctx, False)
