"""Layer: kernels. Device time a step, self time, every phase, of the
vertices whose layer is an attention layer (``scope_kinds()``:
``DifferentialAttentionLayer``, ``RotaryAttentionLayer``,
``LatentAttentionLayer``): projections, rotary, layout copies and both Pallas
kernels — attention whole, where ``attention_*_roofline_share`` time the
kernels alone. ``stop`` and the table are ``scope_ms.update``'s."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metrics_scope_ms_update",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "scope_ms.update.py"))
_first = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_first)

stop = _first.stop

KINDS = ("DifferentialAttentionLayer", "RotaryAttentionLayer",
         "LatentAttentionLayer")


def read(ctx):
    return _first.total(ctx, lambda r: r.get("kind") in KINDS)
