"""Layer: kernels. Device time a step, self time, every phase, of the
``RoutedExpertsLayer`` vertices LESS the events named ``moe_gmm*`` (the
grouped products' Pallas kernels, which ``moe_gmm_roofline_share`` times):
router, sorts, gathers, masks, ``silu * mul``, the weighted gather-back —
what runs over the worst-case buffer whatever was routed. ``stop`` and the
table are ``scope_ms.update``'s."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metrics_scope_ms_update",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "scope_ms.update.py"))
_first = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_first)

stop = _first.stop


def read(ctx):
    return _first.total(ctx, lambda r: r.get("kind") == "RoutedExpertsLayer"
                        and not r["op"].startswith("moe_gmm"))
