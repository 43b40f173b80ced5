"""Layer: kernels. Device time a step, self time, every phase, of the
attention vertices of the cell's window layers (``sliding_attention`` in the
configuration's ``layer_types``: a band of 2,048 keys, rotary positions):
projections, per-head norms, rotary, the output gate, layout copies, the
forward kernel and the backward's XLA loops. ``trinity_scope_ms.full_attention``
is the same of the full layer; together they are what the 3:1 mix costs.
The vertices come from the configuration's layer table
(``conf.attention_nodes``). ``stop`` and the table are ``scope_ms.update``'s
(taken from that file, not copied): one profiled one-epoch ``fit`` call once
the window has closed, so the entry comes last among the cell's metrics."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metrics_scope_ms_update",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "scope_ms.update.py"))
_first = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_first)

stop = _first.stop


def attention_ms(ctx, sliding):
    """ms a step of the window layers' attention vertices (``sliding``) or
    of the full layers'; None where the table or the layer table is not
    there."""
    nodes = getattr(ctx["conf"], "attention_nodes", None)
    if nodes is None:
        return None
    mine = set(nodes(ctx["sizes"], sliding))
    return _first.total(ctx, lambda r: r["vertex"] in mine)


def read(ctx):
    return attention_ms(ctx, True)
