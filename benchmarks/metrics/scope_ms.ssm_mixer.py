"""Layer: kernels. Device time a step, self time, every phase, of the
vertices whose layer is a Mamba-2 mixer (``scope_kinds()``:
``Mamba2Layer``): the input and output projections, the convolution, the
decays, the SSD scan's Pallas kernels and the gated norm — the mixer whole,
where ``ssd_roofline_share`` times the scan alone. ``stop`` and the table
are ``scope_ms.update``'s (taken from that file, not copied): one profiled
one-epoch ``fit`` call once the window has closed, so the entry comes last
among the cell's metrics. A program without the layer gives nothing to
read."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metrics_scope_ms_update",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "scope_ms.update.py"))
_first = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_first)

stop = _first.stop

KINDS = ("Mamba2Layer",)


def read(ctx):
    return _first.total(ctx, lambda r: r.get("kind") in KINDS)
