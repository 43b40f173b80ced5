"""Layer: compiled step. Device time a step, self time, of the phase
``recompute``: the forward run again in the backward pass under per-vertex
rematerialisation (``jax.checkpoint``'s ``rematted_computation`` in the name
stack), the Pallas kernels it runs again included. ``stop`` and the table
are ``scope_ms.update``'s; the profile runs once."""

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
    return _first.total(ctx, lambda r: r["phase"] == "recompute")
