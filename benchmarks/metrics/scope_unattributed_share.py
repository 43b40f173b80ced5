"""Layer: device. The health of the instrumentation itself: the share of a
step's device time (self time) whose op carries no phase, or no vertex in a
phase that has vertices (forward, recompute, backward) — ``unattributed_ms /
step_ms`` of ``scope_ms.update``'s table, in %. A share of the step, never of
a peak."""

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
    table = ctx.get("scope_table")
    if not table or not table["step_ms"]:
        return None
    return 100.0 * table["unattributed_ms"] / table["step_ms"]
