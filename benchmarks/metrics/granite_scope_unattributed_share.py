"""Layer: device. ``scope_unattributed_share`` in the cell
``granite_4_h_micro.train_s16k`` (the accepted entry's list of cells is
closed to a later PR): the same definition, taken from that file and not
copied — the share of a step's device time whose op carries no phase, or no
vertex in a phase that has vertices. It says how far the cell's
``scope_ms.ssm_mixer`` can be trusted: a share near 0 leaves nothing of the
mixer's time outside its vertices."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metrics_scope_unattributed_share",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "scope_unattributed_share.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)

stop = _accepted.stop
read = _accepted.read
