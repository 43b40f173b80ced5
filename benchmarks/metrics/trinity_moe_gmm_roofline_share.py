"""Layer: kernels. ``moe_gmm_roofline_share`` in the cell
``trinity_mini.train_s16k`` (the accepted entry's list of cells is closed to
a later PR): the same definition, taken from that file and not copied —
``expert_flops`` of the rows the traced call REALLY routed to the 16 held
experts of the four routed layers over the ``moe_gmm*`` events of the same
steps; ``stop`` reads the load as the window closes and ``read`` the reading
that ``Job.free`` keeps. A perfect kernel reads 75% (the forward runs twice
under rematerialisation). This entry comes before the cell's ``trinity_scope_ms.*``
entries, whose profiled call takes the reading again."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metrics_moe_gmm_roofline_share",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "moe_gmm_roofline_share.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)

PREFIX = _accepted.PREFIX
stop = _accepted.stop
read = _accepted.read
