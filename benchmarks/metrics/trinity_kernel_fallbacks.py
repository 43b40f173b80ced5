"""Layer: kernels. ``moe_kernel_fallbacks`` in the cell
``trinity_mini.train_s16k`` (the accepted entry's list of cells is closed to
a later PR): the same counters, taken from that file and not copied —
``moe/gmm_fallback`` + ``seq/attn_fallback`` + ``seq/attn_bwd_fallback`` as
the window closes, call sites counted as the step is traced.

Unlike ``moe_kernel_fallbacks``, this reads MORE than 0 on the chip, and
that is the configuration's shape, not a fault: a group of 8 query heads of
128 on one key/value head keeps a dq of ``8 x 16,384 x 128`` in the
backward kernel's VMEM, 128 MiB at the kernel's ``4 + 2 x 2`` bytes an
element against the 32 MiB that ``supports_band_bwd_kernel`` allows, so the
attention backward of each of the five layers takes the XLA loops and
counts ``seq/attn_bwd_fallback`` once a traced call site. A reading of 5
a traced step says that the forward kernel and ``moe_gmm`` ran and the
backward took its loops; more than that says that a forward or a grouped
product fell back too. A backward kernel for such groups is the next
``perf_opt`` of this cell, and this number then falls to 0."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metrics_moe_kernel_fallbacks",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "moe_kernel_fallbacks.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)

COUNTERS = _accepted.COUNTERS
stop = _accepted.stop
read = _accepted.read
