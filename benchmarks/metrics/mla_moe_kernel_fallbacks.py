"""Layer: kernels. ``moe_kernel_fallbacks`` in the cell
``joyai_llm_flash.train_b2_s8k`` (the accepted entry's list of cells is closed
to a later PR): the same counters, taken from that file and not copied —
``moe/gmm_fallback`` + ``seq/attn_fallback`` + ``seq/attn_bwd_fallback`` as
the window closes. 0 on the chip says that both attention kernels ran at 192 /
128 wide heads and ``moe_gmm`` at 768-wide experts; anything else is not
support."""

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
