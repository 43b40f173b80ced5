"""Layer: kernels. ``attention_bwd_roofline_share`` in the cell
``joyai_llm_flash.train_b2_s8k`` (the accepted entry's list of cells is closed
to a later PR): the same definition, taken from that file and not copied —
twice this configuration's ``attention_fwd_flops`` (the backward's four
required products, dQ and dK 192 wide, dV and dP 128 wide) over the events
named ``flash_attention_bwd*``, once a step."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metrics_attention_bwd_roofline_share",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "attention_bwd_roofline_share.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)

PREFIX = _accepted.PREFIX
read = _accepted.read
