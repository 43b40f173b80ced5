"""Layer: kernels. ``attention_fwd_roofline_share`` in the cell
``joyai_llm_flash.train_b2_s8k`` (the accepted entry's list of cells is closed
to a later PR): the same definition, taken from that file and not copied,
with this configuration's ``attention_fwd_flops`` — six latent-attention
blocks, 32 heads, scores 192 wide (128 + the 64 rotated), values 128 wide, the
causal half. The forward runs twice a step under rematerialisation and is
counted once."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metrics_attention_fwd_roofline_share",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "attention_fwd_roofline_share.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)

PREFIX = _accepted.PREFIX
read = _accepted.read
