"""Layer: kernels. ``attention_fwd_roofline_share`` in the cell
``trinity_mini.train_s16k`` (the accepted entry's list of cells is closed to
a later PR): the same definition, taken from that file and not copied, with
this configuration's ``attention_fwd_flops`` — four window layers' bands of
2,048 keys and one full layer's causal half at 16,384 tokens, 32 query heads
of 128 over 4 key/value heads. The forward runs twice a step under
rematerialisation and is counted once."""

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
