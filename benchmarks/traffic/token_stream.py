"""Traffic generator ``token_stream``: seed -> host batches of packed token
sequences with next-token labels.

Reads ``batch``, ``seq`` and ``batches`` from the mix and the vocabulary from
the configuration's sizes. A batch is ``batch`` sequences of ``seq + 1`` ids
drawn uniformly from 1..vocab_size-1: the first ``seq`` are the inputs, the
last ``seq`` the labels (so the last label is drawn too). Every position is
real: no padding, no mask, concatenate-and-chunk. Every seed gives the same
amount of work; only the values change.
"""

from __future__ import annotations

import numpy as np


def make(mix: dict, sizes: dict, seed: int, count: int) -> list:
    """The first ``count`` batches of the seed's data set."""
    rng = np.random.default_rng(int(seed))
    out = []
    for _ in range(count):
        s = rng.integers(1, sizes["vocab_size"],
                         (mix["batch"], mix["seq"] + 1)).astype(np.int32)
        out.append({"ids": s[:, :-1], "labels": s[:, 1:]})
    return out


def examples(mix: dict) -> int:
    """Sequences in one pass over the data."""
    return mix["batch"] * mix["batches"]
