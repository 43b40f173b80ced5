"""Traffic generator ``token_batches``: seed -> host batches of padded token
sequences with a mask and one-hot labels.

Reads ``batch``, ``seq``, ``min_len`` and ``batches`` from the mix and the
vocabulary and class count from the configuration's sizes. Sentence lengths
are drawn uniformly from ``min_len``..``seq`` and padded to ``seq`` (id 0,
mask 0), so every batch has one shape and every seed the same amount of
work. Position 0 is always a real token (the classifier reads it).
"""

from __future__ import annotations

import numpy as np


def make(mix: dict, sizes: dict, seed: int, count: int) -> list:
    """The first ``count`` batches of the seed's data set."""
    rng = np.random.default_rng(int(seed))
    b, t = mix["batch"], mix["seq"]
    out = []
    for _ in range(count):
        lengths = rng.integers(mix["min_len"], t + 1, b)
        mask = (np.arange(t)[None, :] < lengths[:, None]).astype(np.int32)
        ids = rng.integers(1, sizes["vocab_size"], (b, t)).astype(np.int32) * mask
        labels = rng.integers(0, sizes["num_classes"], b)
        y = np.zeros((b, sizes["num_classes"]), np.float32)
        y[np.arange(b), labels] = 1.0
        out.append({"ids": ids, "types": np.zeros((b, t), np.int32),
                    "mask": mask, "y": y})
    return out


def examples(mix: dict) -> int:
    return mix["batch"] * mix["batches"]
