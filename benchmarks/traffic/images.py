"""Traffic generator ``images``: seed -> host batches of images and labels.

Reads its parameters from the mix (``batch``, ``batches``) and the image
shape and class count from the configuration's sizes. Every row differs; the
same seed gives the same rows. Every seed gives the same amount of work:
only the values change.
"""

from __future__ import annotations

import numpy as np


def make(mix: dict, sizes: dict, seed: int, count: int) -> list:
    """The first ``count`` batches of the seed's data set."""
    rng = np.random.default_rng(int(seed))
    b = mix["batch"]
    shape = (sizes["channels"], sizes["image_size"], sizes["image_size"])
    out = []
    for _ in range(count):
        x = rng.standard_normal((b,) + shape, dtype=np.float32)
        labels = rng.integers(0, sizes["num_classes"], b)
        y = np.zeros((b, sizes["num_classes"]), np.float32)
        y[np.arange(b), labels] = 1.0
        out.append({"x": x, "y": y})
    return out


def examples(mix: dict) -> int:
    """Examples in one pass over the data."""
    return mix["batch"] * mix["batches"]
