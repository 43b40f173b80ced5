"""DataSet / MultiDataSet — features+labels(+masks) containers.

Reference: nd4j-api ``org.nd4j.linalg.dataset.{DataSet, MultiDataSet}``
(SURVEY.md §2.1 datasets row): holds feature/label arrays with optional
per-timestep masks, supports shuffle/split/batching/serialization.
"""

from __future__ import annotations

import io
from typing import Iterator, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..ndarray.ndarray import NDArray
from ..ndarray.rng import get_random


def _nd(x) -> Optional[NDArray]:
    if x is None or isinstance(x, NDArray):
        return x
    # hand the value straight to NDArray (its constructor does jnp.asarray):
    # np.asarray here would force a device->host readback for jax-array input
    return NDArray(x)


class DataSet:
    def __init__(self, features=None, labels=None,
                 features_mask=None, labels_mask=None):
        self.features = _nd(features)
        self.labels = _nd(labels)
        self.features_mask = _nd(features_mask)
        self.labels_mask = _nd(labels_mask)

    # --- basic info ----------------------------------------------------
    def num_examples(self) -> int:
        return self.features.shape[0] if self.features is not None else 0

    def get_features(self) -> NDArray:
        return self.features

    def get_labels(self) -> NDArray:
        return self.labels

    # --- manipulation --------------------------------------------------
    def shuffle(self, seed: Optional[int] = None) -> None:
        n = self.num_examples()
        rng = np.random.RandomState(seed) if seed is not None else np.random
        perm = rng.permutation(n)
        self.features = NDArray(self.features.to_numpy()[perm])
        if self.labels is not None:
            self.labels = NDArray(self.labels.to_numpy()[perm])
        if self.features_mask is not None:
            self.features_mask = NDArray(self.features_mask.to_numpy()[perm])
        if self.labels_mask is not None:
            self.labels_mask = NDArray(self.labels_mask.to_numpy()[perm])

    def split_test_and_train(self, n_train: int) -> Tuple["DataSet", "DataSet"]:
        def cut(arr, lo, hi):
            return NDArray(arr.to_numpy()[lo:hi]) if arr is not None else None

        n = self.num_examples()
        train = DataSet(cut(self.features, 0, n_train), cut(self.labels, 0, n_train),
                        cut(self.features_mask, 0, n_train), cut(self.labels_mask, 0, n_train))
        test = DataSet(cut(self.features, n_train, n), cut(self.labels, n_train, n),
                       cut(self.features_mask, n_train, n), cut(self.labels_mask, n_train, n))
        return train, test

    def batch_by(self, batch_size: int,
                 drop_remainder: bool = False) -> Iterator["DataSet"]:
        n = self.num_examples()
        if drop_remainder:
            n = (n // batch_size) * batch_size
        for i in range(0, n, batch_size):
            yield DataSet(
                NDArray(self.features.to_numpy()[i:i + batch_size]),
                NDArray(self.labels.to_numpy()[i:i + batch_size]) if self.labels is not None else None,
                NDArray(self.features_mask.to_numpy()[i:i + batch_size]) if self.features_mask is not None else None,
                NDArray(self.labels_mask.to_numpy()[i:i + batch_size]) if self.labels_mask is not None else None,
            )

    @staticmethod
    def merge(datasets: Sequence["DataSet"]) -> "DataSet":
        def cat(attr):
            if getattr(datasets[0], attr) is None:
                return None
            return np.concatenate([getattr(d, attr).to_numpy() for d in datasets])

        return DataSet(cat("features"), cat("labels"),
                       cat("features_mask"), cat("labels_mask"))

    # --- serialization -------------------------------------------------
    def save(self, path: str) -> None:
        if not path.endswith(".npz"):
            path = path + ".npz"  # np.savez appends it; keep save/load symmetric
        arrays = {"features": self.features.to_numpy()}
        if self.labels is not None:
            arrays["labels"] = self.labels.to_numpy()
        if self.features_mask is not None:
            arrays["features_mask"] = self.features_mask.to_numpy()
        if self.labels_mask is not None:
            arrays["labels_mask"] = self.labels_mask.to_numpy()
        np.savez(path, **arrays)

    @staticmethod
    def load(path: str) -> "DataSet":
        if not path.endswith(".npz"):
            path = path + ".npz"
        z = np.load(path)
        return DataSet(z["features"], z.get("labels"),
                       z.get("features_mask"), z.get("labels_mask"))

    def __repr__(self) -> str:
        f = self.features.shape if self.features is not None else None
        l = self.labels.shape if self.labels is not None else None
        return f"DataSet(features={f}, labels={l})"


class MultiDataSet:
    """N features + M labels (reference MultiDataSet for ComputationGraph)."""

    def __init__(self, features: Sequence, labels: Sequence,
                 features_masks: Optional[Sequence] = None,
                 labels_masks: Optional[Sequence] = None):
        self.features: List[NDArray] = [_nd(f) for f in features]
        self.labels: List[NDArray] = [_nd(l) for l in labels]
        self.features_masks = [_nd(m) for m in features_masks] if features_masks else None
        self.labels_masks = [_nd(m) for m in labels_masks] if labels_masks else None

    def num_examples(self) -> int:
        return self.features[0].shape[0]

    def batch_by(self, batch_size: int) -> Iterator["MultiDataSet"]:
        """Consecutive batches of ``batch_size`` examples, every array cut
        alike (a missing mask stays missing)."""
        groups = [g and [a if a is None else a.to_numpy() for a in g]
                  for g in (self.features, self.labels, self.features_masks,
                            self.labels_masks)]
        for i in range(0, self.num_examples(), batch_size):
            yield MultiDataSet(*[
                g and [a if a is None else a[i:i + batch_size] for a in g]
                for g in groups])

    def __repr__(self) -> str:
        return (f"MultiDataSet(features={[f.shape for f in self.features]}, "
                f"labels={[l.shape for l in self.labels]})")
