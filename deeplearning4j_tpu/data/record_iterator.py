"""RecordReader → DataSet iterators + async prefetch.

Reference: deeplearning4j-datavec-iterators
``RecordReaderDataSetIterator`` / ``SequenceRecordReaderDataSetIterator``
(label-column extraction, one-hot for classification, regression mode,
alignment + padding masks) and deeplearning4j-utility-iterators
``AsyncDataSetIterator`` (SURVEY.md §2.1 datasets row, §2.3 DataVec rows;
VERDICT round-1 weak #3 names the missing prefetch as the LeNet TPU
bottleneck).

``AsyncDataSetIterator`` here overlaps the three host stages with device
compute: a background thread reads + vectorizes the next batches while the
accelerator trains on the current one, optionally staging arrays onto the
device (``jax.device_put``) ahead of use so ``fit`` never waits on H2D.
"""

from __future__ import annotations

import queue
import threading
from typing import Iterator, List, Optional, Sequence

import numpy as np

from .dataset import DataSet
from .iterators import DataSetIterator
from .records import RecordReader, SequenceRecordReader


class RecordReaderDataSetIterator(DataSetIterator):
    """Assemble flat records into (features, labels) DataSet batches.

    Classification: ``label_index`` column → one-hot over ``num_classes``.
    Regression: ``regression=True`` keeps label columns as float values
    (``label_index``..``label_index_to`` inclusive, reference semantics).
    Image records (cell 0 is an ndarray) batch by stacking.
    """

    def __init__(self, reader: RecordReader, batch_size: int,
                 label_index: int = -1, num_classes: Optional[int] = None,
                 regression: bool = False,
                 label_index_to: Optional[int] = None):
        self.reader = reader
        self.batch_size = batch_size
        self.label_index = label_index
        self.num_classes = num_classes
        self.regression = regression
        self.label_index_to = label_index_to if label_index_to is not None \
            else label_index

    def batch(self) -> int:
        return self.batch_size

    def reset(self) -> None:
        self.reader.reset()

    def __iter__(self) -> Iterator[DataSet]:
        self.reset()
        batch: List[list] = []
        for rec in self.reader:
            batch.append(rec)
            if len(batch) == self.batch_size:
                yield self._apply_pre(self._assemble(batch))
                batch = []
        if batch:
            yield self._apply_pre(self._assemble(batch))

    def _assemble(self, batch: List[list]) -> DataSet:
        first = batch[0]
        if isinstance(first[0], np.ndarray) and first[0].ndim >= 2:
            # image records: [chw_array, label]
            x = np.stack([r[0] for r in batch]).astype(np.float32)
            y_idx = np.asarray([int(r[1]) for r in batch])
            n = self.num_classes or \
                (self.reader.num_labels()
                 if hasattr(self.reader, "num_labels") else 0)
            if not n:
                # per-batch max(label)+1 would give inconsistent one-hot
                # widths across batches
                raise ValueError("classification needs num_classes (or a "
                                 "reader exposing num_labels())")
            y = np.eye(n, dtype=np.float32)[y_idx]
            return DataSet(x, y)
        width = len(first)
        li = self.label_index % width if self.label_index is not None else None
        if li is None:
            x = np.asarray(batch, dtype=np.float32)
            return DataSet(x, None)
        lt = self.label_index_to % width
        feat_cols = [i for i in range(width) if not li <= i <= lt]
        x = np.asarray([[float(r[i]) for i in feat_cols] for r in batch],
                       dtype=np.float32)
        if self.regression:
            y = np.asarray([[float(r[i]) for i in range(li, lt + 1)]
                            for r in batch], dtype=np.float32)
        else:
            if not self.num_classes:
                raise ValueError("classification needs num_classes")
            y_idx = np.asarray([int(float(r[li])) for r in batch])
            if (y_idx < 0).any() or (y_idx >= self.num_classes).any():
                raise ValueError(
                    f"label index out of range [0, {self.num_classes}): "
                    f"{sorted(set(y_idx.tolist()))[:10]}")
            y = np.eye(self.num_classes, dtype=np.float32)[y_idx]
        return DataSet(x, y)


class SequenceRecordReaderDataSetIterator(DataSetIterator):
    """Sequence records → [N, T, F] batches with per-timestep label masks,
    padded to the longest sequence in the batch (reference:
    SequenceRecordReaderDataSetIterator, ALIGN_END label alignment with
    padding masks; SURVEY §5.7 masking row).

    DOCUMENTED LAYOUT DIVERGENCE: the reference emits [batch, features,
    time]; this framework's recurrent layers are jax-natural
    [batch, time, features] throughout (see nn/conf/layers LSTM), so the
    iterator emits that — labels [N, T, C] one-hot for classification,
    [N, T] masks marking real timesteps.
    """

    def __init__(self, reader: SequenceRecordReader, batch_size: int,
                 label_index: int = -1, num_classes: Optional[int] = None,
                 regression: bool = False):
        self.reader = reader
        self.batch_size = batch_size
        self.label_index = label_index
        self.num_classes = num_classes
        self.regression = regression

    def batch(self) -> int:
        return self.batch_size

    def reset(self) -> None:
        self.reader.reset()

    def __iter__(self) -> Iterator[DataSet]:
        self.reset()
        batch: List[list] = []
        for seq in self.reader.sequences():
            batch.append(seq)
            if len(batch) == self.batch_size:
                yield self._apply_pre(self._assemble(batch))
                batch = []
        if batch:
            yield self._apply_pre(self._assemble(batch))

    def _assemble(self, seqs: List[list]) -> DataSet:
        width = len(seqs[0][0])
        li = self.label_index % width
        feat_cols = [i for i in range(width) if i != li]
        T = max(len(s) for s in seqs)
        N, F = len(seqs), len(feat_cols)
        x = np.zeros((N, T, F), np.float32)
        mask = np.zeros((N, T), np.float32)
        if self.regression:
            y = np.zeros((N, T, 1), np.float32)
        else:
            if not self.num_classes:
                raise ValueError("classification needs num_classes")
            y = np.zeros((N, T, self.num_classes), np.float32)
        for n, seq in enumerate(seqs):
            for t, rec in enumerate(seq):
                for f, col in enumerate(feat_cols):
                    x[n, t, f] = float(rec[col])
                mask[n, t] = 1.0
                if self.regression:
                    y[n, t, 0] = float(rec[li])
                else:
                    y[n, t, int(float(rec[li]))] = 1.0
        return DataSet(x, y, features_mask=mask, labels_mask=mask)


class AsyncDataSetIterator(DataSetIterator):
    """Background-thread prefetch wrapper (reference:
    AsyncDataSetIterator with its blocking queue of ``queue_size``).

    ``device_prefetch=True`` additionally stages each batch's arrays onto
    the default device from the worker thread, overlapping H2D transfer
    with the current training step — the role the reference's workspace
    pre-population plays on CUDA.
    """

    _END = object()

    def __init__(self, base: DataSetIterator, queue_size: int = 4,
                 device_prefetch: bool = True,
                 feature_transform=None):
        self.base = base
        self.queue_size = queue_size
        self.device_prefetch = device_prefetch
        # Optional jax fn applied to the FEATURES on device after the put
        # (e.g. ``lambda x: x.astype(jnp.float32) / 255`` for uint8 image
        # containers: shipping the 4×-smaller raw bytes and converting on
        # device moves the cast off the host decode thread; not measured
        # on this chip)
        if feature_transform is not None and not device_prefetch:
            raise ValueError("feature_transform is applied on device and "
                             "requires device_prefetch=True")
        if feature_transform is None:
            self._feature_transform = None
        else:
            from ..common import xprof

            self._feature_transform = xprof.register_jit(
                "data/feature_transform",
                __import__("jax").jit(feature_transform))

    def batch(self) -> int:
        return self.base.batch()

    def reset(self) -> None:
        self.base.reset()

    def _stage(self, ds) -> DataSet:
        import jax

        from ..ndarray.ndarray import NDArray

        if isinstance(ds, tuple):
            # raw numpy (x, y) from a jax-free worker (the binary-record
            # fast path) — build the DataSet here on the consumer thread.
            # device_prefetch=False matches the non-tuple branch: no
            # explicit committed device_put; the NDArray wrap still runs
            # jnp.asarray (a default-device transfer on TPU), exactly as
            # it would when the caller constructs a DataSet itself
            x, y = ds
            if self.device_prefetch:
                xd = NDArray(jax.device_put(x))
                if self._feature_transform is not None:
                    xd = NDArray(self._feature_transform(xd.value))
                yd = NDArray(jax.device_put(y)) if y is not None else None
            else:
                xd = NDArray(x)
                yd = NDArray(y) if y is not None else None
            out = DataSet.__new__(DataSet)
            out.features = xd
            out.labels = yd
            out.features_mask = None
            out.labels_mask = None
            return out
        if not self.device_prefetch:
            return ds

        def put(nd):
            if nd is None:
                return None
            return NDArray(jax.device_put(nd.value))

        out = DataSet.__new__(DataSet)
        out.features = put(ds.features)
        if self._feature_transform is not None and out.features is not None:
            out.features = NDArray(
                self._feature_transform(out.features.value))
        out.labels = put(ds.labels)
        out.features_mask = put(ds.features_mask)
        out.labels_mask = put(ds.labels_mask)
        return out

    def __iter__(self) -> Iterator[DataSet]:
        from ..common.background import prefetch_iter

        # Device staging runs on the CONSUMER thread: chosen on a set-up
        # that is gone (device_put from a non-main thread serialized
        # there); not measured on this chip. Consumer-side device_put is
        # itself async. CAVEAT: the worker thread is fully jax-free only for
        # bases yielding raw (x, y) numpy tuples (binary-record
        # ``raw_numpy=True``); bases that construct DataSet inside their
        # own __next__ still touch jax there, because NDArray eagerly
        # converts (ndarray.py) — prefer the tuple protocol for new bases.
        for ds in prefetch_iter(iter(self.base), maxsize=self.queue_size):
            yield self._stage(ds)
