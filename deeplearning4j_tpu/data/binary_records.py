"""Pre-decoded on-disk dataset container (VERDICT r3 item 4).

Reference: ``datavec-arrow`` columnar interchange + ``nd4j-serde`` binary
DataSet serializers (SURVEY §2.3 DataVec-execution row, §2.1 nd4j-serde) —
the reference's answer to "don't re-decode JPEGs every epoch". This is the
TPU rebuild's chunked binary record format:

``.d4tbin`` layout (little-endian)::

    b"D4TB" | u32 version | u64 header_len | header JSON (padded to 4 KiB)
    chunk 0 | chunk 1 | ...

The header records the column schema (name/shape/dtype), chunk size, and
total record count. Every chunk stores ``chunk_records`` records (the last
one fewer) column-major: all of column 0's records contiguously, then
column 1, ... Fixed shapes + raw dtypes mean the reader is a ``np.memmap``
slice-and-reshape — no parsing, no decode; training reads run at page-cache
speed, which is meant to make a disk-fed ResNet TPU-bound instead of
PIL-decode-bound (the disk-fed rate is not measured on this chip).

Components:
- :class:`BinaryRecordWriter` — streaming writer.
- :class:`BinaryRecordReader` — RecordReader SPI (record-at-a-time) plus
  the fast ``iter_chunks`` path.
- :class:`BinaryRecordDataSetIterator` — DataSetIterator over the
  container (chunk reads, optional uint8→float scaling + one-hot labels).
- :func:`write_records` — converter from any RecordReader whose records
  are ``[features: ndarray, label: int]`` (e.g. ImageRecordReader), the
  "decode once" tool.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .dataset import DataSet
from .records import Record, RecordReader  # Record = List[Any]

_MAGIC = b"D4TB"
_VERSION = 1
_HEADER_PAD = 4096


class BinaryRecordWriter:
    """Append fixed-shape records column-wise into a chunked container."""

    def __init__(self, path: str,
                 columns: Sequence[Tuple[str, Tuple[int, ...], str]],
                 chunk_records: int = 512):
        self.path = str(path)
        self.columns = [(str(n), tuple(int(d) for d in shp), np.dtype(dt))
                        for n, shp, dt in columns]
        self.chunk_records = int(chunk_records)
        self._buf: List[List[np.ndarray]] = [[] for _ in self.columns]
        self._n = 0
        self._f = open(self.path, "wb")
        self._write_header()

    def _write_header(self) -> None:
        header = {
            "columns": [{"name": n, "shape": list(shp), "dtype": dt.name}
                        for n, shp, dt in self.columns],
            "chunk_records": self.chunk_records,
            "n_records": self._n,
        }
        blob = json.dumps(header).encode()
        if len(blob) > _HEADER_PAD:
            raise ValueError("schema too large for the 4 KiB header")
        self._f.seek(0)
        self._f.write(_MAGIC)
        self._f.write(np.uint32(_VERSION).tobytes())
        self._f.write(np.uint64(len(blob)).tobytes())
        self._f.write(blob.ljust(_HEADER_PAD, b"\0"))

    def append(self, *values) -> None:
        if len(values) != len(self.columns):
            raise ValueError(f"expected {len(self.columns)} columns, "
                             f"got {len(values)}")
        for (name, shp, dt), v, buf in zip(self.columns, values, self._buf):
            arr = np.asarray(v, dtype=dt)
            if arr.shape != shp:
                raise ValueError(
                    f"column {name!r}: shape {arr.shape} != schema {shp}")
            buf.append(arr)
        self._n += 1
        if len(self._buf[0]) >= self.chunk_records:
            self._flush_chunk()

    def append_batch(self, *batches) -> None:
        n = np.asarray(batches[0]).shape[0]
        for i in range(n):
            self.append(*(np.asarray(b)[i] for b in batches))

    def _flush_chunk(self) -> None:
        if not self._buf[0]:
            return
        for (name, shp, dt), buf in zip(self.columns, self._buf):
            self._f.write(np.ascontiguousarray(
                np.stack(buf).astype(dt)).tobytes())
        self._buf = [[] for _ in self.columns]

    def close(self) -> None:
        if self._f.closed:
            return
        self._flush_chunk()
        self._write_header()     # final n_records
        self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class _Container:
    """Shared memmap view + chunk geometry."""

    def __init__(self, path: str):
        self.path = str(path)
        with open(self.path, "rb") as f:
            if f.read(4) != _MAGIC:
                raise ValueError(f"{path}: not a .d4tbin container")
            version = int(np.frombuffer(f.read(4), np.uint32)[0])
            if version != _VERSION:
                raise ValueError(f"{path}: unsupported version {version}")
            hlen = int(np.frombuffer(f.read(8), np.uint64)[0])
            header = json.loads(f.read(hlen).decode())
        self.columns = [(c["name"], tuple(c["shape"]), np.dtype(c["dtype"]))
                        for c in header["columns"]]
        self.chunk_records = int(header["chunk_records"])
        self.n_records = int(header["n_records"])
        self._data_start = 4 + 4 + 8 + _HEADER_PAD
        self._mm = np.memmap(self.path, np.uint8, mode="r")
        self._rec_bytes = [int(np.prod(shp, dtype=np.int64)) * dt.itemsize
                           for _, shp, dt in self.columns]
        # a crash mid-write (header written, last chunk not flushed) must
        # fail HERE with a clear message, not later inside read_chunk with
        # an opaque reshape error
        need = self._data_start + self.n_records * sum(self._rec_bytes)
        if self._mm.size < need:
            raise ValueError(
                f"{path}: truncated container — header promises "
                f"{self.n_records} records ({need} bytes) but the file is "
                f"{self._mm.size} bytes; the writer likely crashed "
                "mid-write (re-create the container or re-run the "
                "converter)")

    def n_chunks(self) -> int:
        return -(-self.n_records // self.chunk_records) \
            if self.n_records else 0

    def chunk_len(self, c: int) -> int:
        if c < self.n_chunks() - 1:
            return self.chunk_records
        return self.n_records - c * self.chunk_records

    def read_chunk(self, c: int) -> Dict[str, np.ndarray]:
        """Zero-copy column views of chunk ``c`` (arrays [n, *shape])."""
        n = self.chunk_len(c)
        # chunks before the last are all full-sized
        off = self._data_start + c * self.chunk_records \
            * sum(self._rec_bytes)
        out = {}
        for (name, shp, dt), rb in zip(self.columns, self._rec_bytes):
            nbytes = n * rb
            view = self._mm[off:off + nbytes].view(dt).reshape((n,) + shp)
            out[name] = view
            off += nbytes
        return out


class BinaryRecordReader(RecordReader):
    """RecordReader SPI over a container (record-at-a-time; use
    :class:`BinaryRecordDataSetIterator` for the fast batched path)."""

    def __init__(self, path: Optional[str] = None):
        if path is not None:
            self._open(path)

    def _open(self, path: str) -> None:
        self._c = _Container(path)
        self._i = 0
        self._chunk_idx = -1
        self._chunk: Optional[Dict[str, np.ndarray]] = None

    def initialize(self, split) -> None:
        locs = split.locations() if hasattr(split, "locations") else [split]
        if len(locs) != 1:
            raise ValueError("BinaryRecordReader reads one container")
        self._open(str(locs[0]))

    def reset(self) -> None:
        self._i = 0
        self._chunk_idx = -1
        self._chunk = None

    def has_next(self) -> bool:
        return self._i < self._c.n_records

    def next(self) -> Record:
        if not self.has_next():
            raise StopIteration
        c, s = divmod(self._i, self._c.chunk_records)
        if c != self._chunk_idx:
            self._chunk = self._c.read_chunk(c)
            self._chunk_idx = c
        self._i += 1
        vals: Record = []
        for name, shp, dt in self._c.columns:
            v = self._chunk[name][s]
            # .item() preserves the column dtype (int()-coercion would
            # truncate float scalar columns, e.g. regression targets)
            vals.append(v.item() if v.shape == () else np.asarray(v))
        return vals

    @property
    def n_records(self) -> int:
        return self._c.n_records

    @property
    def schema_columns(self):
        return list(self._c.columns)


class BinaryRecordDataSetIterator:
    """DataSetIterator over a container: chunked memmap reads assembled
    into DataSet batches. ``feature_scale`` (e.g. 1/255 for uint8 images)
    converts to float32 on the fly; ``num_classes`` one-hots the label."""

    def __init__(self, path: str, batch_size: int,
                 feature_col: str = "features", label_col: str = "label",
                 num_classes: Optional[int] = None,
                 feature_scale: Optional[float] = None,
                 raw_numpy: bool = False):
        self._c = _Container(path)
        self.batch_size = int(batch_size)
        self.feature_col = feature_col
        self.label_col = label_col
        self.num_classes = num_classes
        self.feature_scale = feature_scale
        # raw_numpy=True yields (x, y) numpy tuples instead of DataSet:
        # DataSet/NDArray construction eagerly device-puts, which must NOT
        # happen on a prefetch worker thread (AsyncDataSetIterator stages
        # raw tuples consumer-side; see the note in its __iter__)
        self.raw_numpy = bool(raw_numpy)
        names = [n for n, _, _ in self._c.columns]
        for col in (feature_col, label_col):
            if col not in names:
                raise ValueError(f"column {col!r} not in container "
                                 f"({names})")
        self.reset()

    def reset(self) -> None:
        self._cursor = 0

    def has_next(self) -> bool:
        return self._cursor < self._c.n_records

    def __iter__(self):
        self.reset()
        return self

    def __next__(self) -> DataSet:
        if not self.has_next():
            raise StopIteration
        start, end = self._cursor, min(self._cursor + self.batch_size,
                                       self._c.n_records)
        self._cursor = end
        feats, labels = [], []
        i = start
        while i < end:
            c, s = divmod(i, self._c.chunk_records)
            take = min(end - i, self._c.chunk_len(c) - s)
            chunk = self._c.read_chunk(c)
            feats.append(chunk[self.feature_col][s:s + take])
            labels.append(chunk[self.label_col][s:s + take])
            i += take
        x = np.concatenate(feats) if len(feats) > 1 else feats[0]
        y = np.concatenate(labels) if len(labels) > 1 else labels[0]
        if self.feature_scale is not None:
            x = x.astype(np.float32) * np.float32(self.feature_scale)
        else:
            x = np.ascontiguousarray(x)
        if self.num_classes is not None:
            y = np.eye(self.num_classes,
                       dtype=np.float32)[np.asarray(y, np.int64).reshape(-1)]
        if self.raw_numpy:
            return x, np.asarray(y)
        return DataSet(x, y)

    # DataSetIterator parity helpers
    def batch(self) -> int:
        return self.batch_size

    def total_examples(self) -> int:
        return self._c.n_records


def write_records(reader: RecordReader, path: str,
                  feature_shape: Tuple[int, ...],
                  features_dtype: str = "uint8",
                  feature_scale: float = 255.0,
                  chunk_records: int = 512) -> int:
    """Decode-once converter: drain ``reader`` (records shaped
    ``[features ndarray, label int]`` — ImageRecordReader's output) into a
    container at ``path``. Float features in [0,1] quantize to uint8 by
    default (4× smaller on disk; read back with feature_scale=1/255).
    Returns the record count."""
    fdt = np.dtype(features_dtype)
    with BinaryRecordWriter(
            path,
            [("features", tuple(feature_shape), fdt.name),
             ("label", (), "int32")],
            chunk_records=chunk_records) as w:
        reader.reset()
        while reader.has_next():
            rec = reader.next()
            feats, label = rec[0], rec[1]
            arr = np.asarray(feats)
            if fdt == np.uint8 and np.issubdtype(arr.dtype, np.floating):
                arr = np.clip(np.round(arr * feature_scale), 0,
                              255).astype(np.uint8)
            w.append(arr, int(label))
        return w._n
