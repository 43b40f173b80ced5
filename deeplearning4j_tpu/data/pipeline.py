"""Shared input/dispatch pipeline for the training loops.

One implementation feeds ``MultiLayerNetwork.fit``, ``ComputationGraph.fit``
and ``ParallelWrapper.fit`` (SURVEY §3.1's "one compiled train-step per
minibatch", with the host side around it made shape-stable and overlapped):

- **shape-stable batching** (:func:`stable_batches`): every batch a fit
  config sees has the SAME leading dimension — the final partial batch is
  padded to the target size by wrapping real rows, with a per-example
  weight vector (1 = real, 0 = pad) threaded into the loss so padded rows
  contribute exactly nothing. One shape ⇒ the jitted train step compiles
  exactly once per config instead of recompiling on the remainder batch
  (whole-loop compilation with stable shapes is what keeps a TPU pipeline
  saturated — cf. arXiv:1810.09868). ``drop_remainder=True`` skips the
  partial batch instead.
- **async device feed** (:func:`device_feed`, built on
  ``common.background.staged_iter``): batch placement (``jax.device_put``
  or a sharded put) is issued ``depth`` batches ahead of the consumer, so
  the H2D transfer of batch *n+1* overlaps the device compute of batch
  *n*.
- **multi-step dispatch** (:func:`chunked`): group K stable batches per
  Python dispatch; the networks stack them and run a ``lax.scan`` device
  loop, amortizing Python/dispatch overhead over K steps (the same lever
  as update-sharding's dispatch amortization, arXiv:2004.13336).
- **observability**: :func:`timed_iter` feeds the ``pipeline/next_batch``
  vs ``pipeline/dispatch`` sections of ``common.profiler.OpProfiler``,
  and the step builders bump ``trace/*`` counters at trace time — tests
  and the bench assert "one compile per config" on those.

Padding wraps REAL rows (``row[i % n]``) rather than zero-filling:
zero rows would pollute cross-example statistics (BatchNorm batch stats),
while wrapped rows keep them in-distribution; the wrapped rows' loss and
gradient contributions are removed exactly by the example-weight mask.
"""

from __future__ import annotations

from typing import Any, Iterable, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common import faultinject, flightrec, xprof
from ..common.background import staged_iter
from ..common.profiler import OpProfiler
from ..ndarray.ndarray import NDArray
from .dataset import DataSet, MultiDataSet


def resolve_batch_size(data: Any, batch_size: Optional[int]) -> Optional[int]:
    """The pipeline's target (padded) batch size. A source that makes its
    own batches (an iterator reporting ``batch()``) keeps its native size
    — an explicit ``batch_size`` cannot re-batch an iterator (the pre-
    pipeline fit ignored it there too) and padding every batch UP to a
    larger figure would silently multiply per-step FLOPs. The explicit
    argument applies to sources the pipeline slices itself (DataSet /
    tuple). None = no stable target; batches pass through unpadded."""
    b = getattr(data, "batch", None)
    if callable(b):
        try:
            n = b()
            if n and n > 0:
                return int(n)
        except NotImplementedError:
            pass
    return int(batch_size) if batch_size else None


def iter_datasets(data: Any, batch_size: Optional[int] = None,
                  allow_multi: bool = False) -> Iterator[Any]:
    """The one batch-source protocol shared by every fit loop: DataSet
    iterators (reset + __iter__), a single DataSet (optionally re-batched
    by ``batch_size``), a (features, labels) tuple, and — for the graph —
    a MultiDataSet (re-batched alike)."""
    if isinstance(data, (DataSet, MultiDataSet)):
        if isinstance(data, MultiDataSet) and not allow_multi:
            raise TypeError("MultiDataSet requires ComputationGraph.fit")
        if batch_size is None:
            yield data
        else:
            yield from data.batch_by(batch_size)
        return
    if hasattr(data, "reset") and hasattr(data, "__iter__"):
        data.reset()
        yield from data
        return
    if isinstance(data, tuple) and len(data) == 2:
        yield from iter_datasets(DataSet(data[0], data[1]), batch_size)
        return
    raise TypeError(f"cannot iterate data of type {type(data)}")


def _wrap_rows(value: jnp.ndarray, idx: np.ndarray) -> jnp.ndarray:
    return jnp.asarray(value)[idx]


def _pad_nd(nd: Optional[NDArray], idx: np.ndarray) -> Optional[NDArray]:
    if nd is None:
        return None
    return NDArray(_wrap_rows(nd.value, idx))


def pad_rows(arr: np.ndarray, target: int,
             axis: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Pad ``arr`` to ``target`` entries along ``axis`` by WRAPPING real
    rows (``row[i % n]``) — the same rule :func:`pad_dataset` applies to
    training batches, host-side (numpy) for the serving tier's bucket
    padding. Returns ``(padded, w)`` with ``w`` the [target] float32
    example-weight vector (1 = real, 0 = pad).

    The inertness argument is the same as training's: wrapped rows are
    REAL rows, so any per-example computation produces for pad slots an
    exact copy of a real slot's output, and the consumer discards them by
    the mask / by slicing ``[:n]`` — nothing about the real rows' results
    depends on the pad rows (proven bitwise in tests/test_serving.py for
    the inference forward)."""
    arr = np.asarray(arr)
    n = arr.shape[axis]
    if n > target:
        raise ValueError(f"{n} rows exceed the pad target {target}")
    w = (np.arange(target) < n).astype(np.float32)
    if n == target:
        return arr, w
    idx = np.arange(target) % n
    return np.take(arr, idx, axis=axis), w


def pad_dataset(ds: Any, target: int) -> Tuple[Any, jnp.ndarray]:
    """Pad ``ds`` (DataSet or MultiDataSet) to ``target`` examples by
    wrapping real rows; returns ``(padded_ds, w)`` with the example-weight
    vector ``w`` ([target] float32, 1 = real row, 0 = pad row).

    The padded arrays live where NDArray places them (the jax default
    device — NDArray converts eagerly, so a host-side gather is not an
    option here). ParallelWrapper's numpy bind therefore pays one host
    round-trip per PADDED batch before the sharded placement; keep the
    batch size a multiple of the worker count so only the final remainder
    batch pays it."""
    n = ds.num_examples()
    if n > target:
        raise ValueError(f"batch of {n} examples exceeds the pipeline "
                         f"target batch size {target}")
    idx = np.arange(target) % n
    w = jnp.asarray((np.arange(target) < n).astype(np.float32))
    if isinstance(ds, MultiDataSet):
        out = MultiDataSet.__new__(MultiDataSet)
        out.features = [_pad_nd(f, idx) for f in ds.features]
        out.labels = [_pad_nd(l, idx) for l in ds.labels]
        out.features_masks = ([_pad_nd(m, idx) for m in ds.features_masks]
                              if ds.features_masks else None)
        out.labels_masks = ([_pad_nd(m, idx) for m in ds.labels_masks]
                            if ds.labels_masks else None)
        return out, w
    out = DataSet.__new__(DataSet)
    out.features = _pad_nd(ds.features, idx)
    out.labels = _pad_nd(ds.labels, idx)
    out.features_mask = _pad_nd(ds.features_mask, idx)
    out.labels_mask = _pad_nd(ds.labels_mask, idx)
    return out, w


def stable_batches(data: Any, batch_size: Optional[int] = None,
                   pad_partial: bool = True, drop_remainder: bool = False,
                   round_to_multiple_of: int = 1,
                   allow_multi: bool = False
                   ) -> Iterator[Tuple[Any, jnp.ndarray, int]]:
    """Yield ``(dataset, w, n_real)`` triples with a stable leading
    dimension. The target size is ``resolve_batch_size(...)`` (falling
    back to the first batch's size), rounded up to
    ``round_to_multiple_of`` (ParallelWrapper's worker-count divisibility).
    Batches already at the target get ``w`` = ones; smaller batches are
    dropped (``drop_remainder``) or padded with zero-weight wrapped rows;
    larger batches pass through unpadded (their own ones-``w``) — a
    mixed-size source degrades to today's per-shape retraces instead of
    failing."""
    target = resolve_batch_size(data, batch_size)
    prof = OpProfiler.get()
    ones_cache: dict = {}

    def ones_w(n: int) -> jnp.ndarray:
        if n not in ones_cache:
            ones_cache[n] = jnp.ones((n,), jnp.float32)
        return ones_cache[n]

    m = max(1, int(round_to_multiple_of))
    for ds in iter_datasets(data, batch_size, allow_multi=allow_multi):
        n = ds.num_examples()
        if target is None:
            target = n
        tgt = -(-target // m) * m
        if n == tgt:
            yield ds, ones_w(n), n
        elif drop_remainder and n < target:
            # a batch is a droppable REMAINDER only vs the un-rounded
            # target: full batches merely short of the worker multiple
            # must still train (padded below), else a batch_size that is
            # not a multiple of the worker count would drop EVERY batch
            prof.count("pipeline/dropped_batches")
            continue
        elif n > tgt or not pad_partial:
            # oversize or padding disabled: pass through; round up to the
            # worker multiple only (the wrapper cannot run otherwise)
            tgt_n = -(-n // m) * m
            if tgt_n == n:
                yield ds, ones_w(n), n
            else:
                prof.count("pipeline/padded_batches")
                padded, w = pad_dataset(ds, tgt_n)
                yield padded, w, n
        else:
            prof.count("pipeline/padded_batches")
            padded, w = pad_dataset(ds, tgt)
            yield padded, w, n


def device_feed(batches: Iterable, place=None, depth: int = 2) -> Iterator:
    """Stage ``place(batch)`` (device placement) ``depth`` batches ahead of
    the consumer — see ``common.background.staged_iter`` for the threading
    contract. ``depth=0`` disables lookahead (fully serial feed)."""
    if place is None:
        place = lambda b: b  # noqa: E731
    return staged_iter(batches, stage=place, depth=depth)


def timed_iter(it: Iterable, section: str = "pipeline/next_batch",
               step: int = 0):
    """Yield from ``it`` with each blocking ``next()`` timed into the
    profiler — the host-wait half of the transfer-vs-compute overlap
    ledger (the other half is the ``pipeline/dispatch`` section the fit
    loops record around step dispatch). ``step`` numbers the first item;
    each section carries the step its batch is for, as that step's
    ``pipeline/dispatch`` does."""
    prof = OpProfiler.get()
    src = iter(it)
    while True:
        try:
            with prof.time_section(section, step=step):
                item = next(src)
        except StopIteration:
            return
        yield item
        step += 1


def _poison_nan(batch):
    """Apply an injected ``nan`` fault: every floating array of the
    batch's FIRST element (features — array, dict, or list alike) is
    multiplied by NaN, which drives the step's loss and gradients
    non-finite exactly the way a corrupt record would. Composes with the
    telemetry layer's NanSentinelListener policies."""
    def nanify(a):
        if hasattr(a, "dtype") and np.issubdtype(np.dtype(a.dtype),
                                                 np.floating):
            return a * float("nan")
        return a

    return (jax.tree.map(nanify, batch[0]),) + tuple(batch[1:])


def run_epochs(data: Any, epochs: int, batch_size: Optional[int],
               pad_partial: bool, drop_remainder: bool, prefetch: int,
               steps_per_dispatch: int, bind, place, dispatch_one,
               dispatch_chunk, stackable, on_epoch,
               round_to_multiple_of: int = 1,
               allow_multi: bool = False,
               skip: Optional[Tuple[int, int]] = None,
               pre_dispatch=None, first_step: int = 0) -> None:
    """The one training-loop skeleton shared by MultiLayerNetwork.fit,
    ComputationGraph.fit, and ParallelWrapper.fit: per epoch, stable
    batches are bound (``bind(ds, w)`` → jit argument tuple), staged
    ``prefetch`` ahead through ``place``, and dispatched either per step
    or in ``steps_per_dispatch``-sized chunks — a chunk tail (or a
    shape-unstable group, per ``stackable``) falls back to the per-step
    path instead of compiling a second device loop for its length.

    **Fault tolerance** (common.faultinject): ``bind`` and ``place`` are
    wrapped in :func:`faultinject.retry_call` — transient failures
    (injected or user-marked via a ``transient`` attribute) retry with
    bounded exponential backoff, profiler-counted under
    ``pipeline/retries``. Fault-plan sites fire here deterministically:
    ``pipeline/bind`` (indexed by the fit call's batch ordinal; advisory
    ``nan`` specs poison the bound batch), ``pipeline/place``,
    ``train/step`` (indexed by dispatch ordinal; a ``crash`` spec raises
    :class:`faultinject.SimulatedCrash` before the step dispatches — the
    in-process stand-in for preemption), and ``device/loss`` (same
    indexing; a ``device_loss`` spec raises
    :class:`faultinject.DeviceLostError` naming the lost replica — the
    deterministic elastic shrink-and-continue drill).

    **Resume** (``skip=(epochs_done, steps_in_epoch)``): fast-forward a
    checkpoint cursor by REPLAYING the host side — completed epochs are
    consumed from the source (advancing any per-epoch shuffle RNG exactly
    as the killed run did) without binding or dispatching, and the resume
    epoch's first ``steps_in_epoch`` stable batches are drawn and
    discarded. Dispatch then continues with the restored params/updater/
    RNG key, making the continuation bit-identical to the uninterrupted
    run. The post-checkpoint remainder of the resume epoch replays fully,
    including its ``on_epoch`` boundary.

    ``pre_dispatch(ordinal)``: optional per-dispatch hook run after the
    generic fault points and before the dispatch — path-specific fault
    sites (the pipeline trainer's ``pipeline/stage`` stage-loss/straggler
    drills) fire here sharing the fit call's dispatch ordinal, so a drill
    plan indexes one counter regardless of which fit path runs it.

    ``first_step``: the holder's iteration as the call begins, so that a
    batch's ``pipeline/next_batch`` section carries the same ``step`` as
    the ``pipeline/dispatch`` section the holder records for it."""
    k = max(1, int(steps_per_dispatch))
    prof = OpProfiler.get()
    skip_epochs, skip_steps = skip if skip is not None else (0, 0)
    n_bound = 0       # batch ordinal within this fit call (fault indexing)
    n_dispatched = 0  # dispatch ordinal within this fit call

    def guarded_bind(ds, w):
        nonlocal n_bound
        ordinal = n_bound
        n_bound += 1

        def attempt():
            advisory = faultinject.fault_point("pipeline/bind", ordinal)
            b = bind(ds, w)
            for spec in advisory:
                if spec["kind"] == "nan":
                    b = _poison_nan(b)
            return b

        return faultinject.retry_call(attempt, "pipeline/bind")

    n_placed = [0]

    def guarded_place(b):
        ordinal = n_placed[0]
        n_placed[0] += 1

        def attempt():
            faultinject.fault_point("pipeline/place", ordinal)
            return place(b)

        return faultinject.retry_call(attempt, "pipeline/place")

    for e in range(max(1, epochs)):
        if e < skip_epochs:
            # completed pre-kill: consume (advances iterator/shuffle
            # state), dispatch nothing, and do NOT re-fire on_epoch —
            # its effects are part of the restored checkpoint state
            for _ in iter_datasets(data, batch_size,
                                   allow_multi=allow_multi):
                pass
            continue
        with flightrec.span("pipeline/epoch", epoch=e):
            gen = stable_batches(data, batch_size, pad_partial=pad_partial,
                                 drop_remainder=drop_remainder,
                                 round_to_multiple_of=round_to_multiple_of,
                                 allow_multi=allow_multi)
            if e == skip_epochs and skip_steps:
                skipped = 0
                for _ in gen:
                    skipped += 1
                    if skipped >= skip_steps:
                        break
                if skipped < skip_steps:
                    import logging

                    logging.getLogger("deeplearning4j_tpu").warning(
                        "resume cursor wants %d steps into the epoch but "
                        "the source produced %d batches — did the data "
                        "change since the checkpoint?", skip_steps, skipped)
            bound = (guarded_bind(ds, w) for ds, w, _n in gen)
            feed = timed_iter(device_feed(
                bound, place=guarded_place, depth=max(0, int(prefetch))),
                step=first_step + n_dispatched)
            if k == 1:
                for b in feed:
                    faultinject.fault_point("train/step", n_dispatched)
                    # a wedge here is a hung dispatch: the thread blocks
                    # until the supervisor's watchdog abandons it
                    # (release_wedges); a device_loss here is a replica
                    # dying BETWEEN dispatches — the holder's state stays
                    # boundary-consistent, which is what lets the
                    # supervisor shrink the data axis online instead of
                    # checkpoint-restarting
                    faultinject.fault_point("train/wedge", n_dispatched)
                    faultinject.fault_point("device/loss", n_dispatched)
                    if pre_dispatch is not None:
                        pre_dispatch(n_dispatched)
                    flightrec.event("pipeline/dispatch",
                                    ordinal=n_dispatched)
                    n_dispatched += 1
                    dispatch_one(b)
            else:
                for group in chunked(feed, k):
                    for j in range(len(group)):
                        faultinject.fault_point("train/step",
                                                n_dispatched + j)
                        faultinject.fault_point("train/wedge",
                                                n_dispatched + j)
                        faultinject.fault_point("device/loss",
                                                n_dispatched + j)
                        if pre_dispatch is not None:
                            pre_dispatch(n_dispatched + j)
                    flightrec.event("pipeline/dispatch",
                                    ordinal=n_dispatched,
                                    steps=len(group))
                    n_dispatched += len(group)
                    if len(group) == k and stackable(group):
                        dispatch_chunk(group)
                    else:
                        for b in group:
                            dispatch_one(b)
            with prof.time_section("fit/epoch_end", epoch=e):
                on_epoch()
                # HBM watermark: one live-buffer census per epoch (the
                # same walk /api/health serves) feeds the per-phase peak
                # gauges — epoch cadence, never per dispatch
                xprof.memory_watermark("fit")


def note_steps(holder: Any, listeners: Iterable, losses,
               auxes: Optional[List] = None) -> None:
    """Shared post-dispatch bookkeeping for every fit loop: advance the
    holder's iteration counter, publish the DEVICE loss scalar (listeners
    sync at their own print/collect boundaries, never here), and notify
    listeners once per step — identical whether the losses came from one
    per-step dispatch or a K-step scan chunk. ``auxes`` (aligned with
    ``losses``) carries the in-graph telemetry pytrees of DEVICE values
    when the step was built with telemetry; listeners exposing
    ``telemetry_done`` receive them un-synced (TelemetrySink /
    NanSentinelListener batch their own readbacks)."""
    last = len(losses) - 1
    for i, loss in enumerate(losses):
        holder._iteration += 1
        # resume-cursor bookkeeping: steps completed within the current
        # epoch (reset by the fit loops' on_epoch), and whether the
        # holder's published params correspond to THIS step — inside a
        # scan chunk they only do at the final step, so checkpoint-style
        # listeners defer their snapshot to the dispatch boundary
        holder._steps_in_epoch = getattr(holder, "_steps_in_epoch", 0) + 1
        holder._at_dispatch_boundary = (i == last)
        holder._score_dev = loss
        aux = auxes[i] if auxes is not None else None
        for lst in listeners:
            lst.iteration_done(holder, holder._iteration, loss)
            if aux is not None:
                cb = getattr(lst, "telemetry_done", None)
                if cb is not None:
                    cb(holder, holder._iteration, aux)


def unstack_aux(auxes, k: int) -> List:
    """Split a scan-stacked telemetry aux pytree ([K, ...] leaves) into K
    per-step pytrees of device values (lazy slices — no host sync)."""
    return [jax.tree.map(lambda a, _i=i: a[_i], auxes) for i in range(k)]


def note_dispatch(holder: Any, listeners: Iterable, out, telemetry: bool,
                  k: Optional[int] = None) -> None:
    """Decode ONE train-step (``k=None``) or scan-chunk (``k`` steps)
    output — a 4-tuple, or a 5-tuple carrying the telemetry aux when the
    step was built with it — publish the carried state onto ``holder``,
    then run :func:`note_steps`. The single place the step builders'
    return contract is unpacked; all three networks' dispatchers share it.

    Ordering matters: the holder's ``_params``/``_states``/
    ``_updater_state`` MUST be replaced before listeners run — the step
    donated the old buffers, so a listener reading ``model._params``
    during ``iteration_done`` (StatsListener, EvaluativeListener) would
    otherwise touch deleted arrays."""
    params, states, upd = out[0], out[1], out[2]
    holder._params, holder._states, holder._updater_state = \
        params, states, upd
    if k is None:
        loss = out[3]
        note_steps(holder, listeners, [loss],
                   [out[4]] if telemetry else None)
        return
    losses = out[3]
    note_steps(holder, listeners, [losses[i] for i in range(k)],
               unstack_aux(out[4], k) if telemetry else None)


def chunked(it: Iterable, k: int) -> Iterator[List]:
    """Group ``k`` items per yield for multi-step dispatch; the final
    group may be shorter (the fit loops run it through the per-step path
    rather than compiling a second device loop for the tail)."""
    if k < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")
    group: List = []
    for item in it:
        group.append(item)
        if len(group) == k:
            yield group
            group = []
    if group:
        yield group
