"""Background-thread producer/consumer helpers.

``prefetch_iter`` is the generic core of the overlap pattern
``AsyncDataSetIterator`` uses for ETL (reference:
``AsyncDataSetIterator``'s blocking queue): run a generator on a worker
thread, hand items to the consumer through a bounded queue, propagate
exceptions, and never leave the worker blocked if the consumer abandons
the iteration. Word2Vec uses it to overlap host pair-generation with
device training rounds (reference analog: the 20-thread
``SequenceVectors`` fit loop keeps the JNI kernels fed; here ONE producer
thread keeps the XLA dispatch queue fed).
"""

from __future__ import annotations

import collections
import queue
import threading
from typing import Callable, Iterable, Iterator, List, Optional, TypeVar

T = TypeVar("T")
U = TypeVar("U")

_END = object()


def prefetch_iter(source: Iterable[T], maxsize: int = 8) -> Iterator[T]:
    """Yield items of ``source``, produced on a background thread through
    a bounded queue of ``maxsize`` items.

    Exceptions raised by ``source`` re-raise at the consuming site after
    already-produced items drain. Abandoning the returned iterator
    (``break`` / GC) releases the worker.
    """
    q: "queue.Queue" = queue.Queue(maxsize=maxsize)
    stop = threading.Event()
    err: List[BaseException] = []

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for item in source:
                if stop.is_set() or not _put(item):
                    return
        except BaseException as e:
            err.append(e)
        finally:
            _put(_END)

    t = threading.Thread(target=worker, daemon=True,
                         name="dl4j-prefetch-worker")
    t.start()
    try:
        while True:
            item = q.get()
            if item is _END:
                break
            yield item
        if err:
            # re-raising the ORIGINAL exception object surfaces the
            # producer's frames at the consuming site: its __traceback__
            # (captured on the worker thread) is preserved and the
            # consumer's raise appends this frame to it
            raise err[0]
    finally:
        stop.set()
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass
        t.join(timeout=5.0)


def staged_iter(source: Iterable[T],
                stage: Optional[Callable[[T], U]] = None,
                depth: int = 2,
                host_prefetch: int = 0) -> Iterator[U]:
    """Double-buffered staging: yield ``stage(item)`` for each item of
    ``source``, with ``stage`` issued up to ``depth`` items AHEAD of the
    consumer.

    This is the async-device-feed core of the training input pipeline:
    ``stage`` is typically ``jax.device_put`` (or a sharded placement),
    which returns immediately while the H2D copy proceeds asynchronously —
    so with ``depth`` >= 1 the transfer of batch *n+1* overlaps the
    consumer's compute on batch *n*, and ``depth`` = 2 keeps one extra
    batch in flight (classic double buffering). Device memory held is
    bounded by ``depth`` staged batches.

    ``stage`` runs on the CONSUMER thread: chosen on a set-up that is
    gone (worker-thread device_put serialized there); not measured on
    this chip. Consumer-side device_put is itself async. Host-side work
    (decode / vectorize / pad) can still run on a worker thread by passing
    ``host_prefetch`` > 0, which routes ``source`` through
    :func:`prefetch_iter` with that queue size.
    """
    if depth < 0:
        raise ValueError(f"depth must be >= 0, got {depth}")
    it: Iterator[T] = (prefetch_iter(source, maxsize=host_prefetch)
                       if host_prefetch > 0 else iter(source))
    if stage is None:
        stage = lambda x: x  # noqa: E731
    buf: "collections.deque" = collections.deque()
    try:
        for item in it:
            buf.append(stage(item))
            if len(buf) > depth:
                yield buf.popleft()
        while buf:
            yield buf.popleft()
    finally:
        # an abandoned staged_iter must close the inner prefetch
        # generator NOW (running its finally: stop + drain + join) rather
        # than leaving the worker thread to GC timing — tests that break
        # out of a fit epoch would otherwise leak daemon threads
        close = getattr(it, "close", None)
        if close is not None:
            close()
