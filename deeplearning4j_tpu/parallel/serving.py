"""Production inference serving: continuous batching over AOT shape buckets.

The millions-of-users tier (ROADMAP item 2). :class:`ParallelInference`
gives this stack a replica pool with health probes, retirement,
resurrection and per-request deadlines — but it dispatches each coalesced
batch AT ITS OWN SHAPE, so concurrent traffic at varying batch/sequence
sizes retraces and serializes behind jit compiles. This module closes the
gap with the compile-once-run-many recipe the whole-graph-compilation
literature argues for (TVM, arXiv:1802.04799; nGraph, arXiv:1801.08058):

- **Shape buckets** (:class:`BucketLadder`): a configurable batch-size
  ladder (and optional sequence-length ladder). Every request is padded UP
  to the smallest admitting bucket, so the set of shapes the model ever
  sees is small, fixed, and known at startup.
- **AOT executables per bucket**: each bucket's inference function is
  ``jax.jit(...).lower(...).compile()``-d at pool startup
  (:meth:`ServingEngine.warmup`), so steady-state serving NEVER traces —
  the ``serving/traces_after_warmup`` counter must stay 0 and the
  serving-smoke bench hard-fails when it doesn't. Warmup cost is paid
  once, up front, per bucket (the ``serving/warmup`` profiler section
  ledgers it).
- **Pad-and-mask reuse**: bucket padding is :func:`data.pipeline.pad_rows`
  — the SAME wrap-real-rows rule the training pipeline uses, so padding
  rows are provably inert: a pad slot is an exact copy of a real row,
  per-example inference computes for it exactly what it computed for the
  real row, and the scatter slices it off. ``tests/test_serving.py``
  proves the bucketed output BITWISE-equal to an unpadded direct
  ``model.output``. (BatchNorm is no caveat here: inference-mode BN uses
  running stats, which are per-example.)
- **Continuous batching**: replica workers drain the shared request queue
  into the largest fillable bucket under a ``max_wait_ms`` deadline — a
  request that would overflow the largest bucket (or mismatch the batch's
  non-batch shape) is stashed for the next batch, never dropped.
- **bf16 inference params** (``Builder.bf16(True)``): one cast at startup
  (and on :meth:`refresh_params`), halving weight bytes and engaging the
  bf16 matmul units; inputs/outputs stay float32 at the API boundary.
  Numerics change (~1e-2 relative) — the bitwise guarantee above is the
  fp32 path's.
- **Replica-pool integration**: ServingEngine IS a ParallelInference — it
  inherits retirement, health-probe resurrection, deadlines and shutdown
  draining. Retirement is additionally TRANSPARENT to in-flight requests:
  a dying replica's batch is requeued (bounded by ``max_requeues``, true
  queue-entry timestamps preserved) instead of failed, so the
  kill-a-replica-mid-load drill completes with zero failed requests while
  the PR-4 resurrection machinery refills the pool. When the LAST replica
  dies, queued requests still fail fast (the pool's bounded-latency
  contract outranks transparency).

**Admission rule for oversize requests** (documented contract): a request
with more rows than the largest batch bucket is, under
``oversize="split"`` (the default), split into largest-bucket-sized
chunks served independently and re-concatenated in order — its latency is
then bounded by ``ceil(n/max_batch)`` bucket dispatches; under
``oversize="reject"`` it raises :class:`OversizeRequest` synchronously at
submission, before anything is queued. A sequence length over the ladder
ALWAYS rejects — time steps cannot be split across executables by a
serving layer that does not know the model's temporal semantics.

**Overload safety** (ISSUE 11): requests optionally carry an SLO CLASS
(:class:`SLOClass` — e.g. ``gold``/``silver``/``batch``, each with a
priority, a p99 budget, and a per-class queue budget). Admission is
synchronous: a shed request gets :class:`Overloaded` (HTTP 429) with a
``Retry-After`` derived from the MEASURED queue drain rate, never a slot
in a queue it would time out of. Under overload the
:class:`BrownoutController` sheds classes strictly
lowest-priority-first — one level step per controller tick, cleared only
after several consecutive clean evaluations (hysteresis; a request is
never flapped) — defending the top class's p99 budget. The queue-depth
signal is a decaying WINDOWED high-water mark (``queue_depth_hwm``; the
lifetime max lives separately in ``queue_depth_peak``), so it can drive
scale-DOWN as well as scale-up.

**Elastic capacity**: ``scale_to(n)`` grows/shrinks the worker pool
online — new workers reuse the already-compiled bucket executables
(recompiles stay at one per bucket x device slot at ANY replica count),
surplus workers exit at a batch boundary. The closed-loop autoscaler
driving it from the windowed HWM / rolling p99 / fill-ratio signals is
:class:`parallel.autoscale.Autoscaler`.

**Canaried train-to-serve handoff**: :meth:`ServingEngine.
publish_checkpoint` hot-swaps retrained weights onto ONE canary replica
(zero recompiles — the executables take params as arguments), promotes
fleet-wide after an SLO-clean window, and auto-rollbacks BITWISE (the
exact prior device arrays are restored) on violation; the ``pub<N>``
correlation id chains train-commit -> canary -> promote/rollback in the
flight recorder.

HTTP serving lives on the existing UI server: ``UIServer.attach_serving``
exposes ``POST /api/infer`` next to ``/api/health`` (whose ``serving``
section is :func:`serving_health`); sheds map to ``429`` +
``Retry-After``. Load-test with ``python bench.py --config
serving-smoke`` (open-loop Poisson, hard-fail p50/p99/QPS SLO gates,
kill-a-replica drill) and ``--config autoscale-smoke`` (diurnal + spike
replay at 5x the serving-smoke rate, shed-order/scale-latency/canary
gates).
"""

from __future__ import annotations

import collections
import os
import queue
import threading
import time
import weakref
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..common import faultinject, flightrec, xprof
from ..common import integrity as _integ
from ..common.profiler import OpProfiler
from ..data.pipeline import pad_rows
from ..ndarray.ndarray import NDArray
from ..ndarray.rng import get_random
from .inference import ParallelInference, _Request, logger
from .mesh import serving_devices

# live engines, for the /api/health serving census (weak: dropped → gone)
_ENGINES: "weakref.WeakSet" = weakref.WeakSet()

# process-global publication ordinal: the pub<N> correlation id must be
# unique across every engine's lifetime or one grep of the timeline
# could conflate two publications (it is also the serving/promote fault
# drill index — see next_publication_ordinal)
_pub_lock = threading.Lock()
_pub_next = [0]


def next_publication_ordinal() -> int:
    """The ordinal (= ``serving/promote`` fault index, = the N in the
    ``pub<N>`` correlation id) the NEXT ``publish_checkpoint`` call will
    get — how drills target a specific publication deterministically."""
    with _pub_lock:
        return _pub_next[0]

_MISS = object()     # _exec sentinel: None is a real (generic-model) entry


class OversizeRequest(ValueError):
    """A request the bucket ladder refuses to admit: more rows than the
    largest batch bucket under ``oversize="reject"``, or a sequence longer
    than the largest sequence bucket (never splittable). Raised
    synchronously at submission — nothing is queued."""


class BucketLadder:
    """The bucket policy: sorted batch-size ladder, optional sequence-
    length ladder, and the oversize admission rule (see module docstring).

    ``bucket_batch(n)`` / ``bucket_seq(t)`` return the smallest admitting
    rung; ``admit(n)`` returns the chunk row-counts a request is served
    as (``[n]`` for an in-ladder request)."""

    def __init__(self, batch_sizes: Sequence[int] = (1, 2, 4, 8, 16, 32),
                 seq_lens: Optional[Sequence[int]] = None,
                 oversize: str = "split"):
        sizes = sorted({int(b) for b in batch_sizes})
        if not sizes or sizes[0] < 1:
            raise ValueError(f"batch ladder needs positive sizes, got "
                             f"{batch_sizes!r}")
        self.batch_sizes: Tuple[int, ...] = tuple(sizes)
        self.seq_lens: Optional[Tuple[int, ...]] = None
        if seq_lens is not None:
            sl = sorted({int(t) for t in seq_lens})
            if not sl or sl[0] < 1:
                raise ValueError(f"sequence ladder needs positive lengths, "
                                 f"got {seq_lens!r}")
            self.seq_lens = tuple(sl)
        if oversize not in ("split", "reject"):
            raise ValueError(f"oversize must be 'split' or 'reject', got "
                             f"{oversize!r}")
        self.oversize = oversize

    @property
    def max_batch(self) -> int:
        return self.batch_sizes[-1]

    def bucket_batch(self, n: int) -> Optional[int]:
        """Smallest batch bucket >= n, or None when n exceeds the ladder."""
        for b in self.batch_sizes:
            if b >= n:
                return b
        return None

    def bucket_seq(self, t: int) -> int:
        """Smallest sequence bucket >= t. Oversize sequences ALWAYS
        reject (module docstring: time steps cannot be split)."""
        assert self.seq_lens is not None
        for s in self.seq_lens:
            if s >= t:
                return s
        raise OversizeRequest(
            f"sequence length {t} exceeds the largest sequence bucket "
            f"{self.seq_lens[-1]}; lengthen the ladder or truncate "
            f"upstream")

    def admit(self, n: int) -> List[int]:
        """The admission rule. Raises :class:`OversizeRequest` under
        ``oversize='reject'``; splits into max-bucket chunks (+ remainder)
        under ``'split'``."""
        if n < 1:
            raise ValueError(f"a request needs at least one row, got {n}")
        if n <= self.max_batch:
            return [n]
        if self.oversize == "reject":
            raise OversizeRequest(
                f"request of {n} rows exceeds the largest batch bucket "
                f"{self.max_batch} (oversize='reject'); split it client-"
                f"side or configure oversize='split'")
        chunks = [self.max_batch] * (n // self.max_batch)
        if n % self.max_batch:
            chunks.append(n % self.max_batch)
        return chunks

    def shapes(self, feat: Tuple[int, ...]) -> List[Tuple[int, ...]]:
        """Every input shape the ladder admits — the warmup compile set.
        ``feat`` is the per-request feature shape (no batch dim); with a
        sequence ladder its leading entry is the time axis and is replaced
        by each sequence rung."""
        if self.seq_lens is None:
            return [(b,) + tuple(feat) for b in self.batch_sizes]
        if not feat:
            raise ValueError("a sequence ladder needs a feature shape "
                             "with a leading time axis")
        return [(b, t) + tuple(feat[1:])
                for b in self.batch_sizes for t in self.seq_lens]


# THE fp32-boundary cast, shared with the training side's low-precision
# updater state — learning/precision.py owns the dtype-boundary rules
# (one doc, one helper; this module used to carry its own copy).
from ..learning.precision import cast_floating as _cast_floating


class Overloaded(RuntimeError):
    """Synchronous load-shed rejection (the HTTP tier maps it to 429):
    the engine is inside a brownout and this request's SLO class is
    currently shed, or the class's queue budget is exhausted. Carries
    ``retry_after_s`` derived from the MEASURED queue drain rate (the
    ``Retry-After`` header), so clients back off proportionally to the
    actual backlog instead of a fixed guess. Raised at submission —
    nothing is queued."""

    def __init__(self, message: str, slo_class: str, reason: str,
                 retry_after_s: float):
        super().__init__(message)
        self.slo_class = slo_class
        self.reason = reason          # "brownout" | "queue_budget" | "fault"
        self.retry_after_s = float(retry_after_s)


class SLOClass:
    """One admission class. ``priority`` orders shedding — strictly
    lowest-priority-first, and the top class is NEVER shed. ``p99_ms``
    is the class's latency budget: the top class's budget is what the
    brownout controller defends and what the canary publication's
    SLO-clean window defaults to. ``queue_budget`` bounds how many
    requests of this class may be outstanding at once (per-class
    backpressure: one flooding tenant cannot fill the shared queue for
    everyone else)."""

    def __init__(self, name: str, priority: int, p99_ms: float,
                 queue_budget: int = 128):
        self.name = str(name)
        self.priority = int(priority)
        self.p99_ms = float(p99_ms)
        self.queue_budget = int(queue_budget)
        if not self.name:
            raise ValueError("an SLO class needs a non-empty name")
        if self.p99_ms <= 0 or self.queue_budget < 1:
            raise ValueError(f"SLO class {name!r} needs p99_ms > 0 and "
                             f"queue_budget >= 1")

    def __repr__(self) -> str:
        return (f"SLOClass({self.name!r}, priority={self.priority}, "
                f"p99_ms={self.p99_ms}, queue_budget={self.queue_budget})")


class AdmissionController:
    """Per-class admission state: outstanding counts against queue
    budgets, the brownout shed LEVEL (0 admits everything; level k sheds
    the k lowest-priority classes), completion-rate tracking for
    ``Retry-After``, and the per-class shed counters
    (``serving/shed/<class>``). Shedding is strictly bottom-up BY CLASS
    and the level only moves at controller cadence with hysteresis
    (:class:`BrownoutController`) — an individual request is never
    flapped: its class is either shed right now or it is not."""

    DRAIN_WINDOW_S = 5.0

    def __init__(self, classes: Sequence[SLOClass],
                 default: Optional[str] = None):
        classes = list(classes)
        if not classes:
            raise ValueError("admission control needs >= 1 SLO class")
        names = [c.name for c in classes]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate SLO class names: {names}")
        if len({c.priority for c in classes}) != len(classes):
            raise ValueError("SLO class priorities must be unique — they "
                             "define the shed order")
        self._lock = threading.Lock()
        # ascending priority: index 0 sheds first, the last never sheds
        self.by_shed_order: Tuple[SLOClass, ...] = tuple(
            sorted(classes, key=lambda c: c.priority))
        self.top = self.by_shed_order[-1]
        self.by_name = {c.name: c for c in classes}
        self._rank = {c.name: i for i, c in enumerate(self.by_shed_order)}
        self.default = default if default is not None else self.top.name
        if self.default not in self.by_name:
            raise ValueError(f"default class {self.default!r} is not one "
                             f"of the configured SLO classes {names}")
        self._level = 0
        self._outstanding: Dict[str, int] = {c.name: 0 for c in classes}
        self._done: "collections.deque" = collections.deque(maxlen=4096)

    def resolve(self, name: Optional[str]) -> SLOClass:
        if name is None:
            name = self.default
        cls = self.by_name.get(name)
        if cls is None:
            raise ValueError(f"unknown SLO class {name!r}; configured: "
                             f"{sorted(self.by_name)}")
        return cls

    def level(self) -> int:
        with self._lock:
            return self._level

    def shed_names(self) -> List[str]:
        with self._lock:
            return [c.name for c in self.by_shed_order[:self._level]]

    def set_level(self, level: int, reason: str = "manual") -> int:
        """Move the shed level (the brownout controller's actuator, and
        the deterministic overload drill hook). Clamped so the top class
        is never shed. A CHANGE emits one ``serving/shed`` event and
        updates the ``serving/shed_level`` gauge — per level transition,
        never per request."""
        level = max(0, min(int(level), len(self.by_shed_order) - 1))
        with self._lock:
            prev = self._level
            self._level = level
        if level != prev:
            prof = OpProfiler.get()
            prof.gauge("serving/shed_level", level)
            prof.count("serving/brownout_raise" if level > prev
                       else "serving/brownout_lower")
            flightrec.event(
                "serving/shed", severity="warn", level=level, prev=prev,
                shed=[c.name for c in self.by_shed_order[:level]],
                reason=str(reason)[:200])
            logger.warning("serving brownout level %d -> %d (%s)", prev,
                           level, reason)
        return level

    def note_queued(self, name: str) -> None:
        with self._lock:
            self._outstanding[name] = self._outstanding.get(name, 0) + 1

    def release(self, name: str, n: int = 1) -> None:
        """Return ``n`` reserved slots WITHOUT recording completions —
        for an admitted request that never reached the queue (an
        injected enqueue fault); completions go through note_done so
        the drain rate only counts work that actually drained."""
        with self._lock:
            self._outstanding[name] = max(
                0, self._outstanding.get(name, 0) - n)

    def note_done(self, name: str) -> None:
        with self._lock:
            self._outstanding[name] = max(
                0, self._outstanding.get(name, 0) - 1)
            self._done.append(time.monotonic())

    def _drain_rate_locked(self, now: float) -> float:
        recent = sum(1 for t in self._done
                     if now - t <= self.DRAIN_WINDOW_S)
        return recent / self.DRAIN_WINDOW_S

    def retry_after_s(self) -> float:
        """Backlog / measured drain rate, clamped to [0.1s, 30s] — how
        long a shed client should wait before the queue has plausibly
        drained. With no completions observed yet the estimate falls
        back to a per-request pessimistic constant."""
        now = time.monotonic()
        with self._lock:
            outstanding = sum(self._outstanding.values())
            rate = self._drain_rate_locked(now)
        if rate <= 0:
            return min(30.0, 1.0 + outstanding * 0.05)
        return float(min(30.0, max(0.1, outstanding / rate)))

    def admit(self, cls: SLOClass, n_chunks: int = 1) -> None:
        """The admission decision: raises :class:`Overloaded` when the
        class is inside the brownout shed set or its queue budget is
        exhausted; otherwise RESERVES ``n_chunks`` outstanding slots
        under the same lock (check-then-reserve atomically — concurrent
        HTTP threads must not all pass the same budget headroom) and
        returns. The caller releases the reservation via the per-chunk
        completion callbacks (:meth:`note_done`) or, for a submission
        that never reaches the queue, :meth:`release`."""
        with self._lock:
            if self._rank[cls.name] < self._level:
                reason = "brownout"
            elif self._outstanding.get(cls.name, 0) + n_chunks \
                    > cls.queue_budget:
                reason = "queue_budget"
            else:
                self._outstanding[cls.name] = \
                    self._outstanding.get(cls.name, 0) + n_chunks
                return
        self.count_shed(cls.name)
        ra = self.retry_after_s()
        raise Overloaded(
            f"request shed ({reason}): class {cls.name!r} "
            + ("is inside the brownout shed set"
               if reason == "brownout" else
               f"already has {cls.queue_budget} request(s) outstanding "
               f"(its queue budget)")
            + f"; retry after {ra:.2f}s", cls.name, reason, ra)

    @staticmethod
    def count_shed(name: str) -> None:
        prof = OpProfiler.get()
        prof.count(f"serving/shed/{name}")
        prof.count("serving/shed_total")

    def stats(self) -> Dict[str, Any]:
        now = time.monotonic()
        with self._lock:
            return {
                "level": self._level,
                "shed": [c.name for c in self.by_shed_order[:self._level]],
                "classes": [c.name for c in reversed(self.by_shed_order)],
                "outstanding": dict(self._outstanding),
                "drain_rate_rps": round(self._drain_rate_locked(now), 3),
            }


class BrownoutController:
    """Keeps the TOP class inside its p99 budget by progressively
    shedding lower classes. Evaluates at a fixed cadence (never
    per-request): the level RAISES one step when the top class's recent
    p99 exceeds its budget or the windowed queue-depth HWM crosses the
    depth trigger, and LOWERS one step only after ``clear_ticks``
    consecutive clean evaluations (p99 under ``hysteresis_frac`` x
    budget AND depth back under half the trigger). The asymmetry is the
    hysteresis: overload sheds within one controller interval, recovery
    un-sheds slowly enough that an oscillating load cannot flap a class
    in and out of admission."""

    def __init__(self, engine: "ServingEngine", adm: AdmissionController,
                 interval_s: float = 0.2,
                 depth_trigger: Optional[int] = None,
                 clear_ticks: int = 5, hysteresis_frac: float = 0.7):
        self.engine = engine
        self.adm = adm
        self.interval_s = float(interval_s)
        self.depth_trigger = (int(depth_trigger) if depth_trigger
                              else max(8, engine._queue.maxsize // 4))
        self.clear_ticks = max(1, int(clear_ticks))
        self.hysteresis_frac = float(hysteresis_frac)
        self._clean = 0           # single-writer: the controller thread
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="dl4j-serving-brownout")
        self._thread.start()

    def evaluate(self, p99_ms: Optional[float], depth: int) -> int:
        """One control decision from measured signals (split out so
        tests and drills drive the hysteresis deterministically).
        Returns the level in force after the decision."""
        top = self.adm.top
        over = ((p99_ms is not None and p99_ms > top.p99_ms)
                or depth >= self.depth_trigger)
        level = self.adm.level()
        if over:
            self._clean = 0
            if level < len(self.adm.by_shed_order) - 1:
                return self.adm.set_level(
                    level + 1,
                    reason=f"overload: top p99={p99_ms and round(p99_ms, 1)}"
                           f"ms (budget {top.p99_ms}ms), depth={depth} "
                           f"(trigger {self.depth_trigger})")
            return level
        clean = ((p99_ms is None
                  or p99_ms <= self.hysteresis_frac * top.p99_ms)
                 and depth <= self.depth_trigger // 2)
        if not clean:
            self._clean = 0
            return level
        if level > 0:
            self._clean += 1
            if self._clean >= self.clear_ticks:
                self._clean = 0
                return self.adm.set_level(
                    level - 1, reason=f"recovered: {self.clear_ticks} "
                                      f"clean evaluations")
        return self.adm.level()

    def _run(self) -> None:
        eng = self.engine
        while not eng._shutdown:
            time.sleep(self.interval_s)
            if eng._shutdown:
                return
            try:
                self.evaluate(
                    eng._class_recent_p99(self.adm.top.name),
                    eng.queue_depth_hwm())
            except Exception:
                logger.warning("brownout evaluation failed", exc_info=True)


class PublishHandle:
    """Tracks one canaried weight publication to its terminal state.
    ``result(timeout)`` blocks for ``"promoted"`` (SLO-clean canary +
    confirm windows; the fleet serves the new weights) or
    ``"rolled_back"`` (a violation anywhere restored the prior params
    bitwise). ``corr`` is the flight-recorder correlation id chaining
    train-commit -> canary -> promote/rollback."""

    def __init__(self, corr: str, path: str):
        self.corr = corr
        self.path = path
        self.phase = "canary"
        self._done = threading.Event()
        self._outcome: Optional[str] = None

    def _finish(self, outcome: str) -> None:
        self._outcome = outcome
        self.phase = outcome
        self._done.set()

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> str:
        if not self._done.wait(timeout):
            raise TimeoutError(f"publication {self.corr} still in phase "
                               f"{self.phase!r}")
        return self._outcome


class ServingEngine(ParallelInference):
    """The serving tier: a ParallelInference replica pool whose workers
    drain the shared queue into padded shape buckets served by
    AOT-compiled executables. See the module docstring for the policy
    contract; see :class:`Builder` for knobs."""

    class Builder(ParallelInference.Builder):
        def __init__(self, model):
            super().__init__(model)
            self._max_wait_ms = 2.0      # serving default: tight window
            self._ladder: Optional[BucketLadder] = None
            self._input_shape: Optional[Tuple[int, ...]] = None
            self._in_dtype = np.float32
            self._bf16 = False
            self._warmup = True
            self._max_requeues = 2
            self._pin_devices = False
            self._slo_classes: Optional[List[SLOClass]] = None
            self._default_class: Optional[str] = None
            self._brownout_kw: Dict[str, Any] = {}
            self._qwin_window_s = 5.0

        def inference_mode(self, mode: str) -> "ServingEngine.Builder":
            """Serving IS continuous batching — the drain loop, stash and
            bucket fill only exist in batched mode, so anything else is
            refused loudly instead of silently coerced."""
            if mode.lower() != "batched":
                raise ValueError(
                    f"ServingEngine only serves in 'batched' mode (its "
                    f"continuous-batching drain loop IS the engine), got "
                    f"{mode!r}; use a plain ParallelInference for "
                    f"sequential dispatch")
            return self

        inferenceMode = inference_mode

        def buckets(self, batch_sizes: Sequence[int],
                    seq_lens: Optional[Sequence[int]] = None,
                    oversize: str = "split") -> "ServingEngine.Builder":
            """The bucket ladder (see :class:`BucketLadder`)."""
            self._ladder = BucketLadder(batch_sizes, seq_lens, oversize)
            return self

        def ladder(self, ladder: BucketLadder) -> "ServingEngine.Builder":
            self._ladder = ladder
            return self

        def input_shape(self, shape: Sequence[int],
                        dtype=np.float32) -> "ServingEngine.Builder":
            """Per-request feature shape (WITHOUT the batch dim) — what
            warmup compiles against. With a sequence ladder the leading
            entry is the time axis (any value; the ladder replaces it)."""
            self._input_shape = tuple(int(s) for s in shape)
            self._in_dtype = np.dtype(dtype)
            return self

        def bf16(self, enabled: bool = True) -> "ServingEngine.Builder":
            """Serve with bfloat16 params (one startup cast; float32 at
            the API boundary). Numerics caveat in the module docstring."""
            self._bf16 = enabled
            return self

        def warmup(self, enabled: bool) -> "ServingEngine.Builder":
            """Compile the bucket set at build() (default). Disabling
            defers each bucket's compile to its first hit — only for
            tests; production startup should eat the cost up front."""
            self._warmup = enabled
            return self

        def max_requeues(self, n: int) -> "ServingEngine.Builder":
            """How many replica deaths one request may ride through
            (requeue budget) before it fails like the replica did."""
            self._max_requeues = max(0, int(n))
            return self

        def slo_classes(self, classes: Sequence[SLOClass],
                        default: Optional[str] = None
                        ) -> "ServingEngine.Builder":
            """Enable SLO-class admission control: requests carry a
            class (``output_async(x, slo_class="gold")``; ``default``
            names the class an unclassified request gets — the TOP class
            when omitted). Under overload the brownout controller sheds
            classes strictly lowest-priority-first with a synchronous
            :class:`Overloaded` (HTTP 429 + Retry-After); each class's
            ``queue_budget`` bounds its outstanding requests."""
            self._slo_classes = [c if isinstance(c, SLOClass)
                                 else SLOClass(*c) for c in classes]
            self._default_class = default
            return self

        def queue_hwm_window(self, seconds: float
                             ) -> "ServingEngine.Builder":
            """Window length of the decaying queue-depth high-water mark
            (it decays to 0 within ~2 windows of the backlog clearing);
            the autoscaler's scale-down latency is bounded below by it."""
            self._qwin_window_s = float(seconds)
            return self

        def brownout(self, interval_s: Optional[float] = None,
                     depth_trigger: Optional[int] = None,
                     clear_ticks: Optional[int] = None,
                     hysteresis_frac: Optional[float] = None
                     ) -> "ServingEngine.Builder":
            """Tune the brownout controller (only meaningful with
            :meth:`slo_classes`); see :class:`BrownoutController` for
            the semantics of each knob."""
            for k, v in (("interval_s", interval_s),
                         ("depth_trigger", depth_trigger),
                         ("clear_ticks", clear_ticks),
                         ("hysteresis_frac", hysteresis_frac)):
                if v is not None:
                    self._brownout_kw[k] = v
            return self

        def pin_devices(self, enabled: bool = True
                        ) -> "ServingEngine.Builder":
            """Pin replica workers round-robin across devices
            (:func:`mesh.serving_devices`): each replica gets its own
            device-resident param copy and per-device executables, so
            replicas run on different chips instead of contending for one
            XLA stream. Costs one param copy + one compile set per
            distinct device."""
            self._pin_devices = enabled
            return self

        def build(self) -> "ServingEngine":
            if self._input_shape is None:
                raise ValueError(
                    "ServingEngine needs Builder.input_shape(...): the "
                    "AOT bucket executables are compiled against it at "
                    "warmup, before any request arrives")
            return ServingEngine(
                self._model, self._ladder or BucketLadder(),
                self._input_shape, in_dtype=self._in_dtype,
                bf16=self._bf16, warmup=self._warmup,
                max_requeues=self._max_requeues,
                pin_devices=self._pin_devices,
                slo_classes=self._slo_classes,
                default_class=self._default_class,
                brownout_kw=self._brownout_kw,
                queue_hwm_window_s=self._qwin_window_s,
                batch_limit=self._batch_limit,
                queue_limit=self._queue_limit,
                max_wait_ms=self._max_wait_ms, workers=self._workers,
                request_timeout_ms=self._request_timeout_ms,
                resurrect=self._resurrect,
                resurrect_backoff_ms=self._resurrect_backoff_ms,
                max_resurrections=self._max_resurrections)

    def __init__(self, model, ladder: BucketLadder,
                 input_shape: Tuple[int, ...], in_dtype=np.float32,
                 bf16: bool = False, warmup: bool = True,
                 max_requeues: int = 2, pin_devices: bool = False,
                 slo_classes: Optional[Sequence[SLOClass]] = None,
                 default_class: Optional[str] = None,
                 brownout_kw: Optional[Dict[str, Any]] = None,
                 queue_hwm_window_s: float = 5.0,
                 **pool_kwargs):
        # subclass state FIRST: super().__init__ starts the drain threads,
        # which call into the overridden _drain immediately
        self.ladder = ladder
        self._feat = tuple(input_shape)
        self._in_dtype = np.dtype(in_dtype)
        self._bf16 = bf16
        self.max_requeues = max_requeues
        self._compute_dtype = jnp.bfloat16 if bf16 else None
        self._adm = (AdmissionController(slo_classes, default=default_class)
                     if slo_classes else None)
        # decaying/windowed queue-depth high-water mark (two rolling
        # windows; the scale-down-capable signal) + the lifetime peak
        self._qwin_s = float(queue_hwm_window_s)
        self._qwin_start = time.monotonic()
        self._qwin_max = 0
        self._qwin_prev = 0
        self._q_peak = 0
        self._last_dispatch_t = time.monotonic()
        self._lat_recent: "collections.deque" = collections.deque(
            maxlen=2048)                 # (t_done, latency_s), all classes
        self._class_lats: Dict[str, "collections.deque"] = {}
        self._canary: Optional[Dict[str, Any]] = None
        self._pub_threads: List[threading.Thread] = []
        self._brownout: Optional[BrownoutController] = None
        self._devices = (serving_devices(pool_kwargs.get("workers", 1))
                         if pin_devices else [None])
        # worker -> pinned device slot; a retired worker's slot is freed
        # for its replacement (resurrection mints NEW worker ids, so a
        # plain worker_id % ndev would drift every pool generation onto
        # the wrong chips)
        self._dev_of: Dict[int, int] = {}
        self._dev_free: List[int] = []
        self._stash_lock = threading.Lock()
        self._stashq: "collections.deque" = collections.deque()
        self._exec: Dict[Any, Any] = {}     # (shape, dev_idx) -> runner
        self._exec_lock = threading.Lock()
        self._lat_lock = threading.Lock()
        self._latencies: "collections.deque" = collections.deque(maxlen=4096)
        self._batch_seq = 0
        self._admit_seq = 0          # request ordinal (serving/enqueue)
        self._warm = False
        # THIS engine's trace count (bumped trace-time in _make_infer):
        # the after-warmup alarm must not fire on another engine's warmup
        # bumping the shared trace/serving_infer ledger counter
        self._trace_cell = [0]
        self._traces_seen = 0
        # None = unknown (shape heuristic), True/False once warmup has
        # probed whether outputs carry a per-timestep axis to slice
        self._seq_out_per_timestep: Optional[bool] = None
        self._aot = (hasattr(model, "_forward")
                     and hasattr(model, "_params"))
        # a ComputationGraph's forward takes and returns {node: array};
        # a request is one array, so the engine serves graphs with one
        # input and one output (the zoo's ResNet-50)
        self._graph_io: Optional[Tuple[str, str]] = None
        conf = getattr(model, "conf", None)
        if self._aot and hasattr(conf, "network_inputs"):
            ins, outs = conf.network_inputs, conf.network_outputs
            if len(ins) != 1 or len(outs) != 1:
                raise ValueError(
                    "ServingEngine serves one array per request: a "
                    f"ComputationGraph with inputs {ins} and outputs "
                    f"{outs} needs exactly one of each")
            self._graph_io = (ins[0], outs[0])
        self._infer_jit = None
        self._dev_params: Dict[int, Any] = {}
        pool_kwargs.setdefault("mode", "batched")
        super().__init__(model, **pool_kwargs)
        if self._aot:
            self._key = get_random().next_key()
            self._snapshot_params()
        if warmup:
            self.warmup()
        if self._adm is not None:
            self._brownout = BrownoutController(self, self._adm,
                                                **(brownout_kw or {}))
            self._brownout.start()
        _ENGINES.add(self)

    # --- params / executables -----------------------------------------
    def _cast_serving(self, params, states):
        if self._bf16:
            params = _cast_floating(params, jnp.bfloat16)
            states = _cast_floating(states, jnp.bfloat16)
        return params, states

    def _place_params(self, params, states) -> Dict[int, Any]:
        """One (params, states) copy per device slot — the argument set
        every AOT bucket executable takes, so swapping a slot's entry
        (refresh, canary, promote, rollback) never recompiles."""
        placed: Dict[int, Any] = {}
        for i, dev in enumerate(self._devices):
            if dev is None:
                placed[i] = (params, states)
            else:
                placed[i] = jax.device_put((params, states), dev)
        return placed

    def _snapshot_params(self) -> None:
        params, states = self._cast_serving(self.model._params,
                                            self.model._states)
        placed = self._place_params(params, states)
        with self._lock:
            self._dev_params = placed

    def _params_for(self, worker_id: Optional[int], dev_slot: int):
        """The params a dispatch uses: the canary replica reads the
        candidate weights while a publication is in its canary phase;
        everyone else reads the fleet set. One racy dict read by design
        — a phase transition swaps whole dicts under the pool lock, and
        a batch that catches the old reference simply serves the
        previous (complete, consistent) weight set."""
        can = self._canary
        if can is not None and worker_id is not None \
                and can.get("phase") == "canary" \
                and can.get("worker") == worker_id:
            return can["canary_params"]
        return self._dev_params[dev_slot]

    def refresh_params(self) -> None:
        """Re-snapshot the model's (possibly retrained) params into the
        serving copies. CHEAP: the AOT executables take params as
        arguments, so same-shape updates swap in without any recompile
        (bf16 pays its cast again). Refused while a canaried publication
        is in flight — :meth:`publish_checkpoint` owns the param set
        until it resolves, or a rollback could restore weights the
        refresh already replaced."""
        if not self._aot:
            return
        with self._lock:
            if self._canary is not None:
                raise RuntimeError(
                    f"refresh_params refused: publication "
                    f"{self._canary['corr']} is in flight (phase "
                    f"{self._canary['phase']!r}); wait for it to resolve "
                    f"or use publish_checkpoint for the next weights")
        self._snapshot_params()

    def _make_infer(self):
        model = self.model
        cdt = self._compute_dtype
        cell = self._trace_cell
        graph_io = self._graph_io

        def infer(params, states, x, key):
            # trace-time only: the retrace ledger the serving SLO gates on
            OpProfiler.get().count("trace/serving_infer")
            cell[0] += 1
            if cdt is not None:
                x = x.astype(cdt)
            if graph_io is not None:
                acts, _ = model._forward(params, states, {graph_io[0]: x},
                                         False, key)
                out = acts[graph_io[1]]
            else:
                out, _ = model._forward(params, states, x, False, key, None)
            return out.astype(jnp.float32)

        return infer

    def _compile_bucket(self, shape: Tuple[int, ...],
                        dev_idx: int = 0):
        """AOT-compile (``.lower().compile()``) the bucket executable for
        one input shape (and one pinned device, when pinning). Called for
        the whole ladder at :meth:`warmup`; a lazy hit (warmup disabled)
        compiles here on first use."""
        key = (shape, dev_idx)
        # lock-free hot path: every steady-state dispatch lands here, and
        # it must not queue behind another worker's (lazy) compile
        exe = self._exec.get(key, _MISS)
        if exe is not _MISS:
            return exe
        with self._exec_lock:
            if key in self._exec:
                return self._exec[key]
            if self._aot:
                if self._infer_jit is None:
                    self._infer_jit = jax.jit(self._make_infer())
                params, states = self._dev_params[dev_idx]
                aval = jax.ShapeDtypeStruct(shape, self._in_dtype)
                t0 = time.monotonic()
                exe = self._infer_jit.lower(
                    params, states, aval, self._key).compile()
                # executable census: the bucket ladder's AOT executables
                # feed the xla roofline ledger (cost/memory analysis is
                # extracted from the ALREADY-compiled object — nothing
                # retraces here)
                xprof.register_aot("serving/bucket", exe,
                                   variant=f"{shape}/dev{dev_idx}",
                                   compile_s=time.monotonic() - t0)
            else:
                # generic model (no jittable forward exposed): no AOT
                # executable — the model.output call right after this in
                # _run_bucket warms its jit cache at the bucket shape.
                # "never traces in steady state" still holds (every
                # later request reuses the shape), but the trace ledger
                # cannot see inside
                exe = None
            self._exec[key] = exe
            OpProfiler.get().count("serving/buckets_compiled")
            return exe

    def warmup(self) -> Dict[str, float]:
        """Compile every ladder bucket (× pinned device) up front — pool
        startup pays the whole trace/compile bill so steady-state serving
        never does. Returns {shape: seconds}; total time is ledgered
        under the ``serving/warmup`` profiler section."""
        prof = OpProfiler.get()
        timings: Dict[str, float] = {}
        seq_out: Dict[int, Optional[int]] = {}
        with prof.time_section("serving/warmup"):
            for shape in self.ladder.shapes(self._feat):
                for i in range(len(self._devices)):
                    t0 = time.perf_counter()
                    self._compile_bucket(shape, i)
                    # execute once too: the first run of a fresh
                    # executable pays allocator/dispatch setup that must
                    # not land on the first real request's latency
                    out = self._run_bucket(np.zeros(shape, self._in_dtype),
                                           i)
                    if i == 0 and self.ladder.seq_lens is not None:
                        seq_out[shape[1]] = (out.shape[1]
                                             if out.ndim >= 2 else None)
                    timings[f"{shape}@{i}" if len(self._devices) > 1
                            else str(shape)] = time.perf_counter() - t0
        if len(seq_out) >= 2:
            # ≥2 sequence rungs disambiguate per-timestep outputs (dim 1
            # tracks the padded length) from pooled ones (constant dim 1
            # that may coincide with ONE rung); a single rung stays on
            # the dispatch-time shape heuristic
            # graftlint: disable=lock-discipline -- startup phase: warmup
            # completes before the pool serves; _warm below is the fence
            self._seq_out_per_timestep = all(w == t
                                             for t, w in seq_out.items())
        # graftlint: disable=lock-discipline -- startup publication:
        # workers only consult the trace alarm once _warm flips, and both
        # stores happen-before any dispatch observes _warm=True
        self._traces_seen = self._trace_cell[0]
        # graftlint: disable=lock-discipline -- same startup publication
        self._warm = True
        # HBM watermark: the warmup just materialized every bucket
        # executable + per-device param copies — the serving tier's
        # steady-state memory footprint starts here
        xprof.memory_watermark("serving_warmup")
        return timings

    def _run_bucket(self, padded: np.ndarray, dev_idx: int = 0,
                    worker_id: Optional[int] = None) -> np.ndarray:
        exe = self._compile_bucket(tuple(padded.shape),
                                   dev_idx % len(self._devices))
        if exe is None:                       # generic-model fallback
            out = self.model.output(padded)
            out = out[0] if isinstance(out, list) else out
            return out.to_numpy()
        params, states = self._params_for(worker_id,
                                          dev_idx % len(self._devices))
        return np.asarray(exe(params, states,
                              padded.astype(self._in_dtype, copy=False),
                              self._key))

    def _run(self, batch: np.ndarray) -> NDArray:
        """Single-batch path (health probes, sequential mode): the same
        bucket executables, padded and sliced like any served request."""
        n = batch.shape[0]
        bucket = self.ladder.bucket_batch(n)
        if bucket is None:
            return super()._run(batch)        # oversize probe: direct
        padded, _w = pad_rows(batch, bucket)
        return NDArray(self._run_bucket(padded)[:n])

    # --- request admission ---------------------------------------------
    def output_async(self, x, slo_class: Optional[str] = None) -> Future:
        """Admit one request (see the module docstring's admission rule).
        Oversize rejections, ladder violations and SLO-class sheds
        (:class:`Overloaded` — brownout or queue budget, HTTP 429) raise
        SYNCHRONOUSLY — nothing is queued; every admitted request
        resolves through its future (deadline-bounded via
        :meth:`output`). ``slo_class`` names the request's admission
        class when classes are configured; ``None`` takes the default
        class."""
        arr = np.asarray(x.value if isinstance(x, NDArray) else x)
        if arr.ndim != len(self._feat) + 1:
            raise ValueError(
                f"request rank {arr.ndim} does not match the serving "
                f"input shape (batch, *{self._feat})")
        if arr.dtype != self._in_dtype:
            arr = arr.astype(self._in_dtype)
        prof = OpProfiler.get()
        with self._lock:
            # the documented serving REQUEST ordinal (0, 1, 2, ... per
            # output_async call) — distinct from _req_seq, which ticks
            # once per queued CHUNK and would leave enqueue-drill
            # indices unreachable for split requests
            admit_seq = self._admit_seq
            self._admit_seq += 1
        cls = None
        if self._adm is not None:
            cls = self._adm.resolve(slo_class)
            try:
                # the admission drill site (request ordinal): `slow`
                # stalls the decision, `transient` forces THIS request
                # shed — the deterministic 429 drill
                faultinject.fault_point("serving/admission", admit_seq)
            except faultinject.TransientFault as e:
                AdmissionController.count_shed(cls.name)
                raise Overloaded(
                    f"injected admission fault shed request {admit_seq} "
                    f"(class {cls.name!r})", cls.name, "fault",
                    self._adm.retry_after_s()) from e
        elif slo_class is not None:
            raise ValueError(
                f"slo_class={slo_class!r} given but no SLO classes are "
                f"configured (Builder.slo_classes)")
        t_real = None
        if self.ladder.seq_lens is not None:
            t = int(arr.shape[1])
            tb = self.ladder.bucket_seq(t)    # oversize seq: raises
            if arr.shape[2:] != self._feat[1:]:
                raise ValueError(
                    f"request feature shape {arr.shape[2:]} does not "
                    f"match the serving input shape {self._feat[1:]}")
            if tb != t:
                arr, _w = pad_rows(arr, tb, axis=1)
                prof.count("serving/seq_padded")
            t_real = t
        elif arr.shape[1:] != self._feat:
            raise ValueError(
                f"request feature shape {arr.shape[1:]} does not match "
                f"the serving input shape {self._feat}")
        try:
            chunks = self.ladder.admit(arr.shape[0])
        except OversizeRequest:
            prof.count("serving/oversize_rejected")
            raise
        if cls is not None:
            self._adm.admit(cls, len(chunks))     # Overloaded: sheds here;
            #                                       reserves the chunk slots
        try:
            fired = faultinject.fault_point("serving/enqueue", admit_seq)
            del fired  # advisory kinds have no enqueue-side meaning (yet)
        except BaseException:
            if cls is not None:     # reservation must not leak on a drill
                self._adm.release(cls.name, len(chunks))
            raise
        slo = cls.name if cls is not None else None
        if len(chunks) == 1:
            return self._submit(arr, t_real, slo=slo)
        prof.count("serving/oversize_split")
        futs, off = [], 0
        for c in chunks:
            futs.append(self._submit(arr[off:off + c], t_real, slo=slo))
            off += c
        return self._aggregate(futs)

    def _submit(self, arr: np.ndarray, t_real: Optional[int],
                slo: Optional[str] = None) -> Future:
        fut: Future = Future()
        if slo is not None:
            # the slot was RESERVED in admit(); the done-callback returns
            # it on every resolution path (result, batch error, requeue
            # exhaustion, the fast-fail exits just below, shutdown — a
            # callback added after set_exception fires immediately), so
            # the per-class budget can never leak
            fut.add_done_callback(
                lambda f, _n=slo: self._adm.note_done(_n))
        if self._shutdown:
            fut.set_exception(RuntimeError(
                "ServingEngine is shut down; no replicas will serve this "
                "request"))
            return fut
        if self.alive_replicas() == 0:
            fut.set_exception(RuntimeError(
                "all serving replicas have been retired; a resurrection "
                "may be pending — retry, or rebuild the engine"))
            return fut
        with self._lock:
            seq = self._req_seq
            self._req_seq += 1
            depth = self._queue.qsize() + 1
        self._qwin_update(depth)
        self._publish_queue_gauges()
        # request lifecycle, leg 1 of enqueue → batch → dispatch → reply;
        # the request ordinal IS the correlation id, so one grep follows
        # a request through replica deaths and requeues. Emitted BEFORE
        # the queue put: once a worker can see the request, its batch/
        # reply events must not be able to precede this one. Guarded
        # like legs 2/4: per-request kwargs stay off the disabled path
        if flightrec.enabled():
            flightrec.event("serving/enqueue", corr=f"req{seq}", req=seq,
                            rows=int(arr.shape[0]))
        self._enqueue(_Request(arr, fut, seq, time.monotonic(),
                               t_real=t_real, slo=slo))
        return fut

    def _aggregate(self, futs: List[Future]) -> Future:
        """Recombine a split oversize request: chunk results concatenate
        in submission order; the first chunk failure fails the whole
        request (partial answers are worse than retried ones)."""
        parent: Future = Future()
        parent.enqueued_at = min(getattr(f, "enqueued_at", time.monotonic())
                                 for f in futs)
        remaining = [len(futs)]
        lock = threading.Lock()

        def one_done(f: Future) -> None:
            with lock:
                if parent.done():
                    return
                exc = f.exception()
                if exc is not None:
                    parent.set_exception(exc)
                    return
                remaining[0] -= 1
                if remaining[0]:
                    return
            parts = [fu.result().to_numpy() for fu in futs]
            parent.set_result(NDArray(np.concatenate(parts, axis=0)))

        for f in futs:
            f.add_done_callback(one_done)
        return parent

    # --- load signals ---------------------------------------------------
    def _qwin_update(self, depth: Optional[int] = None) -> int:
        """Roll the two-window queue-depth high-water state (and fold in
        a new sample); returns the current WINDOWED high-water mark —
        max over the current and previous windows, so it decays to 0
        within ~2 windows of the backlog clearing (the scale-DOWN-capable
        signal the old only-rising fleet gauge could never be). The
        lifetime maximum is kept separately (:attr:`queue_depth_peak`)."""
        now = time.monotonic()
        with self._lock:
            elapsed = now - self._qwin_start
            if elapsed >= 2 * self._qwin_s:
                self._qwin_prev = 0
                self._qwin_max = 0
                self._qwin_start = now
            elif elapsed >= self._qwin_s:
                self._qwin_prev = self._qwin_max
                self._qwin_max = 0
                self._qwin_start = now
            if depth is not None:
                if depth > self._qwin_max:
                    self._qwin_max = depth
                if depth > self._q_peak:
                    self._q_peak = depth
            return max(self._qwin_max, self._qwin_prev)

    def queue_depth_hwm(self) -> int:
        """The decaying/windowed queue-depth high-water mark."""
        return self._qwin_update()

    @property
    def queue_depth_peak(self) -> int:
        """Lifetime queue-depth maximum (only ever rises)."""
        return self._q_peak

    def _publish_queue_gauges(self) -> None:
        """Fleet gauges: ``serving/queue_depth_hwm`` = max WINDOWED
        high-water over live engines (falls when backlogs clear);
        ``serving/queue_depth_peak`` = lifetime fleet max (only rises).
        Computed outside any engine lock — each read takes its owner's."""
        prof = OpProfiler.get()
        win, peak = 0, 0
        for e in list(_ENGINES):
            win = max(win, e.queue_depth_hwm())
            peak = max(peak, e._q_peak)
        prof.gauge("serving/queue_depth_hwm", win)
        if peak > prof.counter_value("serving/queue_depth_peak"):
            prof.gauge("serving/queue_depth_peak", peak)

    def idle_seconds(self) -> float:
        """Seconds since the last batch dispatch (autoscaler scale-down
        signal)."""
        return time.monotonic() - self._last_dispatch_t

    def recent_p99_ms(self, window_s: float = 5.0,
                      min_samples: int = 5) -> Optional[float]:
        """p99 latency over requests completed in the trailing window
        (all classes) — the autoscaler's reactive latency signal; the
        engine-lifetime rolling quantiles stay in
        :meth:`latency_stats`."""
        now = time.monotonic()
        with self._lat_lock:
            vals = [lat for t, lat in self._lat_recent
                    if now - t <= window_s]
        if len(vals) < min_samples:
            return None
        return float(np.percentile(np.asarray(vals) * 1e3, 99))

    def _class_recent_p99(self, name: str, window_s: float = 5.0,
                          min_samples: int = 5) -> Optional[float]:
        now = time.monotonic()
        with self._lat_lock:
            dq = self._class_lats.get(name)
            vals = ([lat for t, lat in dq if now - t <= window_s]
                    if dq else [])
        if len(vals) < min_samples:
            return None
        return float(np.percentile(np.asarray(vals) * 1e3, 99))

    def class_recent_p99(self, name: str, window_s: float = 5.0,
                         min_samples: int = 5) -> Optional[float]:
        """Public windowed per-class p99 (ms) — the watchtower's
        latency-SLO signal; None until ``min_samples`` land in the
        window."""
        return self._class_recent_p99(name, window_s=window_s,
                                      min_samples=min_samples)

    def slo_classes(self) -> List[SLOClass]:
        """The configured SLO classes, highest priority first (empty for
        an unclassified engine)."""
        if self._adm is None:
            return []
        return list(reversed(self._adm.by_shed_order))

    def class_latency_stats(self) -> Dict[str, Dict[str, float]]:
        """Rolling per-SLO-class p50/p99 over each class's last ≤2048
        served requests, in ms — the engine-wide window alone cannot
        price a non-top class's burn rate."""
        with self._lat_lock:
            per_class = {name: [lat for _, lat in dq]
                         for name, dq in self._class_lats.items() if dq}
        out: Dict[str, Dict[str, float]] = {}
        for name, vals in per_class.items():
            arr = np.asarray(vals) * 1e3
            out[name] = {"window": len(vals),
                         "p50_ms": float(np.percentile(arr, 50)),
                         "p99_ms": float(np.percentile(arr, 99))}
        return out

    def _on_scaled_out(self, worker_id: int) -> None:
        """A worker exiting via scale-down frees its pinned-device slot
        for whatever scale-up (or resurrection) comes next."""
        with self._lock:
            dev = self._dev_of.pop(worker_id, None)
            if dev is not None:
                self._dev_free.append(dev)

    # --- continuous-batching drain --------------------------------------
    def _next_request(self, timeout: float) -> Optional[_Request]:
        with self._stash_lock:
            if self._stashq:
                return self._stashq.popleft()
        try:
            return self._queue.get(timeout=max(0.0, timeout))
        except queue.Empty:
            return None

    def _stash(self, req: _Request) -> None:
        """Hold a request this batch cannot take (bucket overflow or a
        non-batch-shape mismatch) for the NEXT batch — stashed requests
        outrank the queue, so nothing is starved or reordered past one
        batch."""
        with self._stash_lock:
            self._stashq.append(req)

    def _drain(self, worker_id: int) -> None:
        prof = OpProfiler.get()
        with self._lock:
            if worker_id not in self._dev_of:
                # claim a pinned-device slot: a retired worker's freed
                # slot first (the replacement takes over its chip),
                # round-robin otherwise (the startup pool)
                self._dev_of[worker_id] = (
                    self._dev_free.pop() if self._dev_free
                    else worker_id % len(self._devices))
        while not self._shutdown:
            if self._take_scale_down(worker_id):
                return     # scaled out at a batch boundary, nothing held
            first = self._next_request(0.1)
            if first is None:
                continue
            batch, rows = [first], first.n
            shape_tail = first.arr.shape[1:]
            # fill toward the LARGEST bucket under one absolute deadline
            # (continuous batching: the window caps added latency, the
            # ladder caps the fill)
            deadline = time.monotonic() + self.max_wait_s
            while rows < self.ladder.max_batch:
                nxt = self._next_request(deadline - time.monotonic())
                if nxt is None:
                    break
                if (nxt.arr.shape[1:] != shape_tail
                        or rows + nxt.n > self.ladder.max_batch):
                    self._stash(nxt)
                    break
                batch.append(nxt)
                rows += nxt.n
            with self._lock:
                self._busy += 1
            try:
                self._dispatch(worker_id, batch, rows, prof)
            except faultinject.DeadReplicaFault:
                return          # replica retired inside _dispatch
            finally:
                with self._lock:
                    self._busy -= 1
        with self._lock:
            self._alive -= 1

    def _dispatch(self, worker_id: int, batch: List[_Request], rows: int,
                  prof) -> None:
        with self._lock:
            ordinal = self._batch_seq
            self._batch_seq += 1
            self._last_dispatch_t = time.monotonic()
        # leg 2: the batch formed by continuous batching — emitted BEFORE
        # the dispatch drill site, so a killed dispatch still shows which
        # requests were aboard (the incident-reconstruction contract).
        # enabled() guard: the reqs list is per-batch hot-path allocation
        # that must not be built just to be discarded
        if flightrec.enabled():
            flightrec.event("serving/batch", batch=ordinal, rows=rows,
                            worker=worker_id,
                            reqs=[int(r.seq) for r in batch])
        try:
            faultinject.fault_point("serving/dispatch", ordinal)
        except faultinject.TransientFault:
            # one deterministic requeue-and-retry (drill for the retry
            # path); the requests keep their queue-entry timestamps
            self._requeue(batch, faultinject.TransientFault(
                "serving dispatch retry budget exhausted"))
            return
        except faultinject.DeadReplicaFault as e:
            self._retire_serving(worker_id, e, batch)
            raise
        bucket = self.ladder.bucket_batch(rows)
        merged = (batch[0].arr if len(batch) == 1
                  else np.concatenate([r.arr for r in batch], axis=0))
        padded, _w = pad_rows(merged, bucket)
        try:
            with prof.time_section("serving/dispatch"):
                result = self._run_bucket(
                    padded, self._dev_of.get(worker_id, 0),
                    worker_id=worker_id)
        except faultinject.DeadReplicaFault as e:
            self._retire_serving(worker_id, e, batch)
            raise
        except Exception as e:
            prof.count("serving/batch_errors")
            self._note_canary_result(worker_id, error=True)
            for r in batch:
                if not r.fut.done():
                    r.fut.set_exception(e)
            return
        except BaseException as e:
            # bookkeeping parity with ParallelInference._serve_batch: an
            # injected SimulatedCrash must still retire cleanly
            self._retire(worker_id, e, [r.fut for r in batch])
            raise
        # graftlint: disable=lock-discipline -- last-write-wins slot: one
        # atomic reference store of a fresh owning copy (same contract as
        # ParallelInference._serve_batch)
        self._probe_input = padded[:1].copy()
        t_done = time.monotonic()
        t_pad = padded.shape[1] if padded.ndim >= 2 else None
        off = 0
        lats = []
        for r in batch:
            out = result[off:off + r.n]
            off += r.n
            if (r.t_real is not None and out.ndim >= 2
                    and out.shape[1] == t_pad
                    and self._seq_out_per_timestep is not False):
                # per-timestep output: slice the sequence pad back off.
                # warmup probes the ladder to rule OUT pooled outputs
                # whose width merely coincides with one sequence rung
                out = out[:, :r.t_real]
            lats.append(t_done - r.t_enq)
            r.fut.set_result(NDArray(out))
            # leg 4 (leg 3, the dispatch itself, is the profiler's
            # serving/dispatch section — an X lane in the Chrome trace);
            # guarded: per-request latency math + kwargs stay off the
            # disabled hot path
            if flightrec.enabled():
                flightrec.event(
                    "serving/reply", corr=f"req{r.seq}", req=int(r.seq),
                    batch=ordinal,
                    latency_ms=round((t_done - r.t_enq) * 1e3, 3))
        with self._lat_lock:
            self._latencies.extend(lats)
            self._lat_recent.extend((t_done, lat) for lat in lats)
            for r, lat in zip(batch, lats):
                if r.slo is not None:
                    self._class_lats.setdefault(
                        r.slo, collections.deque(maxlen=2048)
                    ).append((t_done, lat))
        self._note_canary_result(worker_id, lats=lats)
        prof.count("serving/requests", len(batch))
        prof.count("serving/batches")
        prof.count("serving/rows", rows)
        prof.count("serving/pad_rows", bucket - rows)
        prof.count("serving/capacity_rows", bucket)
        if self._warm:
            traces = self._trace_cell[0]
            if traces > self._traces_seen:
                # the one thing steady-state serving must never do. Under
                # the pool lock: concurrent workers racing the unlocked
                # read-modify-write would double-count the alarm delta
                with self._lock:
                    delta = traces - self._traces_seen
                    if delta > 0:
                        prof.count("serving/traces_after_warmup", delta)
                        self._traces_seen = traces
                if delta > 0:
                    logger.warning("serving traced AFTER warmup (shape "
                                   "%s) — a bucket escaped the warmup "
                                   "set", padded.shape)

    def _requeue(self, batch: List[_Request], exhausted_exc) -> None:
        prof = OpProfiler.get()
        for r in batch:
            r.attempts += 1
            if r.attempts > self.max_requeues:
                if not r.fut.done():
                    r.fut.set_exception(exhausted_exc)
                continue
            try:
                self._queue.put_nowait(r)
            except queue.Full:
                if not r.fut.done():
                    r.fut.set_exception(TimeoutError(
                        "serving queue full while requeueing a request "
                        "from a retired replica"))
                continue
            # only a requeue that actually landed is a ride-through
            prof.count("serving/requeued")

    def _retire_serving(self, worker_id: int, exc: BaseException,
                        batch: List[_Request]) -> None:
        """Retirement TRANSPARENT to in-flight requests: requeue the
        dying replica's batch (bounded by ``max_requeues``) so surviving
        replicas serve it, then run the pool's shared retirement
        bookkeeping (which fails whatever is queued if this was the LAST
        replica — bounded latency outranks transparency — and schedules
        resurrection)."""
        flightrec.event("serving/retire", severity="warn",
                        worker=worker_id, error=repr(exc)[:200],
                        requeued=[int(r.seq) for r in batch])
        self._requeue(batch, exc)
        with self._lock:
            # free the dead worker's pinned-device slot for its
            # resurrected replacement
            dev = self._dev_of.pop(worker_id, None)
            if dev is not None:
                self._dev_free.append(dev)
        self._retire(worker_id, exc, [])      # casualties already failed

    def _probe(self) -> None:
        """Resurrection health probe on the device slot the REPLACEMENT
        will claim — the base class probes through ``_run``, which always
        dispatches on device 0 and would validate a healthy chip while
        refilling a dead one's slot."""
        faultinject.fault_point("inference/probe", self._next_probe_seq())
        probe = self._probe_input
        if probe is None:
            return
        with self._lock:
            dev = self._dev_free[-1] if self._dev_free else 0
        bucket = self.ladder.bucket_batch(probe.shape[0])
        if bucket is None:
            self._run(probe)
            return
        padded, _w = pad_rows(probe, bucket)
        self._run_bucket(padded, dev)

    # --- canaried train-to-serve handoff --------------------------------
    _CANARY_PHASES = {"idle": 0, "canary": 1, "confirm": 2}

    def _note_canary_result(self, worker_id: int, lats: Sequence[float] = (),
                            error: bool = False) -> None:
        """Feed one dispatch outcome into the live publication's SLO
        evidence: during the canary phase only the canary replica's
        samples count; after promote every replica serves the candidate
        weights, so the whole fleet's do."""
        can = self._canary
        if can is None:
            return
        with self._lock:
            can = self._canary
            if can is None:
                return
            if can["phase"] == "canary" and can.get("worker") != worker_id:
                return
            if error:
                can["errors"] += 1
            else:
                can["lats"].extend(lats)

    def _set_canary_phase(self, phase: str) -> None:
        OpProfiler.get().gauge("serving/canary_phase",
                               self._CANARY_PHASES[phase])

    def publish_checkpoint(self, path: str, canary_window_s: float = 3.0,
                           confirm_window_s: Optional[float] = None,
                           check_interval_s: float = 0.25,
                           min_samples: int = 8,
                           violation_p99_ms: Optional[float] = None
                           ) -> PublishHandle:
        """Canaried train-to-serve handoff: load retrained weights from a
        committed checkpoint and hot-swap them — zero recompiles, the AOT
        executables take params as arguments — onto ONE canary replica.
        After an SLO-clean ``canary_window_s`` the weights PROMOTE
        fleet-wide; a ``confirm_window_s`` watch follows, and any
        violation (serving errors on the new weights, p99 over
        ``violation_p99_ms`` — default: the top SLO class's budget — or
        an injected ``serving/promote`` fault) AUTO-ROLLBACKS by
        restoring the prior param set bitwise (the exact prior device
        arrays, not a re-cast copy). When a p99 budget is in force the
        promote additionally REQUIRES ``min_samples`` of canary evidence
        — a canary replica that served nothing (retired, scaled out, or
        simply idle) rolls back rather than promoting untested weights;
        budget-less publications keep the time-based promote with
        error-only violation detection. The returned handle's ``corr``
        id (``pub<N>``) chains train-commit -> canary -> promote/
        rollback in the flight recorder. One publication may be in
        flight at a time; ``refresh_params()`` during a publication is
        refused for the same reason."""
        if not self._aot:
            raise RuntimeError(
                "publish_checkpoint needs an AOT-served model (the "
                "generic-model fallback serves through model.output and "
                "owns its own weights)")
        # claim the publication slot FIRST (a refused publish must not
        # burn a pub ordinal — drills arm fault plans against
        # next_publication_ordinal() — nor pay the checkpoint read)
        with self._lock:
            if self._canary is not None:
                raise RuntimeError(
                    f"publication {self._canary['corr']} is still in "
                    f"flight (phase {self._canary['phase']!r})")
            self._canary = {"phase": "loading", "corr": "pending",
                            "worker": None, "errors": 0, "lats": []}
        try:
            from ..util.checkpoint import read_checkpoint_params

            params, states = read_checkpoint_params(
                path, self.model._params, self.model._states)
            params, states = self._cast_serving(params, states)
            new_placed = self._place_params(params, states)
            # the canary replica: any live worker that has claimed a
            # device slot (they all do on their first drain iteration)
            deadline = time.monotonic() + 5.0
            worker = None
            while time.monotonic() < deadline:
                with self._lock:
                    if self._dev_of:
                        worker = next(iter(self._dev_of))
                        break
                time.sleep(0.01)
            if worker is None:
                raise RuntimeError("no live serving worker to canary "
                                   "onto")
        except BaseException:
            with self._lock:
                self._canary = None
            raise
        prof = OpProfiler.get()
        with _pub_lock:
            ordinal = _pub_next[0]
            _pub_next[0] += 1
        with self._lock:
            corr = f"pub{ordinal}"
            handle = PublishHandle(corr, path)
            slot = self._dev_of.get(worker, 0)
            budget = violation_p99_ms
            if budget is None and self._adm is not None:
                budget = self._adm.top.p99_ms
            self._canary = {
                "ordinal": ordinal, "corr": corr,
                "file": os.path.basename(path), "phase": "canary",
                "worker": worker, "canary_params": new_placed[slot],
                "new": new_placed, "prior": dict(self._dev_params),
                "lats": [], "errors": 0, "budget_ms": budget,
                "min_samples": int(min_samples), "handle": handle,
            }
        prof.count("serving/publications")
        self._set_canary_phase("canary")
        flightrec.event("serving/canary", corr=corr,
                        file=os.path.basename(path), worker=worker,
                        window_s=canary_window_s,
                        budget_ms=budget)
        t = threading.Thread(
            target=self._canary_monitor,
            args=(canary_window_s,
                  canary_window_s if confirm_window_s is None
                  else confirm_window_s,
                  max(0.01, float(check_interval_s))),
            daemon=True, name=f"dl4j-serving-canary-{ordinal}")
        with self._lock:
            # only one publication is ever in flight — drop the finished
            # monitors so a long-lived engine with periodic publishes
            # does not accumulate dead Thread objects
            self._pub_threads = [x for x in self._pub_threads
                                 if x.is_alive()]
            self._pub_threads.append(t)
        t.start()
        return handle

    def _canary_monitor(self, canary_window_s: float,
                        confirm_window_s: float, interval_s: float) -> None:
        with self._lock:
            can = self._canary
        if can is None:
            return
        deadline = time.monotonic() + canary_window_s
        while time.monotonic() < deadline:
            if self._shutdown:
                self._rollback(can, "canary", "engine shutdown")
                return
            time.sleep(interval_s)
            v = self._canary_violation(can)
            if v:
                self._rollback(can, "canary", v)
                return
        with self._lock:
            evidence = len(can["lats"]) + can["errors"]
            budget = can["budget_ms"]
        if budget is not None and evidence < can["min_samples"]:
            # an SLO budget is in force but the canary replica produced
            # no judgeable evidence (no traffic reached it — e.g. it was
            # retired or scaled out mid-window): promoting would ship
            # UNTESTED weights, the exact failure the canary exists to
            # prevent. Roll back instead; error-only publications (no
            # budget) keep their time-based promote.
            self._rollback(can, "canary",
                           f"insufficient canary evidence: {evidence} "
                           f"sample(s), need {can['min_samples']}")
            return
        # SLO-clean canary window: PROMOTE fleet-wide (atomic dict swap —
        # in-flight batches finish on whichever complete set they read)
        with self._lock:
            self._dev_params = can["new"]
            can["phase"] = "confirm"
            can["lats"] = []         # confirm judges fresh fleet evidence
            can["errors"] = 0
        can["handle"].phase = "confirm"
        self._set_canary_phase("confirm")
        flightrec.event("serving/promote", corr=can["corr"],
                        file=can["file"], replicas=self.alive_replicas())
        # post-promote fleet verify: every slot's freshly-installed param
        # copy must digest bitwise-identical. A copy corrupted in transit
        # (device_put, HBM) would otherwise serve divergent answers from
        # one replica until the NEXT publication; the digest read is one
        # batched host readback per slot, off the request path.
        prof = OpProfiler.get()
        prof.count("integrity/publish_checks")
        digests = {slot: _integ.host_fingerprint(entry[0])
                   for slot, entry in can["new"].items()}
        counts = collections.Counter(digests.values())
        if len(counts) > 1:
            majority = counts.most_common(1)[0][0]
            bad = sorted(s for s, d in digests.items() if d != majority)
            prof.count("integrity/publish_divergences")
            self._rollback(can, "confirm",
                           f"post-promote fingerprint mismatch on "
                           f"slot(s) {bad}")
            return
        deadline = time.monotonic() + confirm_window_s
        while time.monotonic() < deadline:
            if self._shutdown:
                self._rollback(can, "confirm", "engine shutdown")
                return
            time.sleep(interval_s)
            try:
                # the forced-violation drill site: a transient here is
                # "the promoted weights are violating" (publication
                # ordinal-indexed, so drills pick their publication)
                faultinject.fault_point("serving/promote", can["ordinal"])
            except faultinject.TransientFault as e:
                self._rollback(can, "confirm", f"injected violation: {e}")
                return
            v = self._canary_violation(can)
            if v:
                self._rollback(can, "confirm", v)
                return
        with self._lock:
            self._canary = None
        prof = OpProfiler.get()
        prof.count("serving/promotions")
        self._set_canary_phase("idle")
        can["handle"]._finish("promoted")
        logger.info("serving publication %s promoted fleet-wide (%s)",
                    can["corr"], can["file"])

    def _canary_violation(self, can: Dict[str, Any]) -> Optional[str]:
        with self._lock:
            errors = can["errors"]
            lats = list(can["lats"])
            budget = can["budget_ms"]
            need = can["min_samples"]
        if errors:
            return f"{errors} serving error(s) on the candidate weights"
        if budget is not None and len(lats) >= need:
            p99 = float(np.percentile(np.asarray(lats) * 1e3, 99))
            if p99 > budget:
                return (f"p99 {p99:.1f}ms over the {budget:.0f}ms budget "
                        f"({len(lats)} samples)")
        return None

    def _rollback(self, can: Dict[str, Any], phase: str,
                  reason: str) -> None:
        """Restore the prior param set BITWISE: the rollback re-installs
        the exact prior device arrays (kept, not re-derived), so a
        post-rollback read is indistinguishable from never publishing."""
        with self._lock:
            self._dev_params = can["prior"]
            self._canary = None
        prof = OpProfiler.get()
        prof.count("serving/rollbacks")
        self._set_canary_phase("idle")
        flightrec.event("serving/rollback", severity="warn",
                        corr=can["corr"], file=can["file"], phase=phase,
                        reason=str(reason)[:200])
        logger.warning("serving publication %s rolled back during %s: %s",
                       can["corr"], phase, reason)
        can["handle"]._finish("rolled_back")

    def shutdown(self, drain_timeout_s: float = 2.0) -> None:
        super().shutdown(drain_timeout_s)
        # canary monitors observe _shutdown and resolve their handles
        for t in list(self._pub_threads):
            t.join(timeout=1.0)
        bt = self._brownout._thread if self._brownout else None
        if bt is not None:
            bt.join(timeout=1.0)
        # out of the health census: a shut-down engine must not report
        # itself (or its stale latency window) as live serving capacity
        _ENGINES.discard(self)

    def _fail_queued(self, exc) -> int:
        """The stash is queue too: a request held for the next batch must
        fail with the rest when the pool dies or shuts down — the base
        contract ('no waiter is left hanging') covers both stores."""
        n = super()._fail_queued(exc)
        while True:
            with self._stash_lock:
                if not self._stashq:
                    return n
                req = self._stashq.popleft()
            if not req.fut.done():
                req.fut.set_exception(exc)
                n += 1

    # --- stats ----------------------------------------------------------
    def latency_stats(self) -> Dict[str, float]:
        """Rolling p50/p99 over the last ≤4096 served requests, in ms."""
        with self._lat_lock:
            window = list(self._latencies)
        if not window:
            return {"window": 0}
        arr = np.asarray(window) * 1e3
        return {"window": len(window),
                "p50_ms": float(np.percentile(arr, 50)),
                "p99_ms": float(np.percentile(arr, 99)),
                "max_ms": float(arr.max())}

    def serving_stats(self) -> Dict[str, Any]:
        """This engine's census for :func:`serving_health`: pool
        live/retired/resurrected, bucket/warmup state, the windowed
        queue-depth high-water + lifetime peak, admission/brownout state,
        the canary phase, rolling latency quantiles."""
        out: Dict[str, Any] = dict(self.pool_stats())
        out.update(self.latency_stats())
        cl = self.class_latency_stats()
        if cl:
            out["class_latency"] = cl
        with self._exec_lock:
            out["buckets_compiled"] = len(self._exec)
        out["warm"] = self._warm
        out["queue_depth_hwm"] = self.queue_depth_hwm()   # windowed
        out["queue_depth_peak"] = self._q_peak            # lifetime
        out["bf16"] = self._bf16
        if self._adm is not None:
            out["admission"] = self._adm.stats()
        with self._lock:
            can = self._canary
            out["canary_phase"] = can["phase"] if can else "idle"
            if can:
                out["canary_corr"] = can["corr"]
        self._publish_queue_gauges()    # reads refresh the fleet gauges
        return out


def serving_health() -> Dict[str, Any]:
    """The ``/api/health`` "serving" section: the profiler's
    ``serving_stats()`` ledger (requests, batches, fill ratio, pad waste,
    traces-after-warmup, dispatch/warmup time) merged with a per-engine
    census and the rolling latency quantiles only the engines hold."""
    out: Dict[str, Any] = dict(OpProfiler.get().serving_stats())
    engines = list(_ENGINES)
    out["engines"] = len(engines)
    if engines:
        out["engine_stats"] = [e.serving_stats() for e in engines]
        samples: List[float] = []
        class_samples: Dict[str, List[float]] = {}
        for e in engines:
            with e._lat_lock:
                samples.extend(e._latencies)
                for name, dq in e._class_lats.items():
                    class_samples.setdefault(name, []).extend(
                        lat for _, lat in dq)
        if samples:
            arr = np.asarray(samples) * 1e3
            out["latency_p50_ms"] = float(np.percentile(arr, 50))
            out["latency_p99_ms"] = float(np.percentile(arr, 99))
        if class_samples:
            # fleet-wide per-SLO-class rolling quantiles: the signal the
            # watchtower latency SLOs and dl4j_serving_latency_ms{class=}
            # price burn rates from
            out["class_latency"] = {
                name: {"window": len(vals),
                       "p50_ms": float(np.percentile(
                           np.asarray(vals) * 1e3, 50)),
                       "p99_ms": float(np.percentile(
                           np.asarray(vals) * 1e3, 99))}
                for name, vals in class_samples.items() if vals}
    return out
