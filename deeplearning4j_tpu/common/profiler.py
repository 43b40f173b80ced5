"""OpProfiler-shaped profiling front (SURVEY §5.1).

Reference: nd4j ``OpProfiler`` (per-op timing aggregation, NAN_PANIC mode)
and ``PerformanceTracker`` (bandwidth numbers). On this stack the per-op
dimension lives inside XLA, so the device-side story is a trace: ``start()``/
``stop()`` (or ``with trace(logdir)``) drive ``jax.profiler`` and produce a
TensorBoard-loadable trace of every kernel. The host-side section API
(``time_section``) aggregates wall times by name — the analog of the
reference's per-op counters for the Python orchestration layer — and
writes each section into that trace as a host span, so the trace says
what the host was doing in every gap between the device's ops.

NAN_PANIC itself is ``Environment.get().set_check_nan(True)`` →
``jax_debug_nans`` (§5.1's named toggle).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from . import flightrec


class OpProfiler:
    _instance: Optional["OpProfiler"] = None
    _lock = threading.Lock()

    #: every derived ledger the profiler exposes, by (label, method
    #: name) — the one list ``print_statistics``, ``/api/health`` and
    #: the ``/api/metrics`` Prometheus renderer all iterate, so a new
    #: ledger can never be health-only or metrics-only by accident.
    LEDGERS: Tuple[Tuple[str, str], ...] = (
        ("overlap", "overlap_stats"),
        ("telemetry", "telemetry_stats"),
        ("checkpoint", "checkpoint_stats"),
        ("supervisor", "supervisor_stats"),
        ("collectives", "collective_stats"),
        ("elastic", "elastic_stats"),
        ("pipeline", "pipeline_stats"),
        ("serving", "serving_stats"),
        ("autoscale", "autoscale_stats"),
        ("fleet", "fleet_stats"),
        ("precision", "precision_stats"),
        ("sequence", "sequence_stats"),
        ("moe", "moe_stats"),
        ("xla", "xla_stats"),
        ("tracecheck", "tracecheck_stats"),
        ("faults", "fault_stats"),
        ("watchtower", "watchtower_stats"),
        ("integrity", "integrity_stats"),
    )

    def __init__(self) -> None:
        self._trace_dir: Optional[str] = None
        self._last_trace_dir: Optional[str] = None
        self._sections: Dict[str, Dict[str, float]] = {}
        self._counters: Dict[str, int] = {}
        self._gauge_names: set = set()

    @classmethod
    def get(cls) -> "OpProfiler":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    # --- device trace (jax.profiler → TensorBoard trace viewer) ---------
    def start(self, logdir: str) -> None:
        import jax

        with self._lock:
            if self._trace_dir is not None:
                raise RuntimeError("profiler already tracing")
            self._trace_dir = self._last_trace_dir = logdir
        try:
            jax.profiler.start_trace(logdir)
        except BaseException:
            # a failed start (unwritable logdir) must not wedge the
            # profiler in "already tracing" with no trace to stop
            with self._lock:
                self._trace_dir = None
            raise
        from .environment import Environment

        Environment.get().set_profiling(True)

    def stop(self) -> None:
        import jax

        with self._lock:
            if self._trace_dir is None:
                return
            self._trace_dir = None
        jax.profiler.stop_trace()
        from .environment import Environment

        Environment.get().set_profiling(False)

    @contextlib.contextmanager
    def trace(self, logdir: str):
        self.start(logdir)
        try:
            yield self
        finally:
            self.stop()

    def scope_times(self, logdir: Optional[str] = None,
                    step_program: str = "jit_step") -> dict:
        """Where the device time of a traced step went, by the step's
        named scopes (phase / vertex / the layer's own): the reading of
        ``common.xprof.scope_times`` over the session that
        :meth:`trace` last wrote (or over ``logdir``). ``step_program``:
        the prefix of the step program's name. A model's ``scope_kinds()``
        says which layer class each row's vertex is. ``python3 -m
        deeplearning4j_tpu.common.xprof LOGDIR`` prints the same table."""
        from . import xprof

        logdir = logdir or self._last_trace_dir
        if logdir is None:
            raise ValueError("no trace yet: run the step under trace(logdir)")
        return xprof.scope_times(logdir, step_program)

    # --- host-side section counters (OpProfiler counter analog) ---------
    @contextlib.contextmanager
    def time_section(self, name: str, **attrs):
        """THE span primitive: the body is one named interval of host
        work. It is recorded three ways from this one call — the
        (count, total, max) aggregate, a ``profiler/section`` flight-
        recorder event, and a ``jax.profiler.TraceAnnotation``, so that
        whenever a profiler session is on (``trace(logdir)``, a
        benchmark's traced window) the section is a host span on the
        profiler's clock, in the same ``.xplane.pb`` as the device's
        ops and nested by thread. With no session the annotation is a
        flag check. ``attrs`` (``step=``, ``epoch=``, ``call=``) go to
        the annotation and to the event: the spans of one step share
        ``step``."""
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(name, **attrs):
                yield
        finally:
            dt = time.perf_counter() - t0
            # under the lock: sections are bumped from the training
            # thread, the checkpoint writer and inference workers alike —
            # unlocked read-modify-write drops updates
            with self._lock:
                s = self._sections.setdefault(
                    name, {"count": 0, "total_s": 0.0, "max_s": 0.0})
                s["count"] += 1
                s["total_s"] += dt
                s["max_s"] = max(s["max_s"], dt)
            # individual durations feed the flight recorder's timeline
            # (Chrome-trace X events on the emitting thread's lane);
            # the aggregate above stays the ledger source of truth.
            # Emitted OUTSIDE the profiler lock — the recorder has its
            # own, and nesting them would order the two locks.
            flightrec.event("profiler/section", section=name, dur_s=dt,
                            **attrs)

    def get_statistics(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {k: dict(v) for k, v in self._sections.items()}

    # --- event counters (compile/retrace accounting) --------------------
    # The train-step builders bump ``trace/<name>`` INSIDE the function
    # handed to jax.jit: the Python body only executes while jax traces,
    # so the counter counts (re)traces — each of which implies an XLA
    # compile — and stays silent on cached executions. Tests and the bench
    # assert "one compile per fit config" directly on these.
    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def gauge(self, name: str, value: int) -> None:
        """Set a counter to an absolute value (last-write-wins) — for
        level quantities like the live elastic worker count, where adding
        would be meaningless."""
        with self._lock:
            self._counters[name] = int(value)
            # remembered so /api/metrics can render levels as Prometheus
            # gauges instead of (monotonicity-implying) counters
            self._gauge_names.add(name)

    def gauge_names(self) -> set:
        """Counter names set via :meth:`gauge` (levels, not totals)."""
        with self._lock:
            return set(self._gauge_names)

    def counter_value(self, name: str) -> int:
        return self._counters.get(name, 0)

    def get_counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def trace_counts(self) -> Dict[str, int]:
        """Just the ``trace/*`` counters (the retrace ledger)."""
        return {k: v for k, v in self._counters.items()
                if k.startswith("trace/")}

    def overlap_stats(self) -> Dict[str, float]:
        """Transfer-vs-compute overlap summary for the input pipeline:
        ``host_wait_s`` is time fit() spent blocked on the next (staged)
        batch, ``dispatch_s`` is time spent issuing train steps. A healthy
        overlapped loop keeps host_wait a small fraction of dispatch."""
        out: Dict[str, float] = {}
        for sec, key in (("pipeline/next_batch", "host_wait_s"),
                         ("pipeline/dispatch", "dispatch_s")):
            s = self._sections.get(sec)
            if s:
                out[key] = s["total_s"]
                out[key.replace("_s", "_count")] = s["count"]
        if "host_wait_s" in out and "dispatch_s" in out:
            busy = out["host_wait_s"] + out["dispatch_s"]
            if busy > 0:
                out["host_wait_frac"] = out["host_wait_s"] / busy
        return out

    def telemetry_stats(self) -> Dict[str, float]:
        """In-graph-telemetry drain ledger: host time spent in the batched
        aux readbacks (``telemetry/drain`` sections — the ONLY host sync
        the telemetry layer pays) plus the drained-step counter. Empty
        when telemetry never ran."""
        out: Dict[str, float] = {}
        s = self._sections.get("telemetry/drain")
        if s:
            out = {"drain_s": s["total_s"], "drain_count": s["count"],
                   "drain_max_s": s["max_s"]}
        n = self._counters.get("telemetry/drained_steps")
        if n:
            out["drained_steps"] = n
        return out

    def checkpoint_stats(self) -> Dict[str, float]:
        """Checkpoint-path ledger: snapshot time (the batched readback on
        the training thread — the ONLY hot-loop cost of async
        checkpointing), background write/commit time, committed count and
        bytes. Empty when no checkpoint ever committed."""
        out: Dict[str, float] = {}
        for sec, key in (("checkpoint/snapshot", "snapshot_s"),
                         ("checkpoint/write", "write_s")):
            s = self._sections.get(sec)
            if s:
                out[key] = s["total_s"]
                out[key.replace("_s", "_count")] = s["count"]
        for ctr, key in (("checkpoint/committed", "committed"),
                         ("checkpoint/bytes", "bytes")):
            n = self._counters.get(ctr)
            if n:
                out[key] = n
        return out

    def supervisor_stats(self) -> Dict[str, float]:
        """Self-healing-loop ledger: supervised attempts, restarts,
        watchdog fires, preemptions, storm trips, give-ups (the
        ``supervisor/*`` counters) plus backoff wall time — the /api/health
        and drill-test view of what the restart loop actually did. Empty
        when no supervisor ever ran."""
        out: Dict[str, float] = {
            k.split("/", 1)[1]: v for k, v in self._counters.items()
            if k.startswith("supervisor/")}
        s = self._sections.get("supervisor/backoff")
        if s:
            out["backoff_s"] = s["total_s"]
            out["backoff_count"] = s["count"]
        return out

    def collective_stats(self) -> Dict[str, float]:
        """Gradient-exchange ledger (``collective/*`` + ``zero1/*``
        counters): bytes moved per collective kind (dense ``psum`` vs the
        ZeRO-1 ``reduce_scatter``/``all_gather`` pair), the ZeRO-1 sharded
        updater-state footprint, and the encoded-exchange element counters
        with the derived density and the reference wire-format byte
        estimate — ``ThresholdCompression``'s two encodings: 4-byte sparse
        indices below 1/16 density, 2-bit bitmap above (the estimate takes
        the cheaper per run). Empty when no ParallelWrapper fit ran."""
        out: Dict[str, float] = {
            k.split("/", 1)[1]: v for k, v in self._counters.items()
            if k.startswith("collective/")}
        for ctr, key in (("zero1/updater_state_bytes_total",
                          "zero1_updater_state_bytes_total"),
                         ("zero1/updater_state_bytes_per_replica",
                          "zero1_updater_state_bytes_per_replica")):
            n = self._counters.get(ctr)
            if n:
                out[key] = n
        sent = out.get("encoded_elems_sent")
        total = out.get("encoded_elems_total")
        if total:
            out["encoded_density"] = sent / total
            out["encoded_bytes_est"] = int(min(4 * sent, total // 4))
            out["encoded_dense_bytes_equiv"] = int(4 * total)
        return out

    def elastic_stats(self) -> Dict[str, float]:
        """Online-resize ledger (``elastic/*`` counters): resizes split
        into shrinks/grows, grow-back probe attempts and failures, the
        live ``workers`` gauge, plus the resize wall-time section — the
        /api/health and elastic-smoke view of what the elastic data axis
        actually did. Empty until a parallel fit runs (every parallel fit
        sets the ``workers`` gauge — the live data-axis width is a level,
        not an elastic event); resize/probe counters appear only after an
        actual elastic event."""
        out: Dict[str, float] = {
            k.split("/", 1)[1]: v for k, v in self._counters.items()
            if k.startswith("elastic/")}
        s = self._sections.get("elastic/resize")
        if s:
            out["resize_s"] = s["total_s"]
            out["resize_count"] = s["count"]
        return out

    def pipeline_stats(self) -> Dict[str, float]:
        """Pipeline-parallel ledger (the PipelineTrainer's counters —
        NOT the input pipeline's, which live on the overlap/fault
        ledgers): live ``stages`` gauge, ``remaps`` + remap wall time,
        ``microbatches`` dispatched, schedule tick occupancy
        (``busy_ticks``/``tick_slots`` from the same mask tables the
        compiled step executes) with the derived ``bubble_fraction`` —
        the /api/health, /api/metrics and pipeline-parallel-smoke view
        of what the stage axis actually did. Empty until a
        PipelineTrainer fit runs."""
        out: Dict[str, float] = {}
        for ctr, key in (("pipeline/stages", "stages"),
                         ("pipeline/remaps", "remaps"),
                         ("pipeline/microbatches", "microbatches"),
                         ("pipeline/busy_ticks", "busy_ticks"),
                         ("pipeline/tick_slots", "tick_slots")):
            n = self._counters.get(ctr)
            if n:
                out[key] = n
        slots = out.get("tick_slots")
        if slots:
            out["bubble_fraction"] = 1.0 - out.get("busy_ticks", 0) / slots
        s = self._sections.get("pipeline/remap")
        if s:
            out["remap_s"] = s["total_s"]
            out["remap_count"] = s["count"]
        return out

    def serving_stats(self) -> Dict[str, float]:
        """Serving-tier ledger (``serving/*`` counters + sections): request
        and batch counts, bucket fill ratio (real rows / dispatched bucket
        capacity) and its complement pad waste, queue-depth high-water,
        requeues ridden through replica retirement, oversize admissions,
        the traces-after-warmup counter (MUST stay 0 in steady state —
        the serving-smoke bench hard-fails on it), and the dispatch /
        warmup wall-time sections. Rolling p50/p99 request latency lives
        on the engines themselves (``ServingEngine.latency_stats()`` — a
        quantile is not a counter); ``parallel.serving.serving_health()``
        merges both views for ``/api/health``. Empty when no ServingEngine
        ever dispatched."""
        out: Dict[str, float] = {
            k.split("/", 1)[1]: v for k, v in self._counters.items()
            if k.startswith("serving/")}
        cap = out.get("capacity_rows")
        if cap:
            out["fill_ratio"] = out.get("rows", 0) / cap
            out["pad_waste"] = out.get("pad_rows", 0) / cap
        for sec, key in (("serving/dispatch", "dispatch_s"),
                         ("serving/warmup", "warmup_s")):
            s = self._sections.get(sec)
            if s:
                out[key] = s["total_s"]
                out[key.replace("_s", "_count")] = s["count"]
        return out

    def autoscale_stats(self) -> Dict[str, float]:
        """Closed-loop autoscaler ledger (``autoscale/*`` counters):
        controller ticks, scale-ups/downs actuated, held decisions,
        skipped (drilled) evaluations, and the live ``replicas`` gauge —
        the /api/health and autoscale-smoke view of what the controller
        actually did. Empty until an :class:`parallel.autoscale.
        Autoscaler` ticks."""
        return {k.split("/", 1)[1]: v for k, v in self._counters.items()
                if k.startswith("autoscale/")}

    def fleet_stats(self) -> Dict[str, float]:
        """Vmapped-fleet ledger (``fleet/*`` counters): culls, spawns,
        per-member NaN culls, telemetry-window drains, and the live
        ``members`` gauge (alive count — every FleetTrainer sets it at
        construction and on every lifecycle change). The /api/health,
        /api/metrics and fleet-smoke view of what the population
        actually did. Empty until a :class:`parallel.fleet.FleetTrainer`
        exists."""
        return {k.split("/", 1)[1]: v for k, v in self._counters.items()
                if k.startswith("fleet/")}

    def precision_stats(self) -> Dict[str, float]:
        """Mixed-precision ledger (``precision/*`` counters): fused
        update-kernel hits split by execution engine (``fused_buckets_
        pallas`` vs ``fused_buckets_xla``) and the fallbacks onto the
        per-leaf path, the fused BN epilogue hits / residual-chain hits /
        shape-gate fallbacks, what the stochastic rounding of
        low-precision updater state bakes into the compiled step
        (``sr_blocks``: ``threefry2x32`` blocks run, one per parameter
        element whose slots — names sorted — take its halfwords in the
        order word 0 low, word 0 high, word 1 low, word 1 high;
        ``sr_elements``: stored elements rounded, 16 bits each;
        ``sr_draws``: uint32 words the generator returned, two a block
        and one where ``random_bits_for`` xors them;
        ``16·sr_elements / (64·sr_blocks)`` is the share of generated
        bits that are used), and the live updater-state byte gauges by
        dtype (``updater_state_bytes_<dtype>`` + ``_total`` — the
        footprint the bf16 state mode halves). Counters are trace-time
        (one bump per compiled step, not per execution); byte gauges are
        levels.
        Empty until a fit or fused inference runs."""
        return {k.split("/", 1)[1]: v for k, v in self._counters.items()
                if k.startswith("precision/")}

    def sequence_stats(self) -> Dict[str, float]:
        """Sequence-op ledger (``seq/*`` counters, ``ops/ssm.py`` and
        ``ops/pallas_attention.causal_attention``): call sites that took
        the Pallas kernel or the plain XLA path (``scan_kernel`` /
        ``scan_fallback``, ``attn_kernel`` / ``attn_fallback`` for the
        attention forward, ``attn_bwd_kernel`` / ``attn_bwd_fallback`` for
        its backward, counted where the backward itself is traced, and
        ``attn_bwd_kv_resident`` for the backward kernel's call sites that
        take its query-block-first walk, the key/value head's dk and dv
        resident, where a group's dq outgrows VMEM) and the
        (query block, key block) pairs the attention band computes and
        leaves out of the square (``attn_key_blocks_run`` /
        ``attn_key_blocks_skipped``, per query head);
        ``attn_fwd_grid_steps``: the grid steps the forward kernel's calls
        issue, one a pair a key/value head (``attn_key_blocks_run`` over the
        query heads a key/value head; 0 from a call site on the XLA path);
        ``mla_layers``: latent
        attention layers traced (``LatentAttentionLayer``); and, from the
        ``mtp/*`` counters, ``mtp_modules``: multi-token-prediction modules
        traced (``MTPMergeLayer``). Trace-time counters: one bump per call
        site per compiled program, not per execution. Empty until a sequence
        layer is traced."""
        return {k.replace("seq/", "").replace("/", "_"): v
                for k, v in self._counters.items()
                if k.startswith(("seq/", "mtp/"))}

    def moe_stats(self) -> Dict[str, float]:
        """Routed-expert ledger (``moe/*`` counters, ``ops/moe.py`` and
        ``RoutedExpertsLayer``): call sites of the grouped matrix product
        that took the Pallas kernels or ``lax.ragged_dot`` (``gmm_kernel`` /
        ``gmm_fallback``), call sites of the experts' gated MLP that ran as
        one fused op or as two products around an XLA activation
        (``gated_kernel`` / ``gated_fallback``; either way its two products
        count as grouped products) and the static rows of the dispatch buffers
        (``dispatch_rows``: k x tokens a routed layer, the worst case of a
        dropless layer). Trace-time counters: one bump per call site per
        traced program, not per execution. The realised load is layer
        state (``ComputationGraph.expert_load()``). Empty until a routed
        layer is traced."""
        return {k.split("/", 1)[1]: v for k, v in self._counters.items()
                if k.startswith("moe/")}

    def xla_stats(self) -> Dict[str, float]:
        """XLA performance-observatory ledger (``common.xprof``): the
        per-executable roofline rows — calls, mean dispatch ms, retrace
        generations, compile wall, analytic flops/bytes, arithmetic
        intensity, MFU and the compute-vs-HBM-bound verdict — plus the
        census totals and the per-phase HBM watermark gauges, flattened
        under slash-keys. Cost fields appear after ``xprof.analyze()``
        ran (analysis re-traces, so it is explicit — never per step);
        everything else accrues live. Empty until an executable
        registers with the census."""
        try:
            from . import xprof

            return xprof.ledger()
        except Exception:       # census import/jax failure: ledger-silent
            return {}

    def tracecheck_stats(self) -> Dict[str, float]:
        """Steady-state sanitizer ledger (``tracecheck/*`` counters):
        regions armed and regions that tripped. The bench smoke configs
        assert both directions — clean runs arm without tripping, the
        injected-retrace drill must trip. Empty until a
        ``tracecheck.steady_state`` region runs."""
        return {k.split("/", 1)[1]: v for k, v in self._counters.items()
                if k.startswith("tracecheck/")}

    def fault_stats(self) -> Dict[str, float]:
        """Fault-tolerance ledger: injected-fault counters
        (``faults/<site>/<kind>``), pipeline retry count, and backoff wall
        time. The fault-smoke bench asserts on these both ways: injected
        faults fired, and clean configs fired none."""
        out: Dict[str, float] = {k: v for k, v in self._counters.items()
                                 if k.startswith("faults/")}
        n = self._counters.get("pipeline/retries")
        if n:
            out["retries"] = n
        s = self._sections.get("pipeline/retry_backoff")
        if s:
            out["retry_backoff_s"] = s["total_s"]
        return out

    def integrity_stats(self) -> Dict[str, float]:
        """Silent-corruption-defense ledger (``integrity/*`` counters):
        in-graph fingerprint checks and divergences, injected bitflip
        drills, scrub passes / verified entries / retries, and
        quarantined checkpoint generations (replica quarantines ride the
        supervisor ledger as ``quarantines``). Empty until an
        IntegrityListener or CheckpointScrubber runs — a clean soak
        window must show ``checks`` advancing with zero ``divergences``
        and zero ``quarantined_checkpoints``."""
        return {k.split("/", 1)[1]: v for k, v in self._counters.items()
                if k.startswith("integrity/")}

    def watchtower_stats(self) -> Dict[str, float]:
        """SLO watchtower ledger (``common.watchtower``): per-SLO alert
        state (0 ok / 1 warn / 2 page), fast-window burn rate and error
        budget remaining, plus evaluation/incident totals. Riding
        :data:`LEDGERS` puts it on ``/api/health``, ``/api/metrics`` and
        ``print_statistics`` in one move. Empty until a
        :class:`~.watchtower.Watchtower` is installed."""
        try:
            from . import watchtower

            return watchtower.stats()
        except Exception:   # watchtower absent/uninstalled: ledger-silent
            return {}

    def ledger_stats(self) -> Dict[str, Dict[str, float]]:
        """Every non-empty derived ledger (:data:`LEDGERS`), keyed by
        label — the same set ``print_statistics`` renders and
        ``/api/metrics`` exports."""
        out: Dict[str, Dict[str, float]] = {}
        for label, attr in self.LEDGERS:
            stats = getattr(self, attr)()
            if stats:
                out[label] = stats
        return out

    def print_statistics(self) -> str:
        lines = [f"{'section':<32}{'count':>8}{'total ms':>12}"
                 f"{'mean ms':>12}{'max ms':>12}"]
        for name, s in sorted(self.get_statistics().items(),
                              key=lambda kv: -kv[1]["total_s"]):
            mean = s["total_s"] / max(s["count"], 1)
            lines.append(f"{name:<32}{s['count']:>8}"
                         f"{s['total_s'] * 1e3:>12.2f}"
                         f"{mean * 1e3:>12.2f}{s['max_s'] * 1e3:>12.2f}")
        for label, stats in self.ledger_stats().items():
            lines.append(f"[{label}] " + "  ".join(
                f"{k}={round(v, 6) if isinstance(v, float) else v}"
                for k, v in sorted(stats.items())
                if isinstance(v, (int, float))))
        out = "\n".join(lines)
        print(out)
        return out

    def reset(self) -> None:
        with self._lock:
            self._sections.clear()
            self._counters.clear()
            self._gauge_names.clear()
