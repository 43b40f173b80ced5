"""XLA performance observatory: executable census, roofline ledger, HBM
watermarks, device time by model scope.

The repo can time a step (profiler sections, bench fences) but before
this module it could not say *why* a step is slow: no per-executable
FLOPs/bytes, no compute-vs-memory-bound verdict, no HBM watermark, no
compile ledger. The whole-graph-compilation line of work (TVM, nGraph —
PAPERS.md) argues that graph-level optimization is only steerable with
per-kernel cost models; this is that layer, built on jax's own
``lowered.cost_analysis()`` / ``compiled.memory_analysis()`` artifacts.

Four instruments, one module:

1. **Executable census** — every long-lived compiled function in the
   package registers under a stable name from :data:`EXEC_SITES`
   (enforced project-wide by graftlint's ``executable-census`` rule, the
   fault-site-registry 4-way pattern: call sites vs registry vs the
   docstring table below vs the test/bench corpus).
   :func:`register_jit` wraps a ``jax.jit`` callable and tracks, per
   entry: call count, cumulative dispatch wall time, a retrace
   GENERATION counter (``jit._cache_size()`` growth — a new input
   signature means a new executable), the first-call wall time of each
   generation (trace+compile+first run), and the argument avals of the
   newest generation (``ShapeDtypeStruct`` only — donation-safe, no
   buffer retention). :func:`register_aot` records explicitly
   ``.lower().compile()``-d executables (the serving bucket ladder) with
   their cost/memory analysis extracted immediately — already compiled,
   nothing re-traced. :func:`note_subexec` records fused kernels that
   live INSIDE a parent executable (the Pallas flat-bucket updaters)
   with analytic counted cost at trace time.
2. **Roofline attribution ledger** — :func:`analyze` lowers registered
   entries against their stored avals and extracts
   ``cost_analysis()`` (flops, bytes accessed, transcendentals) and,
   with ``compile=True``, ``memory_analysis()`` (argument/output/temp/
   generated-code bytes) plus an input-sharding fingerprint. Backends
   without cost analysis degrade to a COUNTED fallback (bytes from the
   avals, flops omitted) — never a crash. :func:`roofline` joins the
   analytic cost with measured dispatch time into per-executable MFU,
   arithmetic intensity, and a compute-bound vs HBM-bound verdict
   against the device's roof (:data:`DEVICE_PEAKS`, keyed by
   ``device_kind``; :func:`set_roof` to override);
   :func:`ledger` flattens it into the ``xla`` entry of
   ``OpProfiler.LEDGERS`` so ``/api/health``, ``/api/metrics`` and
   ``print_statistics`` all carry it for free. CAVEATS: dispatch wall
   time is host-side submit time — on an async backend it converges to
   device time only when the caller fences (the bench does; feed the
   fenced per-step median via :func:`note_measured` for honest MFU);
   ``analyze`` RE-TRACES the function body (trace counters move, jax
   compile events fire) — call it outside ``tracecheck.steady_state``
   regions, never in a hot loop.
3. **HBM watermarks** — :func:`memory_watermark` takes the SAME
   device/host memory census ``/api/health`` serves
   (``common.system_info.memory_summary``: per-device PJRT stats + the
   ``jax.live_arrays`` walk — one census function, two consumers) and
   folds it into per-phase peak gauges. ``data.pipeline.run_epochs``
   samples once per epoch (phase ``fit``), the serving warmup samples
   ``serving_warmup``, and the supervisor's crash blackbox dumps the
   full census (:func:`dump_memory_census` → ``memcensus.json`` beside
   ``blackbox.jsonl``) so OOM-class failures carry the memory picture
   alongside the event tail.

4. **Device time by model scope** — :func:`scope_times` reads a profiler
   session's raw ``XSpace`` (``OpProfiler.trace(logdir)``) and books every
   device op of the traced step, self time only, to the step's own names:
   phase (``forward`` / ``recompute`` / ``backward`` / ``update``), vertex,
   the layer's inner scope, with the op's ``hlo_category``, flops and
   bytes; the program's host sections ride the same file.
   ``python3 -m deeplearning4j_tpu.common.xprof LOGDIR`` prints it.

Census overhead is one enabled-flag read plus two ``perf_counter`` calls
and a lock per dispatch (``configure(enabled=False)`` reduces it to the
flag read); the ``xprof-smoke`` bench config A/B-gates it at <=5% with a
zero retrace delta.

Executable-census registry
--------------------------
==========================  ============================================
census name                 executable / registrar
==========================  ============================================
mln/infer                   MultiLayerNetwork.output jit
mln/fit_step                MultiLayerNetwork per-step train jit
mln/fit_chunk               MultiLayerNetwork steps_per_dispatch scan jit
mln/tbptt_step              MultiLayerNetwork TBPTT segment jit
mln/pretrain_step           MultiLayerNetwork layerwise pretrain jit
graph/infer                 ComputationGraph.output jit
graph/fit_step              ComputationGraph per-step train jit
graph/fit_chunk             ComputationGraph scan-chunk jit
transfer/featurize          TransferLearningHelper frozen-bottom jit
pw/fit_step                 ParallelWrapper shard_map step jit (dense +
                            ZeRO-1 paths — one executable)
pw/fit_chunk                ParallelWrapper scan-chunk jit
pipeline/fit_step           PipelineTrainer whole-schedule step jit (one
                            generation per (stage-count, schedule))
pipeline/legacy_fwd         legacy PipelineParallel forward jit
pipeline/legacy_step        legacy PipelineParallel train-step jit
pipeline/hetero_fwd         HeterogeneousPipeline forward jit
pipeline/hetero_step        HeterogeneousPipeline train-step jit
fleet/step                  FleetTrainer vmapped population step jit
fleet/infer                 FleetTrainer vmapped inference jit
embeddings/lookup           ShardedEmbeddings gather jit
embeddings/update           ShardedEmbeddings scatter-update jit
serving/bucket              ServingEngine AOT bucket executables (one
                            variant per (shape, device slot))
samediff/exec               SameDiff cached forward-exec jit
samediff/grad               SameDiff cached gradient jit
samediff/fit_step           SameDiff fused train-step jit
nlp/w2v_subsample           Word2Vec device subsampling jit
nlp/w2v_sg_block            Word2Vec skip-gram pair-block jit
nlp/w2v_table_block         Word2Vec dense-round table jit (plain +
                            sharded-table variants)
nlp/w2v_cbow_block          Word2Vec CBOW windowed-block jit
nlp/pv_dbow_block           ParagraphVectors DBOW block jit
nlp/pv_dm_block             ParagraphVectors DM (CBOW-class) block jit
nlp/pv_pos_map              ParagraphVectors shuffled-pair-order jit
nlp/pv_subsample            ParagraphVectors 3-stream subsampling jit
nlp/fasttext_block          FastText subword CBOW block jit
nlp/glove_block             GloVe AdaGrad descent block jit
data/feature_transform      AsyncDataSetIterator on-device transform jit
pallas/update_bucket        fused flat-bucket updater kernels (counted
                            sub-executable: dispatches inside the parent
                            step; analytic flops/bytes at trace time)
==========================  ============================================
"""

from __future__ import annotations

import bisect
import functools
import glob
import gzip
import json
import os
import re
import threading
import time
import weakref
from typing import Any, Dict, Optional, Tuple

from . import flightrec
from .profiler import OpProfiler

#: The central executable-census registry (generated-checked against the
#: module docstring table by graftlint's ``executable-census`` rule):
#: census name -> what registers it + the drill that proves it. A
#: ``register_jit``/``register_aot``/``note_subexec`` call with an
#: unregistered literal is a lint finding AND a runtime ValueError.
EXEC_SITES: Dict[str, Dict[str, str]] = {
    "mln/infer": {
        "desc": "MultiLayerNetwork.output inference jit",
        "drill": "test_xprof census coverage"},
    "mln/fit_step": {
        "desc": "MultiLayerNetwork per-step train jit",
        "drill": "test_xprof census coverage; xprof-smoke"},
    "mln/fit_chunk": {
        "desc": "MultiLayerNetwork steps_per_dispatch scan jit",
        "drill": "test_xprof census coverage"},
    "mln/tbptt_step": {
        "desc": "MultiLayerNetwork TBPTT segment jit",
        "drill": "test_xprof registry table"},
    "mln/pretrain_step": {
        "desc": "MultiLayerNetwork layerwise pretrain jit",
        "drill": "test_xprof registry table"},
    "graph/infer": {
        "desc": "ComputationGraph.output inference jit",
        "drill": "test_xprof census coverage"},
    "graph/fit_step": {
        "desc": "ComputationGraph per-step train jit",
        "drill": "test_xprof census coverage; bench resnet50 roofline"},
    "graph/fit_chunk": {
        "desc": "ComputationGraph scan-chunk jit",
        "drill": "test_xprof registry table"},
    "transfer/featurize": {
        "desc": "TransferLearningHelper frozen-bottom featurize jit",
        "drill": "test_xprof registry table"},
    "pw/fit_step": {
        "desc": "ParallelWrapper shard_map step jit (dense + ZeRO-1)",
        "drill": "test_xprof census coverage"},
    "pw/fit_chunk": {
        "desc": "ParallelWrapper scan-chunk jit",
        "drill": "test_xprof registry table"},
    "pipeline/fit_step": {
        "desc": "PipelineTrainer whole-schedule step jit",
        "drill": "test_xprof registry table"},
    "pipeline/legacy_fwd": {
        "desc": "legacy PipelineParallel forward jit",
        "drill": "test_xprof registry table"},
    "pipeline/legacy_step": {
        "desc": "legacy PipelineParallel train-step jit",
        "drill": "test_xprof registry table"},
    "pipeline/hetero_fwd": {
        "desc": "HeterogeneousPipeline forward jit",
        "drill": "test_xprof registry table"},
    "pipeline/hetero_step": {
        "desc": "HeterogeneousPipeline train-step jit",
        "drill": "test_xprof registry table"},
    "fleet/step": {
        "desc": "FleetTrainer vmapped population step jit",
        "drill": "test_xprof census coverage"},
    "fleet/infer": {
        "desc": "FleetTrainer vmapped inference jit",
        "drill": "test_xprof registry table"},
    "embeddings/lookup": {
        "desc": "ShardedEmbeddings gather jit",
        "drill": "test_xprof registry table"},
    "embeddings/update": {
        "desc": "ShardedEmbeddings scatter-update jit",
        "drill": "test_xprof registry table"},
    "serving/bucket": {
        "desc": "ServingEngine AOT bucket executable (variant per "
                "(shape, device slot))",
        "drill": "test_xprof serving AOT census; xprof-smoke"},
    "samediff/exec": {
        "desc": "SameDiff cached forward-exec jit",
        "drill": "test_xprof registry table"},
    "samediff/grad": {
        "desc": "SameDiff cached gradient jit",
        "drill": "test_xprof registry table"},
    "samediff/fit_step": {
        "desc": "SameDiff fused train-step jit",
        "drill": "test_xprof registry table"},
    "nlp/w2v_subsample": {
        "desc": "Word2Vec device subsampling jit",
        "drill": "test_xprof registry table"},
    "nlp/w2v_sg_block": {
        "desc": "Word2Vec skip-gram pair-block jit",
        "drill": "test_xprof registry table"},
    "nlp/w2v_table_block": {
        "desc": "Word2Vec dense-round table jit (plain + sharded)",
        "drill": "test_xprof registry table"},
    "nlp/w2v_cbow_block": {
        "desc": "Word2Vec CBOW windowed-block jit",
        "drill": "test_xprof registry table"},
    "nlp/pv_dbow_block": {
        "desc": "ParagraphVectors DBOW block jit",
        "drill": "test_xprof registry table"},
    "nlp/pv_dm_block": {
        "desc": "ParagraphVectors DM block jit",
        "drill": "test_xprof registry table"},
    "nlp/pv_pos_map": {
        "desc": "ParagraphVectors shuffled-pair-order jit",
        "drill": "test_xprof registry table"},
    "nlp/pv_subsample": {
        "desc": "ParagraphVectors 3-stream subsampling jit",
        "drill": "test_xprof registry table"},
    "nlp/fasttext_block": {
        "desc": "FastText subword CBOW block jit",
        "drill": "test_xprof registry table"},
    "nlp/glove_block": {
        "desc": "GloVe AdaGrad descent block jit",
        "drill": "test_xprof registry table"},
    "data/feature_transform": {
        "desc": "AsyncDataSetIterator on-device feature transform jit",
        "drill": "test_xprof registry table"},
    "pallas/update_bucket": {
        "desc": "fused flat-bucket updater kernels (counted "
                "sub-executable inside the parent step)",
        "drill": "test_xprof counted sub-executable test"},
}

#: Device rooflines keyed by ``jax.devices()[0].device_kind``:
#: (peak flops/s, peak memory bytes/s) of ONE chip. The one table of
#: peaks in the repository (``bench.py`` imports it).
#:
#: - ``"TPU v5 lite"`` is how JAX names a TPU v5e chip. Google Cloud
#:   documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s of HBM.
#: - ``"cpu"`` is a NOMINAL single-core planning roof for the CPU drills
#:   (MFU/bound verdicts against it are approximate by construction and
#:   say nothing about a chip — override with :func:`set_roof` when the
#:   host is characterized).
#:
#: A device that is not in the table is an error, not a default.
DEVICE_PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v5 lite": (197e12, 819e9),
    "cpu": (5e10, 2e10),
}


def device_peaks(device=None) -> Tuple[float, float]:
    """``(peak flops/s, peak bytes/s)`` of ``device`` (default: the first
    JAX device) from :data:`DEVICE_PEAKS`; raises for a device kind the
    table does not hold."""
    if device is None:
        import jax

        device = jax.devices()[0]
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device.device_kind!r} "
            f"(platform {device.platform!r}): add its row, with its "
            "source, to common.xprof.DEVICE_PEAKS") from None


def _now() -> float:
    return time.perf_counter()


class _Entry:
    """One census entry: identity + accumulated dispatch/compile
    accounting + the newest generation's avals + analysis results."""

    __slots__ = ("name", "calls", "dispatch_s", "generations", "compile_s",
                 "avals", "fn_ref", "fingerprint", "cost", "memory",
                 "cost_source", "analyzed_gen", "measured_step_s",
                 "variants", "error", "subexec")

    def __init__(self, name: str):
        self.name = name
        self.calls = 0
        self.dispatch_s = 0.0
        self.generations = 0        # distinct compiled executables seen
        self.compile_s = 0.0        # sum of first-call-per-generation wall
        self.avals = None           # (args, kwargs) aval trees, newest gen
        self.fn_ref = None          # weakref to the live jit function
        self.fingerprint: Dict[str, Any] = {}
        self.cost: Optional[Dict[str, float]] = None
        self.memory: Optional[Dict[str, float]] = None
        self.cost_source: Optional[str] = None   # "xla" | "counted"
        self.analyzed_gen = 0       # generation the analysis belongs to
        self.measured_step_s: Optional[float] = None
        self.variants = 0           # AOT variants folded in (serving)
        self.error: Optional[str] = None
        self.subexec = False        # counted-only sub-executable

    def summary(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "name": self.name, "calls": self.calls,
            "dispatch_s": round(self.dispatch_s, 6),
            "generations": self.generations,
            "compile_s": round(self.compile_s, 6),
            "fingerprint": dict(self.fingerprint),
            "cost_source": self.cost_source,
        }
        if self.cost:
            out["cost"] = dict(self.cost)
        if self.memory:
            out["memory"] = dict(self.memory)
        if self.variants:
            out["variants"] = self.variants
        if self.measured_step_s is not None:
            out["measured_step_s"] = self.measured_step_s
        if self.subexec:
            out["subexec"] = True
        if self.error:
            out["error"] = self.error
        return out


class ExecutableCensus:
    """The process-wide census (instantiable for tests). Thread-safe:
    dispatches land from the training thread, serving workers and the
    checkpoint writer alike."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: Dict[str, _Entry] = {}
        self._enabled = True
        self._roof: Optional[Tuple[float, float]] = None
        self._watermarks: Dict[str, Dict[str, Any]] = {}

    # -- config -----------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def configure(self, enabled: Optional[bool] = None) -> "ExecutableCensus":
        with self._lock:
            if enabled is not None:
                self._enabled = bool(enabled)
        return self

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()
            self._watermarks.clear()

    def set_roof(self, peak_flops: float, peak_bytes_per_s: float) -> None:
        with self._lock:
            self._roof = (float(peak_flops), float(peak_bytes_per_s))

    def _platform_roof(self) -> Tuple[float, float]:
        if self._roof is not None:
            return self._roof
        return device_peaks()

    # -- registration -----------------------------------------------------
    def _entry(self, name: str) -> _Entry:
        if name not in EXEC_SITES:
            raise ValueError(
                f"unknown executable-census site {name!r} — register it "
                "in common.xprof.EXEC_SITES (and the docstring table)")
        with self._lock:
            e = self._entries.get(name)
            if e is None:
                e = self._entries[name] = _Entry(name)
            return e

    def register_jit(self, name: str, fn, *, donate=None,
                     static_argnames=None):
        """Wrap a ``jax.jit`` callable under census ``name``. The wrapper
        is call-transparent (attribute access, ``.lower`` included, falls
        through to the jit) and donation-safe — only avals are retained.
        Re-registering a name (a rebuilt step) accumulates onto the same
        entry: that IS the retrace-generation ledger. Wrappers resolve
        their entry BY NAME per dispatch, so a :meth:`reset` opens a
        clean window without orphaning live wrappers."""
        fp: Dict[str, Any] = {}
        if donate is not None:
            fp["donate_argnums"] = tuple(donate)
        if static_argnames is not None:
            fp["static_argnames"] = tuple(static_argnames)
        e = self._entry(name)
        with self._lock:
            e.fingerprint.update(fp)
        return _Censused(self, name, fn, fp)

    def register_aot(self, name: str, compiled, *, variant: str = "",
                     compile_s: Optional[float] = None) -> None:
        """Record an explicitly ``.lower().compile()``-d executable. Cost
        and memory analysis are extracted IMMEDIATELY (the object is
        already compiled — nothing traces); repeated variants (serving
        buckets) accumulate flops/bytes onto the one entry."""
        if compiled is None:
            return
        e = self._entry(name)
        cost = _cost_dict(compiled)
        mem = _memory_dict(compiled)
        source = "xla" if cost is not None else "counted"
        if cost is None and mem is not None:
            # counted fallback for backends without AOT cost analysis:
            # bytes from the executable's own argument/output footprint
            # (the same degradation contract analyze() applies)
            nbytes = mem.get("argument_bytes", 0) + mem.get(
                "output_bytes", 0)
            if nbytes:
                cost = {"bytes_accessed": float(nbytes)}
        with self._lock:
            e.generations += 1
            e.variants += 1
            if compile_s:
                e.compile_s += float(compile_s)
            if cost is not None:
                # key-UNION merge: a variant whose analysis omits a key
                # (e.g. no transcendentals) must not erase the other
                # variants' accumulated mass; mixed xla/counted ladders
                # keep every variant's bytes and report the stronger
                # source
                prev = e.cost or {}
                e.cost = {k: prev.get(k, 0.0) + cost.get(k, 0.0)
                          for k in set(prev) | set(cost)}
                e.cost_source = ("xla" if "xla" in (source, e.cost_source)
                                 else "counted")
            elif e.cost_source is None:
                e.cost_source = "counted"
                e.cost = {}
            if mem is not None:
                prev_m = e.memory or {}
                e.memory = {k: prev_m.get(k, 0) + v for k, v in mem.items()}
            if variant:
                e.fingerprint["last_variant"] = variant
            gen = e.generations
        flightrec.event("xprof/exec", executable=name,
                        generation=gen, variant=variant or None,
                        aot=True)

    def note_subexec(self, name: str, flops: Optional[float] = None,
                     bytes_accessed: Optional[float] = None,
                     **attrs) -> None:
        """Counted census entry for a kernel dispatched INSIDE a parent
        executable (fused Pallas updaters). Called at trace time — once
        per parent compile, like the ``precision/*`` counters. The cost
        is LAST-TRACE-WINS, never accumulated: the analytic flops/bytes
        always describe one execution of the most recent parent (a
        rebuild, an analysis re-lowering, or a second fused model must
        not inflate the row); ``generations`` counts the traces seen."""
        e = self._entry(name)
        with self._lock:
            e.subexec = True
            e.generations += 1
            e.cost_source = "counted"
            cost: Dict[str, float] = {}
            if flops is not None:
                cost["flops"] = float(flops)
            if bytes_accessed is not None:
                cost["bytes_accessed"] = float(bytes_accessed)
            e.cost = cost
            for k, v in attrs.items():
                e.fingerprint[k] = v
            gen = e.generations
        flightrec.event("xprof/exec", executable=name,
                        generation=gen, subexec=True)

    # -- dispatch accounting (wrapper callback) ---------------------------
    def _note_call(self, name: str, fn, wrapper, dt: float, args,
                   kwargs) -> None:
        try:
            size = fn._cache_size()
        except Exception:
            size = None
        avals = None
        with self._lock:
            # the entry is resolved BY NAME per dispatch (a reset() must
            # not orphan live wrappers), and wrapper._last_cache is read
            # AND advanced under the census lock: concurrent dispatches
            # through one wrapper (serving workers share a model) must
            # bill one real compile as one generation, not one per
            # racing thread
            e = self._entries.get(name)
            if e is None:
                e = self._entries[name] = _Entry(name)
                e.fingerprint.update(wrapper._fp)
            last = wrapper._last_cache
            if size is None:
                # no cache introspection on this jax: fall back to
                # "first call through this wrapper = one generation"
                compiled_now = last == 0
                size = last + (1 if compiled_now else 0)
            else:
                compiled_now = size > last
            # post-reset (or census re-enabled): the warm executable
            # serving this call joins the fresh window as its FIRST
            # generation — exactly one, no compile wall credited
            # (nothing compiled during this call)
            window_seed = (not compiled_now and e.generations == 0
                           and size > 0)
            wrapper._last_cache = size
            e.calls += 1
            e.dispatch_s += dt
            if compiled_now:
                e.generations += size - last
                e.compile_s += dt
            elif window_seed:
                e.generations += 1
            new_gen = compiled_now or window_seed
            if new_gen:
                gen = e.generations
        if new_gen:
            # aval capture walks the argument pytrees — off-lock, then
            # published in one assignment (last-writer-wins is fine:
            # both racers saw the same signatures)
            avals = _avalize(args, kwargs)
            with self._lock:
                e.avals = avals
                e.fn_ref = weakref.ref(fn)
            flightrec.event("xprof/exec", executable=e.name,
                            generation=gen,
                            compile_s=(round(dt, 6) if compiled_now
                                       else None))

    def note_measured(self, name: str, step_s: float) -> None:
        """Feed a FENCED per-step time (the bench's value-fenced median)
        so the roofline joins against real device time instead of
        host-side submit time."""
        e = self._entry(name)
        with self._lock:
            e.measured_step_s = float(step_s)

    # -- analysis ---------------------------------------------------------
    def analyze(self, names=None, compile: bool = True) -> Dict[str, dict]:
        """Extract XLA cost/memory analysis for registered jit entries by
        re-lowering against their stored avals. RE-TRACES the function
        bodies (trace/* counters move, jax compile events fire) — run
        outside ``tracecheck.steady_state`` regions, at collection time,
        never per step. ``compile=False`` skips the AOT compile (cost
        analysis only, no memory analysis — cheaper). Backends whose
        analysis is unavailable degrade to the counted fallback."""
        with self._lock:
            todo = [e for e in self._entries.values()
                    if (names is None or e.name in names)
                    and not e.subexec and not e.variants
                    and e.avals is not None
                    and (e.cost_source is None
                         or e.analyzed_gen != e.generations)]
        out = {}
        for e in todo:
            self._analyze_one(e, compile)
            out[e.name] = e.summary()
        return out

    def _analyze_one(self, e: _Entry, do_compile: bool) -> None:
        fn = e.fn_ref() if e.fn_ref is not None else None
        args, kwargs = e.avals
        cost = mem = None
        err = None
        fp: Dict[str, Any] = {}
        if fn is None:
            err = "executable collected before analysis"
        else:
            try:
                lowered = fn.lower(*args, **kwargs)
                cost = _cost_dict(lowered)
                try:
                    mem = _out_bytes_dict(lowered)
                except Exception:
                    mem = None
                if do_compile:
                    compiled = lowered.compile()
                    mem = _memory_dict(compiled) or mem
                    if cost is None:
                        cost = _cost_dict(compiled)
                    fp = _sharding_fingerprint(compiled)
            except Exception as exc:   # analysis must never take down
                err = f"{type(exc).__name__}: {exc}"
        with self._lock:
            e.fingerprint.update(fp)
            if cost is not None:
                e.cost = cost
                e.cost_source = "xla"
            else:
                # counted fallback: input bytes from the avals (plus
                # output bytes when the lowering got far enough)
                counted = {"bytes_accessed": _aval_bytes(args, kwargs)}
                if mem and mem.get("output_bytes"):
                    counted["bytes_accessed"] += mem["output_bytes"]
                e.cost = counted
                e.cost_source = "counted"
            if mem is not None:
                e.memory = mem
            e.analyzed_gen = e.generations
            e.error = err

    # -- roofline ---------------------------------------------------------
    def roofline(self) -> Dict[str, dict]:
        """Per-executable roofline attribution: measured step time joined
        with analytic flops/bytes -> MFU, arithmetic intensity, and the
        compute-vs-HBM-bound verdict (AI against the roof's ridge
        point). Entries without analysis carry what they have."""
        peak_f, peak_b = self._platform_roof()
        ridge = (peak_f / peak_b) if peak_f and peak_b else None
        out: Dict[str, dict] = {}
        with self._lock:
            entries = list(self._entries.values())
        for e in entries:
            row = e.summary()
            step_s = e.measured_step_s
            if step_s is None and e.calls:
                step_s = e.dispatch_s / e.calls
            cost = e.cost or {}
            flops = cost.get("flops")
            nbytes = cost.get("bytes_accessed")
            if step_s:
                row["step_s"] = round(step_s, 6)
            if flops and nbytes:
                row["arithmetic_intensity"] = flops / nbytes
                if ridge is not None:
                    row["bound"] = ("compute" if flops / nbytes >= ridge
                                    else "hbm")
            if flops and step_s and peak_f:
                row["effective_flops_per_s"] = flops / step_s
                row["mfu"] = flops / step_s / peak_f
            if nbytes and step_s and peak_b:
                row["achieved_bytes_per_s"] = nbytes / step_s
            out[e.name] = row
        return out

    def ledger(self) -> Dict[str, float]:
        """The flat ``xla`` profiler ledger (``OpProfiler.LEDGERS``):
        per-executable roofline numbers under slash-keys plus census
        totals and the HBM watermark gauges — everything numeric, so
        ``/api/metrics`` and ``print_statistics`` render it as-is."""
        rows = self.roofline()
        peak_f, peak_b = self._platform_roof()
        out: Dict[str, float] = {}
        if rows:
            out["executables"] = len(rows)
            out["analyzed"] = sum(1 for r in rows.values() if "cost" in r)
            out["calls"] = sum(r.get("calls", 0) for r in rows.values())
            out["dispatch_s"] = round(sum(r.get("dispatch_s", 0.0)
                                          for r in rows.values()), 6)
            if peak_f:
                out["roof_peak_flops"] = peak_f
            if peak_b:
                out["roof_peak_bytes_per_s"] = peak_b
        for name, r in rows.items():
            cost = r.get("cost", {})
            if r.get("calls"):
                out[f"{name}/calls"] = r["calls"]
                out[f"{name}/dispatch_ms"] = round(
                    r["dispatch_s"] / r["calls"] * 1e3, 4)
            if r.get("generations"):
                out[f"{name}/generations"] = r["generations"]
            if r.get("compile_s"):
                out[f"{name}/compile_s"] = round(r["compile_s"], 4)
            if cost.get("flops"):
                out[f"{name}/flops"] = cost["flops"]
            if cost.get("bytes_accessed"):
                out[f"{name}/bytes"] = cost["bytes_accessed"]
            if r.get("memory", {}).get("temp_bytes") is not None:
                out[f"{name}/temp_bytes"] = r["memory"]["temp_bytes"]
            if "arithmetic_intensity" in r:
                out[f"{name}/ai"] = round(r["arithmetic_intensity"], 4)
            if "mfu" in r:
                out[f"{name}/mfu"] = round(r["mfu"], 6)
            if r.get("bound"):
                out[f"{name}/compute_bound"] = float(r["bound"] == "compute")
            if r.get("cost_source") == "counted":
                out[f"{name}/counted"] = 1.0
        with self._lock:
            wms = {p: dict(w) for p, w in self._watermarks.items()}
        for phase, wm in wms.items():
            out[f"hbm/{phase}/peak_live_bytes"] = wm["peak_live_bytes"]
            out[f"hbm/{phase}/last_live_bytes"] = wm["last_live_bytes"]
            out[f"hbm/{phase}/samples"] = wm["samples"]
            if wm.get("peak_device_bytes"):
                out[f"hbm/{phase}/peak_device_bytes"] = \
                    wm["peak_device_bytes"]
        return out

    # -- HBM watermarks ---------------------------------------------------
    def memory_watermark(self, phase: str = "global") -> Dict[str, Any]:
        """Take one memory census (``system_info.memory_summary`` — the
        SAME function ``/api/health`` serves, never a second walk) and
        fold it into the per-phase peak gauges. Returns the census."""
        if not self._enabled:
            return {}
        from .system_info import memory_summary

        census = memory_summary()
        live = int(census.get("live_buffers", {}).get("bytes", 0))
        dev = sum(int(d.get("bytes_in_use", 0))
                  for d in census.get("devices", []))
        rose = False
        with self._lock:
            wm = self._watermarks.setdefault(phase, {
                "peak_live_bytes": 0, "last_live_bytes": 0,
                "peak_device_bytes": 0, "samples": 0})
            wm["samples"] += 1
            wm["last_live_bytes"] = live
            if live > wm["peak_live_bytes"]:
                wm["peak_live_bytes"] = live
                rose = True
            if dev > wm["peak_device_bytes"]:
                wm["peak_device_bytes"] = dev
                rose = True
            peak = wm["peak_live_bytes"]
        prof = OpProfiler.get()
        prof.gauge("xprof/live_buffer_bytes", live)
        if rose:
            prof.gauge(f"xprof/peak_live_bytes/{phase}", peak)
            flightrec.event("xprof/hbm", phase=phase, live_bytes=live,
                            peak_live_bytes=peak, device_bytes=dev)
        return census

    def watermarks(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {p: dict(w) for p, w in self._watermarks.items()}

    def dump_memory_census(self, path: str) -> str:
        """Write the full memory picture (per-phase watermarks + a fresh
        census) as JSON, atomically — the crash-blackbox companion
        (``memcensus.json`` beside ``blackbox.jsonl``), so OOM-class
        postmortems carry the memory state with no live process."""
        from .system_info import memory_summary

        payload = {"watermarks": self.watermarks(),
                   "census": memory_summary(),
                   "ledger": self.ledger()}
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(payload, f, default=str)
        os.replace(tmp, path)
        return path


class _Censused:
    """Call-transparent census wrapper around one ``jax.jit`` callable.
    ``__getattr__`` falls through (``.lower``, ``._cache_size``, …) so
    existing AOT/introspection code sees the jit unchanged. The entry is
    looked up by name per dispatch — never captured — so a census reset
    cannot orphan a live wrapper."""

    __slots__ = ("_census", "_name", "_fn", "_fp", "_last_cache")

    def __init__(self, census: ExecutableCensus, name: str, fn,
                 fp: Dict[str, Any]):
        self._census = census
        self._name = name
        self._fn = fn
        self._fp = fp
        self._last_cache = 0

    @property
    def wrapped(self):
        return self._fn

    def __call__(self, *args, **kwargs):
        census = self._census
        if not census._enabled:
            return self._fn(*args, **kwargs)
        t0 = _now()
        out = self._fn(*args, **kwargs)
        census._note_call(self._name, self._fn, self,
                          _now() - t0, args, kwargs)
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)


# -- analysis plumbing -----------------------------------------------------

def _cost_dict(lowered_or_compiled) -> Optional[Dict[str, float]]:
    """Normalize ``cost_analysis()`` output (dict, or per-device list)
    to {flops, bytes_accessed, transcendentals}; None when the backend
    has nothing (the graceful-degradation contract)."""
    try:
        cost = lowered_or_compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not cost:
        return None
    out: Dict[str, float] = {}
    for src, dst in (("flops", "flops"),
                     ("bytes accessed", "bytes_accessed"),
                     ("transcendentals", "transcendentals")):
        v = cost.get(src)
        if v is not None and v > 0:
            out[dst] = float(v)
    return out or None


def _memory_dict(compiled) -> Optional[Dict[str, int]]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for src, dst in (("argument_size_in_bytes", "argument_bytes"),
                     ("output_size_in_bytes", "output_bytes"),
                     ("temp_size_in_bytes", "temp_bytes"),
                     ("alias_size_in_bytes", "alias_bytes"),
                     ("generated_code_size_in_bytes",
                      "generated_code_bytes")):
        v = getattr(ma, src, None)
        if v is not None:
            out[dst] = int(v)
    return out or None


def _out_bytes_dict(lowered) -> Optional[Dict[str, int]]:
    """Output bytes from the lowering's out_info (pre-compile) — feeds
    the counted fallback when cost analysis is unavailable."""
    info = getattr(lowered, "out_info", None)
    if info is None:
        return None
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(info):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            total += int(np.prod(shape)) * np.dtype(dtype).itemsize
    return {"output_bytes": total}


def _sharding_fingerprint(compiled) -> Dict[str, Any]:
    try:
        ins = compiled.input_shardings
        flat = []
        for group in ins if isinstance(ins, tuple) else (ins,):
            try:
                flat.extend(list(group))
            except TypeError:
                flat.append(group)
        kinds = sorted({type(s).__name__ for s in flat if s is not None})
        return {"input_sharding_kinds": tuple(kinds),
                "input_sharding_count": len(flat)}
    except Exception:
        return {}


def _avalize(args, kwargs):
    """(args, kwargs) with array leaves replaced by ShapeDtypeStruct —
    metadata survives donation; non-array leaves (static scalars, None)
    pass through so a later ``lower()`` reproduces the signature."""
    import jax

    def conv(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            try:
                return jax.ShapeDtypeStruct(tuple(shape), dtype)
            except Exception:
                return x
        return x

    return (jax.tree.map(conv, args), jax.tree.map(conv, kwargs))


def _aval_bytes(args, kwargs) -> int:
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves((args, kwargs)):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            total += int(np.prod(shape)) * np.dtype(dtype).itemsize
    return total


# -- the process-wide census + module facade -------------------------------

_CENSUS = ExecutableCensus()


def get() -> ExecutableCensus:
    return _CENSUS


def configure(enabled: Optional[bool] = None) -> ExecutableCensus:
    return _CENSUS.configure(enabled=enabled)


def enabled() -> bool:
    return _CENSUS._enabled


def reset() -> None:
    _CENSUS.reset()


def set_roof(peak_flops: float, peak_bytes_per_s: float) -> None:
    _CENSUS.set_roof(peak_flops, peak_bytes_per_s)


def register_jit(name: str, fn, *, donate=None, static_argnames=None):
    return _CENSUS.register_jit(name, fn, donate=donate,
                                static_argnames=static_argnames)


def register_aot(name: str, compiled, *, variant: str = "",
                 compile_s: Optional[float] = None) -> None:
    _CENSUS.register_aot(name, compiled, variant=variant,
                         compile_s=compile_s)


def note_subexec(name: str, flops: Optional[float] = None,
                 bytes_accessed: Optional[float] = None, **attrs) -> None:
    _CENSUS.note_subexec(name, flops=flops, bytes_accessed=bytes_accessed,
                         **attrs)


def note_measured(name: str, step_s: float) -> None:
    _CENSUS.note_measured(name, step_s)


def analyze(names=None, compile: bool = True) -> Dict[str, dict]:
    return _CENSUS.analyze(names=names, compile=compile)


def roofline() -> Dict[str, dict]:
    return _CENSUS.roofline()


def ledger() -> Dict[str, float]:
    return _CENSUS.ledger()


def census() -> Dict[str, dict]:
    """Structured snapshot of every entry (no analysis triggered)."""
    with _CENSUS._lock:
        return {n: e.summary() for n, e in _CENSUS._entries.items()}


def memory_watermark(phase: str = "global") -> Dict[str, Any]:
    return _CENSUS.memory_watermark(phase)


def watermarks() -> Dict[str, Dict[str, Any]]:
    return _CENSUS.watermarks()


def dump_memory_census(path: str) -> str:
    return _CENSUS.dump_memory_census(path)


# --- device time by model scope (the reader of the step's named scopes) ------
#
# nn.train_step names the step's phases (``forward``, ``update`` ...), the
# networks name each vertex, the layers their inner parts. A profiler session
# (``OpProfiler.trace(logdir)``) writes every device op with the name stack of
# the instruction it ran: the stat ``tf_op`` of the op's event metadata in the
# raw ``XSpace`` proto, beside ``hlo_category``, ``flops`` and
# ``bytes_accessed`` (``jax.profiler.ProfileData`` exposes none of them).

#: JAX's own wrapping in a name stack, never a scope. ``while`` and ``cond``
#: only with JAX's next word behind them: an imported graph has directories
#: of those names
_WRAPPING = re.compile(
    r"(?<![^/])(?:while/(?:body|cond)|cond/branch_\w+|checkpoint|"
    r"rematted_computation|closed_call|shard_map)(?![^/])")
_REMAT = "rematted_computation"     # jax.checkpoint's forward run again


#: what the reader touches of tsl/profiler/protobuf/xplane.proto (the rest of
#: a message stays in it as unknown fields): message -> (field, number,
#: scalar type | *repeated message | {map's value message})
_XPLANE_SCHEMA = {
    "XStat": (("metadata_id", 1, "int64"), ("double_value", 2, "double"),
              ("uint64_value", 3, "uint64"), ("int64_value", 4, "int64"),
              ("str_value", 5, "string"), ("bytes_value", 6, "bytes"),
              ("ref_value", 7, "uint64")),
    "XEvent": (("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64"), ("stats", 4, "*XStat")),
    "XLine": (("name", 2, "string"), ("timestamp_ns", 3, "int64"),
              ("events", 4, "*XEvent")),
    "XEventMetadata": (("id", 1, "int64"), ("name", 2, "string"),
                       ("stats", 5, "*XStat")),
    "XStatMetadata": (("id", 1, "int64"), ("name", 2, "string")),
    "XPlane": (("name", 2, "string"), ("lines", 3, "*XLine"),
               ("event_metadata", 4, "{XEventMetadata"),
               ("stat_metadata", 5, "{XStatMetadata")),
    "XSpace": (("planes", 1, "*XPlane"),),
}


@functools.lru_cache(maxsize=None)
def _xplane_classes():
    """``XSpace`` as a message class built from :data:`_XPLANE_SCHEMA` in a
    descriptor pool of its own: no TensorFlow import, C-speed parsing."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    T = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="dl4j_tpu/xplane.proto", package="dl4j_tpu.xplane",
        syntax="proto3")

    def add(fields, number, name, kind, label=T.LABEL_OPTIONAL):
        if kind[0] in "*{":
            fields.add(name=name, number=number, type=T.TYPE_MESSAGE,
                       label=label, type_name=".dl4j_tpu.xplane." + kind[1:])
        else:
            fields.add(name=name, number=number, label=label,
                       type=getattr(T, "TYPE_" + kind.upper()))

    for message, fields in _XPLANE_SCHEMA.items():
        m = f.message_type.add(name=message)
        for name, number, kind in fields:
            if kind[0] == "{":     # map<int64, message>: a nested entry type
                entry = m.nested_type.add(name=name.title().replace("_", "")
                                          + "Entry")
                entry.options.map_entry = True
                add(entry.field, 1, "key", "int64")
                add(entry.field, 2, "value", kind)
                kind = f"*{message}.{entry.name}"
            add(m.field, number, name, kind,
                T.LABEL_REPEATED if kind[0] == "*" else T.LABEL_OPTIONAL)
    stat = f.message_type[0]        # XStat's values are one ``oneof``
    stat.oneof_decl.add(name="value")
    for field in stat.field[1:]:
        field.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("dl4j_tpu.xplane.XSpace"))


def read_xspace(path: str):
    """The ``XSpace`` of a profiler session: ``path`` is its ``logdir`` (the
    newest ``*.xplane.pb`` under it) or one such file, ``.gz`` or not."""
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no *.xplane.pb under {path}")
        path = found[-1]
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as fh:
        return _xplane_classes().FromString(fh.read())


def short_op_name(hlo: str) -> str:
    """``%fusion.12 = f32[..] fusion(..), kind=kOutput, ..`` (or the bare
    ``fusion.12``) -> ``fusion[kOutput]``: the benchmark reducer's
    ``short_name``."""
    name = re.sub(r"\.\d+$", "", hlo.split(" = ", 1)[0].lstrip("%"))
    kind = re.search(r"kind=(k\w+)", hlo)
    return f"{name}[{kind.group(1)}]" if kind else name


def classify_scope(tf_op: str) -> Tuple[str, str, str]:
    """``(phase, vertex, inner)`` of an op's name stack
    (``jit(step)/transpose(jvp(forward))/jvp(forward)/checkpoint/
    rematted_computation/attn_1/mla_q/dot_general:``). The phase is the
    step's first scope (``nn.train_step.FORWARD`` / ``UPDATE``), bare or as a
    transformation spells it: JAX writes the backward of ``forward``
    ``transpose(jvp(forward))`` (under ``vmap``:
    ``vmap(transpose(jvp(forward)))``) and the forward run again under
    ``jax.checkpoint`` ``.../rematted_computation/...``. A component in
    parentheses is never a scope, JAX's own wrapping (:data:`_WRAPPING`) is
    dropped, and the last component is the primitive. In ``update`` there is
    no vertex: its first scope is ``inner``."""
    from ..nn.train_step import FORWARD, UPDATE    # the names the step emits

    parts = [p for p in re.split("[:;]", tf_op, 1)[0].split("/") if p][:-1]
    remat = _REMAT in parts
    scopes, phase = [], ""
    for p in _WRAPPING.sub("", "/".join(parts)).split("/"):
        name = p.rsplit("(", 1)[-1].rstrip(")")
        if not phase and not scopes and name in (FORWARD, UPDATE):
            phase = "backward" if "transpose(" in p else name
        elif p and "(" not in p:
            scopes.append(p)
    if phase == UPDATE:
        return phase, "", "/".join(scopes[:1])
    if remat:
        phase = "recompute"
    return phase or "other", "/".join(scopes[:1]), "/".join(scopes[1:])


def _self_times(events: list) -> list:
    """Self time of each ``(start, end, ...)`` event of one line: at any
    instant the innermost running event owns the time, so an op nested in a
    ``while`` is counted once and the self times sum to the union."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    out, stack, cursor = [0] * len(events), [], 0

    def close(until):
        nonlocal cursor
        while stack and events[stack[-1]][1] <= until:
            top = stack.pop()
            out[top] += max(0, events[top][1] - cursor)
            cursor = max(cursor, events[top][1])

    for i in order:
        start = events[i][0]
        close(start)
        if stack:
            out[stack[-1]] += max(0, start - cursor)
        cursor = max(cursor, start)
        stack.append(i)
    close(float("inf"))
    return out


def _stat_value(stat, names: dict):
    """An ``XStat``'s value; a string interned as a stat's name
    (``ref_value``) looked up in ``names``."""
    field = stat.WhichOneof("value")
    if field == "ref_value":
        return names.get(stat.ref_value, "")
    return getattr(stat, field) if field else None


def _book_sections(plane, names, host: Dict[str, list]) -> None:
    """Add a host plane's spans called one of ``names`` (the program's
    sections, ``OpProfiler.time_section``; nested by thread) to ``host``:
    ``[count, total, self]`` in ps. Self time is a section's time outside
    the sections inside it; the Python tracer's and the runtime's own spans
    do not count against it."""
    for line in plane.lines:
        t0 = line.timestamp_ns * 1000
        spans = [(t0 + e.offset_ps, t0 + e.offset_ps + e.duration_ps, name)
                 for e in line.events for name in
                 [plane.event_metadata[e.metadata_id].name.split("#", 1)[0]]
                 if name in names]
        for (s, e, name), own in zip(spans, _self_times(spans)):
            h = host.setdefault(name, [0, 0, 0])
            h[0] += 1
            h[1] += e - s
            h[2] += own


def scope_times(logdir: str, step_program: str = "jit_step") -> Dict[str, Any]:
    """Device time of the traced step by model scope, self time only,
    averaged over the device planes, for the executions of the programs whose
    name starts with ``step_program``: ``{"steps", "step_ms", "rows":
    [{"phase", "vertex", "inner", "op", "category", "ms", "calls", "flops",
    "bytes"}, ...], "unattributed_ms", "host": {section: {"count",
    "total_ms", "self_ms"}}}``, ms a step; rows sum to ``step_ms``.
    Unattributed: an op with no vertex outside ``update`` — outside every
    scope, or in forward, recompute or backward beside the vertices.
    ``host``: the sections this process's ``OpProfiler`` has timed, as the
    trace's host planes hold them. A trace with device planes and no such
    program is an error; one without a device plane (a CPU run) gives no
    rows."""
    space = read_xspace(logdir)
    sections = set(OpProfiler.get().get_statistics())
    rows: Dict[tuple, list] = {}
    host: Dict[str, list] = {}
    programs = set()
    steps = busy = planes = 0
    for plane in space.planes:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        lines = {l.name: l for l in plane.lines}
        if not (plane.name.startswith("/device:") and "XLA Ops" in lines
                and "XLA Modules" in lines):
            _book_sections(plane, sections, host)
            continue
        planes += 1
        meta = {}
        for mid, m in plane.event_metadata.items():
            stats = {stat_names.get(s.metadata_id, ""):
                     _stat_value(s, stat_names) for s in m.stats}
            meta[mid] = (
                classify_scope(str(stats.get("tf_op", ""))),
                short_op_name(m.name),
                str(stats.get("hlo_category", "")),
                float(stats.get("flops") or 0),
                float(stats.get("bytes_accessed") or 0))
        t0 = lines["XLA Modules"].timestamp_ns * 1000
        programs.update(plane.event_metadata[e.metadata_id].name
                        for e in lines["XLA Modules"].events)
        runs = sorted(
            (t0 + e.offset_ps, t0 + e.offset_ps + e.duration_ps)
            for e in lines["XLA Modules"].events
            if plane.event_metadata[e.metadata_id].name.startswith(
                step_program))
        steps += len(runs)
        starts = [r[0] for r in runs]
        t0 = lines["XLA Ops"].timestamp_ns * 1000
        events = [(t0 + e.offset_ps, t0 + e.offset_ps + e.duration_ps,
                   e.metadata_id) for e in lines["XLA Ops"].events]
        for (s, e, mid), own in zip(events, _self_times(events)):
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or s >= runs[k][1]:
                continue
            busy += own
            row = rows.setdefault(meta[mid][:3], [0, 0, 0.0, 0.0])
            row[0] += own
            row[1] += 1
            row[2] += meta[mid][3]
            row[3] += meta[mid][4]
    if planes and not steps:
        raise ValueError(f"no program named {step_program!r}* ran in the "
                         f"trace; it holds {sorted(programs)}")
    n = max(steps, 1)
    ms = 1e-9 / n       # picoseconds over all planes' steps -> ms a step
    table = [{"phase": ph, "vertex": vx, "inner": inner, "op": op,
              "category": cat, "ms": own * ms, "calls": calls / n,
              "flops": flops / n, "bytes": nbytes / n}
             for ((ph, vx, inner), op, cat), (own, calls, flops, nbytes)
             in rows.items()]
    table.sort(key=lambda r: -r["ms"])
    return {
        "steps": steps / max(planes, 1), "step_ms": busy * ms, "rows": table,
        "unattributed_ms": sum(r["ms"] for r in table
                               if not r["vertex"] and r["phase"] != "update"),
        "host": {name: {"count": c, "total_ms": t * 1e-9,
                        "self_ms": own * 1e-9}
                 for name, (c, t, own) in sorted(host.items())}}


def scope_table(times: Dict[str, Any], by: str = "vertex") -> str:
    """``scope_times`` as text, grouped ``by`` ``vertex`` or ``phase`` (or
    a key the caller joined to the rows, a model's ``scope_kinds()`` as
    ``kind``): ms a step, most expensive first."""
    groups: Dict[str, float] = {}
    for r in times["rows"]:
        key = r.get(by) or f"({r['phase']}/{r['inner'] or '-'})"
        groups[key] = groups.get(key, 0.0) + r["ms"]
    total = times["step_ms"] or 1.0
    out = [f"{times['steps']:g} steps, {times['step_ms']:.3f} ms a step "
           f"on the device, unattributed {times['unattributed_ms']:.3f} ms"]
    out += [f"{ms:10.3f} ms {100 * ms / total:6.2f}%  {key}"
            for key, ms in sorted(groups.items(), key=lambda kv: -kv[1])]
    return "\n".join(out)


def _main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python3 -m deeplearning4j_tpu.common.xprof",
        description="Device time of a traced step by model scope.")
    ap.add_argument("logdir", help="OpProfiler.trace's logdir, or one "
                    "*.xplane.pb[.gz]")
    ap.add_argument("--by", default="vertex", choices=("vertex", "phase"))
    ap.add_argument("--inner", default="", help="also list the rows of this "
                    "layer scope (moe_experts, mla_q, ...) by phase and op, "
                    "summed over the vertices")
    args = ap.parse_args(argv)
    times = scope_times(args.logdir)
    print(scope_table(times, args.by))
    if args.inner:
        rows = [{**r, "phase/op": f"{r['phase']:<10}{r['op']}"}
                for r in times["rows"] if r["inner"] == args.inner]
        print(scope_table({**times, "rows": rows}, "phase/op"))
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
