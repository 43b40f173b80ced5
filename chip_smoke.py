#!/usr/bin/env python
"""Chip smoke: drive the fit()/output() main path once on the TPU.

One process, the normal public entry points, the flagship models at their
full published width (``bench.py``'s own builders, so the two cannot
drift), a few steps or requests per phase. Every phase prints one JSON line
(``{"phase": ..., "ok": ..., "wall_s": ..., "compile_s": ..., ...}``); a
failing phase prints its line with ``"ok": false`` and the script exits
non-zero at once. The last line of a passing run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Without a TPU the script fails — it never carries on on the CPU. The times
it prints are SMOKE times (cold, compile included, a handful of steps):
they go into no metric and are not benchmark numbers.

    python chip_smoke.py              one chip: device, attention,
                                      embeddings, train, serve, samediff
    python chip_smoke.py --chips 4    only the multi-chip path and what it
                                      is compared with: ParallelWrapper
                                      (dense, ZeRO-1) against single-chip
                                      fit, pinned serving replicas
    python chip_smoke.py --rehearse   control-flow rehearsal on whatever
                                      backend JAX has (the CPU here) at
                                      tiny sizes; prints no result line

The chip belongs to one process: this script starts no other that uses JAX.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

import numpy as np


class SmokeFailure(Exception):
    """A phase's output is not what the repo's own reference says."""


def check(cond, what: str, **context) -> None:
    if not cond:
        raise SmokeFailure(f"{what} {context}" if context else what)


@dataclass(frozen=True)
class Sizes:
    image: int                  # ResNet-50 input resolution
    batch: int                  # ResNet-50 / LeNet global batch
    steps: int                  # ResNet-50 train steps (one chip)
    buckets: Tuple[int, ...]    # serving batch ladder
    requests: Tuple[int, ...]   # request sizes, in order
    bert: Tuple[int, int]       # (batch, seq)
    attention: Tuple[Tuple[int, int, bool], ...]   # (B, T, masked)
    corpus_words: int


# the cells' sizes (bench.py: ResNet-50 batch 128 / 224², BERT-base batch
# 32 / seq 128, Word2Vec on the 400k-word cold-audit corpus)
REAL = Sizes(image=224, batch=128, steps=6, buckets=(1, 8, 32),
             requests=(1, 3, 8, 5, 32, 17, 2, 40, 1, 8, 6, 32, 4, 1, 12, 7,
                       33, 8, 2, 1),
             bert=(32, 128),
             attention=((32, 128, True), (1, 4096, False)),
             corpus_words=400_000)
# --rehearse: same code paths, sizes a CPU finishes in minutes
TINY = Sizes(image=64, batch=16, steps=6, buckets=(1, 4),
             requests=(1, 3, 4, 2, 6, 1),
             bert=(2, 16),
             attention=((2, 128, True), (1, 256, False)),
             corpus_words=40_000)


class Meter:
    """What JAX itself reports per phase: seconds spent tracing, lowering
    and compiling (or loading from the persistent cache), and the
    persistent cache's hits and misses."""

    _COMPILE = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration",
                "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        import jax.monitoring

        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def reset(self) -> None:
        self.compile_s, self.hits, self.misses = 0.0, 0, 0

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in self._COMPILE:
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def run_phase(meter: Meter, name: str, fn: Callable[[], dict]) -> None:
    meter.reset()
    t0 = time.perf_counter()
    try:
        extra = fn()
    except BaseException as e:
        emit({"phase": name, "ok": False,
              "wall_s": round(time.perf_counter() - t0, 2),
              "error": f"{type(e).__name__}: {e}"[:2000]})
        raise
    emit({"phase": name, "ok": True,
          "wall_s": round(time.perf_counter() - t0, 2),
          "compile_s": round(meter.compile_s, 2),
          "cache_hits": meter.hits, "cache_misses": meter.misses, **extra})
    gc.collect()    # drop the phase's device arrays before the next one


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _leaf_report(tree, device) -> dict:
    """dtypes of a state pytree; every leaf on exactly ``device``."""
    import jax

    leaves = jax.tree.leaves(tree)
    check(leaves, "state tree has no leaves")
    for leaf in leaves:
        check(leaf.devices() == {device}, "state leaf is not on the chip",
              on=str(leaf.devices()), want=str(device))
    return {"leaves": len(leaves),
            "dtypes": sorted({str(l.dtype) for l in leaves})}


def _image_batch(cfg: Sizes, n: int, seed: int = 0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 3, cfg.image, cfg.image).astype(np.float32)
    y = np.eye(1000, dtype=np.float32)[rng.randint(0, 1000, n)]
    return x, y


def _fit_losses(fit: Callable, model, epochs: int) -> List[float]:
    """Run ``fit`` with a score listener on ``model``; one loss per step."""
    from deeplearning4j_tpu.optimize.listeners import (
        CollectScoresIterationListener)

    scores = CollectScoresIterationListener()
    model.set_listeners(scores)
    fit(epochs)
    losses = [s for _, s in scores.scores]
    check(len(losses) == epochs, "one step per epoch expected",
          steps=len(losses), epochs=epochs)
    _check_losses(losses)
    return losses


def _check_losses(losses: List[float]) -> None:
    """Finite, and falling — held as "falls below its start", not "is
    lower at the end". Both flagships overshoot on one fixed batch from
    random weights: ResNet-50 (lr 0.1, momentum 0.9, no warm-up) drops on
    its first update, spikes from the third and recovers over tens of
    steps; BERT-base (Adam 2e-5, whose first updates are sign steps over
    109.5M parameters) rises for two updates and is below its start by the
    fifth. The CPU shows the same trajectories at the same sizes, so this
    is the optimiser, not the chip. Every loss is printed."""
    check(all(np.isfinite(losses)), "non-finite training loss",
          losses=losses)
    check(min(losses[1:]) < losses[0],
          "training loss never fell below its start", losses=losses)


def _enable_fused_epilogue(model) -> None:
    """Post-build enablement on a zoo model built with the default off:
    flip the global knob and re-cascade it onto the BN layers (what the
    builder's ``.fused_epilogue()`` does at build time)."""
    from deeplearning4j_tpu.nn.conf import layers as L

    model.conf.global_conf.fused_epilogue = True
    for name in model.conf.order:
        node = model.conf.nodes[name]
        if node.kind == "layer" and isinstance(node.layer,
                                               L.BatchNormalization):
            node.layer.fused_epilogue = True


def _rel_err(a, ref) -> float:
    a, ref = np.asarray(a, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(a - ref)) / max(np.max(np.abs(ref)), 1e-30))


# serving answers against direct model.output: tests/test_serving.py holds
# bf16 serving to atol=5e-2 on O(1) outputs. The flagship's outputs are
# probabilities over 1000 classes, far below that, so a bound RELATIVE to
# the largest reference value does the work: the engine's bucket programs
# and the direct call are different compilations of the same bf16 forward
# (7.6e-6 seen on the chip, PR 21).
SERVE_ATOL = 5e-2
SERVE_REL = 1e-3


def _check_answers(got: np.ndarray, want: np.ndarray, what: str) -> float:
    check(got.shape == want.shape, f"{what}: wrong shape",
          got=got.shape, want=want.shape)
    check(np.isfinite(got).all(), f"{what}: non-finite answer")
    check(np.allclose(got, want, atol=SERVE_ATOL, rtol=0.0),
          f"{what}: answer differs from the reference")
    rel = _rel_err(got, want)
    check(rel <= SERVE_REL, f"{what}: answer differs from the reference",
          rel_err=rel)
    return rel


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_device() -> dict:
    import jax

    from deeplearning4j_tpu import native
    from deeplearning4j_tpu.common.environment import Environment
    from deeplearning4j_tpu.common.xprof import device_peaks

    dev = jax.devices()[0]
    check(Environment.get().is_tpu() == (dev.platform == "tpu"),
          "Environment.is_tpu() disagrees with jax.devices()")
    # was a library lying in the tree, or is it built here from the
    # tracked .cpp? (a failed build is visible, not fatal: numpy fallback)
    prebuilt = os.path.exists(native._so_path())
    peak_flops, peak_bytes = device_peaks(dev)   # unknown kind raises
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
            # the persistent cache's key covers the device: a later call
            # that is handed another chip of the host misses this one's
            "device_id": dev.id, "coords": getattr(dev, "coords", None),
            "is_tpu": Environment.get().is_tpu(),
            "native_available": native.available(),
            "native_prebuilt": prebuilt,
            "native_error": native.load_error(),
            "compile_cache_dir": Environment.get().compile_cache_dir(),
            "compile_cache_from_env":
                bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "peak_flops": peak_flops, "peak_bytes_per_s": peak_bytes}


def phase_train(cfg: Sizes, holder: dict) -> dict:
    """ResNet-50 exactly as the flagship cell builds it, through
    ComputationGraph.fit on one fixed synthetic batch."""
    import jax
    import jax.numpy as jnp

    import bench
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import DataSet

    prof = OpProfiler.get()
    prof.reset()
    model = bench._resnet50_train_model(cfg.image)
    x, y = _image_batch(cfg, cfg.batch)
    ds = DataSet(jnp.asarray(x), jnp.asarray(y))
    t0 = time.perf_counter()
    losses = _fit_losses(lambda n: model.fit(ds, epochs=n), model,
                         cfg.steps)
    fit_s = time.perf_counter() - t0
    model.set_listeners()

    dev = jax.devices()[0]
    params = _leaf_report(model._params, dev)
    states = _leaf_report(model._states, dev)
    upd = _leaf_report(model._updater_state, dev)
    check(params["dtypes"] == ["float32"], "params are not f32", **params)
    check(upd["dtypes"] == ["bfloat16"], "updater state is not bf16", **upd)
    check(set(states["dtypes"]) <= {"float32", "bfloat16"},
          "layer state carries a dtype wider than f32", **states)
    traces = prof.counter_value("trace/graph_fit_step")
    check(traces == 1, "the train step compiled more than once",
          traces=traces)
    pallas = prof.counter_value("precision/fused_buckets_pallas")
    xla = prof.counter_value("precision/fused_buckets_xla")
    check(pallas == 0 and xla == 0,
          "the unsharded step built a flat bucket (the update runs leaf "
          "by leaf, in the layout the state lives in)",
          fused_buckets_pallas=pallas, fused_buckets_xla=xla)
    holder["model"] = model      # the serve phase serves this model
    return {"model": "ResNet-50", "image": cfg.image, "batch": cfg.batch,
            "steps": cfg.steps, "losses": [round(l, 4) for l in losses],
            "fit_s_smoke": round(fit_s, 2),
            "param_dtypes": params["dtypes"],
            "updater_state_dtypes": upd["dtypes"],
            "trace/graph_fit_step": traces,
            "precision/fused_buckets_pallas": pallas,
            "precision/fused_buckets_xla": xla}


def phase_serve(cfg: Sizes, holder: dict) -> dict:
    """ServingEngine over the same ResNet-50 with the fused BN epilogue:
    mixed-size requests, every answer against direct model.output."""
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.parallel.serving import ServingEngine

    model = holder.pop("model")
    _enable_fused_epilogue(model)
    prof = OpProfiler.get()
    prof.reset()
    pool, _ = _image_batch(cfg, 2 * max(cfg.buckets), seed=1)
    want = model.output(pool)[0].to_numpy()
    check(np.isfinite(want).all(), "direct model.output is not finite")
    eng = (ServingEngine.Builder(model).buckets(list(cfg.buckets))
           .input_shape((3, cfg.image, cfg.image)).workers(1).build())
    try:
        def rows(i: int, n: int) -> np.ndarray:
            return (np.arange(n) + 7 * i) % len(pool)

        worst = 0.0
        # one at a time, then the same requests all in flight at once
        # (continuous batching merges them into shared buckets)
        for i, n in enumerate(cfg.requests):
            got = eng.output(pool[rows(i, n)]).to_numpy()
            worst = max(worst, _check_answers(got, want[rows(i, n)],
                                              f"request {i} (n={n})"))
        futs = [eng.output_async(pool[rows(i, n)])
                for i, n in enumerate(cfg.requests)]
        for i, (n, f) in enumerate(zip(cfg.requests, futs)):
            got = f.result(timeout=120).to_numpy()
            worst = max(worst, _check_answers(
                got, want[rows(i, n)], f"concurrent request {i} (n={n})"))
        stats = eng.serving_stats()
    finally:
        eng.shutdown()
    check(not any(t.is_alive() for t in eng._workers),
          "a replica thread is still alive after shutdown()")
    after = prof.counter_value("serving/traces_after_warmup")
    check(after == 0, "a request traced after warm-up", traces=after)
    hits = prof.counter_value("precision/epilogue_hits")
    check(hits > 0, "the fused BN epilogue was never taken", hits=hits)
    return {"model": "ResNet-50 + fused_epilogue",
            "buckets": list(cfg.buckets), "requests": 2 * len(cfg.requests),
            "worst_rel_err": worst, "atol": SERVE_ATOL, "rel": SERVE_REL,
            "serving/traces_after_warmup": after,
            "serving/buckets_compiled":
                prof.counter_value("serving/buckets_compiled"),
            "precision/epilogue_hits": hits,
            "precision/epilogue_fallbacks":
                prof.counter_value("precision/epilogue_fallbacks"),
            "p50_ms_smoke": stats.get("p50_ms")}


def phase_samediff(cfg: Sizes) -> dict:
    """BERT-base: TF frozen-graph import, then SameDiff.fit fine-tune
    steps over all parameters (Adam)."""
    import bench
    from deeplearning4j_tpu.optimize.listeners import (
        CollectScoresIterationListener)

    steps = 8
    batch, seq = cfg.bert
    t0 = time.perf_counter()
    sd, ph, n_params = bench._bert_samediff(batch, seq)
    import_s = time.perf_counter() - t0
    scores = CollectScoresIterationListener()
    sd.fit([ph] * steps, epochs=1, listeners=[scores])
    losses = [s for _, s in scores.scores]
    check(len(losses) == steps, "one loss per step expected",
          losses=losses)
    _check_losses(losses)
    return {"model": "BERT-base (TF import)", "batch": batch, "seq": seq,
            "params": int(n_params), "steps": steps,
            "losses": [round(l, 5) for l in losses],
            "import_s_smoke": round(import_s, 2)}


def phase_attention(cfg: Sizes, kernels: bool) -> dict:
    """multi_head_dot_product_attention on its default lowering (flash on
    the TPU) against dot_product_attention in f32, forward and grad."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.common.environment import Environment
    from deeplearning4j_tpu.ops.nn import multi_head_dot_product_attention

    heads, d = 12, 64
    dm = heads * d
    fwd_tol, grad_tol = 3e-2, 5e-2      # bf16: 8 bits of mantissa
    env = Environment.get()
    report = {}

    def build(dtype):
        # a fresh function per call: the lowering is chosen at trace time.
        # Weights, cotangent and mask are ARGUMENTS: as closure constants
        # they were baked into ~20 MB executables, and four of those
        # pushed one smoke's cache writes past the machine's LRU cap
        def f(q, k, v, ws, cot, mask):
            out = multi_head_dot_product_attention(
                q.astype(dtype), k.astype(dtype), v.astype(dtype),
                *(w.astype(dtype) for w in ws), mask=mask,
                num_heads=heads).astype(jnp.float32)
            return (out * cot).sum(), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    for B, T, masked in cfg.attention:
        rng = np.random.RandomState(T)
        q, k, v = (jnp.asarray(rng.randn(B, T, dm), jnp.bfloat16)
                   for _ in range(3))
        ws = [jnp.asarray(rng.randn(dm, dm) / np.sqrt(dm), jnp.bfloat16)
              for _ in range(4)]
        cot = jnp.asarray(rng.randn(B, T, dm), jnp.float32)
        mask = None
        if masked:      # right-padded sequences, none empty
            lengths = rng.randint(T // 2, T + 1, size=B)
            mask = jnp.asarray(np.arange(T)[None, :] < lengths[:, None],
                               jnp.float32)
        args = (q, k, v, ws, cot, mask)
        fn = build(jnp.bfloat16)
        flash = "tpu_custom_call" in fn.lower(*args).as_text()
        if kernels:
            check(flash, "the flash kernel is not in the lowered step",
                  B=B, T=T, masked=masked)
        (_, out), grads = fn(*args)
        # reference: the dense op (Pallas disallowed), f32, full precision
        env.set_allow_pallas(False)
        try:
            with jax.default_matmul_precision("highest"):
                (_, ref), ref_grads = build(jnp.float32)(*args)
                ref, ref_grads = jax.block_until_ready((ref, ref_grads))
        finally:
            env.set_allow_pallas(True)
        e_fwd = _rel_err(out, ref)
        e_grad = max(_rel_err(g, r) for g, r in zip(grads, ref_grads))
        check(np.isfinite(np.asarray(out)).all(), "attention not finite")
        check(e_fwd <= fwd_tol, "attention forward off the reference",
              T=T, rel_err=e_fwd, tol=fwd_tol)
        check(e_grad <= grad_tol, "attention grad off the reference",
              T=T, rel_err=e_grad, tol=grad_tol)
        report[f"B{B}_T{T}_{'masked' if masked else 'unmasked'}"] = {
            "flash": flash, "fwd_rel_err": e_fwd, "grad_rel_err": e_grad}
    return {"heads": heads, "head_dim": d, "fwd_tol": fwd_tol,
            "grad_tol": grad_tol, "cases": report}


def phase_embeddings(cfg: Sizes) -> dict:
    """Word2Vec.fit, skip-gram and CBOW, at the cells' widths."""
    import bench

    sents = bench._zipf_sentences(cfg.corpus_words)
    report = {}
    for algorithm in ("skipgram", "cbow"):
        w2v = bench._w2v_model(algorithm)
        w2v.set_sentence_iterator(sents)
        w2v.fit()                       # builds vocab, compiles, trains
        cold = w2v.words_per_sec
        w2v.fit()                       # same compiled block
        check(np.isfinite(w2v.last_loss), f"{algorithm} loss not finite",
              loss=w2v.last_loss)
        vec = np.asarray(w2v.get_word_vector("w1"))
        check(vec.shape == (100,) and np.isfinite(vec).all(),
              f"{algorithm} word vector is not a finite [100]")
        report[algorithm] = {
            "vocab": len(w2v.vocab), "loss": round(w2v.last_loss, 4),
            "words_per_s_cold_smoke": round(cold),
            "words_per_s_smoke": round(w2v.words_per_sec)}
    return {"corpus_words": cfg.corpus_words, "layer_size": 100,
            "window": 5, "negative": 5, "batch": 8192, **report}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _per_device_bytes(tree) -> Dict[int, int]:
    import jax

    out: Dict[int, int] = {}
    for leaf in jax.tree.leaves(tree):
        for s in leaf.addressable_shards:
            out[s.device.id] = out.get(s.device.id, 0) + s.data.nbytes
    return out


def _check_replicated(params, devices) -> None:
    """Replicated params: on all of ``devices`` and bitwise equal there."""
    import jax

    want = {d.id for d in devices}
    for leaf in jax.tree.leaves(params):
        check({d.id for d in leaf.sharding.device_set} == want,
              "a replicated param is not on every chip of the mesh",
              on=sorted(d.id for d in leaf.sharding.device_set))
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        check(len(copies) == len(want)
              and all(np.array_equal(copies[0], c) for c in copies[1:]),
              "replicas of a param differ after the last step")


def _mesh_devices(pw, n: int, platform: str) -> List[int]:
    devs = list(pw.mesh.devices.ravel())
    check(len({d.id for d in devs}) == n
          and all(d.platform == platform for d in devs),
          "the wrapper's mesh is not n distinct chips",
          devices=[str(d) for d in devs])
    return devs


def phase_pw_resnet50(cfg: Sizes, kernels: bool, n: int) -> dict:
    """ParallelWrapper over the flagship ResNet-50 — dense all-reduce
    (trees, updated leaf by leaf) and ZeRO-1 (flat buckets, the fused
    Pallas update) — against single-chip fit on the
    same global batch and seed. Per-shard BatchNorm statistics (batch/n
    rows) differ from whole-batch ones, so this is not the strict check
    (that is phase_pw_lenet): the FIRST loss — same parameters, forward
    only — is held to a band around the single-chip one; later steps are
    printed, not held, because the flagship's lr 0.1 without warm-up
    amplifies any difference (see _fit_losses). Dense and ZeRO-1 compute
    the same update on the same shards, so they are held to each other at
    every step."""
    import jax

    import bench
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import DataSet
    from deeplearning4j_tpu.ndarray.rng import set_default_seed
    from deeplearning4j_tpu.parallel import (ParallelWrapper,
                                             ReduceScatterAccumulator)

    steps, band, pair_tol = 3, 0.05, 2e-2
    x, y = _image_batch(cfg, cfg.batch)
    ds = DataSet(x, y)
    prof = OpProfiler.get()
    platform = jax.devices()[0].platform

    set_default_seed(99)
    single = bench._resnet50_train_model(cfg.image)
    ref = _fit_losses(lambda e: single.fit(ds, epochs=e), single, steps)
    dense_state_bytes = sum(_per_device_bytes(
        single._updater_state).values())
    del single
    gc.collect()

    report = {"single_chip_losses": [round(l, 4) for l in ref]}
    exact: Dict[str, List[float]] = {}
    for name, acc in (("dense", None),
                      ("zero1", ReduceScatterAccumulator())):
        prof.reset()
        set_default_seed(99)
        model = bench._resnet50_train_model(cfg.image)
        b = ParallelWrapper.Builder(model).workers(n)
        if acc is not None:
            b.gradients_accumulator(acc)
        pw = b.build()
        devs = _mesh_devices(pw, n, platform)
        losses = _fit_losses(
            lambda e: pw.fit(ds, epochs=e, batch_size=cfg.batch), model,
            steps)
        _check_replicated(model._params, devs)
        first = abs(losses[0] - ref[0]) / abs(ref[0])
        check(first <= band, f"{name}: first loss off the single-chip run",
              losses=losses, single_chip=ref, band=band)
        traces = prof.counter_value("trace/pw_fit_step")
        check(traces == 1, f"{name}: step compiled more than once",
              traces=traces)
        pallas = prof.counter_value("precision/fused_buckets_pallas")
        xla = prof.counter_value("precision/fused_buckets_xla")
        if acc is None:
            # dense all-reduce keeps trees: no bucket in the step
            check(pallas == 0 and xla == 0,
                  "dense: the step built a flat bucket",
                  fused_buckets_pallas=pallas, fused_buckets_xla=xla)
        elif kernels:
            check(pallas > 0 and xla == 0,
                  "zero1: fused update did not run as the Pallas kernel",
                  fused_buckets_pallas=pallas, fused_buckets_xla=xla)
        exact[name] = losses
        row = {"losses": [round(l, 4) for l in losses],
               "first_loss_rel_diff": first, "band": band,
               "mesh_devices": [d.id for d in devs],
               "precision/fused_buckets_pallas": pallas,
               "precision/fused_buckets_xla": xla}
        state = _per_device_bytes(model._updater_state)
        if acc is not None:
            # ZeRO-1: each chip holds about a quarter of the updater state
            per_replica = prof.counter_value(
                "zero1/updater_state_bytes_per_replica")
            total = prof.counter_value("zero1/updater_state_bytes_total")
            slack = n * 8 * 4       # bucket padding to a multiple of n
            check(sorted(state) == sorted(d.id for d in devs),
                  "ZeRO-1 state is not spread over the mesh")
            for dev_id, nbytes in state.items():
                check(abs(nbytes - dense_state_bytes / n) <= slack,
                      "ZeRO-1 updater state on a chip is not ~1/n",
                      device=dev_id, bytes=nbytes,
                      dense_bytes=dense_state_bytes)
            check(per_replica == total // n
                  and abs(per_replica - dense_state_bytes / n) <= slack,
                  "zero1/* ledger disagrees with the placed state",
                  per_replica=per_replica, total=total)
            row["zero1/updater_state_bytes_per_replica"] = per_replica
            row["zero1/updater_state_bytes_total"] = total
        row["updater_state_bytes_by_device"] = state
        report[name] = row
        del model, pw
        gc.collect()
    pair = max(abs(a - b) / abs(b)
               for a, b in zip(exact["zero1"], exact["dense"]))
    check(pair <= pair_tol, "ZeRO-1 losses differ from dense all-reduce",
          rel_diff=pair, tol=pair_tol)
    report["zero1_vs_dense_rel_diff"] = pair
    report["zero1_vs_dense_tol"] = pair_tol
    report["single_chip_updater_state_bytes"] = dense_state_bytes
    return {"model": "ResNet-50", "workers": n, "global_batch": cfg.batch,
            "steps": steps, **report}


# LeNet has no cross-example statistics, so workers(n) and single-chip fit
# compute the same mathematical update. The CPU mesh holds this comparison
# to atol=1e-5 (tests/test_parallel.py). On the chip f32 matmuls and
# convolutions run at the default, reduced precision and the per-shard
# reduction order differs, but the same bound holds there: the largest
# difference seen on four v5e chips was 1.08e-7 (PR 21).
LENET_ATOL = 1e-5


def phase_pw_lenet(cfg: Sizes, n: int) -> dict:
    import jax

    import bench
    from deeplearning4j_tpu.data import DataSet
    from deeplearning4j_tpu.ndarray.rng import set_default_seed
    from deeplearning4j_tpu.parallel import ParallelWrapper

    steps, batch = 3, 128
    rng = np.random.RandomState(0)
    x = rng.randn(batch, 1, 28, 28).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, batch)]
    ds = DataSet(x, y)

    set_default_seed(99)
    single = bench._lenet_model()
    ref = _fit_losses(lambda e: single.fit(ds, epochs=e), single, steps)
    set_default_seed(99)
    model = bench._lenet_model()
    pw = ParallelWrapper.Builder(model).workers(n).build()
    devs = _mesh_devices(pw, n, jax.devices()[0].platform)
    losses = _fit_losses(
        lambda e: pw.fit(ds, epochs=e, batch_size=batch), model, steps)
    _check_replicated(model._params, devs)
    diffs = [float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
             for a, b in zip(jax.tree.leaves(model._params),
                             jax.tree.leaves(single._params))]
    check(max(diffs) <= LENET_ATOL,
          "workers(n) params differ from single-chip fit",
          max_abs_diff=max(diffs), atol=LENET_ATOL)
    return {"model": "LeNet", "workers": n, "global_batch": batch,
            "steps": steps, "max_abs_param_diff": max(diffs),
            "atol": LENET_ATOL,
            "losses": [round(l, 5) for l in losses],
            "single_chip_losses": [round(l, 5) for l in ref]}


def phase_serve_pinned(cfg: Sizes, n: int) -> dict:
    """ServingEngine.workers(n).pin_devices(True): one param copy per
    chip, answers equal to the one-replica engine's."""
    import jax

    import bench
    from deeplearning4j_tpu.parallel.serving import ServingEngine

    bucket = 8
    model = bench._resnet50_model(cfg.image)
    _enable_fused_epilogue(model)
    pool, _ = _image_batch(cfg, 4 * bucket, seed=2)
    sizes = [bucket, 3, 1, 5] * n          # enough to occupy every replica

    def builder():
        return (ServingEngine.Builder(model).buckets([bucket])
                .input_shape((3, cfg.image, cfg.image)))

    def rows(i: int, k: int) -> np.ndarray:
        return (np.arange(k) + 5 * i) % len(pool)

    one = builder().workers(1).build()
    try:
        want = [one.output(pool[rows(i, k)]).to_numpy()
                for i, k in enumerate(sizes)]
    finally:
        one.shutdown()
    eng = builder().workers(n).pin_devices(True).build()
    try:
        homes = []
        for slot in range(n):
            on = {d.id for leaf in jax.tree.leaves(eng._dev_params[slot])
                  for d in leaf.devices()}
            check(len(on) == 1, "a replica's params span devices",
                  slot=slot, devices=sorted(on))
            homes.append(on.pop())
        check(sorted(homes) == sorted(d.id for d in jax.devices()[:n]),
              "pinned replicas do not each have their own chip",
              homes=homes)
        futs = [eng.output_async(pool[rows(i, k)])
                for i, k in enumerate(sizes)]
        worst = 0.0
        for i, (f, w) in enumerate(zip(futs, want)):
            got = f.result(timeout=120).to_numpy()
            worst = max(worst, _check_answers(got, w, f"pinned request {i}"))
    finally:
        eng.shutdown()
    return {"model": "ResNet-50 + fused_epilogue", "workers": n,
            "bucket": bucket, "requests": len(sizes),
            "replica_devices": homes, "worst_rel_err": worst,
            "atol": SERVE_ATOL, "rel": SERVE_REL}


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the multi-chip path and what it is "
                         "compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="control-flow rehearsal at tiny sizes on whatever "
                         "backend JAX has; prints no result line")
    args = ap.parse_args()

    import jax

    from deeplearning4j_tpu.common.environment import (
        enable_compilation_cache)

    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform == "tpu":
            sys.exit("--rehearse is for a machine without the chip; run "
                     "without it here")
    elif platform != "tpu":
        sys.exit(f"chip_smoke needs a TPU; JAX found {devices}")
    if len(devices) < args.chips:
        sys.exit(f"--chips {args.chips} needs {args.chips} devices; JAX "
                 f"found {len(devices)}")
    enable_compilation_cache()
    cfg = TINY if args.rehearse else REAL
    kernels = platform == "tpu"     # Pallas kernels compiled, not stood in
    meter = Meter()
    holder: dict = {}

    if args.chips == 1:
        # cheapest compiles first: a refused kernel shows in seconds
        phases = [
            ("device", phase_device),
            ("attention", lambda: phase_attention(cfg, kernels)),
            ("embeddings", lambda: phase_embeddings(cfg)),
            ("train", lambda: phase_train(cfg, holder)),
            ("serve", lambda: phase_serve(cfg, holder)),
            ("samediff", lambda: phase_samediff(cfg)),
        ]
    else:
        n = args.chips
        phases = [
            ("device", phase_device),
            ("pw_resnet50", lambda: phase_pw_resnet50(cfg, kernels, n)),
            ("pw_lenet_strict", lambda: phase_pw_lenet(cfg, n)),
            ("serve_pinned", lambda: phase_serve_pinned(cfg, n)),
        ]
    for name, fn in phases:
        run_phase(meter, name, fn)

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if args.rehearse:
        # deliberately not the result line: no "ok", never "tpu"
        emit({"rehearsal": True, "phases_passed": [n for n, _ in phases],
              "device": device})
    else:
        emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
