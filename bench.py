#!/usr/bin/env python
"""Benchmark entry point (driver contract): prints ONE JSON line whose first
keys are {"metric", "value", "unit", "vs_baseline"}; extra keys carry the
self-validation evidence.

Self-validating methodology (round-2 contract):
- every timed step is synced (``jax.block_until_ready``) so per-step times are
  real device times, reported as median/p10/p90 over >= 30 steps;
- FLOPs per step come from XLA's own cost analysis of the compiled train-step
  module (fallback: none, fields omitted);
- effective TFLOP/s and MFU vs the chip's published peak are printed, and the
  run HARD-FAILS if MFU > 100% (physically impossible => timing bug);
- batch/image size/steps/data provenance are pinned in the JSON line.

The throughput value is batch / median_step_time: robust to warmup bleed and
host-side hiccups, and reproducible run-to-run within a few percent.

Usage: python bench.py [--config lenet|resnet50] [--steps N] [--with-listener]
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

# Planning anchors the ``vs_baseline`` key divides by. The LeNet row is this
# same config measured with the jax CPU backend on the build machine.
BASELINES = {
    "lenet_mnist_train": {"value": 1470.0, "unit": "images/sec"},
    # North star: "match nd4j-cuda on V100"; the reference publishes no numbers
    # (SURVEY.md §6), so the planning anchor is V100 fp32 ResNet-50 ~390 img/s.
    "resnet50_imagenet_train": {"value": 390.0, "unit": "images/sec"},
    # Planning anchor (not reference-derived): V100 BERT-base fine-tune at
    # seq 128 ~ 100 samples/sec in contemporary frameworks.
    "bert_base_finetune": {"value": 100.0, "unit": "samples/sec"},
    # Planning anchor: the chaos soak heals its 8-fault catalog in under
    # ~4 min of wall clock (faults healed per soak minute; see
    # bench_soak_smoke gates — the value is throughput of PROVEN recovery,
    # every fault must close a complete-chain incident to count at all).
    "soak_smoke": {"value": 2.0, "unit": "faults/min"},
}

# Steps per timed chunk: the value-readback fence that ends a chunk costs
# one host round trip, which amortizes to fence/CHUNK per step. 40 was
# chosen on a set-up that is gone; not measured on this chip.
CHUNK = 40


def _timed_steps(run_step, fence_value, warmup: int, steps: int):
    """Chunked per-step wall times with a VALUE-readback fence per chunk.

    The fence reads back the loss VALUE, which cannot return early: train
    step n consumes step n-1's params, so the chunk's final loss existing
    implies every step in the chunk executed. Steps are timed in chunks of
    CHUNK so the fence round-trip amortizes and dispatch still pipelines
    inside a chunk (the steady-state regime); the per-step figure is
    chunk_time / CHUNK.
    """
    for _ in range(warmup):
        run_step()
    fence_value()
    times = []
    n_chunks = max(6, (steps + CHUNK - 1) // CHUNK)
    for _ in range(n_chunks):
        t0 = time.perf_counter()
        for _ in range(CHUNK):
            run_step()
        fence_value()
        times.append((time.perf_counter() - t0) / CHUNK)
    return times


def _flops_of(step_fn, args) -> float | None:
    """XLA's FLOP count for a jitted step. The lowered (pre-compile) module's
    cost analysis is tried first — it avoids paying a second AOT compile of a
    step the jit cache already holds; the optimized-executable count is the
    fallback. Call BEFORE the timed loop if the step donates its arguments.
    Off the TPU a failed analysis returns None and the MFU fields are
    omitted; on the TPU they are the point of the run, so it raises."""
    import jax

    errors = []
    try:
        lowered = step_fn.lower(*args)
        getters = (lowered.cost_analysis,
                   lambda: lowered.compile().cost_analysis())
    except Exception as e:   # noqa: BLE001 — re-raised below on a TPU
        errors.append(repr(e))
        getters = ()
    for get in getters:
        try:
            cost = get()
        except Exception as e:   # noqa: BLE001 — re-raised below on a TPU
            errors.append(repr(e))
            continue
        if isinstance(cost, list):  # per-device list on some backends
            cost = cost[0]
        f = cost.get("flops") if cost else None
        if f and f > 0:
            return float(f)
        errors.append("cost analysis reported no flops")
    if jax.devices()[0].platform == "tpu":
        raise RuntimeError("XLA cost analysis failed on the TPU: "
                           + "; ".join(errors))
    return None


def _flops_per_step(model, args) -> float | None:
    return _flops_of(model._fit_step, args)


def _summarize(metric: str, times, batch: int, flops_per_step, platform: str,
               extra: dict) -> dict:
    med = statistics.median(times)
    p10 = np.percentile(times, 10)
    p90 = np.percentile(times, 90)
    result = {
        "metric": metric,
        "value": batch / med,
        "unit": "images/sec",
        "steps_timed": len(times) * CHUNK,
        "chunk": CHUNK,
        "batch": batch,
        "step_ms_median": round(med * 1e3, 3),
        "step_ms_p10": round(float(p10) * 1e3, 3),
        "step_ms_p90": round(float(p90) * 1e3, 3),
        "platform": platform,
        **extra,
    }
    if flops_per_step:
        eff_tflops = flops_per_step / med / 1e12
        result["flops_per_step"] = flops_per_step
        result["effective_tflops"] = round(eff_tflops, 2)
        if platform == "tpu":
            from deeplearning4j_tpu.common.xprof import device_peaks

            mfu = eff_tflops * 1e12 / device_peaks()[0]
            result["mfu_vs_bf16_peak"] = round(mfu, 4)
            if mfu > 1.0:
                print(json.dumps({"error": "MFU > 100% of chip peak — timing "
                                  "or FLOP accounting is broken", **result}))
                sys.exit(1)
    return result


def _ab_rounds(timed_epoch, rounds: int = 6):
    """Interleaved A/B rounds with alternating order (time-correlated
    host-load drift hits both halves of each pair equally); returns
    per-config times and per-round on/off ratios."""
    times = {"off": [], "on": []}
    ratios = []
    for r in range(rounds):
        order = ("on", "off") if r % 2 == 0 else ("off", "on")
        round_t = {name: timed_epoch(name) for name in order}
        times["on"].append(round_t["on"])
        times["off"].append(round_t["off"])
        ratios.append(round_t["on"] / round_t["off"])
    return times, ratios


def _ab_overhead_gate(what: str, budget: float, run_rounds, fail):
    """De-noised A/B overhead gate, shared by every overhead smoke
    (telemetry/fault/supervisor/obs — ISSUE 11 satellite). The estimator
    is the MIN over per-round on/off ratios: host-load noise on this box
    can only INFLATE a ratio (the measured effects are small and
    additive), so the min is the tightest honest bound — the same
    estimator mfu-smoke already uses. A gate breach automatically
    re-runs the WHOLE A/B pair once before hard-failing, and both
    measurements are logged either way (in the emitted JSON on pass, in
    the failure payload on fail). ``run_rounds() -> (times, ratios)``;
    returns ``(overhead, times, runs)`` of the passing (or last) run."""
    runs = []
    for attempt in (1, 2):
        times, ratios = run_rounds()
        overhead = min(ratios) - 1.0
        runs.append({"attempt": attempt,
                     "overhead_frac": round(overhead, 4),
                     "ratios": [round(r, 4) for r in ratios],
                     "off_s": [round(t, 4) for t in times["off"]],
                     "on_s": [round(t, 4) for t in times["on"]]})
        if overhead <= budget:
            return overhead, times, runs
        if attempt == 1:
            print(json.dumps({"warning": f"{what} overhead "
                              f"{overhead:.1%} over the {budget:.0%} "
                              f"budget — re-running the A/B pair once "
                              f"before failing", "measurement": runs[-1]}),
                  file=sys.stderr, flush=True)
    fail(f"{what} overhead {overhead:.1%} exceeds the {budget:.0%} "
         f"budget in both A/B runs", measurements=runs)


def _resnet50_model(image_size: int = 224):
    """The flagship ResNet-50 exactly as benched (bf16 compute / fp32
    params) — shared by the throughput bench and the cold-start audit so
    the two can never drift apart silently."""
    from deeplearning4j_tpu.models import ResNet50

    model = ResNet50(num_classes=1000, image_size=image_size).init()
    model.conf.global_conf.compute_dtype = "bfloat16"
    return model


def _resnet50_train_model(image_size: int = 224):
    """The flagship training cell's model: :func:`_resnet50_model` with
    bf16 updater state w/ stochastic rounding. ``fused_update`` is set as
    the benchmark's configuration sets it and selects nothing since PR 27
    (the unsharded step updates leaf by leaf; ZeRO-1 takes the bucket
    kernel unasked). Shared by bench_resnet50 and chip_smoke.py."""
    model = _resnet50_model(image_size)
    model.conf.global_conf.fused_update = True
    model.conf.global_conf.updater.state_dtype = "bfloat16"
    return model


def _bert_samediff(batch: int = 32, seq: int = 128):
    """BERT-base imported from a frozen TF GraphDef with a classifier head,
    loss and Adam training config grafted on (shared by bench_bert, the
    cold-start audit and chip_smoke.py). Returns (sd, placeholders,
    n_params)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.autodiff.samediff import TrainingConfig
    from deeplearning4j_tpu.imports import import_frozen_tf
    from deeplearning4j_tpu.imports.tf_fixtures import (
        build_bert_frozen_graph, make_bert_batch)
    from deeplearning4j_tpu.learning import Adam

    hidden, vocab, n_classes = 768, 30522, 3
    gd, in_names, n_params = build_bert_frozen_graph(
        batch=batch, seq=seq, hidden=hidden, vocab=vocab)
    sd = import_frozen_tf(gd)
    sd.convert_to_variables()
    pooled = sd.get_variable(sd.tf_outputs[0])
    w = sd.var("cls_w", shape=(hidden, n_classes), init="xavier")
    b = sd.var("cls_b", shape=(n_classes,), init="zeros")
    pooled.mmul(w).add(b).rename("logits")
    sd.placeholder("labels", shape=(batch, n_classes))
    sd.ops.softmax_cross_entropy(sd.get_variable("logits"),
                                 sd.get_variable("labels"), name="loss")
    sd.set_loss_variables("loss")
    sd.set_training_config(TrainingConfig(updater=Adam(2e-5),
                                          loss_name="loss"))
    ids, types, mask, y = make_bert_batch(batch, seq, vocab, n_classes)
    ph = {k: jnp.asarray(v) for k, v in
          {**dict(zip(in_names, (ids, types, mask))), "labels": y}.items()}
    return sd, ph, n_params


def _bert_training(batch: int = 32, seq: int = 128):
    """The BERT fine-tune step as a bare jitted function (bench_bert times
    it without the fit loop). Returns (step, params, upd, ph, n_params)."""
    sd, ph, n_params = _bert_samediff(batch, seq)
    params = sd._params()
    upd = sd._training_config.updater.init(params)
    step = sd._train_step_fn("loss", tuple(sd.placeholders()))
    return step, params, upd, ph, n_params


def _lenet_model():
    """The flagship LeNet config (shared bench / cold-audit)."""
    from deeplearning4j_tpu.learning import Nesterovs
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import layers as L

    conf = (NeuralNetConfiguration.builder()
            .seed(123)
            .updater(Nesterovs(learning_rate=0.01, momentum=0.9))
            .activation("relu")
            .weight_init("xavier")
            .list()
            .layer(L.ConvolutionLayer(n_out=20, kernel_size=(5, 5)))
            .layer(L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
            .layer(L.ConvolutionLayer(n_out=50, kernel_size=(5, 5)))
            .layer(L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
            .layer(L.DenseLayer(n_out=500))
            .layer(L.OutputLayer(n_out=10, loss="mcxent",
                                 activation="softmax"))
            .set_input_type(InputType.convolutional(28, 28, 1))
            .build())
    return MultiLayerNetwork(conf).init()


def _w2v_model(algorithm: str = "skipgram"):
    """The flagship Word2Vec hyperparameters (shared bench / cold-audit)."""
    from deeplearning4j_tpu.nlp import Word2Vec

    return Word2Vec(min_word_frequency=5, layer_size=100, window=5,
                    negative=5, sampling=1e-3, epochs=1, batch_size=8192,
                    seed=42, algorithm=algorithm)


def bench_resnet50(steps: int, batch: int = 64, image_size: int = 224,
                   with_listener: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.data import DataSet

    # the flagship trains with the full hot-path stack on; here it reports
    # the footprint win alongside throughput
    model = _resnet50_train_model(image_size)
    if with_listener:
        from deeplearning4j_tpu.optimize.listeners import ScoreIterationListener

        model.set_listeners(ScoreIterationListener(print_iterations=10))

    rng = np.random.RandomState(0)
    x = rng.randn(batch, 3, image_size, image_size).astype(np.float32)
    y = np.eye(1000, dtype=np.float32)[rng.randint(0, 1000, batch)]
    # DataSet/NDArray hold device arrays, so the synthetic batch uploads once
    # regardless; passing jnp arrays just skips the host-side staging copy.
    # (The disk-fed input pipeline is the resnet50-disk config.)
    ds = DataSet(jnp.asarray(x), jnp.asarray(y))

    times = _timed_steps(lambda: model.fit(ds, epochs=1),
                         lambda: float(model._score_dev),
                         warmup=3, steps=steps)
    assert np.isfinite(float(model._score_dev)), "non-finite training loss"

    inputs = {model.conf.network_inputs[0]: jnp.asarray(x)}
    labels = {model.conf.network_outputs[0]: jnp.asarray(y)}
    flops = _flops_per_step(
        model, (model._params, model._states, model._updater_state, inputs,
                labels, {}, jax.random.PRNGKey(0), jnp.asarray(0)))
    from deeplearning4j_tpu.common import xprof
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.learning.precision import updater_state_bytes

    state_bytes = updater_state_bytes(jax.device_get(model._updater_state))
    pstats = OpProfiler.get().precision_stats()
    # the performance observatory (ISSUE 15): join the value-fenced step
    # median onto the census and attach the per-executable roofline —
    # the cost/MFU/bound fields the BENCH_r06+ trajectory carries.
    # analyze(compile=False): cost analysis from the lowering only — an
    # AOT re-compile here would double the bench's compile bill.
    xprof.note_measured("graph/fit_step", statistics.median(times))
    xprof.analyze(compile=False)
    # single-DataSet fits ride the serial path (no run_epochs epoch
    # boundary), so sample the steady-state HBM watermark explicitly —
    # one live-buffer census at the end of the timed loop
    xprof.memory_watermark("fit")
    roofline = {}
    for name, row in xprof.roofline().items():
        if not (row.get("calls") or row.get("generations")):
            continue
        out_row = {k: (round(v, 6) if isinstance(v, float) else v)
                   for k, v in row.items()
                   if k in ("calls", "generations", "step_s", "mfu",
                            "arithmetic_intensity", "bound",
                            "cost_source")}
        cost = row.get("cost", {})
        if cost:
            out_row["flops"] = cost.get("flops")
            out_row["bytes"] = cost.get("bytes_accessed")
        roofline[name] = out_row
    return _summarize(
        "resnet50_imagenet_train", times, batch, flops,
        jax.devices()[0].platform,
        {"image_size": image_size,
         "dtype": "bf16 compute / fp32 params / bf16 updater state",
         # the BENCH_r* trajectory captures the footprint win, not just
         # img/s: state bytes by dtype + the fused-kernel hit ledger
         "updater_state_bytes": state_bytes,
         "fused_kernel": {k: int(v) for k, v in pstats.items()
                          if k.startswith(("fused_", "sr_"))},
         "xla_roofline": roofline,
         "hbm_watermarks": xprof.watermarks(),
         "data": "synthetic batch, device-resident (train-step config; the "
                 "disk-fed input pipeline is the resnet50-disk config)",
         "listener": with_listener})


def bench_bert(steps: int, batch: int = 32, seq: int = 128) -> dict:
    """North-star config 3: BERT-base imported from a frozen TF GraphDef,
    fine-tune step (forward+backward+Adam over all 110M params) timed."""
    import jax
    import jax.numpy as jnp

    step, params, upd, ph, n_params = _bert_training(batch, seq)
    state = {"params": params, "upd": upd, "loss": None}

    # FLOP count must be taken BEFORE the timed loop: the jitted step donates
    # its params/state, so lowering against them afterwards hits deleted arrays
    flops = _flops_of(step, (params, upd, ph, jax.random.PRNGKey(0),
                             jnp.asarray(0)))

    def run_step():
        state["params"], state["upd"], state["loss"] = step(
            state["params"], state["upd"], ph, jax.random.PRNGKey(0),
            jnp.asarray(0))

    times = _timed_steps(run_step, lambda: float(state["loss"]),
                         warmup=2, steps=steps)
    assert np.isfinite(float(state["loss"])), "non-finite BERT loss"
    res = _summarize("bert_base_finetune", times, batch, flops,
                     jax.devices()[0].platform,
                     {"seq_len": seq, "dtype": "fp32",
                      "model_params": n_params,
                      "data": "synthetic ids/mask (frozen graph built with "
                              "local TF at random init; no egress)"})
    res["unit"] = "samples/sec"
    return res


def bench_lenet(steps: int, with_listener: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.data import MnistDataSetIterator

    batch = 128
    model = _lenet_model()
    if with_listener:
        from deeplearning4j_tpu.optimize.listeners import ScoreIterationListener

        model.set_listeners(ScoreIterationListener(print_iterations=10))

    it = MnistDataSetIterator(batch_size=batch, train=True,
                              num_examples=batch, flatten=False)
    ds = next(iter(it))
    mnist_real = not it.synthetic

    times = _timed_steps(lambda: model.fit(ds, epochs=1),
                         lambda: float(model._score_dev),
                         warmup=3, steps=steps)

    x = jnp.asarray(ds.features.value)
    y = jnp.asarray(ds.labels.value)
    flops = _flops_per_step(
        model, (model._params, model._states, model._updater_state, x, y,
                None, jax.random.PRNGKey(0), jnp.asarray(0)))
    return _summarize(
        "lenet_mnist_train", times, batch, flops, jax.devices()[0].platform,
        {"image_size": 28, "dtype": "fp32",
         "data": ("MNIST IDX files" if mnist_real
                  else "deterministic synthetic MNIST fallback (no IDX files "
                       "on disk)"),
         "listener": with_listener})


def bench_resnet50_disk(steps: int, batch: int = 64,
                        image_size: int = 224) -> dict:
    """ResNet-50 training fed from JPEG FILES ON DISK through the full ETL
    path — ImageRecordReader (parallel decode) → RecordReaderDataSetIterator
    → AsyncDataSetIterator (device prefetch) → fit. The number the VERDICT
    asked for: sustained throughput facing a real input pipeline, not
    device-resident arrays. Dataset: synthetic JPEGs generated once into a
    cache dir (no egress; decode cost is what matters, not content)."""
    import tempfile
    from pathlib import Path

    import jax

    from deeplearning4j_tpu.data import (AsyncDataSetIterator, FileSplit,
                                         ImageRecordReader,
                                         RecordReaderDataSetIterator)
    from deeplearning4j_tpu.models import ResNet50

    n_images = (max(steps, 10) + 2) * batch   # +warmup batch headroom
    cache = Path(tempfile.gettempdir()) / \
        f"d4t_bench_jpegs_{image_size}_{n_images}"
    if not cache.exists() or len(list(cache.rglob("*.jpg"))) < n_images:
        from PIL import Image

        rng = np.random.default_rng(0)
        for cls in range(10):
            (cache / f"class_{cls:02d}").mkdir(parents=True, exist_ok=True)
        for i in range(n_images):
            d = cache / f"class_{i % 10:02d}"
            p = d / f"{i:06d}.jpg"
            if not p.exists():
                arr = rng.integers(0, 255, (image_size, image_size, 3),
                                   dtype=np.uint8)
                Image.fromarray(arr).save(p, quality=85)

    model = ResNet50(num_classes=1000, image_size=image_size).init()
    model.conf.global_conf.compute_dtype = "bfloat16"

    rr = ImageRecordReader(height=image_size, width=image_size, channels=3,
                           workers=os.cpu_count() or 8)
    rr.initialize(FileSplit(cache, allowed_extensions=[".jpg"]))
    base = RecordReaderDataSetIterator(rr, batch_size=batch, label_index=1,
                                       num_classes=1000)
    it = AsyncDataSetIterator(base, queue_size=8, device_prefetch=True)

    # ONE generator for warmup + timing: a second iter(it) would spawn a
    # second worker thread racing the first over the shared reader state
    gen = iter(it)
    first = next(gen)
    model.fit(first, epochs=1)     # warmup: compile the step
    float(model._score_dev)

    t0 = time.perf_counter()
    n = 0
    for ds in gen:
        if n >= steps:
            break
        model.fit(ds, epochs=1)
        n += 1
    float(model._score_dev)        # value fence: consume the chained loss
    dt = time.perf_counter() - t0
    gen.close()                    # shut the prefetch worker down
    return {
        "metric": "resnet50_imagenet_train_diskpipe",
        "value": n * batch / dt,
        "unit": "images/sec",
        "steps_timed": n, "batch": batch,
        "platform": jax.devices()[0].platform,
        "image_size": image_size,
        "dtype": "bf16 compute / fp32 params",
        "decode_workers": rr.workers,
        "data": f"{n_images} synthetic JPEGs on disk -> ImageRecordReader -> "
                "async device prefetch",
    }


def bench_resnet50_predecoded(steps: int, batch: int = 64,
                              image_size: int = 224) -> dict:
    """ResNet-50 fed from the PRE-DECODED binary record container
    (data/binary_records.py; VERDICT r3 item 4) — the same disk pipeline
    as resnet50-disk but with JPEG decode paid ONCE at conversion: training
    reads are memmap slices at page-cache speed. On this 1-core host the
    decode-bound path does ~34 img/s; this shows what the container buys."""
    import tempfile
    from pathlib import Path

    import jax

    from deeplearning4j_tpu.data import (AsyncDataSetIterator,
                                         BinaryRecordDataSetIterator)
    from deeplearning4j_tpu.models import ResNet50

    n_images = (max(steps, 10) + 2) * batch
    container = Path(tempfile.gettempdir()) / \
        f"d4t_bench_predec_{image_size}_{n_images}.d4tbin"
    if not container.exists():
        # decode-once conversion: synthesize pixels straight into the
        # container (decoding n JPEGs first would take n/34 s on this
        # 1-core host and measure nothing new — the round-trip fidelity of
        # ImageRecordReader→write_records is covered in tests). Write to a
        # temp name + rename so an interrupted conversion never leaves a
        # truncated container that later runs would trust.
        from deeplearning4j_tpu.data.binary_records import BinaryRecordWriter

        rng = np.random.default_rng(0)
        tmp = container.with_suffix(".tmp")
        with BinaryRecordWriter(
                str(tmp),
                [("features", (3, image_size, image_size), "uint8"),
                 ("label", (), "int32")], chunk_records=batch) as w:
            for i in range(n_images):
                w.append(rng.integers(0, 255,
                                      (3, image_size, image_size),
                                      dtype=np.uint8), i % 10)
        os.replace(tmp, container)

    model = ResNet50(num_classes=1000, image_size=image_size).init()
    model.conf.global_conf.compute_dtype = "bfloat16"

    import jax.numpy as jnp

    # ship raw uint8 (4× less H2D traffic than f32), scale ON DEVICE, and
    # keep the worker thread jax-free (raw_numpy). Both choices were made
    # on a set-up that is gone (a 1-core host whose f32 cast and
    # worker-thread device_put were cliffs); not measured on this chip
    base = BinaryRecordDataSetIterator(str(container), batch_size=batch,
                                       num_classes=1000, raw_numpy=True)
    it = AsyncDataSetIterator(
        base, queue_size=8, device_prefetch=True,
        feature_transform=lambda x: x.astype(jnp.float32) / 255.0)
    gen = iter(it)
    first = next(gen)
    model.fit(first, epochs=1)     # warmup: compile the step
    float(model._score_dev)

    t0 = time.perf_counter()
    n = 0
    for ds in gen:
        if n >= steps:
            break
        model.fit(ds, epochs=1)
        n += 1
    float(model._score_dev)
    dt = time.perf_counter() - t0
    gen.close()
    return {
        "metric": "resnet50_imagenet_train_predecoded",
        "value": n * batch / dt,
        "unit": "images/sec",
        "steps_timed": n, "batch": batch,
        "platform": jax.devices()[0].platform,
        "image_size": image_size,
        "dtype": "bf16 compute / fp32 params",
        "container_bytes": container.stat().st_size,
        "data": f"{n_images} pre-decoded uint8 records in a .d4tbin "
                "container on disk -> memmap chunk reads -> async device "
                "prefetch",
    }


def bench_pipeline_smoke(steps: int, batch: int = 64,
                         steps_per_dispatch: int = 4) -> dict:
    """Fast CPU-friendly smoke of the shared input/dispatch pipeline
    (data/pipeline.py): a small MLP trained from an iterator whose final
    batch is PARTIAL, with padding + async device feed + multi-step
    dispatch all on. Self-validating: hard-fails unless the retrace
    counters prove the per-step jit traced at most once and the scan chunk
    exactly once. The emitted metrics (padded batches, host-wait vs
    dispatch overlap) are the input-pipeline ledger for BENCH_*.json
    rounds."""
    import jax

    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import NDArrayDataSetIterator
    from deeplearning4j_tpu.learning import Nesterovs
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.optimize.listeners import PipelineMetricsListener

    conf = (NeuralNetConfiguration.builder().seed(123)
            .updater(Nesterovs(learning_rate=0.01, momentum=0.9))
            .activation("relu").weight_init("xavier").list()
            .layer(L.DenseLayer(n_out=256))
            .layer(L.DenseLayer(n_out=128))
            .layer(L.OutputLayer(n_out=10, loss="mcxent",
                                 activation="softmax"))
            .set_input_type(InputType.feed_forward(784)).build())
    model = MultiLayerNetwork(conf).init()
    listener = PipelineMetricsListener()
    model.set_listeners(listener)

    rng = np.random.RandomState(0)
    n = steps * batch + batch // 2      # the half batch forces a partial tail
    x = rng.randn(n, 784).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)]
    it = NDArrayDataSetIterator(x, y, batch_size=batch)

    from deeplearning4j_tpu.common import tracecheck

    prof = OpProfiler.get()
    prof.reset()
    model.fit(it, epochs=1, steps_per_dispatch=steps_per_dispatch)  # warmup
    float(model._score_dev)
    prof.reset()
    t0 = time.perf_counter()
    try:
        # the timed epoch is a DECLARED steady-state region: counters
        # were reset after the warmup fit, so any trace/compile/device_get
        # in here is a hot-loop regression and the sanitizer raises
        with tracecheck.steady_state("pipeline-smoke timed epoch"):
            model.fit(it, epochs=1, steps_per_dispatch=steps_per_dispatch)
            float(model._score_dev)     # value fence
    except tracecheck.SteadyStateViolation as e:
        print(json.dumps({"error": "input pipeline violated steady state "
                          "— shape-stable batching is broken",
                          "violation": str(e).splitlines()[0],
                          "report": {k: v for k, v in e.report.items()
                                     if k != "first_stack"}}))
        sys.exit(1)
    dt = time.perf_counter() - t0
    traces = prof.trace_counts()

    # the sanitizer itself must be ARMED, not just quiet: inject a real
    # retrace (a fit at a different batch size re-traces the step) inside
    # a declared region and require the hard failure
    xs = rng.randn(batch, 784).astype(np.float32)
    ys = np.eye(10, dtype=np.float32)[rng.randint(0, 10, batch)]
    try:
        with tracecheck.steady_state("injected-retrace drill",
                                     max_host_syncs=None):
            model.fit(NDArrayDataSetIterator(xs, ys,
                                             batch_size=batch // 2),
                      epochs=1)
        print(json.dumps({"error": "trace sanitizer FAILED to detect an "
                          "injected steady-state retrace"}))
        sys.exit(1)
    except tracecheck.SteadyStateViolation:
        pass                            # armed and firing
    images = n + (batch - n % batch) % batch    # padded count actually run
    return {
        "metric": "input_pipeline_smoke",
        "value": images / dt,
        "unit": "images/sec",
        "steps_timed": -(-images // batch),
        "batch": batch,
        "steps_per_dispatch": steps_per_dispatch,
        "platform": jax.devices()[0].platform,
        "traces": traces,
        "tracecheck": prof.tracecheck_stats(),   # 2 regions, 1 violation
        "padded_batches": prof.counter_value("pipeline/padded_batches"),
        "overlap": {k: (round(v, 4) if isinstance(v, float) else v)
                    for k, v in prof.overlap_stats().items()},
        "data": "synthetic MLP batches with a partial final batch "
                "(pipeline padding + async feed + multi-step dispatch)",
    }


def bench_telemetry_smoke(steps: int, batch: int = 64,
                          steps_per_dispatch: int = 4) -> dict:
    """CPU-friendly smoke of the in-graph telemetry layer: a LeNet-class
    conv model (realistic FLOP:param ratio — telemetry cost is O(params)
    while the step is O(params x batch)) trained from an iterator with a
    partial final batch, once with telemetry off and once with a
    TelemetrySink + NanSentinelListener attached. Self-validating
    hard-fails:

    - any retrace in either timed window (telemetry must not destabilize
      shapes), checked on BOTH the per-step jit and the
      ``steps_per_dispatch`` scan chunk;
    - any delta between the two configs' compile footprints (each must
      trace each step kind exactly once);
    - telemetry step-time overhead > 10%.

    Timing methodology (shared by every overhead smoke via
    ``_ab_overhead_gate``): the off/on epochs are INTERLEAVED with
    alternating order and the overhead estimator is the MIN over
    per-round ratios, so host-load drift (this box swings >20%
    run-to-run, and noise can only inflate a ratio) hits both configs
    equally instead of masquerading as telemetry overhead; a gate breach
    re-runs the whole A/B pair once, logging both measurements. The
    emitted JSON carries the overlap ledger and the telemetry drain
    ledger (batched-readback time — the only host sync telemetry
    pays)."""
    import statistics as _stats

    import jax

    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import NDArrayDataSetIterator
    from deeplearning4j_tpu.optimize import (NanSentinelListener,
                                             TelemetrySink)
    from deeplearning4j_tpu.ui import InMemoryStatsStorage

    rng = np.random.RandomState(0)
    n = steps * batch + batch // 2      # the half batch forces a partial tail
    x = rng.randn(n, 1, 28, 28).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)]
    it = NDArrayDataSetIterator(x, y, batch_size=batch)
    prof = OpProfiler.get()

    storage = InMemoryStatsStorage()
    models = {"off": _lenet_model(), "on": _lenet_model()}
    models["on"].set_listeners(TelemetrySink(storage, drain_every_n=25),
                               NanSentinelListener("warn", check_every_n=25))

    def fail(msg, **extra):
        print(json.dumps({"error": msg, **extra}))
        sys.exit(1)

    # compile footprint: one warmup fit per config on the CHUNKED path
    # (traces both the per-step jit and the scan chunk); the footprints
    # must be identical — telemetry rides the same single trace per kind
    warm = {}
    for name, model in models.items():
        prof.reset()
        model.fit(it, epochs=1, steps_per_dispatch=steps_per_dispatch)
        float(model._score_dev)
        warm[name] = prof.trace_counts()
    if warm["on"] != warm["off"]:
        fail("telemetry changed the compile footprint (retrace delta)",
             off_traces=warm["off"], on_traces=warm["on"])

    from deeplearning4j_tpu.common import tracecheck

    prof.reset()

    def timed_epoch(name):
        model = models[name]
        t0 = time.perf_counter()
        model.fit(it, epochs=1, steps_per_dispatch=steps_per_dispatch)
        float(model._score_dev)         # value fence
        return time.perf_counter() - t0

    try:
        # the interleaved timed rounds are one steady-state region; the
        # telemetry drain's batched device_get cadence is data-dependent
        # by design, so host syncs are counted but not policed here
        with tracecheck.steady_state("telemetry-smoke timed rounds",
                                     max_host_syncs=None):
            overhead, times, overhead_runs = _ab_overhead_gate(
                "telemetry step-time", 0.10,
                lambda: _ab_rounds(timed_epoch, rounds=5), fail)
    except tracecheck.SteadyStateViolation as e:
        fail("train step retraced inside a timed window — telemetry or "
             "pipeline shape stability is broken",
             violation=str(e).splitlines()[0])
    t_off = _stats.median(times["off"])
    t_on = _stats.median(times["on"])
    if not storage.series("loss") \
            or not any(t.startswith("grad_norm/") for t in storage.tags()):
        fail("telemetry enabled but no grad-norm series reached the "
             "storage", tags=storage.tags())

    images = n + (batch - n % batch) % batch    # padded count actually run
    return {
        "metric": "telemetry_smoke",
        "value": images / t_on,
        "unit": "images/sec",
        "batch": batch,
        "steps_per_dispatch": steps_per_dispatch,
        "platform": jax.devices()[0].platform,
        "traces": warm["on"],
        "telemetry_overhead_frac": round(overhead, 4),
        "overhead_runs": overhead_runs,
        "epoch_s_off_median": round(t_off, 4),
        "epoch_s_on_median": round(t_on, 4),
        "overlap": {k: (round(v, 4) if isinstance(v, float) else v)
                    for k, v in prof.overlap_stats().items()},
        "telemetry_drain": {k: (round(v, 5) if isinstance(v, float) else v)
                            for k, v in prof.telemetry_stats().items()},
        "series_collected": len(storage.tags()),
        "data": "synthetic LeNet batches with a partial final batch; "
                "telemetry on vs off interleaved, identical pipeline knobs",
    }


def bench_fault_smoke(steps: int, batch: int = 64,
                      checkpoint_every: int | None = None) -> dict:
    """CPU-friendly smoke of the fault-tolerance layer: a LeNet-class
    conv model (realistic step-compute : checkpoint-bytes ratio — the
    checkpoint payload is O(params) while the step is O(params x batch))
    trained from an iterator with a partial final batch, once with
    checkpointing off and once with an async-atomic CheckpointListener
    attached, then one injected transient input fault, then a simulated
    kill + exact resume. ``checkpoint_every`` defaults to 2 checkpoints
    per epoch — a cadence the background writer sustains without
    backpressure (submissions spaced further apart than one
    serialize+commit), which is the regime async checkpointing is
    designed for. Self-validating hard-fails:

    - resume-parity mismatch: a run crashed mid-fit (injected
      ``SimulatedCrash``) and resumed from its last intact checkpoint
      must reproduce the uninterrupted run's loss sequence EXACTLY
      (bit-identical float equality, CPU);
    - any retrace in a timed window, or any compile-footprint delta
      between the checkpoint-on and checkpoint-off configs;
    - injected transient fault not retried/recovered (retry counter must
      read exactly the injected count and training must complete);
    - async checkpointing step-time overhead > 10% vs checkpoint-off
      (interleaved A/B min-over-ratios with one automatic re-run, the
      shared ``_ab_overhead_gate`` methodology).

    Emits the checkpoint ledger (snapshot readback time — the only
    hot-loop cost — plus background write time and bytes) and the fault
    ledger."""
    import shutil
    import statistics as _stats
    import tempfile

    import jax

    from deeplearning4j_tpu.common import faultinject
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import NDArrayDataSetIterator
    from deeplearning4j_tpu.ndarray.rng import set_default_seed
    from deeplearning4j_tpu.optimize.listeners import (
        CheckpointListener, CollectScoresIterationListener)

    if checkpoint_every is None:
        checkpoint_every = max(5, (steps + 1) // 2)
    rng = np.random.RandomState(0)
    n = steps * batch + batch // 2      # the half batch forces a partial tail
    x = rng.randn(n, 1, 28, 28).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)]

    def make_it():
        return NDArrayDataSetIterator(x, y, batch_size=batch)

    def fail(msg, **extra):
        print(json.dumps({"error": msg, **extra}))
        sys.exit(1)

    prof = OpProfiler.get()
    faultinject.clear_plan()
    ckdir = tempfile.mkdtemp(prefix="dl4j_fault_smoke_")
    try:
        listeners = {}
        models = {"off": _lenet_model(), "on": _lenet_model()}
        listeners["on"] = CheckpointListener(
            ckdir, save_every_n_iterations=checkpoint_every, keep_last=2)
        models["on"].set_listeners(listeners["on"])

        # compile footprint: checkpointing must not change it
        warm = {}
        for name, model in models.items():
            prof.reset()
            model.fit(make_it(), epochs=1, batch_size=batch)
            float(model._score_dev)
            warm[name] = prof.trace_counts()
        if warm["on"] != warm["off"]:
            fail("checkpointing changed the compile footprint "
                 "(retrace delta)", off_traces=warm["off"],
                 on_traces=warm["on"])

        # paired A/B timing: async checkpoint overhead vs off. Each
        # "on" window carries its own snapshots + the writer thread's
        # concurrent serialize/commit contention; the residual in-flight
        # tail is drained BETWEEN windows (untimed) so the "off" windows
        # stay clean. Host-load drift on this box is time-correlated and
        # larger than the effect measured, so the shared
        # _ab_overhead_gate estimator applies: interleaved rounds,
        # min-over-ratios, one automatic A/B re-run before failing.
        def timed_epoch(name):
            t0 = time.perf_counter()
            models[name].fit(make_it(), epochs=1, batch_size=batch)
            float(models[name]._score_dev)      # value fence
            dt = time.perf_counter() - t0
            if name == "on":
                listeners["on"].flush()         # drain tail, untimed
            return dt

        timed_epoch("on")                       # untimed settle-in round
        timed_epoch("off")
        prof.reset()
        overhead, times, overhead_runs = _ab_overhead_gate(
            "async checkpoint", 0.10,
            lambda: _ab_rounds(timed_epoch, rounds=6), fail)
        hot = prof.trace_counts()
        if any(hot.values()):
            fail("train step retraced inside a timed window", traces=hot)
        ckpt_ledger = prof.checkpoint_stats()
        t_off = _stats.median(times["off"])
        t_on = _stats.median(times["on"])

        # one injected transient input fault: retried, recovered, counted
        prof.reset()
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "pipeline/bind", "index": 1, "kind": "transient"}]))
        models["on"].fit(make_it(), epochs=1, batch_size=batch)
        faultinject.clear_plan()
        if prof.counter_value("pipeline/retries") != 1:
            fail("injected transient fault was not retried exactly once",
                 retries=prof.counter_value("pipeline/retries"))
        if prof.trace_counts():
            fail("fault retry retraced the train step",
                 traces=prof.trace_counts())
        fault_ledger = prof.fault_stats()

        # kill-resume parity: uninterrupted baseline vs crash+resume.
        # Retire the timing listener's writer BEFORE clearing its
        # directory out from under it.
        listeners["on"].close()
        shutil.rmtree(ckdir)
        os.makedirs(ckdir)
        par_epochs = 2
        par_steps = min(steps, 8)
        xs, ys = x[:par_steps * batch], y[:par_steps * batch]

        def par_it():
            return NDArrayDataSetIterator(xs, ys, batch_size=batch,
                                          shuffle=True, seed=3)

        set_default_seed(99)
        base_model = _lenet_model()
        base_scores = CollectScoresIterationListener()
        base_model.set_listeners(base_scores)
        base_model.fit(par_it(), epochs=par_epochs, batch_size=batch)
        baseline = [s for _, s in base_scores.scores]

        set_default_seed(99)
        victim = _lenet_model()
        vs = CollectScoresIterationListener()
        cl = CheckpointListener(ckdir, save_every_n_iterations=3,
                                keep_last=2)
        victim.set_listeners(vs, cl)
        crash_at = par_steps + 1       # mid-epoch-2
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "train/step", "index": crash_at, "kind": "crash"}]))
        crashed = False
        try:
            victim.fit(par_it(), epochs=par_epochs, batch_size=batch)
        except faultinject.SimulatedCrash:
            crashed = True
        faultinject.clear_plan()
        cl.close()
        if not crashed:
            fail("injected crash did not fire", crash_at=crash_at)
        last = CheckpointListener.last_checkpoint(ckdir)
        if last is None:
            fail("no intact checkpoint after simulated kill")
        resumed_model = _lenet_model()
        rs = CollectScoresIterationListener()
        resumed_model.set_listeners(rs)
        resumed_model.fit(par_it(), epochs=par_epochs, batch_size=batch,
                          resume_from=last)
        resumed = [s for _, s in rs.scores]
        if resumed != baseline:
            diff = next((i for i, (a, b) in enumerate(zip(baseline, resumed))
                         if a != b), min(len(baseline), len(resumed)))
            fail("resume-parity mismatch: killed+resumed loss sequence "
                 "differs from the uninterrupted run",
                 first_diff_step=diff, baseline_len=len(baseline),
                 resumed_len=len(resumed),
                 resumed_from=os.path.basename(last))

        images = (n + (batch - n % batch) % batch)
        return {
            "metric": "fault_smoke",
            "value": images / t_on,
            "unit": "images/sec",
            "batch": batch,
            "platform": jax.devices()[0].platform,
            "traces": warm["on"],
            "checkpoint_overhead_frac": round(overhead, 4),
            "overhead_runs": overhead_runs,
            "epoch_s_off_median": round(t_off, 4),
            "epoch_s_on_median": round(t_on, 4),
            "checkpoint_ledger": {k: (round(v, 5) if isinstance(v, float)
                                      else v)
                                  for k, v in ckpt_ledger.items()},
            "fault_ledger": fault_ledger,
            "resume_parity": "exact",
            "resume_steps_compared": len(baseline),
            "data": "synthetic LeNet batches with a partial final batch; "
                    "async checkpointing on vs off interleaved, one "
                    "injected transient fault, one simulated kill+resume",
        }
    finally:
        faultinject.clear_plan()
        shutil.rmtree(ckdir, ignore_errors=True)


def bench_supervisor_smoke(steps: int, batch: int = 64,
                           checkpoint_every: int | None = None) -> dict:
    """CPU-friendly smoke of the self-healing layer (ISSUE 4): the same
    LeNet-class config as fault-smoke, trained once per round under a
    plain CheckpointListener ("off") and once under a TrainingSupervisor
    ("on" — incarnation claim, anchor checkpoint, heartbeat listener,
    monitor thread, same checkpoint cadence), interleaved A/B; then one
    injected mid-epoch crash that the supervisor must heal WITHOUT human
    intervention. Self-validating hard-fails:

    - resume-parity mismatch: the supervised run with an injected restart
      must reproduce the uninterrupted run's loss sequence EXACTLY
      (bit-identical float equality, CPU);
    - any retrace inside a timed no-fault window (supervision must not
      perturb the compile story);
    - supervision overhead > 10% in the no-fault case (min over
      per-round on/off ratios with one automatic A/B re-run, the shared
      ``_ab_overhead_gate`` estimator; the "on"
      window deliberately pays the supervisor's FULL per-fit cost —
      incarnation claim, anchor save_now, writer drain on close — and
      each timed window spans several epochs so that fixed per-fit cost
      amortizes the way any real run amortizes it);
    - supervisor counters not visible (restart/attempt ledger empty after
      the healed run).

    Emits the supervisor ledger alongside the checkpoint ledger."""
    import shutil
    import statistics as _stats
    import tempfile

    import jax

    from deeplearning4j_tpu.common import faultinject
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import NDArrayDataSetIterator
    from deeplearning4j_tpu.ndarray.rng import set_default_seed
    from deeplearning4j_tpu.optimize.listeners import (
        CheckpointListener, CollectScoresIterationListener)
    from deeplearning4j_tpu.parallel import TrainingSupervisor

    if checkpoint_every is None:
        checkpoint_every = max(5, (steps + 1) // 2)
    rng = np.random.RandomState(0)
    n = steps * batch + batch // 2
    x = rng.randn(n, 1, 28, 28).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)]

    def make_it():
        return NDArrayDataSetIterator(x, y, batch_size=batch)

    def fail(msg, **extra):
        print(json.dumps({"error": msg, **extra}))
        sys.exit(1)

    prof = OpProfiler.get()
    faultinject.clear_plan()
    dirs = {"off": tempfile.mkdtemp(prefix="dl4j_sup_smoke_off_"),
            "on": tempfile.mkdtemp(prefix="dl4j_sup_smoke_on_")}
    try:
        models = {"off": _lenet_model(), "on": _lenet_model()}
        off_ckpt = CheckpointListener(
            dirs["off"], save_every_n_iterations=checkpoint_every,
            keep_last=2)
        models["off"].set_listeners(off_ckpt)
        sup = TrainingSupervisor(models["on"], dirs["on"],
                                 save_every_n_iterations=checkpoint_every,
                                 keep_last=2, backoff_base_s=0.01)

        def run(name, epochs=1):
            if name == "off":
                models["off"].fit(make_it(), epochs=epochs,
                                  batch_size=batch)
            else:
                res = sup.fit(make_it, epochs=epochs, batch_size=batch,
                              resume="never")
                if res.status != "completed" or res.restarts:
                    fail("no-fault supervised epoch did not complete "
                         "cleanly", result=repr(res))
            float(models[name]._score_dev)      # value fence

        # compile footprint: supervision must not change it
        warm = {}
        for name in ("off", "on"):
            prof.reset()
            run(name)
            warm[name] = prof.trace_counts()
        if warm["on"] != warm["off"]:
            fail("supervision changed the compile footprint (retrace "
                 "delta)", off_traces=warm["off"], on_traces=warm["on"])

        # interleaved A/B timing (same estimator as fault-smoke: median
        # of per-round on/off ratios after one untimed settle round);
        # several epochs per window so the supervisor's fixed per-fit
        # cost (anchor checkpoint + close drain) amortizes realistically
        round_epochs = 4

        def timed_epoch(name):
            t0 = time.perf_counter()
            run(name, epochs=round_epochs)
            dt = time.perf_counter() - t0
            if name == "off":
                off_ckpt.flush()                # drain tail, untimed
            return dt

        timed_epoch("on")
        timed_epoch("off")
        prof.reset()
        overhead, times, overhead_runs = _ab_overhead_gate(
            "supervision", 0.10,
            lambda: _ab_rounds(timed_epoch, rounds=6), fail)
        hot = prof.trace_counts()
        if any(hot.values()):
            fail("train step retraced inside a timed window", traces=hot)
        ckpt_ledger = prof.checkpoint_stats()
        t_off = _stats.median(times["off"])
        t_on = _stats.median(times["on"])
        off_ckpt.close()

        # injected restart: crash mid-epoch-2, supervisor heals, loss
        # sequence bitwise-equal to the uninterrupted baseline
        prof.reset()
        par_epochs = 2
        par_steps = min(steps, 8)
        xs, ys = x[:par_steps * batch], y[:par_steps * batch]

        def par_it():
            return NDArrayDataSetIterator(xs, ys, batch_size=batch,
                                          shuffle=True, seed=3)

        set_default_seed(99)
        base_model = _lenet_model()
        base_scores = CollectScoresIterationListener()
        base_model.set_listeners(base_scores)
        base_model.fit(par_it(), epochs=par_epochs, batch_size=batch)
        baseline = [s for _, s in base_scores.scores]

        set_default_seed(99)
        victim = _lenet_model()
        vs = CollectScoresIterationListener()
        victim.set_listeners(vs)
        crash_at = par_steps + 1
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "train/step", "index": crash_at, "kind": "crash"}]))
        heal_dir = tempfile.mkdtemp(prefix="dl4j_sup_smoke_heal_")
        try:
            sup2 = TrainingSupervisor(victim, heal_dir,
                                      save_every_n_iterations=3,
                                      keep_last=2, backoff_base_s=0.01)
            res = sup2.fit(par_it, epochs=par_epochs, batch_size=batch,
                           resume="never")
        finally:
            faultinject.clear_plan()
            shutil.rmtree(heal_dir, ignore_errors=True)
        if res.status != "completed" or res.restarts != 1:
            fail("supervisor did not heal the injected crash with exactly "
                 "one restart", result=repr(res),
                 history=res.history)
        resumed = [s for _, s in vs.scores]
        if resumed != baseline:
            diff = next((i for i, (a, b) in enumerate(zip(baseline, resumed))
                         if a != b), min(len(baseline), len(resumed)))
            fail("resume-parity mismatch: supervised+healed loss sequence "
                 "differs from the uninterrupted run",
                 first_diff_step=diff, baseline_len=len(baseline),
                 resumed_len=len(resumed))
        sup_ledger = prof.supervisor_stats()
        if sup_ledger.get("restarts") != 1 or \
                sup_ledger.get("attempts") != 2:
            fail("supervisor ledger does not show the healed restart",
                 ledger=sup_ledger)

        images = (n + (batch - n % batch) % batch) * round_epochs
        return {
            "metric": "supervisor_smoke",
            "value": images / t_on,
            "unit": "images/sec",
            "batch": batch,
            "platform": jax.devices()[0].platform,
            "traces": warm["on"],
            "supervision_overhead_frac": round(overhead, 4),
            "overhead_runs": overhead_runs,
            "epoch_s_off_median": round(t_off, 4),
            "epoch_s_on_median": round(t_on, 4),
            "supervisor_ledger": {k: (round(v, 5) if isinstance(v, float)
                                      else v)
                                  for k, v in sup_ledger.items()},
            "checkpoint_ledger": {k: (round(v, 5) if isinstance(v, float)
                                      else v)
                                  for k, v in ckpt_ledger.items()},
            "resume_parity": "exact",
            "resume_steps_compared": len(baseline),
            "data": "synthetic LeNet batches; supervised vs plain "
                    "checkpointed epochs interleaved, one injected "
                    "mid-epoch crash healed by restart",
        }
    finally:
        faultinject.clear_plan()
        for d in dirs.values():
            shutil.rmtree(d, ignore_errors=True)


def bench_zero1_smoke(steps: int, batch: int = 64, workers: int = 4) -> dict:
    """CPU-friendly smoke of ZeRO-1 cross-replica weight-update sharding
    (ISSUE 5; arXiv:2004.13336): the flagship LeNet config trained through
    ParallelWrapper once with the dense all-reduce accumulator and once
    with ReduceScatterAccumulator (reduce-scatter grads → sharded updater
    apply → all-gather params), paired interleaved A/B. Self-validating
    hard-fails:

    - parity break: the sharded-updater loss sequence (and final params)
      must be BITWISE-equal to the dense path's on CPU;
    - any retrace delta between the two paths, or any retrace inside a
      timed window (the sharded step must stay one-compile-per-config);
    - per-replica updater-state bytes not ≈ 1/workers of the dense
      footprint (asserted via the zero1/* memory ledger; the flat
      bucketing may pad by at most one shard per dtype bucket);
    - step-time regression > 5% vs dense (median of per-round ratios —
      the ZeRO-1 point on one host is the memory/redundancy win, it must
      not cost step time);
    - encoded-exchange density/bytes counters empty after a short
      EncodedGradientsAccumulator fit (the DCN-path ledger must populate).

    Emits the collective-bytes ledger alongside the timing."""
    import shutil  # noqa: F401  (parity with sibling smokes' imports)
    import statistics as _stats

    # a multi-replica mesh is the whole point: on single-device hosts
    # (CPU build machines) request virtual CPU devices BEFORE jax loads
    if "jax" not in sys.modules:
        _flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in _flags:
            os.environ["XLA_FLAGS"] = (
                _flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import NDArrayDataSetIterator
    from deeplearning4j_tpu.ndarray.rng import set_default_seed
    from deeplearning4j_tpu.optimize.listeners import (
        CollectScoresIterationListener)
    from deeplearning4j_tpu.parallel import (EncodedGradientsAccumulator,
                                             ParallelWrapper,
                                             ReduceScatterAccumulator)

    def fail(msg, **extra):
        print(json.dumps({"error": msg, **extra}))
        sys.exit(1)

    workers = min(workers, len(jax.devices()))
    if workers < 2:
        fail("zero1-smoke needs >= 2 devices (virtual CPU device request "
             "came too late — is jax initialized before bench dispatch?)",
             devices=len(jax.devices()))
    rng = np.random.RandomState(0)
    n = steps * batch
    x = rng.randn(n, 1, 28, 28).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)]

    def make_it():
        return NDArrayDataSetIterator(x, y, batch_size=batch)

    def build(acc):
        set_default_seed(99)
        model = _lenet_model()
        b = ParallelWrapper.Builder(model).workers(workers)
        if acc is not None:
            b.gradients_accumulator(acc)
        return model, b.build()

    prof = OpProfiler.get()
    prof.reset()

    # --- bitwise parity + compile footprint (one warmup epoch each) ----
    seqs, models, wrappers, warm = {}, {}, {}, {}
    for name, acc in (("dense", None), ("zero1", ReduceScatterAccumulator())):
        model, pw = build(acc)
        scores = CollectScoresIterationListener()
        pw.set_listeners(scores)
        prof.reset()
        pw.fit(make_it(), epochs=1, batch_size=batch)
        float(model._score_dev)
        warm[name] = prof.trace_counts()
        seqs[name] = [s for _, s in scores.scores]
        models[name], wrappers[name] = model, pw
    if seqs["zero1"] != seqs["dense"]:
        diff = next((i for i, (a, b) in enumerate(
            zip(seqs["dense"], seqs["zero1"])) if a != b),
            min(len(seqs["dense"]), len(seqs["zero1"])))
        fail("ZeRO-1 parity break: sharded-updater loss sequence is not "
             "bitwise-identical to the dense path", first_diff_step=diff)
    pd = jax.device_get(models["dense"]._params)
    pz = jax.device_get(models["zero1"]._params)
    if not all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(pd), jax.tree.leaves(pz))):
        fail("ZeRO-1 parity break: final params differ from the dense "
             "path's")
    if warm["zero1"] != warm["dense"]:
        fail("retrace delta between dense and ZeRO-1 paths",
             dense_traces=warm["dense"], zero1_traces=warm["zero1"])

    # --- memory ledger: sharded updater state is ~1/workers of dense ---
    dense_upd_bytes = int(sum(
        l.size * l.dtype.itemsize
        for l in jax.tree.leaves(jax.device_get(
            models["dense"]._updater_state))))
    per_replica = OpProfiler.get().counter_value(
        "zero1/updater_state_bytes_per_replica")
    # flat bucketing pads each dtype bucket to a multiple of `workers`
    pad_slack = workers * 8 * 4
    if not (0 < per_replica <= dense_upd_bytes // workers + pad_slack):
        fail("sharded updater-state footprint is not ~1/workers of dense",
             dense_bytes=dense_upd_bytes, per_replica_bytes=per_replica,
             workers=workers)

    # --- interleaved A/B step time (median of per-round ratios) --------
    def timed_epoch(name):
        t0 = time.perf_counter()
        wrappers[name].fit(make_it(), epochs=1, batch_size=batch)
        float(models[name]._score_dev)
        return time.perf_counter() - t0

    timed_epoch("zero1")
    timed_epoch("dense")                 # settle round, untimed
    prof.reset()
    times = {"dense": [], "zero1": []}
    ratios = []
    for r in range(6):
        order = ("zero1", "dense") if r % 2 == 0 else ("dense", "zero1")
        round_t = {name: timed_epoch(name) for name in order}
        times["dense"].append(round_t["dense"])
        times["zero1"].append(round_t["zero1"])
        ratios.append(round_t["zero1"] / round_t["dense"])
    hot = prof.trace_counts()
    if any(hot.values()):
        fail("train step retraced inside a timed window", traces=hot)
    coll_ledger = prof.collective_stats()
    t_dense = _stats.median(times["dense"])
    t_zero1 = _stats.median(times["zero1"])
    regression = _stats.median(ratios) - 1.0
    if regression > 0.05:
        fail(f"ZeRO-1 step-time regression {regression:.1%} exceeds the "
             "5% budget",
             dense_s=round(t_dense, 4), zero1_s=round(t_zero1, 4),
             zero1_times=[round(t, 4) for t in times["zero1"]],
             dense_times=[round(t, 4) for t in times["dense"]])

    # --- encoded-exchange ledger populates (short DCN-path fit) --------
    prof.reset()
    model_e, pw_e = build(EncodedGradientsAccumulator())
    pw_e.fit(NDArrayDataSetIterator(x[:4 * batch], y[:4 * batch],
                                    batch_size=batch), epochs=1,
             batch_size=batch)
    float(model_e._score_dev)
    enc = prof.collective_stats()
    if not (enc.get("encoded_steps") and enc.get("encoded_elems_total")
            and "encoded_density" in enc and enc.get("encoded_bytes_est")):
        fail("encoded-exchange ledger did not populate", ledger=enc)

    return {
        "metric": "zero1_smoke",
        "value": n / t_zero1,
        "unit": "images/sec",
        "batch": batch,
        "workers": workers,
        "platform": jax.devices()[0].platform,
        "traces": warm["zero1"],
        "parity": "exact",
        "parity_steps_compared": len(seqs["dense"]),
        "step_time_ratio_zero1_vs_dense": round(1.0 + regression, 4),
        "epoch_s_dense_median": round(t_dense, 4),
        "epoch_s_zero1_median": round(t_zero1, 4),
        "updater_state_bytes_dense": dense_upd_bytes,
        "updater_state_bytes_per_replica": per_replica,
        "collective_ledger": {k: (round(v, 5) if isinstance(v, float)
                                  else v)
                              for k, v in coll_ledger.items()},
        "encoded_ledger": {k: (round(v, 5) if isinstance(v, float) else v)
                           for k, v in enc.items()},
        "data": "synthetic LeNet batches; dense vs ZeRO-1 sharded-updater "
                "epochs interleaved, bitwise parity enforced",
    }


def bench_mfu_smoke(steps: int, batch: int = 64) -> dict:
    """CPU-friendly smoke of the in-graph MFU tier (ISSUE 8): the
    flagship LeNet config with an Adam updater trained three ways —
    per-leaf fp32 baseline (A), fused flat-bucket update (B), fused +
    bf16 updater state with stochastic rounding (C) — interleaved A/B
    timing, same estimator as zero1-smoke. Self-validating hard-fails:

    - fused fp32 kernel not BITWISE-identical to the per-leaf reference
      at the kernel level (fused_apply vs updater.apply on the warmed
      model's real param/grad trees, production mode);
    - fit-level fused fp32 params drifting past the documented ulp bound
      (4e-6 — XLA's fma contraction on the flat shape, nothing more;
      measured 0.6-2.0e-6 on CPU across step counts, and bitwise-stable
      against the flat-backward epilogue);
    - bf16-state parity outside the documented envelope
      (|Δ| <= 1e-3 + 0.05*|ref| per step loss and final params);
    - updater-state footprint above 0.55x fp32 (the halving is the
      point: moments are the whole Adam state);
    - any retrace delta between configs, or any retrace inside a timed
      window;
    - step-time regression (ratio of min-over-interleaved-rounds — the
      additive-noise-robust estimator): fused fp32 > 12% over base on
      CPU (quiet-box truth is +1-3%; shared runners resolve no finer
      than ~±10%, and the budget still catches an accidental per-leaf
      fallback), fused+bf16 > 20% on CPU (adds the software-threefry SR
      draws); both 5% on TPU where timing is clean and the PRNG is
      hardware;
    - fused epilogue: inference parity break vs the dense ops on a
      residual BN block, or an empty precision ledger.

    Emits the precision ledger alongside the timing."""
    import statistics as _stats

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import NDArrayDataSetIterator
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.learning.precision import updater_state_bytes
    from deeplearning4j_tpu.ndarray.rng import set_default_seed
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.ops import pallas_update
    from deeplearning4j_tpu.optimize.listeners import (
        CollectScoresIterationListener)
    from deeplearning4j_tpu.parallel import Zero1Plan

    def fail(msg, **extra):
        print(json.dumps({"error": msg, **extra}))
        sys.exit(1)

    rng = np.random.RandomState(0)
    n = steps * batch
    x = rng.randn(n, 1, 28, 28).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)]

    def make_it():
        return NDArrayDataSetIterator(x, y, batch_size=batch)

    def build(fused: bool, state_dtype):
        set_default_seed(99)
        upd = Adam(learning_rate=1e-3)
        upd.state_dtype = state_dtype
        b = (NeuralNetConfiguration.builder().seed(123).updater(upd)
             .activation("relu").weight_init("xavier"))
        if fused:
            b = b.fused_update()
        conf = (b.list()
                .layer(L.ConvolutionLayer(n_out=20, kernel_size=(5, 5)))
                .layer(L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
                .layer(L.ConvolutionLayer(n_out=50, kernel_size=(5, 5)))
                .layer(L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
                .layer(L.DenseLayer(n_out=500))
                .layer(L.OutputLayer(n_out=10, loss="mcxent",
                                     activation="softmax"))
                .set_input_type(InputType.convolutional(28, 28, 1))
                .build())
        return MultiLayerNetwork(conf).init()

    prof = OpProfiler.get()
    configs = {"base": (False, None), "fused": (True, None),
               "fused16": (True, "bfloat16")}
    models, seqs, warm = {}, {}, {}
    for name, (fused, sd) in configs.items():
        m = build(fused, sd)
        scores = CollectScoresIterationListener()
        m.set_listeners(scores)
        prof.reset()
        m.fit(make_it(), epochs=1, batch_size=batch)
        float(m._score_dev)
        warm[name] = prof.trace_counts()
        seqs[name] = [s for _, s in scores.scores]
        models[name] = m

    # the warm fits' trace-time precision counters (reset below wipes
    # them before the timed windows)
    fit_ledger = prof.precision_stats()

    # --- gate 1: kernel-level bitwise (production mode, real trees) ----
    base = models["base"]
    params = jax.tree.map(jnp.asarray, jax.device_get(base._params))
    grads = jax.tree.map(
        lambda p: (jax.random.normal(jax.random.PRNGKey(7), p.shape)
                   * 0.01).astype(p.dtype), params)
    upd = Adam(learning_rate=1e-3)
    state = upd.init(params)
    ref_p, ref_s = upd.apply(grads, state, params, 5)
    plan = Zero1Plan(params, 1)
    # the bitwise invariant is mode-local to "xla" (pallas_update doc:
    # the kernel's own compile may fma-contract, ulp-bounded) — pin the
    # mode so the gate cannot flake on TPU where default is "pallas"
    nf, ns = pallas_update.fused_apply(
        upd, plan.flatten(params), plan.flatten(grads),
        plan.flatten_state(state, xp=jnp), 5, None, mode="xla")
    got_p = plan.unflatten(nf)
    got_s = {k: plan.unflatten(v, xp=jnp) for k, v in ns.items()}
    for a, b in zip(jax.tree.leaves(jax.device_get((ref_p, ref_s))),
                    jax.tree.leaves(jax.device_get((got_p, got_s)))):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            fail("fused fp32 kernel (mode=xla) is not bitwise-identical "
                 "to the per-leaf reference")

    # --- gate 2: fit-level parity envelopes ----------------------------
    for a, b in zip(jax.tree.leaves(jax.device_get(base._params)),
                    jax.tree.leaves(jax.device_get(
                        models["fused"]._params))):
        d = float(np.max(np.abs(a - b)))
        # measured envelope on this config: 0.6-2.0e-6 across step
        # counts (the drift is XLA fma-contracting Adam's flat-shape
        # update differently — it wanders, it does not compound; the
        # flat-backward epilogue is BITWISE vs the legacy fused step,
        # gated in remat-smoke). 4e-6 is 2x the measured worst case.
        if d > 4e-6:
            fail(f"fused fp32 fit-level param drift {d:.2e} exceeds the "
                 "documented 4e-6 ulp bound")
    for s_a, s_c in zip(seqs["base"], seqs["fused16"]):
        if abs(s_a - s_c) > 1e-3 + 0.05 * abs(s_a):
            fail("bf16-state loss parity outside the documented envelope",
                 base=s_a, fused16=s_c)
    for a, c in zip(jax.tree.leaves(jax.device_get(base._params)),
                    jax.tree.leaves(jax.device_get(
                        models["fused16"]._params))):
        d = float(np.max(np.abs(a - c)))
        # param trajectories accumulate zero-mean rounding noise and
        # wander apart chaotically — the per-step loss envelope above is
        # the numerics gate; this one only catches gross divergence
        if d > 0.01 + 0.1 * float(np.max(np.abs(a))):
            fail(f"bf16-state param divergence {d:.2e} is gross, not "
                 "rounding noise")

    # --- gate 3: compile footprint + state bytes -----------------------
    if not (warm["base"] == warm["fused"] == warm["fused16"]):
        fail("retrace delta between configs", traces=warm)
    bytes_a = updater_state_bytes(jax.device_get(base._updater_state))
    bytes_c = updater_state_bytes(
        jax.device_get(models["fused16"]._updater_state))
    if bytes_c["total"] > 0.55 * bytes_a["total"]:
        fail("bf16 updater-state footprint above 0.55x fp32",
             fp32_bytes=bytes_a["total"], bf16_bytes=bytes_c["total"])
    # (the flat-backward gauge gate is gone with the single-device bucket
    # path, PR 27: `fused` now compiles the same tree step as `base`;
    # ROADMAP.md D5 deletes this smoke)

    # --- gate 4: interleaved A/B step time -----------------------------
    # Two budgets: the FUSION must be free (fused fp32 vs base ≤5% —
    # measured ~+1% CPU), while the bf16-state config additionally pays
    # the stochastic-rounding draws (one threefry block per parameter
    # element per step — ~10% on CPU; on the TPU it is vector-unit work
    # too, PERF.md §6 PR 33) → ≤20% CPU budget, and its real
    # win (0.5x state bytes) is gated above.
    def timed_epoch(name):
        t0 = time.perf_counter()
        models[name].fit(make_it(), epochs=1, batch_size=batch)
        float(models[name]._score_dev)
        return time.perf_counter() - t0

    for name in ("fused16", "fused", "base"):     # settle round, untimed
        timed_epoch(name)
    prof.reset()
    times = {name: [] for name in configs}
    for r in range(10):
        order = (("fused16", "fused", "base") if r % 2 == 0
                 else ("base", "fused", "fused16"))
        for name in order:
            times[name].append(timed_epoch(name))
    hot = prof.trace_counts()
    if any(hot.values()):
        fail("train step retraced inside a timed window", traces=hot)
    t_base = _stats.median(times["base"])
    t_fused = _stats.median(times["fused16"])
    # build boxes carry bursty background load (2x per-epoch swings
    # observed); that noise is strictly ADDITIVE, so the min over rounds
    # is the unloaded estimate — gate on min ratios, report medians
    reg_fused = min(times["fused"]) / min(times["base"]) - 1.0
    reg_16 = min(times["fused16"]) / min(times["base"]) - 1.0
    # CPU budget calibration: quiet-box truth is fused ~+1-3%, but shared
    # build runners resolve no finer than ~±10% even with min-over-rounds
    # (measured: the same config's rounds spread 2x under load bursts).
    # The budgets below catch gross regressions (an accidental per-leaf
    # fallback roughly doubles update cost); the sharp gates in this
    # smoke are parity / footprint / retrace. On TPU the timing floor is
    # clean — hold both paths to 5%.
    on_cpu = jax.devices()[0].platform == "cpu"
    budget_fused = 0.12 if on_cpu else 0.05
    if reg_fused > budget_fused:
        fail(f"fused-update step-time regression {reg_fused:.1%} exceeds "
             f"the {budget_fused:.0%} budget",
             **{f"{k}_times": [round(t, 4) for t in v]
                for k, v in times.items()})
    budget_16 = 0.20 if on_cpu else 0.05
    if reg_16 > budget_16:
        fail(f"fused+bf16 step-time regression {reg_16:.1%} exceeds the "
             f"{budget_16:.0%} budget (SR draws included)",
             **{f"{k}_times": [round(t, 4) for t in v]
                for k, v in times.items()})

    # --- gate 5: fused epilogue (inference tier) -----------------------
    prof.reset()
    from deeplearning4j_tpu.ops import pallas_epilogue
    from deeplearning4j_tpu.ops.registry import get_op

    erng = np.random.default_rng(3)
    ex = jnp.asarray(erng.normal(size=(4, 256, 7, 7)), jnp.float32)
    em = jnp.asarray(erng.normal(size=256), jnp.float32)
    ev = jnp.asarray(erng.uniform(0.5, 2.0, size=256), jnp.float32)
    eg = jnp.asarray(erng.normal(size=256), jnp.float32)
    eb = jnp.asarray(erng.normal(size=256), jnp.float32)
    eres = jnp.asarray(erng.normal(size=(4, 256, 7, 7)), jnp.float32)
    fused_out = pallas_epilogue.bn_act(ex, em, ev, eg, eb, axis=1,
                                       act="relu", residual=eres)
    dense_out = jnp.maximum(get_op("batchnorm").fn(
        ex, em, ev, eg, eb, axis=1) + eres, 0)
    if fused_out is None or not np.allclose(
            np.asarray(fused_out), np.asarray(dense_out),
            rtol=1e-5, atol=1e-5):
        fail("fused epilogue parity break vs dense ops")
    pstats = prof.precision_stats()
    if not pstats.get("epilogue_hits"):
        fail("precision ledger empty after epilogue run", ledger=pstats)

    return {
        "metric": "mfu_smoke",
        "value": n / t_fused,
        "unit": "images/sec",
        "batch": batch,
        "platform": jax.devices()[0].platform,
        "traces": warm["fused16"],
        "kernel_parity": "bitwise",
        "fit_parity_fp32": "<=4e-6",
        "bf16_envelope": "|d| <= 1e-3 + 0.05|ref|",
        "parity_steps_compared": len(seqs["base"]),
        "step_time_ratio_fused_vs_base": round(1.0 + reg_fused, 4),
        "step_time_ratio_fused16_vs_base": round(1.0 + reg_16, 4),
        "epoch_s_base_median": round(t_base, 4),
        "epoch_s_fused16_median": round(t_fused, 4),
        "updater_state_bytes_fp32": bytes_a["total"],
        "updater_state_bytes_bf16": bytes_c["total"],
        "state_bytes_ratio": round(bytes_c["total"] / bytes_a["total"], 4),
        "precision_ledger": {k: (round(v, 5) if isinstance(v, float)
                                 else v)
                             for k, v in {**fit_ledger, **pstats}.items()},
        "data": "synthetic LeNet batches; per-leaf fp32 vs fused vs "
                "fused+bf16-state epochs interleaved",
    }


def bench_remat_smoke(steps: int, batch: int = 64) -> dict:
    """CPU-friendly smoke of policy-driven rematerialization + the
    flat-backward fused epilogue (ISSUE 16): a dense stack with a fused
    Adam updater trained five ways — remat policy none (A), dots_only
    (B), full (C), a selective block list (D), all on the flat-backward
    epilogue, plus the legacy dense-grads-then-flatten step (E,
    flat_backward=False) — interleaved A/B timing with the
    min-over-rounds estimator every overhead smoke shares.
    Self-validating hard-fails:

    - any remat policy NOT bitwise-identical to "none" (loss sequence
      AND final params — remat replays the same ops in the same order;
      on CPU there is no fma excuse);
    - flat-backward vs legacy params/updater-state not bitwise (the
      flat cotangent is the EXACT concatenation of the dense leaf
      cotangents via Zero1Plan.unflatten_diff — drift means the adjoint
      is wrong);
    - any retrace delta between configs, a policy flip that costs more
      than exactly ONE retrace, or any retrace inside the timed
      steady-state windows;
    - flat-backward step time > 12% over legacy on CPU (same budget as
      mfu-smoke's fused-vs-base: shared runners resolve no finer), 5%
      on TPU;
    - ON TPU ONLY: dots_only temp bytes not strictly below none (the
      HBM-watermark claim). The CPU scheduler shows the INVERSE (its
      remat graph allocates MORE temp — the same documented property
      test_l6_features and test_remat_policies gate on), so on CPU the
      per-policy temp bytes are REPORTED, never gated.

    Emits per-policy temp bytes + step times alongside the timing."""
    import statistics as _stats

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.common import tracecheck
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import NDArrayDataSetIterator
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.ndarray.rng import set_default_seed
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.optimize.listeners import (
        CollectScoresIterationListener)

    def fail(msg, **extra):
        print(json.dumps({"error": msg, **extra}))
        sys.exit(1)

    rng = np.random.RandomState(0)
    n = steps * batch
    D, DEPTH = 128, 6
    x = rng.randn(n, D).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)]

    def make_it():
        return NDArrayDataSetIterator(x, y, batch_size=batch)

    def build(policy, flat_backward=True):
        set_default_seed(77)
        b = (NeuralNetConfiguration.builder().seed(55)
             .updater(Adam(learning_rate=1e-3)).fused_update()
             .activation("relu").weight_init("xavier"))
        if policy is not None:
            b = b.remat_policy(policy)
        lb = b.list()
        for _ in range(DEPTH):
            lb = lb.layer(L.DenseLayer(n_out=D))
        conf = (lb.layer(L.OutputLayer(n_out=10, loss="mcxent",
                                       activation="softmax"))
                .set_input_type(InputType.feed_forward(D)).build())
        conf.global_conf.flat_backward = flat_backward
        return MultiLayerNetwork(conf).init()

    prof = OpProfiler.get()
    configs = {"none": (None, True), "dots_only": ("dots_only", True),
               "full": ("full", True), "selective": ([1, 3, 5], True),
               "legacy": (None, False)}
    models, seqs, warm, ledger = {}, {}, {}, {}
    for name, (pol, fb) in configs.items():
        m = build(pol, flat_backward=fb)
        scores = CollectScoresIterationListener()
        m.set_listeners(scores)
        prof.reset()
        m.fit(make_it(), epochs=1, batch_size=batch)
        float(m._score_dev)
        warm[name] = prof.trace_counts()
        ledger[name] = prof.precision_stats()
        seqs[name] = [s for _, s in scores.scores]
        models[name] = m

    def bitwise(a, b):
        la, lb = jax.tree.leaves(jax.device_get(a)), jax.tree.leaves(
            jax.device_get(b))
        return len(la) == len(lb) and all(
            np.array_equal(np.asarray(p), np.asarray(q))
            for p, q in zip(la, lb))

    # --- gate 1: remat policies are numerically free -------------------
    for name in ("dots_only", "full", "selective"):
        if seqs[name] != seqs["none"]:
            fail(f"remat policy {name!r} loss sequence is not bitwise-"
                 "identical to none", steps_compared=len(seqs["none"]))
        if not bitwise(models[name]._params, models["none"]._params):
            fail(f"remat policy {name!r} final params drifted from none")

    # --- gate 2: flat-backward epilogue vs legacy is bitwise -----------
    if seqs["legacy"] != seqs["none"]:
        fail("flat-backward loss sequence is not bitwise-identical to "
             "the legacy dense-grads step")
    if not bitwise(models["legacy"]._params, models["none"]._params):
        fail("flat-backward final params drifted from the legacy step")
    if not bitwise(models["legacy"]._updater_state,
                   models["none"]._updater_state):
        fail("flat-backward updater state drifted from the legacy step")
    # (no gauge gate: since PR 27 both legs compile the tree step — the
    # flat_backward axis lives under ZeRO-1 only, tests/test_remat_policies)

    # --- gate 3: retrace accounting ------------------------------------
    if len({tuple(sorted(w.items())) for w in warm.values()}) != 1:
        fail("retrace delta between configs", traces=warm)
    # the flip drill: switching policy in place costs exactly ONE
    # retrace, then the loop is steady again
    flip = models["none"]
    prof.reset()
    flip.set_remat_policy("dots_only")
    flip.fit(make_it(), epochs=1, batch_size=batch)
    float(flip._score_dev)
    flips = prof.trace_counts()
    if sum(flips.values()) != 1:
        fail("policy flip cost more than one retrace", traces=flips)
    with tracecheck.steady_state("remat-smoke post-flip refit",
                                 max_host_syncs=None):
        flip.fit(make_it(), epochs=1, batch_size=batch)
        float(flip._score_dev)
    flip.set_remat_policy(None)         # restore for the timed rounds
    flip.fit(make_it(), epochs=1, batch_size=batch)
    float(flip._score_dev)

    # --- gate 4: per-policy temp bytes (platform-aware) ----------------
    # XLA's own memory accounting of the compiled grad step. TPU gates
    # the watermark claim; the CPU scheduler's remat graph allocates
    # MORE temp (documented inverse), so CPU reports without gating.
    xb = jnp.asarray(x[:batch])
    yb = jnp.asarray(y[:batch])
    key = jax.random.PRNGKey(0)

    def temp_bytes(name):
        m = models[name]

        def loss_fn(params):
            loss, _ = m._loss(params, m._states, xb, yb, None, True, key)
            return loss

        comp = jax.jit(jax.grad(loss_fn)).lower(m._params).compile()
        return int(comp.memory_analysis().temp_size_in_bytes)

    temps = {name: temp_bytes(name)
             for name in ("none", "dots_only", "full")}
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu and temps["dots_only"] >= temps["none"]:
        fail("dots_only temp bytes not below none on TPU", temps=temps)

    # --- gate 5: interleaved A/B step time -----------------------------
    def timed_epoch(name):
        t0 = time.perf_counter()
        models[name].fit(make_it(), epochs=1, batch_size=batch)
        float(models[name]._score_dev)
        return time.perf_counter() - t0

    order_fwd = tuple(configs)
    for name in order_fwd:                        # settle round, untimed
        timed_epoch(name)
    prof.reset()
    times = {name: [] for name in configs}
    with tracecheck.steady_state("remat-smoke timed rounds",
                                 max_host_syncs=None):
        for r in range(10):
            for name in (order_fwd if r % 2 == 0
                         else tuple(reversed(order_fwd))):
                times[name].append(timed_epoch(name))
    hot = prof.trace_counts()
    if any(hot.values()):
        fail("train step retraced inside a timed window", traces=hot)
    # build boxes carry bursty ADDITIVE noise — min over rounds is the
    # unloaded estimate (the estimator every overhead smoke shares)
    reg_flat = min(times["none"]) / min(times["legacy"]) - 1.0
    on_cpu = jax.devices()[0].platform == "cpu"
    budget = 0.12 if on_cpu else 0.05
    if reg_flat > budget:
        fail(f"flat-backward step-time regression {reg_flat:.1%} "
             f"exceeds the {budget:.0%} budget vs the legacy step",
             **{f"{k}_times": [round(t, 4) for t in v]
                for k, v in times.items()})

    t_none = _stats.median(times["none"])
    return {
        "metric": "remat_smoke",
        "value": n / t_none,
        "unit": "images/sec",
        "batch": batch,
        "platform": jax.devices()[0].platform,
        "traces": warm["none"],
        "policy_parity": "bitwise",
        "flat_vs_legacy_parity": "bitwise",
        "parity_steps_compared": len(seqs["none"]),
        "grads_flat_in_step": ledger["none"].get("grads_flat_in_step"),
        "step_time_ratio_flat_vs_legacy": round(1.0 + reg_flat, 4),
        "temp_bytes": temps,
        "temp_bytes_gated": on_tpu,
        "epoch_s_none_median": round(t_none, 4),
        "epoch_s_dots_only_median": round(
            _stats.median(times["dots_only"]), 4),
        "epoch_s_full_median": round(_stats.median(times["full"]), 4),
        "epoch_s_legacy_median": round(_stats.median(times["legacy"]), 4),
        "data": "synthetic dense-stack batches; remat none/dots_only/"
                "full/selective + legacy dense-grad epochs interleaved",
    }


def bench_elastic_smoke(steps: int, batch: int = 64, workers: int = 4) -> dict:
    """CPU-friendly smoke of ONLINE elastic resize (ISSUE 6; ROADMAP item
    4(b)): the flagship LeNet config through ParallelWrapper with the
    ZeRO-1 accumulator, a deterministic ``device/loss`` fault mid-epoch,
    shrink-and-continue in memory, then interleaved A/B epochs at N and
    N-1 workers through the per-worker-count executable cache.
    Self-validating hard-fails:

    - parity break: the shrunk continuation's final params/updater state
      must be BITWISE-equal to a fresh (N-1)-worker run handed the same
      host-materialized state, pipeline cursor and RNG stream (the
      resharding is a pure permutation — same guarantee as checkpoint
      resharding, no disk involved);
    - retrace: the whole elastic cycle (kill -> shrink -> continue) must
      compile exactly once per worker count, and the interleaved timed
      rounds (6 x resize N <-> N-1) must trigger ZERO further traces —
      any retrace beyond one-recompile-per-worker-count fails;
    - throughput: the post-shrink epoch must sustain at least
      0.9 x (N-1)/N of the pre-shrink throughput (median of interleaved
      rounds — losing a replica may cost its share of the axis, but the
      resize itself must not tax the steady state);
    - the ``elastic/*`` ledger (resize counts, worker gauge) must
      populate — the /api/health section the drill is monitored by.

    Emits the elastic ledger alongside the timing."""
    import statistics as _stats

    # a multi-replica mesh is the whole point: on single-device hosts
    # (CPU build machines) request virtual CPU devices BEFORE jax loads
    if "jax" not in sys.modules:
        _flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in _flags:
            os.environ["XLA_FLAGS"] = (
                _flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.common import faultinject
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import NDArrayDataSetIterator
    from deeplearning4j_tpu.ndarray.rng import get_random, set_default_seed
    from deeplearning4j_tpu.parallel import (ParallelWrapper,
                                             ReduceScatterAccumulator)

    def fail(msg, **extra):
        print(json.dumps({"error": msg, **extra}))
        sys.exit(1)

    workers = min(workers, len(jax.devices()))
    if workers < 2:
        fail("elastic-smoke needs >= 2 devices (virtual CPU device request "
             "came too late — is jax initialized before bench dispatch?)",
             devices=len(jax.devices()))
    rng_np = np.random.RandomState(0)
    n = steps * batch
    x = rng_np.randn(n, 1, 28, 28).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng_np.randint(0, 10, n)]

    def make_it():
        return NDArrayDataSetIterator(x, y, batch_size=batch)

    def build(n_workers):
        set_default_seed(99)
        model = _lenet_model()
        pw = (ParallelWrapper.Builder(model).workers(n_workers)
              .gradients_accumulator(ReduceScatterAccumulator()).build())
        return model, pw

    def host_state(model):
        # owning copies — the same moves resize() makes internally
        return jax.tree.map(np.array, jax.device_get(
            (model._params, model._states, model._updater_state,
             getattr(model, "_acc_state", None) or None)))

    prof = OpProfiler.get()
    prof.reset()
    faultinject.clear_plan()

    # --- elastic run: N workers, device loss mid epoch 2, shrink -------
    m1, pw = build(workers)
    kill_at = steps + max(1, steps // 2)          # mid epoch 2 of 2
    faultinject.set_plan(faultinject.FaultPlan(
        [{"site": "device/loss", "index": kill_at, "kind": "device_loss",
          "replica": 1}]))
    try:
        pw.fit(make_it(), epochs=2, batch_size=batch)
        fail("device/loss fault plan did not fire", kill_at=kill_at)
    except faultinject.DeviceLostError:
        pass
    faultinject.clear_plan()
    cursor = (int(m1._epoch - m1._fit_epoch0), int(m1._steps_in_epoch))
    snap = host_state(m1)
    it_ep = (m1._iteration, m1._epoch)
    rng_state = get_random().get_state()
    removed = pw.resize(workers - 1, lost_replicas=[1])
    if len(removed) != 1:
        fail("shrink did not remove exactly the lost device",
             removed=len(removed))
    pw.fit(make_it(), epochs=2, batch_size=batch, resume_cursor=cursor)
    float(m1._score_dev)
    traces = prof.trace_counts()
    if traces.get("trace/pw_fit_step") != 2:
        fail("elastic cycle broke one-compile-per-worker-count",
             traces=traces)

    # --- reference: fresh (N-1)-worker run from the same state ---------
    set_default_seed(99)
    m2 = _lenet_model()
    params, states, upd, acc = snap
    m2._params = jax.tree.map(jnp.array, params)
    m2._states = jax.tree.map(jnp.array, states)
    m2._updater_state = upd                 # flat: reshards on placement
    m2._acc_state = acc
    m2._iteration, m2._epoch = it_ep
    get_random().set_state(rng_state)
    pw2 = (ParallelWrapper.Builder(m2).workers(workers - 1)
           .gradients_accumulator(ReduceScatterAccumulator()).build())
    pw2.fit(make_it(), epochs=2, batch_size=batch, resume_cursor=cursor)
    float(m2._score_dev)
    for name, a, b in (("params", m1._params, m2._params),
                       ("updater state", m1._updater_state,
                        m2._updater_state)):
        la = jax.tree.leaves(jax.device_get(a))
        lb = jax.tree.leaves(jax.device_get(b))
        if len(la) != len(lb) or not all(
                np.array_equal(np.asarray(p), np.asarray(q))
                for p, q in zip(la, lb)):
            fail(f"elastic parity break: post-shrink {name} differ from a "
                 "fresh run resharded at the same step")

    # --- interleaved A/B throughput via cached executables -------------
    def timed_epoch():
        t0 = time.perf_counter()
        pw.fit(make_it(), epochs=1, batch_size=batch)
        float(m1._score_dev)
        return time.perf_counter() - t0

    pw.resize(workers)                       # grow back: cached, no compile
    timed_epoch()
    pw.resize(workers - 1)
    timed_epoch()                            # settle rounds, untimed
    prof.reset()
    times = {"pre": [], "post": []}
    ratios = []
    for r in range(6):
        pw.resize(workers)
        t_pre = timed_epoch()
        pw.resize(workers - 1)
        t_post = timed_epoch()
        times["pre"].append(t_pre)
        times["post"].append(t_post)
        ratios.append(t_pre / t_post)        # = post/pre throughput ratio
    hot = prof.trace_counts()
    if any(hot.values()):
        fail("resize retraced inside a timed window (executable cache "
             "miss)", traces=hot)
    floor = 0.9 * (workers - 1) / workers
    ratio = _stats.median(ratios)
    if ratio < floor:
        fail(f"post-shrink throughput ratio {ratio:.3f} is below the "
             f"0.9 x (N-1)/N floor {floor:.3f}",
             pre_times=[round(t, 4) for t in times["pre"]],
             post_times=[round(t, 4) for t in times["post"]])
    ledger = prof.elastic_stats()
    if not ledger.get("resizes") or "workers" not in ledger:
        fail("elastic ledger did not populate", ledger=ledger)

    t_pre = _stats.median(times["pre"])
    t_post = _stats.median(times["post"])
    return {
        "metric": "elastic_smoke",
        "value": n / t_post,
        "unit": "images/sec",
        "batch": batch,
        "workers_pre": workers,
        "workers_post": workers - 1,
        "platform": jax.devices()[0].platform,
        "parity": "exact",
        "shrink_cursor": list(cursor),
        "traces": traces,
        "throughput_ratio_post_vs_pre": round(ratio, 4),
        "throughput_floor": round(floor, 4),
        "epoch_s_pre_median": round(t_pre, 4),
        "epoch_s_post_median": round(t_post, 4),
        "elastic_ledger": {k: (round(v, 5) if isinstance(v, float) else v)
                           for k, v in ledger.items()},
        "data": "synthetic LeNet batches; mid-epoch device/loss shrink "
                "N->N-1 with bitwise parity vs a fresh (N-1)-worker run "
                "from the same state, then interleaved N/(N-1) epochs "
                "through the per-worker-count executable cache",
    }


_CLUSTER_TRAINER = r"""
import io, json, os, sys, time
import numpy as np
from deeplearning4j_tpu.parallel import cluster
from deeplearning4j_tpu.parallel.sharding import Zero1Plan
from deeplearning4j_tpu.util import checkpoint as ckpt

(cluster_dir, ckpt_dir, log_path, rank, world, total_iters, crash_rank,
 crash_iter) = (sys.argv[1], sys.argv[2], sys.argv[3], int(sys.argv[4]),
                int(sys.argv[5]), int(sys.argv[6]), int(sys.argv[7]),
                int(sys.argv[8]))
att = os.environ.get("DL4J_ATTEMPT", "0")
N = 25   # odd on purpose: padding differs across worker counts

rt = cluster.ClusterRuntime(cluster_dir, rank, world,
                            heartbeat_interval_s=0.05,
                            incarnation=int(att))
rt.form()
rt.dump_rank_blackbox()
plan = Zero1Plan({"w": np.zeros(N, np.float32)}, world)
bucket = plan.buckets[0]
key, shard, padded = bucket.key, bucket.shard, bucket.padded
lo, hi = rank * shard, (rank + 1) * shard

params = np.linspace(-1.0, 1.0, N).astype(np.float32)
m = np.zeros(padded, np.float32)
start_it = 0
last = ckpt.last_checkpoint(ckpt_dir) if os.path.isdir(ckpt_dir) else None
if last is not None:
    with np.load(last) as z:
        params = z["params"]
        start_it = int(z["iteration"])
        stored = {"m": {key: z["m"]}}
    # the group checkpoint's flat layout is replica-count independent:
    # a relaunch at ANY world size reshards the stored padding to its own
    m = np.asarray(plan.reshard_state(stored)["m"][key])
if rank == 0:
    os.makedirs(ckpt_dir, exist_ok=True)
    rt.claim_commit_incarnation(ckpt_dir)

for it in range(start_it + 1, total_iters + 1):
    gp = np.zeros(padded, np.float32)
    gp[:N] = np.float32(0.05) * params + np.float32(0.001) * np.float32(it)
    m[lo:hi] = np.float32(0.9) * m[lo:hi] + gp[lo:hi]   # OWN shard only
    np.save(os.path.join(cluster_dir, f"m-a{att}-{it}.r{rank}.npy"),
            m[lo:hi])
    rt.barrier(f"step-a{att}", gen=it, deadline_s=30.0)
    m = np.concatenate([
        np.load(os.path.join(cluster_dir, f"m-a{att}-{it}.r{r}.npy"))
        for r in range(world)])
    params = params - (np.float32(0.1) * m)[:N]
    if rank == 0:
        with open(log_path, "a") as f:
            f.write(json.dumps({"iteration": it,
                                "loss": float(np.sum(params))}) + "\n")
    if it % 3 == 0:
        buf = io.BytesIO()
        np.savez(buf, params=params, m=m, iteration=np.int64(it))
        rt.commit_group_checkpoint(ckpt_dir, f"it{it}", buf.getvalue(),
                                   it, seq=it, barrier_deadline_s=30.0)
    if att == "0" and rank == crash_rank and it == crash_iter:
        rt.dump_rank_blackbox()   # the dying rank's last words
        os._exit(1)
"""

_CLUSTER_DEAD_COORD = r"""
import json, sys, time
from deeplearning4j_tpu.parallel import cluster

cluster_dir, port = sys.argv[1], sys.argv[2]
rt = cluster.ClusterRuntime(cluster_dir, 1, 2,
                            coordinator=f"127.0.0.1:{port}",
                            init_deadline_s=4.0,
                            init_backoff_base_s=0.1,
                            init_backoff_max_s=0.5)
t0 = time.monotonic()
try:
    rt.form()
except cluster.ClusterInitError as e:
    rt.shutdown()
    print(json.dumps({"failed": True,
                      "elapsed_s": round(time.monotonic() - t0, 2),
                      "attempts": e.attempts, "coordinator": e.coordinator,
                      "reported": e.reported_ranks, "msg": str(e)}))
    sys.exit(0)
print(json.dumps({"failed": False}))
sys.exit(1)
"""


def bench_cluster_smoke(steps: int, workers: int = 3) -> dict:
    """Hardened cluster-runtime smoke (ISSUE 18): real OS processes
    through ``ClusterRuntime`` + elastic ``supervise_processes``.
    Self-validating hard-fails:

    - kill-a-rank-mid-epoch (full-count restart): the relaunched group
      must resume from the group checkpoint BIT-exactly vs a fresh
      uninterrupted N-world run, with exactly ONE finalized watchtower
      incident whose chain cause is ``cluster/rank_lost`` naming the
      killed rank and carrying the merged per-rank blackboxes;
    - shrink-to-survivors: the same drill relaunched at N-1 ranks,
      resharding the group checkpoint through ``Zero1Plan``'s
      replica-count-independent layout, bit-exact vs a fresh (N-1) run;
    - barrier timeout names the missing rank WITH its heartbeat
      staleness;
    - bring-up against a dead coordinator fails INSIDE the init
      deadline with the full diagnosis (address, attempts, ranks that
      reported) instead of jax's C++ ``abort()``;
    - zero orphan processes after every drill (process-table sweep for
      this run's unique workdir token)."""
    import shutil
    import socket
    import subprocess
    import tempfile

    import jax

    from deeplearning4j_tpu.common import faultinject, watchtower
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.parallel import cluster
    from deeplearning4j_tpu.parallel.distributed import supervise_processes

    def fail(msg, **extra):
        print(json.dumps({"error": msg, **extra}))
        sys.exit(1)

    repo = os.path.dirname(os.path.abspath(__file__))
    env = {"PYTHONPATH": repo + (
        os.pathsep + os.environ["PYTHONPATH"]
        if os.environ.get("PYTHONPATH") else ""),
        "JAX_PLATFORMS": "cpu"}
    total_iters = max(9, min(30, steps))
    crash_iter = total_iters // 2 + 1
    prof = OpProfiler.get()
    faultinject.clear_plan()
    work = tempfile.mkdtemp(prefix="dl4j_cluster_smoke_")
    script = os.path.join(work, "trainer.py")
    with open(script, "w") as f:
        f.write(_CLUSTER_TRAINER)

    def read_log(path):
        with open(path) as f:
            rows = [json.loads(l) for l in f.read().splitlines()]
        return {r["iteration"]: r["loss"] for r in rows}

    def run_fresh(tag, world):
        """An uninterrupted baseline group run."""
        cd = os.path.join(work, f"{tag}-cd")
        log = os.path.join(work, f"{tag}.jsonl")
        procs = [subprocess.Popen(
            [sys.executable, script, cd, os.path.join(work, f"{tag}-ck"),
             log, str(r), str(world), str(total_iters), "-1", "-1"],
            env={**os.environ, **env}) for r in range(world)]
        for r, p in enumerate(procs):
            if p.wait(timeout=120) != 0:
                fail(f"baseline {tag} rank {r} failed", rc=p.returncode)
        return read_log(log)

    def run_drill(tag, world, crash_rank, shrink):
        """Kill-a-rank-mid-epoch under a fresh watchtower; returns
        (summary, losses, incident report)."""
        cd = os.path.join(work, f"{tag}-cd")
        ck = os.path.join(work, f"{tag}-ck")
        log = os.path.join(work, f"{tag}.jsonl")
        inc_dir = os.path.join(work, f"{tag}-inc")
        watchtower.uninstall()
        tower = watchtower.install(watchtower.Watchtower(
            [], incident_dir=inc_dir, interval_s=0.05,
            finalize_after_s=120.0))

        def make_commands(w, attempt):
            return [[sys.executable, script, cd, ck, log, str(r), str(w),
                     str(total_iters), str(crash_rank), str(crash_iter)]
                    for r in range(w)]

        summary = supervise_processes(
            make_commands(world, 0), env=env,
            make_env=lambda attempt: {"DL4J_ATTEMPT": str(attempt)},
            cluster_dir=cd, heartbeat_stale_s=15.0,
            make_commands=make_commands if shrink else None,
            shrink_to_survivors=shrink, min_world=world - 1,
            max_restarts=2, backoff_base_s=0.05, kill_grace_s=3.0,
            storm_min_uptime_s=0.0)
        if summary["status"] != "completed":
            fail(f"{tag}: supervised group did not complete",
                 summary=summary)
        if summary["restarts"] != 1 or \
                summary["history"][0]["failed_rank"] != crash_rank:
            fail(f"{tag}: expected exactly one restart for rank "
                 f"{crash_rank}", summary=summary)
        tower.evaluate_now()
        incs = tower.incidents()
        finalized = [i for i in incs if i.get("finalized")]
        if len(incs) != 1 or len(finalized) != 1:
            fail(f"{tag}: expected exactly one finalized incident",
                 open=len(incs), finalized=len(finalized))
        with open(finalized[0]["path"]) as f:
            report = json.load(f)
        chain = report["chain"]
        if not report["complete"] or \
                chain["cause"]["name"] != "cluster/rank_lost" or \
                chain["cause"]["attrs"].get("rank") != crash_rank:
            fail(f"{tag}: incident chain does not name the lost rank as "
                 "cause", chain=chain)
        if not report.get("attachments", {}).get("rank_blackboxes"):
            fail(f"{tag}: merged per-rank blackboxes missing from the "
                 "incident", attachments=list(report.get("attachments",
                                                         {})))
        watchtower.uninstall()
        return summary, read_log(log), report

    try:
        t0 = time.perf_counter()

        # -- drill 1: kill-a-rank, FULL-count restart, bit-exact resume
        base_n = run_fresh("base-n", workers)
        if sorted(base_n) != list(range(1, total_iters + 1)):
            fail("baseline N-world run incomplete", got=len(base_n))
        sum_full, losses_full, rep_full = run_drill(
            "full", workers, crash_rank=1, shrink=False)
        if sum_full["world"] != workers:
            fail("full-count drill changed the world size",
                 summary=sum_full)
        if losses_full != base_n:
            bad = next((i for i in sorted(base_n)
                        if losses_full.get(i) != base_n[i]), None)
            fail("full-count resume is not bit-exact vs the fresh "
                 "N-world run", first_diff_iteration=bad)

        # -- drill 2: kill-a-rank, SHRINK to survivors, bit-exact vs a
        # fresh (N-1)-world run through the resharded flat state
        base_n1 = run_fresh("base-n1", workers - 1)
        sum_shr, losses_shr, rep_shr = run_drill(
            "shrink", workers, crash_rank=workers - 1, shrink=True)
        if sum_shr["world"] != workers - 1:
            fail("shrink drill did not shrink the group",
                 summary=sum_shr)
        if losses_shr != base_n1:
            bad = next((i for i in sorted(base_n1)
                        if losses_shr.get(i) != base_n1[i]), None)
            fail("shrunk resume is not bit-exact vs the fresh (N-1) "
                 "run", first_diff_iteration=bad)

        # -- drill 3: barrier timeout names the missing rank + staleness
        bdir = os.path.join(work, "barrier-cd")
        rt = cluster.ClusterRuntime(bdir, 0, 2)
        with open(cluster.heartbeat_path(bdir, 1), "w") as f:
            json.dump({"rank": 1, "pid": 0, "incarnation": 0, "seq": 1,
                       "t_wall": time.time() - 4.0, "cadence_s": 0.25}, f)
        try:
            rt.barrier("smoke-fence", deadline_s=0.5)
            fail("barrier against a missing rank did not time out")
        except cluster.BarrierTimeout as e:
            if e.missing != [1] or not (3.0 < (e.staleness[1] or 0) < 10.0) \
                    or "stale" not in str(e):
                fail("barrier timeout diagnosis incomplete",
                     missing=e.missing, staleness=e.staleness,
                     msg=str(e))

        # -- drill 4: dead coordinator fails INSIDE the deadline with
        # the diagnosis (subprocess: jax's client would abort() us)
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
        s.close()   # nobody listens here any more
        dc = os.path.join(work, "deadcoord.py")
        with open(dc, "w") as f:
            f.write(_CLUSTER_DEAD_COORD)
        p = subprocess.run(
            [sys.executable, dc, os.path.join(work, "dead-cd"),
             str(dead_port)],
            env={**os.environ, **env}, capture_output=True, text=True,
            timeout=60)
        if p.returncode != 0:
            fail("dead-coordinator drill did not fail cleanly",
                 rc=p.returncode, err=p.stderr[-1500:])
        diag = json.loads(p.stdout.strip().splitlines()[-1])
        if not diag["failed"] or diag["elapsed_s"] > 8.0 or \
                diag["attempts"] < 2 or \
                f"127.0.0.1:{dead_port}" not in diag["msg"] or \
                "ranks that reported a heartbeat" not in diag["msg"]:
            fail("dead-coordinator diagnosis incomplete", diag=diag)

        # -- drill 5: zero orphans (process-table sweep for this run's
        # unique workdir token in any live cmdline)
        token = os.path.basename(work)
        orphans = []
        for pid in os.listdir("/proc"):
            if not pid.isdigit() or int(pid) == os.getpid():
                continue
            try:
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    if token.encode() in f.read():
                        orphans.append(int(pid))
            except OSError:
                continue
        if orphans:
            fail("orphan worker processes survived the drills",
                 pids=orphans)

        wall = time.perf_counter() - t0
        ledger = {k: prof.counter_value(k) for k in
                  ("cluster/formed", "cluster/groups_formed",
                   "cluster/barriers", "cluster/barrier_timeouts",
                   "cluster/group_commits", "cluster/rank_crash",
                   "cluster/shrinks", "supervisor/proc_restarts")}
        # supervised iterations actually retrained across both drills
        return {
            "metric": "cluster_smoke",
            "value": (2 * total_iters) / wall,
            "unit": "supervised-iters/sec",
            "platform": jax.devices()[0].platform,
            "workers": workers,
            "total_iters": total_iters,
            "crash_iter": crash_iter,
            "full_count_incident": rep_full["id"],
            "shrink_incident": rep_shr["id"],
            "dead_coordinator": {"elapsed_s": diag["elapsed_s"],
                                 "attempts": diag["attempts"]},
            "orphans": 0,
            "resume_parity": "exact",
            "cluster_ledger": ledger,
            "data": "Zero1Plan flat-state trainer over real OS process "
                    "groups; kill-a-rank mid-epoch healed full-count and "
                    "shrunk-to-survivors, bit-exact vs fresh baselines",
        }
    finally:
        watchtower.uninstall()
        faultinject.clear_plan()
        shutil.rmtree(work, ignore_errors=True)


def bench_pipeline_parallel_smoke(steps: int, batch: int = 64) -> dict:
    """Self-healing pipeline-parallel smoke (ISSUE 14; ROADMAP item 2):
    a 12-layer homogeneous dense stack through ``PipelineTrainer`` as
    4-stage 1F1B x 2-way data on the CPU mesh. Self-validating
    hard-fails:

    - bubble: the schedule-accounted bubble fraction (``pipeline``
      ledger — tick occupancy of the very mask tables the compiled step
      executes) must be <= the analytic (S-1)/(M+S-1) bound + 10%. This
      polices the SCHEDULE TABLES against the closed-form bound (a
      schedule_meta regression that pads extra ticks or drops ops
      fails it); it is not a wall-clock measurement — wall-clock
      efficiency is what the throughput gate below owns;
    - retrace flatness: the whole warmup -> kill -> remap -> grow cycle
      compiles exactly once per (stage-count, schedule), and the timed
      interleaved rounds run under ``tracecheck.steady_state`` — any
      trace/compile/host-sync hard-fails;
    - recovery: a mid-epoch ``pipeline/stage`` device_loss drill
      recovers by ``remap_and_continue`` (4 -> 3 stages) with ZERO lost
      microbatches (ledger-counted against the clean expectation) and a
      finite post-remap loss;
    - throughput: the post-remap (3-stage) epoch must sustain at least
      0.9 x (S-1)/S of the 4-stage throughput (median of interleaved
      rounds through the per-stage-count executable cache).

    Emits the pipeline ledger alongside the timing."""
    import statistics as _stats

    if "jax" not in sys.modules:
        _flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in _flags:
            os.environ["XLA_FLAGS"] = (
                _flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    from deeplearning4j_tpu.common import faultinject, tracecheck
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import NDArrayDataSetIterator
    from deeplearning4j_tpu.learning import Sgd
    from deeplearning4j_tpu.ndarray.rng import set_default_seed
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import layers as NL
    from deeplearning4j_tpu.parallel import PipelineTrainer

    def fail(msg, **extra):
        print(json.dumps({"error": msg, **extra}))
        sys.exit(1)

    S, M, D, n_layers, feat = 4, 8, 2, 12, 32
    if len(jax.devices()) < S * D:
        fail("pipeline-parallel-smoke needs >= 8 devices (virtual CPU "
             "device request came too late?)", devices=len(jax.devices()))
    if batch % (D * M):
        fail(f"batch {batch} must divide by data*micro = {D * M}")
    if steps < 2:
        fail("pipeline-parallel-smoke needs --steps >= 2 (the mid-epoch "
             "kill ordinal must land inside the drill fit)", steps=steps)
    rng_np = np.random.RandomState(0)
    n = steps * batch
    x = rng_np.randn(n, feat).astype(np.float32)
    y = np.tanh(x) * 0.5

    def make_it():
        return NDArrayDataSetIterator(x, y, batch_size=batch)

    def build(stages):
        set_default_seed(77)
        b = (NeuralNetConfiguration.builder().seed(77)
             .updater(Sgd(learning_rate=0.02)).list())
        for _ in range(n_layers):
            b.layer(NL.DenseLayer(n_out=feat, activation="tanh"))
        model = MultiLayerNetwork(
            b.set_input_type(InputType.feed_forward(feat)).build()).init()
        return model, PipelineTrainer(model, stages=stages, n_micro=M,
                                      schedule="1f1b", data=D)

    prof = OpProfiler.get()
    prof.reset()
    faultinject.clear_plan()
    model, tr = build(S)

    # --- warmup + bubble gate (4-stage 1F1B) ---------------------------
    busy0 = prof.counter_value("pipeline/busy_ticks")
    slots0 = prof.counter_value("pipeline/tick_slots")
    tr.fit(make_it(), epochs=1, batch_size=batch)
    float(np.asarray(model._score_dev))
    traces = prof.trace_counts()
    if traces.get("trace/pipeline_fit_step") != 1:
        fail("warmup epoch compiled more than once", traces=traces)
    busy = prof.counter_value("pipeline/busy_ticks") - busy0
    slots = prof.counter_value("pipeline/tick_slots") - slots0
    bubble = 1.0 - busy / slots
    bound = (S - 1) / (M + S - 1)
    if bubble > bound * 1.10:
        fail(f"measured bubble fraction {bubble:.4f} exceeds the "
             f"analytic (S-1)/(M+S-1) bound {bound:.4f} + 10%",
             bubble=bubble, bound=bound)

    # --- kill-a-stage drill: remap, zero lost microbatches -------------
    micro0 = prof.counter_value("pipeline/microbatches")
    kill_at = steps + max(1, steps // 2)       # mid epoch 2 of 2
    faultinject.set_plan(faultinject.FaultPlan(
        [{"site": "pipeline/stage", "kind": "device_loss",
          "index": kill_at, "stage": 1}]))
    try:
        tr.fit(make_it(), epochs=2, batch_size=batch)
        fail("pipeline/stage fault plan did not fire", kill_at=kill_at)
    except faultinject.DeviceLostError:
        pass
    faultinject.clear_plan()
    cursor = (int(model._epoch - model._fit_epoch0),
              int(model._steps_in_epoch))
    removed = tr.remap(S - 1, lost_stages=[1])
    if len(removed) != D:
        fail("remap did not retire exactly the lost stage column",
             removed=len(removed))
    tr.fit(make_it(), epochs=2, batch_size=batch, resume_cursor=cursor)
    drill_loss = float(np.asarray(model._score_dev))
    if not np.isfinite(drill_loss):
        fail("post-remap loss went non-finite", loss=drill_loss)
    micro_seen = prof.counter_value("pipeline/microbatches") - micro0
    if micro_seen != 2 * steps * M:
        fail("kill-a-stage drill lost microbatches",
             dispatched=micro_seen, expected=2 * steps * M)
    traces = prof.trace_counts()
    if traces.get("trace/pipeline_fit_step") != 2:
        fail("kill->remap cycle broke one-compile-per-(stage-count, "
             "schedule)", traces=traces)

    # --- interleaved A/B throughput via cached executables -------------
    def timed_epoch():
        t0 = time.perf_counter()
        tr.fit(make_it(), epochs=1, batch_size=batch)
        float(np.asarray(model._score_dev))
        return time.perf_counter() - t0

    tr.remap(S)                     # grow back: cached, no compile
    timed_epoch()
    tr.remap(S - 1)
    timed_epoch()                   # settle rounds, untimed
    times = {"pre": [], "post": []}
    ratios = []
    with tracecheck.steady_state("pipeline timed rounds",
                                 max_host_syncs=None):
        for _ in range(6):
            tr.remap(S)
            t_pre = timed_epoch()
            tr.remap(S - 1)
            t_post = timed_epoch()
            times["pre"].append(t_pre)
            times["post"].append(t_post)
            ratios.append(t_pre / t_post)   # = post/pre throughput ratio
    traces = prof.trace_counts()
    if traces.get("trace/pipeline_fit_step") != 2:
        fail("timed rounds retraced (executable cache miss)",
             traces=traces)
    floor = 0.9 * (S - 1) / S
    ratio = _stats.median(ratios)
    if ratio < floor:
        fail(f"post-remap throughput ratio {ratio:.3f} is below the "
             f"0.9 x (S-1)/S floor {floor:.3f}",
             pre_times=[round(t, 4) for t in times["pre"]],
             post_times=[round(t, 4) for t in times["post"]])
    ledger = prof.pipeline_stats()
    if not ledger.get("remaps") or ledger.get("stages") != S - 1:
        fail("pipeline ledger did not populate", ledger=ledger)

    t_pre = _stats.median(times["pre"])
    t_post = _stats.median(times["post"])
    return {
        "metric": "pipeline_parallel_smoke",
        "value": n / t_pre,
        "unit": "examples/sec",
        "batch": batch,
        "schedule": "1f1b",
        "stages": S,
        "data_axis": D,
        "n_micro": M,
        "layers": n_layers,
        "platform": jax.devices()[0].platform,
        "bubble_fraction": round(bubble, 4),
        "bubble_bound": round(bound, 4),
        "drill": {"kill_at": kill_at, "cursor": list(cursor),
                  "microbatches": micro_seen, "lost": 0},
        "traces": traces,
        "throughput_ratio_post_vs_pre": round(ratio, 4),
        "throughput_floor": round(floor, 4),
        "epoch_s_pre_median": round(t_pre, 4),
        "epoch_s_post_median": round(t_post, 4),
        "pipeline_ledger": {k: (round(v, 5) if isinstance(v, float) else v)
                            for k, v in ledger.items()},
        "data": "synthetic dense-stack batches; 4-stage 1F1B x 2-way "
                "data, mid-epoch pipeline/stage kill recovered by remap "
                "to 3 stages with zero lost microbatches, interleaved "
                "4/3-stage epochs through the per-stage-count executable "
                "cache",
    }


def bench_serving_smoke(steps: int, batch: int = 32,
                        workers: int = 2) -> dict:
    """SLO-gated serving load test (ISSUE 7; ROADMAP item 2): a
    ServingEngine over a small MLP, warmed AOT bucket executables, then an
    OPEN-LOOP Poisson load (arrivals scheduled by the clock, never gated
    on completions — the arrival process a real front door sees) of
    1-8-row requests. Self-validating hard-fails:

    - **zero failed requests** in both phases — every future must resolve
      with a result;
    - **steady-state p99** <= SLO_P99_MS at the target QPS, and the
      generator must actually sustain >= 90% of the target rate (an
      open-loop generator that silently falls behind measures nothing);
    - **zero traces after warmup**: the ``trace/serving_infer`` counter
      must be exactly one-per-bucket from warmup and FLAT through both
      load phases (``serving/traces_after_warmup`` == 0) — the
      compile-once-run-many contract the bucket ladder exists for;
    - **kill-a-replica drill**: a deterministic ``dead_replica`` fault at
      a mid-load dispatch retires one of the two replicas under full
      Poisson load; the in-flight batch REQUEUES (transparent
      retirement), resurrection refills the pool, and the SLO must hold —
      zero failed requests and p99 <= DEGRADED_P99_MS across the drill
      phase.

    Emits steady/degraded p50/p99/QPS plus the serving ledger (fill
    ratio, pad waste, requeues, queue-depth high-water)."""
    import threading

    import jax

    from deeplearning4j_tpu.common import faultinject
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.parallel import ServingEngine

    TARGET_QPS = 100.0
    SLO_P99_MS = 250.0          # steady-state bound (CPU build machines)
    DEGRADED_P99_MS = 600.0     # bound while one of two replicas is dead
    REQ_ROWS_MAX = 8

    def fail(msg, **extra):
        print(json.dumps({"error": msg, **extra}))
        sys.exit(1)

    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
            .activation("tanh").list()
            .layer(L.DenseLayer(n_out=64))
            .layer(L.DenseLayer(n_out=64))
            .layer(L.OutputLayer(n_out=10))
            .set_input_type(InputType.feed_forward(32)).build())
    model = MultiLayerNetwork(conf).init()

    prof = OpProfiler.get()
    prof.reset()
    faultinject.clear_plan()

    t_warm0 = time.perf_counter()
    eng = (ServingEngine.Builder(model)
           .buckets([1, 2, 4, 8, 16, batch]).input_shape((32,))
           .workers(workers).max_wait_ms(2.0)
           .request_timeout_ms(15000)
           .resurrect_dead_replicas(True, backoff_ms=100)
           .build())
    warmup_s = time.perf_counter() - t_warm0
    traces_at_warmup = prof.counter_value("trace/serving_infer")
    n_buckets = len(eng.ladder.batch_sizes)
    if traces_at_warmup != n_buckets:
        fail("warmup did not compile exactly one executable per bucket",
             traces=traces_at_warmup, buckets=n_buckets)

    rng = np.random.RandomState(0)
    inputs = rng.randn(REQ_ROWS_MAX, 32).astype(np.float32)

    def poisson_phase(n_requests, qps, seed):
        """Open-loop: submit on the arrival schedule, collect completion
        latency via done-callbacks. Returns (latencies_s, failures,
        wall_s)."""
        r = np.random.RandomState(seed)
        gaps = r.exponential(1.0 / qps, n_requests)
        sizes = r.randint(1, REQ_ROWS_MAX + 1, n_requests)
        lat, failures, lock = [], [], threading.Lock()
        done = threading.Semaphore(0)

        def submit(i, t_sub):
            fut = eng.output_async(inputs[:sizes[i]])

            def on_done(f, t_sub=t_sub):
                with lock:
                    if f.exception() is not None:
                        failures.append(str(f.exception()))
                    else:
                        lat.append(time.monotonic() - t_sub)
                done.release()

            fut.add_done_callback(on_done)

        t0 = time.monotonic()
        t_next = t0
        for i in range(n_requests):
            t_next += gaps[i]
            delay = t_next - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            submit(i, t_next)      # latency from the SCHEDULED arrival
        for _ in range(n_requests):
            if not done.acquire(timeout=30):
                fail("load phase hung: requests never resolved",
                     resolved=len(lat) + len(failures), of=n_requests)
        wall = time.monotonic() - t0
        return lat, failures, wall

    # --- steady-state phase -------------------------------------------
    n_steady = max(300, steps * 10)
    lat, failures, wall = poisson_phase(n_steady, TARGET_QPS, seed=1)
    if failures:
        fail("steady-state phase had failed requests",
             n=len(failures), first=failures[0])
    qps = n_steady / wall
    p50 = float(np.percentile(np.asarray(lat) * 1e3, 50))
    p99 = float(np.percentile(np.asarray(lat) * 1e3, 99))
    if qps < 0.9 * TARGET_QPS:
        fail(f"open-loop generator fell behind: {qps:.1f} qps vs target "
             f"{TARGET_QPS}", wall_s=round(wall, 2))
    if p99 > SLO_P99_MS:
        fail(f"steady-state p99 {p99:.1f}ms violates the {SLO_P99_MS}ms "
             f"SLO", p50_ms=round(p50, 2), qps=round(qps, 1))

    # --- kill-a-replica drill -----------------------------------------
    kill_batch = prof.counter_value("serving/batches") + 10
    faultinject.set_plan(faultinject.FaultPlan(
        [{"site": "serving/dispatch", "kind": "dead_replica",
          "index": kill_batch}]))
    n_drill = max(300, steps * 10)
    dlat, dfail, dwall = poisson_phase(n_drill, TARGET_QPS, seed=2)
    faultinject.clear_plan()
    retired = prof.counter_value("inference/replica_retired")
    if retired < 1:
        fail("kill drill did not retire a replica (fault never fired)",
             kill_batch=kill_batch,
             batches=prof.counter_value("serving/batches"))
    if dfail:
        fail("kill drill had failed requests — retirement was not "
             "transparent to in-flight load", n=len(dfail),
             first=dfail[0])
    dp50 = float(np.percentile(np.asarray(dlat) * 1e3, 50))
    dp99 = float(np.percentile(np.asarray(dlat) * 1e3, 99))
    if dp99 > DEGRADED_P99_MS:
        fail(f"kill-drill p99 {dp99:.1f}ms violates the degraded-capacity "
             f"{DEGRADED_P99_MS}ms bound", p50_ms=round(dp50, 2))

    # --- retrace + ledger gates ---------------------------------------
    traces = prof.counter_value("trace/serving_infer")
    if traces != traces_at_warmup:
        fail("serving traced AFTER warmup", warmup=traces_at_warmup,
             now=traces)
    if prof.counter_value("serving/traces_after_warmup"):
        fail("serving/traces_after_warmup counter is non-zero",
             n=prof.counter_value("serving/traces_after_warmup"))
    ledger = prof.serving_stats()
    if not ledger.get("requests") or "fill_ratio" not in ledger:
        fail("serving ledger did not populate", ledger=ledger)
    if not ledger.get("requeued"):
        fail("kill drill retired a replica but nothing was requeued — "
             "the in-flight batch was dropped or failed", ledger=ledger)

    eng.shutdown()
    return {
        "metric": "serving_smoke",
        "value": qps,
        "unit": "req/sec",
        "workers": workers,
        "target_qps": TARGET_QPS,
        "platform": jax.devices()[0].platform,
        "requests_steady": n_steady,
        "requests_drill": n_drill,
        "p50_ms": round(p50, 2),
        "p99_ms": round(p99, 2),
        "slo_p99_ms": SLO_P99_MS,
        "drill_p50_ms": round(dp50, 2),
        "drill_p99_ms": round(dp99, 2),
        "drill_slo_p99_ms": DEGRADED_P99_MS,
        "drill_qps": round(n_drill / dwall, 1),
        "replicas_retired": retired,
        "replicas_resurrected":
            prof.counter_value("inference/replica_resurrected"),
        "warmup_s": round(warmup_s, 3),
        "buckets": list(eng.ladder.batch_sizes),
        "traces": traces,
        "serving_ledger": {k: (round(v, 5) if isinstance(v, float) else v)
                           for k, v in ledger.items()},
        "data": "open-loop Poisson load of 1-8-row requests over AOT "
                "bucket executables; SLO hard-fails on p99/QPS/failed "
                "requests/retraces, incl. a kill-a-replica-mid-load "
                "drill with transparent requeue",
    }


def bench_autoscale_smoke(steps: int, batch: int = 32) -> dict:
    """Overload-safe serving smoke (ISSUE 11; ROADMAP item 4): a diurnal
    + spike traffic replay at >= 5x the serving-smoke rate over an
    SLO-classed ServingEngine with the closed-loop autoscaler attached,
    inside a ``tracecheck.steady_state`` region after warmup.
    Self-validating hard-fails:

    - **gold p99 within SLO through the spike**, with **sheds strictly
      bottom-up by class**: zero gold sheds ever, batch sheds first (the
      spike must actually shed — an un-overloaded "overload test"
      measures nothing), every brownout level transition one step;
    - **scale-up reacts** within SCALE_UP_GATE_S of the spike start
      (read off the flight recorder's ``autoscale/scale`` events) and
      **scale-down fires when idle** (fleet back at min within
      SCALE_DOWN_GATE_S after the load stops) — zero process restarts;
    - **recompiles stay at one-per-(bucket x replica count)**: the
      trace counter is FLAT from warmup through every resize
      (``serving/traces_after_warmup`` == 0);
    - **canary -> promote** and **forced-violation -> rollback** drills
      each leave a complete correlation chain in the flight recorder
      (train-commit -> canary -> promote[/rollback] under one ``pub<N>``
      id), the promote serves the checkpoint weights bitwise, the
      rollback restores the prior params bitwise, and BOTH drills
      complete with zero failed gold requests.

    The spike's overload is made deterministic with an injected ``slow``
    dispatch fault (+20ms per dispatch) — this box would otherwise
    absorb 500 qps of toy-MLP traffic without ever shedding."""
    import shutil
    import tempfile
    import threading

    import jax

    from deeplearning4j_tpu.common import faultinject, flightrec, tracecheck
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import NDArrayDataSetIterator
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.optimize.listeners import CheckpointListener
    from deeplearning4j_tpu.parallel import (AutoscalePolicy, Autoscaler,
                                             Overloaded, ServingEngine,
                                             SLOClass)
    from deeplearning4j_tpu.parallel.serving import next_publication_ordinal
    from deeplearning4j_tpu.util.checkpoint import (committed_checkpoints,
                                                    read_checkpoint_params)

    PEAK_QPS = 500.0            # 5x serving-smoke's 100-qps target
    GOLD_SLO_P99_MS = 500.0     # the budget the brownout defends (CPU box)
    SCALE_UP_GATE_S = 4.0
    SCALE_DOWN_GATE_S = 15.0
    REQ_ROWS_MAX = 8

    def fail(msg, **extra):
        faultinject.clear_plan()
        print(json.dumps({"error": msg, **extra}))
        sys.exit(1)

    def build_model(seed):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .updater(Adam(1e-3)).activation("tanh").list()
                .layer(L.DenseLayer(n_out=64))
                .layer(L.DenseLayer(n_out=64))
                .layer(L.OutputLayer(n_out=10))
                .set_input_type(InputType.feed_forward(32)).build())
        return MultiLayerNetwork(conf).init()

    prof = OpProfiler.get()
    prof.reset()
    faultinject.clear_plan()
    # the whole bench timeline in ONE ring: the correlation-chain gates
    # grep it end to end, exactly like a real postmortem would
    flightrec.configure(capacity=65536)
    flightrec.reset()

    # ---- train-commit leg: two committed checkpoints (compiles happen
    # here, before the steady-state region) ------------------------------
    ckdir = tempfile.mkdtemp(prefix="dl4j_autoscale_smoke_")
    try:
        trainee = build_model(seed=11)
        rng = np.random.RandomState(0)
        xs = rng.randn(8 * batch, 32).astype(np.float32)
        ys = np.eye(10, dtype=np.float32)[rng.randint(0, 10, 8 * batch)]
        cl = CheckpointListener(ckdir, save_every_n_iterations=4,
                                keep_last=4)
        trainee.set_listeners(cl)
        trainee.fit(NDArrayDataSetIterator(xs, ys, batch_size=batch),
                    epochs=2)
        cl.close()
        ckpts = committed_checkpoints(ckdir)
        if len(ckpts) < 2:
            fail("training produced fewer than 2 committed checkpoints",
                 n=len(ckpts))
        ck_promote, ck_rollback = ckpts[-2], ckpts[-1]

        # ---- engine + autoscaler --------------------------------------
        model = build_model(seed=7)
        t_warm0 = time.perf_counter()
        eng = (ServingEngine.Builder(model)
               .buckets([1, 2, 4, 8, 16, batch]).input_shape((32,))
               .workers(1).max_wait_ms(2.0).queue_limit(512)
               .request_timeout_ms(15000)
               .slo_classes([SLOClass("gold", 2, GOLD_SLO_P99_MS,
                                      queue_budget=256),
                             SLOClass("silver", 1, 800.0, queue_budget=64),
                             SLOClass("batch", 0, 2000.0, queue_budget=64)])
               .brownout(interval_s=0.1, depth_trigger=24, clear_ticks=5)
               .queue_hwm_window(1.5)
               .resurrect_dead_replicas(True, backoff_ms=100)
               .build())
        warmup_s = time.perf_counter() - t_warm0
        traces_at_warmup = prof.counter_value("trace/serving_infer")
        n_buckets = len(eng.ladder.batch_sizes)
        if traces_at_warmup != n_buckets:
            fail("warmup did not compile exactly one executable per "
                 "bucket", traces=traces_at_warmup, buckets=n_buckets)
        scaler = Autoscaler(eng, AutoscalePolicy(
            min_workers=1, max_workers=4, interval_s=0.1,
            up_queue_depth=8, up_p99_frac=0.8, down_queue_depth=0,
            down_idle_s=0.8, down_fill_frac=0.25,
            cooldown_up_s=0.4, cooldown_down_s=0.8)).start()

        inputs = np.random.RandomState(1).randn(
            REQ_ROWS_MAX, 32).astype(np.float32)
        CLASS_MIX = ["batch"] * 5 + ["silver"] * 3 + ["gold"] * 2

        def phase(n_requests, qps, seed):
            """Open-loop Poisson replay of class-mixed 1-8-row requests.
            Sheds resolve synchronously (Overloaded) and are counted per
            class; admitted requests resolve via done-callbacks."""
            r = np.random.RandomState(seed)
            gaps = r.exponential(1.0 / qps, n_requests)
            sizes = r.randint(1, REQ_ROWS_MAX + 1, n_requests)
            classes = [CLASS_MIX[i] for i in r.randint(0, len(CLASS_MIX),
                                                       n_requests)]
            lat = {c: [] for c in ("gold", "silver", "batch")}
            shed = {c: 0 for c in ("gold", "silver", "batch")}
            failures = []
            lock = threading.Lock()
            done = threading.Semaphore(0)
            admitted = 0
            t0 = time.monotonic()
            t_next = t0
            for i in range(n_requests):
                t_next += gaps[i]
                delay = t_next - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                cls = classes[i]
                try:
                    fut = eng.output_async(inputs[:sizes[i]],
                                           slo_class=cls)
                except Overloaded:
                    shed[cls] += 1
                    continue
                admitted += 1

                def on_done(f, t_sub=t_next, c=cls):
                    with lock:
                        if f.exception() is not None:
                            failures.append(f"{c}: {f.exception()}")
                        else:
                            lat[c].append(time.monotonic() - t_sub)
                    done.release()

                fut.add_done_callback(on_done)
            for _ in range(admitted):
                if not done.acquire(timeout=30):
                    fail("load phase hung: requests never resolved",
                         resolved=sum(len(v) for v in lat.values())
                         + len(failures), of=admitted)
            wall = time.monotonic() - t0
            return {"lat": lat, "shed": shed, "failures": failures,
                    "wall": wall, "n": n_requests, "admitted": admitted}

        def p99_ms(lats):
            return (float(np.percentile(np.asarray(lats) * 1e3, 99))
                    if lats else 0.0)

        # ---- the replay: one steady-state region after warmup ---------
        try:
            with tracecheck.steady_state("autoscale-smoke replay",
                                         max_host_syncs=None):
                # diurnal day: low -> mid -> low (the mid leg may already
                # grow the fleet — that is the controller working)
                diurnal = [phase(250, 50.0, seed=1),
                           phase(450, 150.0, seed=2),
                           phase(150, 50.0, seed=3)]
                # diurnal night: traffic stops — the fleet must return
                # to min (the first scale-down gate), which also resets
                # the spike-reaction measurement to a 1-replica start
                t_night0 = time.monotonic()
                while time.monotonic() - t_night0 < SCALE_DOWN_GATE_S \
                        and eng.alive_replicas() > 1:
                    time.sleep(0.2)
                night_scale_down_s = time.monotonic() - t_night0
                if eng.alive_replicas() != 1:
                    fail("fleet did not scale down to min during the "
                         "idle night", alive=eng.alive_replicas(),
                         ledger=prof.autoscale_stats())
                # spike at 5x serving-smoke, overload made deterministic
                faultinject.set_plan(faultinject.FaultPlan(
                    [{"site": "serving/dispatch", "kind": "slow",
                      "seconds": 0.02, "times": 10 ** 6}]))
                t_spike = time.monotonic()
                spike = phase(int(5 * PEAK_QPS), PEAK_QPS, seed=4)
                faultinject.clear_plan()
        except tracecheck.SteadyStateViolation as e:
            fail("serving retraced/compiled inside the replay — the "
                 "compile-once contract broke under resize or shed",
                 violation=str(e).splitlines()[0])

        # ---- SLO + shed-order gates -----------------------------------
        for name, ph in [("diurnal-low", diurnal[0]),
                         ("diurnal-mid", diurnal[1]),
                         ("diurnal-low2", diurnal[2]),
                         ("spike", spike)]:
            if ph["failures"]:
                fail(f"{name} phase had failed requests",
                     n=len(ph["failures"]), first=ph["failures"][0])
            if ph["shed"]["gold"] != 0:
                fail(f"{name} phase shed gold requests — shed order is "
                     "not bottom-up", shed=ph["shed"])
        if prof.counter_value("serving/shed/gold") != 0:
            fail("gold sheds counted in the ledger",
                 n=prof.counter_value("serving/shed/gold"))
        if spike["shed"]["batch"] == 0:
            fail("the spike never shed batch-class traffic — no overload "
                 "was exercised", shed=spike["shed"],
                 qps=round(spike["n"] / spike["wall"], 1))
        shed_events = flightrec.events("serving/shed")
        for e in shed_events:
            # lowest-class-first is a SET property of every level: no
            # transition may ever shed silver while batch is admitted
            if "silver" in e["attrs"]["shed"] \
                    and "batch" not in e["attrs"]["shed"]:
                fail("a brownout level shed silver while batch was "
                     "still admitted — not lowest-class-first",
                     transition=e["attrs"])
        levels = [e["attrs"]["level"] for e in shed_events]
        prevs = [e["attrs"]["prev"] for e in shed_events]
        if not levels:
            fail("no serving/shed level transitions recorded")
        if any(abs(lv - pv) != 1 for lv, pv in zip(levels, prevs)):
            fail("brownout level jumped more than one step",
                 transitions=list(zip(prevs, levels)))
        first_shed = next(e for e in shed_events
                          if e["attrs"]["level"] > e["attrs"]["prev"])
        if first_shed["attrs"]["shed"] != ["batch"]:
            fail("first brownout step did not shed exactly the batch "
                 "class", shed=first_shed["attrs"]["shed"])
        spike_qps = spike["n"] / spike["wall"]
        if spike_qps < 0.9 * PEAK_QPS:
            fail(f"open-loop generator fell behind: {spike_qps:.0f} qps "
                 f"vs target {PEAK_QPS:.0f}", wall_s=round(spike["wall"], 2))
        gold_spike_p99 = p99_ms(spike["lat"]["gold"])
        if gold_spike_p99 > GOLD_SLO_P99_MS:
            fail(f"gold p99 {gold_spike_p99:.1f}ms violated the "
                 f"{GOLD_SLO_P99_MS:.0f}ms SLO through the spike",
                 gold_requests=len(spike["lat"]["gold"]))

        # ---- autoscale reaction gates ---------------------------------
        scale_ups = [e for e in flightrec.events("autoscale/scale")
                     if e["attrs"]["to"] > e["attrs"]["frm"]
                     and e["m"] >= t_spike]
        if not scale_ups:
            fail("the autoscaler never scaled up during the spike",
                 alive=eng.alive_replicas(),
                 ledger=prof.autoscale_stats())
        scale_up_latency = scale_ups[0]["m"] - t_spike
        if scale_up_latency > SCALE_UP_GATE_S:
            fail(f"scale-up reacted in {scale_up_latency:.1f}s — over "
                 f"the {SCALE_UP_GATE_S}s gate")
        replicas_peak = max(e["attrs"]["to"] for e in scale_ups)
        t_idle0 = time.monotonic()
        while time.monotonic() - t_idle0 < SCALE_DOWN_GATE_S:
            if eng.alive_replicas() == 1:
                break
            time.sleep(0.2)
        scale_down_s = time.monotonic() - t_idle0
        if eng.alive_replicas() != 1:
            fail(f"scale-down did not return the fleet to min within "
                 f"{SCALE_DOWN_GATE_S}s of going idle",
                 alive=eng.alive_replicas(),
                 ledger=prof.autoscale_stats())
        if prof.counter_value("autoscale/scale_downs") < 1:
            fail("no scale-down was ever counted",
                 ledger=prof.autoscale_stats())

        # ---- recompile gate -------------------------------------------
        traces = prof.counter_value("trace/serving_infer")
        if traces != traces_at_warmup:
            fail("serving traced after warmup across resizes",
                 warmup=traces_at_warmup, now=traces)
        if prof.counter_value("serving/traces_after_warmup"):
            fail("serving/traces_after_warmup is non-zero",
                 n=prof.counter_value("serving/traces_after_warmup"))

        # ---- canary -> promote drill ----------------------------------
        gold_x = inputs[:2]

        def gold_load_until(handle):
            failures = []
            while not handle.done:
                try:
                    eng.output(gold_x, slo_class="gold")
                except Exception as e:      # census, not control flow
                    failures.append(str(e))
            return failures

        h1 = eng.publish_checkpoint(ck_promote, canary_window_s=0.8,
                                    confirm_window_s=0.8,
                                    check_interval_s=0.1)
        gold_failures = gold_load_until(h1)
        if h1.result(timeout=15) != "promoted" or gold_failures:
            fail("canary->promote drill failed",
                 outcome=h1.phase, gold_failures=gold_failures[:3])
        want = jax.tree.leaves(read_checkpoint_params(
            ck_promote, model._params, model._states))
        got = jax.tree.leaves(eng._dev_params[0])
        if not all(np.array_equal(np.asarray(g), np.asarray(w))
                   for g, w in zip(got, want)):
            fail("promoted fleet params are not bitwise the checkpoint's")
        chain1 = [e["name"] for e in flightrec.events(corr=h1.corr)]
        commit_files = {e["attrs"].get("file")
                        for e in flightrec.events("checkpoint/commit")}
        if os.path.basename(ck_promote) not in commit_files:
            fail("train-commit leg missing from the recorder",
                 commits=sorted(commit_files))
        if not ("serving/canary" in chain1 and "serving/promote" in chain1
                and chain1.index("serving/canary")
                < chain1.index("serving/promote")):
            fail("promote correlation chain incomplete", chain=chain1,
                 corr=h1.corr)

        # ---- forced-violation -> rollback drill -----------------------
        prior = [np.array(a) for a in jax.tree.leaves(eng._dev_params[0])]
        ordinal = next_publication_ordinal()
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "serving/promote", "kind": "transient",
              "index": ordinal}]))
        h2 = eng.publish_checkpoint(ck_rollback, canary_window_s=0.5,
                                    confirm_window_s=5.0,
                                    check_interval_s=0.1)
        gold_failures = gold_load_until(h2)
        faultinject.clear_plan()
        if h2.result(timeout=15) != "rolled_back" or gold_failures:
            fail("forced-violation drill did not roll back cleanly",
                 outcome=h2.phase, gold_failures=gold_failures[:3])
        after = [np.array(a) for a in jax.tree.leaves(eng._dev_params[0])]
        if not all(np.array_equal(a, b) for a, b in zip(after, prior)):
            fail("rollback did not restore the prior params bitwise")
        chain2 = [e["name"] for e in flightrec.events(corr=h2.corr)]
        if not ("serving/canary" in chain2 and "serving/promote" in chain2
                and "serving/rollback" in chain2):
            fail("rollback correlation chain incomplete", chain=chain2,
                 corr=h2.corr)
        if prof.counter_value("serving/shed/gold") != 0:
            fail("gold sheds during the canary drills",
                 n=prof.counter_value("serving/shed/gold"))

        serving_ledger = prof.serving_stats()
        autoscale_ledger = prof.autoscale_stats()
        scaler.stop()
        eng.shutdown()
        return {
            "metric": "autoscale_smoke",
            "value": spike_qps,
            "unit": "req/sec",
            "platform": jax.devices()[0].platform,
            "peak_qps_target": PEAK_QPS,
            "gold_slo_p99_ms": GOLD_SLO_P99_MS,
            "gold_spike_p99_ms": round(gold_spike_p99, 2),
            "gold_diurnal_p99_ms": round(
                p99_ms([v for ph in diurnal
                        for v in ph["lat"]["gold"]]), 2),
            "spike_shed": spike["shed"],
            "diurnal_shed": {c: sum(ph["shed"][c] for ph in diurnal)
                             for c in ("gold", "silver", "batch")},
            "brownout_transitions": list(zip(prevs, levels)),
            "scale_up_latency_s": round(scale_up_latency, 2),
            "scale_up_gate_s": SCALE_UP_GATE_S,
            "night_scale_down_s": round(night_scale_down_s, 2),
            "scale_down_s": round(scale_down_s, 2),
            "replicas_peak": replicas_peak,
            "canary_promote": {"corr": h1.corr, "outcome": "promoted",
                               "file": os.path.basename(ck_promote)},
            "canary_rollback": {"corr": h2.corr, "outcome": "rolled_back",
                                "file": os.path.basename(ck_rollback)},
            "warmup_s": round(warmup_s, 3),
            "traces": traces,
            "serving_ledger": {k: (round(v, 5) if isinstance(v, float)
                                   else v)
                               for k, v in serving_ledger.items()
                               if isinstance(v, (int, float))},
            "autoscale_ledger": autoscale_ledger,
            "data": "diurnal+spike open-loop Poisson replay of class-"
                    "mixed 1-8-row requests at 5x serving-smoke rate; "
                    "hard gates on gold SLO, bottom-up sheds, scale "
                    "up/down latency, flat recompiles, canaried "
                    "promote/rollback correlation chains",
        }
    finally:
        faultinject.clear_plan()
        flightrec.configure(capacity=4096)
        shutil.rmtree(ckdir, ignore_errors=True)


def bench_soak_smoke(steps: int, batch: int = 32) -> dict:
    """Production-day chaos soak (ISSUE 17): the watchtower SLO engine
    proven end to end. Supervised training publishes checkpoints into a
    live autoscaled serving fleet under replayed traffic while a
    scheduled chaos plan fires the FAULT_SITES catalog — train-step
    crash, device loss, NaN poison, wedged dispatch, SIGTERM preemption,
    dead serving replica, forced promote-violation, pipeline stage kill —
    with the watchtower evaluating compressed-window SLOs (5m/1h/6h
    scaled to 1s/3s/6s) the whole time. Self-validating hard-fails:

    - **clean window is silent**: a no-fault load + train + publish leg
      must page zero times and open zero incidents (false-positive gate);
    - **every fault becomes exactly ONE incident** with a COMPLETE
      cause -> detection -> mitigation -> recovery chain anchored on the
      right fault site (precision = recall = 1.0 over 8 injected faults),
      and supervisor incidents carry the blackbox tail;
    - **zero failed or shed gold requests** through every phase,
      including the dead-replica and rollback drills;
    - **a wobbly evaluator loses a sample, not the alert**: the
      ``watchtower/evaluate`` transient drill must skip exactly one tick
      with no state transition and no incident;
    - **watchtower overhead <= 5%** on a warm training loop (interleaved
      on/off A/B, min-over-ratios via ``_ab_overhead_gate``) with ZERO
      retrace delta inside the timed window;
    - the incident registry is served over HTTP: ``/api/incidents``,
      ``/api/health``'s ``last_incident`` pointer, the ``?corr=``
      filtered ``/api/trace`` export, and the ``dl4j_alert_state`` /
      ``dl4j_serving_latency_ms`` Prometheus families all answer."""
    import shutil
    import tempfile
    import threading
    import urllib.request

    import jax

    from deeplearning4j_tpu.common import (faultinject, flightrec,
                                           tracecheck, watchtower)
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import NDArrayDataSetIterator
    from deeplearning4j_tpu.learning import Adam, Sgd
    from deeplearning4j_tpu.ndarray.rng import set_default_seed
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.optimize.listeners import CheckpointListener
    from deeplearning4j_tpu.optimize.telemetry import NanSentinelListener
    from deeplearning4j_tpu.parallel import (AutoscalePolicy, Autoscaler,
                                             Overloaded, PipelineTrainer,
                                             ServingEngine, SLOClass,
                                             TrainingSupervisor)
    from deeplearning4j_tpu.parallel.serving import next_publication_ordinal
    from deeplearning4j_tpu.ui.server import UIServer
    from deeplearning4j_tpu.util.checkpoint import committed_checkpoints

    TICK_S = 0.1                 # evaluator cadence (compressed time)
    # 5m/1h/6h windows compressed to 1s/3s/6s over a 30s budget period:
    # one bad tick at 0.1s cadence burns fast~100x/mid~33x a 0.1% budget,
    # comfortably over the stock 14.4x page threshold, and ages out of
    # every window seconds later — raise-fast/clear-fast, same math
    WIN = dict(fast_s=1.0, mid_s=3.0, slow_s=6.0, period_s=30.0,
               clear_ticks=2)
    REQ_ROWS_MAX = 8
    CLASS_MIX = ["batch"] * 5 + ["silver"] * 3 + ["gold"] * 2

    def fail(msg, **extra):
        faultinject.clear_plan()
        print(json.dumps({"error": msg, **extra}, default=str))
        sys.exit(1)

    def build_mlp(seed):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .updater(Adam(1e-3)).activation("tanh").list()
                .layer(L.DenseLayer(n_out=64))
                .layer(L.DenseLayer(n_out=64))
                .layer(L.OutputLayer(n_out=10))
                .set_input_type(InputType.feed_forward(32)).build())
        return MultiLayerNetwork(conf).init()

    prof = OpProfiler.get()
    prof.reset()
    faultinject.clear_plan()
    # the whole soak timeline in ONE ring: incident assembly walks it
    flightrec.configure(capacity=65536)
    flightrec.reset()
    t_soak0 = time.monotonic()

    incident_dir = tempfile.mkdtemp(prefix="dl4j_soak_incidents_")
    ckdir = tempfile.mkdtemp(prefix="dl4j_soak_ckpt_")
    tmpdirs = [incident_dir, ckdir]
    eng = scaler = ui = None
    try:
        # ---- train-commit leg: checkpoints the fleet will consume ------
        trainee = build_mlp(seed=11)
        rng = np.random.RandomState(0)
        xs = rng.randn(8 * batch, 32).astype(np.float32)
        ys = np.eye(10, dtype=np.float32)[rng.randint(0, 10, 8 * batch)]
        cl = CheckpointListener(ckdir, save_every_n_iterations=4,
                                keep_last=4)
        trainee.set_listeners(cl)
        trainee.fit(NDArrayDataSetIterator(xs, ys, batch_size=batch),
                    epochs=2)
        cl.close()
        ckpts = committed_checkpoints(ckdir)
        if len(ckpts) < 2:
            fail("training produced fewer than 2 committed checkpoints",
                 n=len(ckpts))
        ck_clean, ck_drill = ckpts[-2], ckpts[-1]

        # ---- serving fleet + autoscaler --------------------------------
        model = build_mlp(seed=7)
        eng = (ServingEngine.Builder(model)
               .buckets([1, 2, 4, 8, 16, batch]).input_shape((32,))
               .workers(2).max_wait_ms(2.0).queue_limit(512)
               .request_timeout_ms(15000)
               .slo_classes([SLOClass("gold", 2, 500.0, queue_budget=256),
                             SLOClass("silver", 1, 800.0, queue_budget=64),
                             SLOClass("batch", 0, 2000.0, queue_budget=64)])
               .brownout(interval_s=0.1, depth_trigger=24, clear_ticks=5)
               .queue_hwm_window(1.5)
               .resurrect_dead_replicas(True, backoff_ms=100)
               .build())
        scaler = Autoscaler(eng, AutoscalePolicy(
            min_workers=2, max_workers=4, interval_s=0.1,
            up_queue_depth=8, up_p99_frac=0.8, down_queue_depth=0,
            down_idle_s=0.8, down_fill_frac=0.25,
            cooldown_up_s=0.4, cooldown_down_s=0.8)).start()

        # ---- the watchtower: stock catalog + drill objectives ----------
        slos = watchtower.default_slos(engine=eng, **WIN)
        slos += [
            watchtower.SLO(
                "replica-health",
                watchtower.counter_increment_sampler(
                    "inference/replica_retired"),
                budget=0.001,
                description="serving replicas stay alive", **WIN),
            watchtower.SLO(
                "rollback-budget",
                watchtower.counter_increment_sampler("serving/rollbacks"),
                budget=0.001,
                description="published checkpoints stick", **WIN),
            watchtower.SLO(
                "remap-budget",
                watchtower.counter_increment_sampler("pipeline/remaps"),
                budget=0.001,
                description="pipeline stages stay up", **WIN),
        ]
        tower = watchtower.install(watchtower.Watchtower(
            slos, interval_s=TICK_S, incident_dir=incident_dir,
            ring_context=600, lookback_s=60.0, finalize_after_s=30.0))
        tower.start()
        ui = UIServer()
        port = ui.enable(0)

        # ---- shared helpers --------------------------------------------
        inputs = np.random.RandomState(1).randn(
            REQ_ROWS_MAX, 32).astype(np.float32)

        def phase(n_requests, qps, seed):
            r = np.random.RandomState(seed)
            gaps = r.exponential(1.0 / qps, n_requests)
            sizes = r.randint(1, REQ_ROWS_MAX + 1, n_requests)
            classes = [CLASS_MIX[i]
                       for i in r.randint(0, len(CLASS_MIX), n_requests)]
            shed = {c: 0 for c in ("gold", "silver", "batch")}
            failures = []
            lock = threading.Lock()
            done = threading.Semaphore(0)
            admitted = 0
            t0 = time.monotonic()
            t_next = t0
            for i in range(n_requests):
                t_next += gaps[i]
                delay = t_next - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                cls = classes[i]
                try:
                    fut = eng.output_async(inputs[:sizes[i]],
                                           slo_class=cls)
                except Overloaded:
                    shed[cls] += 1
                    continue
                admitted += 1

                def on_done(f, c=cls):
                    with lock:
                        if f.exception() is not None:
                            failures.append(f"{c}: {f.exception()}")
                    done.release()

                fut.add_done_callback(on_done)
            for _ in range(admitted):
                if not done.acquire(timeout=30):
                    fail("soak load phase hung: requests never resolved")
            return {"shed": shed, "failures": failures, "n": n_requests,
                    "admitted": admitted,
                    "wall": time.monotonic() - t0}

        def gate_phase(name, ph):
            if ph["failures"]:
                fail(f"{name}: requests failed", n=len(ph["failures"]),
                     first=ph["failures"][0])
            if ph["shed"]["gold"]:
                fail(f"{name}: gold requests shed", shed=ph["shed"])

        gold_x = inputs[:2]

        def gold_load_until(handle):
            failures = []
            while not handle.done:
                try:
                    eng.output(gold_x, slo_class="gold")
                except Exception as e:      # census, not control flow
                    failures.append(str(e))
            return failures

        def wait_for(cond, timeout_s, what):
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                if cond():
                    return
                time.sleep(0.05)
            fail(f"soak: timed out waiting for {what}",
                 alert_states=tower.alert_states(),
                 incidents=watchtower.incidents())

        def incident_ids():
            return {i["id"] for i in watchtower.incidents()}

        chronicle = {}

        def expect_incident(drill, before_ids, *, kind, cause_site=None,
                            detection=None, mitigation=None, recovery=None,
                            timeout_s=25.0):
            """Exactly ONE new incident, finalized with a complete chain
            anchored where the drill says it must be."""
            deadline = time.monotonic() + timeout_s
            new = []
            while time.monotonic() < deadline:
                new = [i for i in watchtower.incidents()
                       if i["id"] not in before_ids]
                if len(new) > 1:
                    fail(f"{drill}: one injected fault opened "
                         f"{len(new)} incidents", incidents=new)
                if new and new[0]["finalized"]:
                    break
                time.sleep(0.05)
            if not new or not new[0]["finalized"]:
                fail(f"{drill}: no finalized incident within "
                     f"{timeout_s}s", incidents=watchtower.incidents(),
                     alert_states=tower.alert_states())
            meta = new[0]
            # the index flips finalized a beat before the finalize
            # rewrite lands on disk — read the file until it agrees
            rep = None
            file_deadline = time.monotonic() + 5.0
            while time.monotonic() < file_deadline:
                with open(meta["path"], "r", encoding="utf-8") as f:
                    rep = json.load(f)
                if rep.get("finalized"):
                    break
                time.sleep(0.05)
            ch = rep["chain"]
            names = {k: (v or {}).get("name")
                     for k, v in ch.items() if k != "complete"}
            if not rep["complete"] or not rep["resolved"]:
                fail(f"{drill}: incident chain incomplete", chain=names,
                     id=meta["id"],
                     rep={k: v for k, v in rep.items()
                          if k not in ("events", "ledgers", "census",
                                       "watermarks", "blackbox")},
                     alert_states=tower.alert_states())
            if rep["kind"] != kind:
                fail(f"{drill}: incident kind {rep['kind']!r}, "
                     f"wanted {kind!r}", id=meta["id"])
            if cause_site is not None and \
                    ch["cause"]["attrs"].get("site") != cause_site:
                fail(f"{drill}: cause anchored on the wrong fault site",
                     cause=ch["cause"])
            for role, allowed in (("detection", detection),
                                  ("mitigation", mitigation),
                                  ("recovery", recovery)):
                if allowed is not None and ch[role]["name"] not in allowed:
                    fail(f"{drill}: {role} anchored on "
                         f"{ch[role]['name']!r}", chain=names)
            seqs = (ch["cause"]["seq"], ch["mitigation"]["seq"],
                    ch["recovery"]["seq"])
            if not (seqs[0] <= seqs[1] <= seqs[2]) or \
                    ch["cause"]["seq"] > ch["detection"]["seq"]:
                fail(f"{drill}: chain events out of causal order",
                     chain=names, seqs=seqs)
            if kind == "supervisor" and not rep.get("blackbox"):
                fail(f"{drill}: supervisor incident carries no blackbox "
                     "tail", id=meta["id"])
            chronicle[drill] = {
                "id": meta["id"], "kind": rep["kind"],
                "reason": rep["reason"], "corr": rep.get("corr"),
                "chain": names,
                "mttr_s": round(rep["updated_t"] - rep["opened_t"], 2)}
            return meta["id"], rep

        # ---- supervised-drill scaffolding ------------------------------
        n_tr = 8 * batch
        tx = rng.randn(n_tr, 32).astype(np.float32)
        ty = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n_tr)]

        def make_train_it():
            return NDArrayDataSetIterator(tx, ty, batch_size=batch)

        def supervised_run(drill, plan, *, listeners=(), policies=None,
                           hang_deadline_s=None, poll_s=0.05,
                           resume="never", sup_dir=None,
                           expect_status="completed", expect_restarts=0):
            d = sup_dir or tempfile.mkdtemp(prefix=f"dl4j_soak_sup_")
            if d not in tmpdirs:
                tmpdirs.append(d)
            m = build_mlp(seed=23)
            if listeners:
                m.set_listeners(*listeners)
            sup = TrainingSupervisor(m, d, save_every_n_iterations=3,
                                     keep_last=2, backoff_base_s=0.01,
                                     hang_deadline_s=hang_deadline_s,
                                     poll_s=poll_s, policies=policies)
            if plan:
                faultinject.set_plan(faultinject.FaultPlan(plan))
            try:
                res = sup.fit(make_train_it, epochs=2, batch_size=batch,
                              resume=resume)
            finally:
                faultinject.clear_plan()
            if res.status != expect_status or \
                    (expect_restarts is not None
                     and res.restarts != expect_restarts):
                fail(f"{drill}: supervised run ended "
                     f"{res.status}/{res.restarts} restarts, wanted "
                     f"{expect_status}/{expect_restarts}",
                     history=res.history)
            return res, d

        # ================================================================
        # Phase 1 — the CLEAN window: load + train + publish, silence
        # ================================================================
        pages0 = prof.counter_value("watchtower/pages")
        clean_phases = [phase(150, 40.0, seed=1),
                        phase(250, 120.0, seed=2)]
        for i, ph in enumerate(clean_phases):
            gate_phase(f"clean-window load {i}", ph)
        supervised_run("clean-window train", None)
        h = eng.publish_checkpoint(ck_clean, canary_window_s=0.5,
                                   confirm_window_s=0.5,
                                   check_interval_s=0.1)
        gold_failures = gold_load_until(h)
        if h.result(timeout=15) != "promoted" or gold_failures:
            fail("clean-window publish did not promote",
                 outcome=h.phase, gold_failures=gold_failures[:3])
        time.sleep(4 * TICK_S)          # let the evaluator see all of it
        if prof.counter_value("watchtower/pages") != pages0:
            fail("false-positive page in the clean window",
                 pages=prof.counter_value("watchtower/pages") - pages0,
                 alert_states=tower.alert_states())
        if incident_ids():
            fail("incident opened during the clean window",
                 incidents=watchtower.incidents())

        # ================================================================
        # Phase 2 — watchtower A/B overhead on a warm training loop
        # ================================================================
        n_ab = 32 * batch
        ax = rng.randn(n_ab, 32).astype(np.float32)
        ay = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n_ab)]
        ab_model = build_mlp(seed=31)

        def ab_epoch():
            ab_model.fit(NDArrayDataSetIterator(ax, ay, batch_size=batch),
                         epochs=3, batch_size=batch)
            float(np.asarray(ab_model._score_dev))     # value fence

        ab_epoch()                                     # warm/compile

        def timed_epoch(name):
            tower.configure(enabled=(name == "on"))
            t0 = time.perf_counter()
            ab_epoch()
            return time.perf_counter() - t0

        timed_epoch("on")
        timed_epoch("off")                             # settle rounds
        traces0 = prof.trace_counts()
        try:
            with tracecheck.steady_state("soak watchtower A/B",
                                         max_host_syncs=None):
                overhead, _times, overhead_runs = _ab_overhead_gate(
                    "watchtower", 0.05,
                    lambda: _ab_rounds(timed_epoch, rounds=5), fail)
        except tracecheck.SteadyStateViolation as e:
            fail("watchtower A/B window retraced/synced",
                 violation=str(e).splitlines()[0])
        if prof.trace_counts() != traces0:
            fail("watchtower A/B window changed the compile footprint",
                 before=traces0, after=prof.trace_counts())
        tower.configure(enabled=True)

        # ================================================================
        # Phase 3 — the chaos plan, one incident per fault
        # ================================================================
        # (a) train-step crash -> restart
        before = incident_ids()
        supervised_run(
            "crash", [{"site": "train/step", "index": 10,
                       "kind": "crash"}], expect_restarts=1)
        expect_incident(
            "crash", before, kind="supervisor", cause_site="train/step",
            detection=("supervisor/attempt_failed",),
            mitigation=("supervisor/restart",),
            recovery=("supervisor/attempt_start", "checkpoint/restore"))

        # (b) device loss -> restart (non-elastic target: the documented
        # shrink_and_continue fallback)
        before = incident_ids()
        supervised_run(
            "device-loss", [{"site": "device/loss", "index": 10,
                             "kind": "device_loss", "replica": 0}],
            expect_restarts=1)
        expect_incident(
            "device-loss", before, kind="supervisor",
            cause_site="device/loss",
            detection=("supervisor/attempt_failed",),
            mitigation=("supervisor/restart",),
            recovery=("supervisor/attempt_start", "checkpoint/restore"))

        # (c) NaN poison -> sentinel raises -> policy restart
        before = incident_ids()
        supervised_run(
            "nan-poison", [{"site": "pipeline/bind", "index": 10,
                            "kind": "nan"}],
            listeners=(NanSentinelListener("raise", check_every_n=1),),
            policies={"poisoned_numerics": "restart"}, expect_restarts=1)
        expect_incident(
            "nan-poison", before, kind="supervisor",
            cause_site="pipeline/bind",
            detection=("supervisor/attempt_failed",),
            mitigation=("supervisor/restart",),
            recovery=("supervisor/attempt_start", "checkpoint/restore"))

        # (d) wedged dispatch -> watchdog abandonment -> restart
        before = incident_ids()
        supervised_run(
            "wedge", [{"site": "train/wedge", "index": 9,
                       "kind": "wedge"}],
            hang_deadline_s=0.5, poll_s=0.02, expect_restarts=1)
        expect_incident(
            "wedge", before, kind="supervisor", cause_site="train/wedge",
            detection=("supervisor/watchdog_fire",
                       "supervisor/attempt_failed"),
            mitigation=("supervisor/restart",),
            recovery=("supervisor/attempt_start", "checkpoint/restore"))

        # (e) SIGTERM preemption -> flush checkpoint -> exit -> resume
        before = incident_ids()
        _, pre_dir = supervised_run(
            "preempt", [{"site": "train/step", "index": 10,
                         "kind": "preempt"}],
            expect_status="preempted", expect_restarts=0)
        supervised_run("preempt-resume", None, resume="auto",
                       sup_dir=pre_dir)
        expect_incident(
            "preempt", before, kind="supervisor", cause_site="train/step",
            detection=("supervisor/attempt_failed",),
            mitigation=("supervisor/preempted",),
            recovery=("supervisor/attempt_start", "checkpoint/restore"))

        # (f) dead serving replica -> retire -> resurrection
        before = incident_ids()
        resurrected0 = len(flightrec.events("inference/resurrected"))
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "serving/dispatch", "kind": "dead_replica",
              "times": 1}]))
        dead_ph = phase(120, 80.0, seed=6)
        faultinject.clear_plan()
        gate_phase("dead-replica load", dead_ph)
        wait_for(lambda: len(flightrec.events("inference/resurrected"))
                 > resurrected0, 10.0, "replica resurrection")
        expect_incident(
            "dead-replica", before, kind="alert",
            cause_site="serving/dispatch",
            detection=("watchtower/alert",),
            mitigation=("serving/retire",),
            recovery=("inference/resurrected", "watchtower/alert"))

        # (g) forced promote-violation -> rollback -> clean republish
        before = incident_ids()
        ordinal = next_publication_ordinal()
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "serving/promote", "kind": "transient",
              "index": ordinal}]))
        h2 = eng.publish_checkpoint(ck_drill, canary_window_s=0.5,
                                    confirm_window_s=5.0,
                                    check_interval_s=0.1)
        gold_failures = gold_load_until(h2)
        faultinject.clear_plan()
        if h2.result(timeout=15) != "rolled_back" or gold_failures:
            fail("forced-violation drill did not roll back cleanly",
                 outcome=h2.phase, gold_failures=gold_failures[:3])
        h3 = eng.publish_checkpoint(ck_clean, canary_window_s=0.5,
                                    confirm_window_s=0.5,
                                    check_interval_s=0.1)
        gold_failures = gold_load_until(h3)
        if h3.result(timeout=15) != "promoted" or gold_failures:
            fail("post-rollback republish did not promote",
                 outcome=h3.phase, gold_failures=gold_failures[:3])
        expect_incident(
            "promote-violation", before, kind="alert",
            cause_site="serving/promote",
            detection=("watchtower/alert",),
            mitigation=("serving/rollback",),
            recovery=("serving/promote", "watchtower/alert"))

        # (h) the evaluator itself wobbles: one skipped tick, no alert
        wait_for(lambda: all(v == 0
                             for v in tower.alert_states().values()),
                 20.0, "alert states to settle before the evaluator drill")
        states0 = tower.alert_states()
        stats0 = tower.stats()
        before = incident_ids()
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "watchtower/evaluate", "kind": "transient",
              "index": int(stats0["evaluations"]) + 2}]))
        wait_for(lambda: tower.stats()["skipped_evals"]
                 >= stats0["skipped_evals"] + 1, 5.0,
                 "the watchtower/evaluate transient to fire")
        faultinject.clear_plan()
        if tower.alert_states() != states0 or incident_ids() != before:
            fail("a skipped evaluation tick changed alert state or "
                 "opened an incident", states=tower.alert_states())

        # (i) pipeline stage kill -> remap -> resume (the scaler is done
        # at this point; stopping it keeps the mitigation anchor exact)
        scaler.stop()
        before = incident_ids()
        batch_pp, M, feat = 16, 4, 16
        n_pp = 6 * batch_pp
        set_default_seed(55)
        pb = (NeuralNetConfiguration.builder().seed(55)
              .updater(Sgd(learning_rate=0.02)).list())
        for _ in range(6):
            pb.layer(L.DenseLayer(n_out=feat, activation="tanh"))
        pmodel = MultiLayerNetwork(
            pb.set_input_type(InputType.feed_forward(feat)).build()).init()
        tr = PipelineTrainer(pmodel, stages=3, n_micro=M,
                             schedule="1f1b", data=1)
        prng = np.random.RandomState(9)
        px = prng.randn(n_pp, feat).astype(np.float32)
        py = prng.randn(n_pp, feat).astype(np.float32)

        def make_pp_it():
            return NDArrayDataSetIterator(px, py, batch_size=batch_pp)

        tr.fit(make_pp_it(), epochs=1, batch_size=batch_pp)   # warm
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "pipeline/stage", "kind": "device_loss",
              "index": 9, "stage": 1}]))
        try:
            tr.fit(make_pp_it(), epochs=2, batch_size=batch_pp)
            fail("pipeline/stage fault plan did not fire")
        except faultinject.DeviceLostError:
            pass
        faultinject.clear_plan()
        cursor = (int(pmodel._epoch - pmodel._fit_epoch0),
                  int(pmodel._steps_in_epoch))
        removed = tr.remap(2, lost_stages=[1])
        if len(removed) != 1:
            fail("stage-kill remap did not retire exactly the lost "
                 "stage column", removed=len(removed))
        tr.fit(make_pp_it(), epochs=2, batch_size=batch_pp,
               resume_cursor=cursor)
        if not np.isfinite(float(np.asarray(pmodel._score_dev))):
            fail("post-remap loss went non-finite")
        expect_incident(
            "stage-kill", before, kind="alert",
            cause_site="pipeline/stage",
            detection=("watchtower/alert",),
            mitigation=("pipeline/remap",),
            recovery=("watchtower/alert",))

        # ================================================================
        # Phase 4 — registry totals + the HTTP surface
        # ================================================================
        DRILLS = ("crash", "device-loss", "nan-poison", "wedge",
                  "preempt", "dead-replica", "promote-violation",
                  "stage-kill")
        incs = watchtower.incidents()
        if len(incs) != len(DRILLS):
            fail(f"{len(DRILLS)} faults injected but {len(incs)} "
                 "incidents assembled (precision/recall broke)",
                 incidents=incs)
        if any(not i["finalized"] or not i["resolved"] for i in incs):
            fail("unresolved incidents at end of soak", incidents=incs)

        def http_json(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=10) as r:
                return json.loads(r.read().decode("utf-8"))

        http_incs = http_json("/api/incidents")
        if len(http_incs) != len(DRILLS):
            fail("/api/incidents does not list every incident",
                 n=len(http_incs))
        served = http_json(f"/api/incidents?id={http_incs[-1]['id']}")
        if not served.get("complete"):
            fail("/api/incidents?id= served an incomplete report",
                 id=http_incs[-1]["id"])
        health = http_json("/api/health")
        li = health.get("last_incident")
        if not li or not (li.get("tail") or {}).get("complete"):
            fail("/api/health last_incident pointer missing or "
                 "incomplete", last_incident=li)
        crash_corr = chronicle["crash"]["corr"]
        trace = http_json(f"/api/trace?corr={crash_corr}")
        tevs = trace.get("traceEvents", [])
        if not tevs or any(e.get("args", {}).get("corr") != crash_corr
                           for e in tevs if e.get("ph") != "M"):
            fail("/api/trace?corr= filter broke", corr=crash_corr,
                 n=len(tevs))
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/metrics", timeout=10) as r:
            metrics_text = r.read().decode("utf-8")
        for needle in ("dl4j_alert_state{",
                       'dl4j_serving_latency_ms{class="gold"'):
            if needle not in metrics_text:
                fail(f"/api/metrics is missing {needle!r}")

        soak_wall_s = time.monotonic() - t_soak0
        tower_stats = tower.stats()
        return {
            "metric": "soak_smoke",
            "value": 60.0 * len(DRILLS) / soak_wall_s,
            "unit": "faults/min",
            "platform": jax.devices()[0].platform,
            "faults_injected": len(DRILLS),
            "incidents_assembled": len(incs),
            "chains_complete": len(DRILLS),
            "mttr_s_mean": round(sum(c["mttr_s"]
                                     for c in chronicle.values())
                                 / len(chronicle), 2),
            "incidents": chronicle,
            "clean_window": {
                "requests": sum(ph["n"] for ph in clean_phases),
                "pages": 0, "incidents": 0},
            "watchtower_overhead_frac": round(overhead, 4),
            "overhead_runs": overhead_runs,
            "pages_total": prof.counter_value("watchtower/pages"),
            "alerts_total": prof.counter_value("watchtower/alerts"),
            "evaluations": int(tower_stats["evaluations"]),
            "skipped_evals": int(tower_stats["skipped_evals"]),
            "soak_wall_s": round(soak_wall_s, 1),
            "data": "clean diurnal window + 8-fault chaos plan over "
                    "supervised training publishing into an autoscaled "
                    "serving fleet; gates: silent clean window, exactly "
                    "one complete-chain incident per fault, zero "
                    "failed/shed gold, <=5% watchtower A/B overhead, "
                    "zero retrace delta, HTTP incident/trace/metrics "
                    "surface",
        }
    finally:
        faultinject.clear_plan()
        watchtower.uninstall()
        if scaler is not None:
            try:
                scaler.stop()
            except Exception:
                pass
        if eng is not None:
            try:
                eng.shutdown()
            except Exception:
                pass
        if ui is not None:
            try:
                ui.stop()
            except Exception:
                pass
        flightrec.configure(capacity=4096)
        for d in tmpdirs:
            shutil.rmtree(d, ignore_errors=True)


def bench_integrity_smoke(steps: int, batch: int = 64,
                          workers: int = 4) -> dict:
    """Silent-corruption defense smoke (ISSUE 19): the in-graph
    replica-consistency fingerprints, the divergent-replica quarantine
    and the checkpoint scrubber proven end to end. Self-validating
    hard-fails:

    - **fingerprint overhead <= 5%**: the uint32 bitcast fold over the
      ZeRO-1 flat buckets plus the cross-replica majority vote, riding
      the jitted step at the TIGHTEST cadence (``check_every=1``),
      against the same wrapper with no IntegrityListener — interleaved
      A/B, min over per-round on/off ratios (the shared
      ``_ab_overhead_gate``), with ZERO retrace delta: identical warm
      compile footprints and zero traces inside the timed
      ``tracecheck.steady_state`` window;
    - **clean window has zero false positives**: every A/B epoch checks
      at cadence 1 and must never count a divergence — bitwise-identical
      replicas are an exact invariant, not a tolerance — and the stock
      ``replica-consistency`` SLO sampler must stay silent through it;
    - **bitflip drill**: one ``integrity/fingerprint`` fault (``bitflip``
      kind) on replica 1 of 4 under a TrainingSupervisor must quarantine
      exactly that replica through the elastic shrink (no restart
      consumed, training completes on 3 workers) and assemble exactly
      ONE finalized watchtower incident whose chain reads cause
      ``fault/fired`` (site ``integrity/fingerprint``, the replica
      named) -> detection ``integrity/divergence`` -> mitigation
      ``integrity/quarantine`` -> recovery; the SLO sampler trips;
    - **scrub drill**: a ``checkpoint/scrub`` transient skips one entry
      for one pass (``integrity/scrub_retries``), then the advisory
      bitflip rots a retained zip ON DISK and the scrubber must
      quarantine that generation in the manifest WITHOUT deleting the
      evidence, every restore path skipping it.

    Emits the ``integrity`` ledger alongside the timing."""
    import shutil
    import statistics as _stats
    import tempfile

    # a multi-replica mesh is the whole point: on single-device hosts
    # (CPU build machines) request virtual CPU devices BEFORE jax loads
    if "jax" not in sys.modules:
        _flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in _flags:
            os.environ["XLA_FLAGS"] = (
                _flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    from deeplearning4j_tpu.common import (faultinject, flightrec,
                                           integrity, tracecheck,
                                           watchtower)
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import NDArrayDataSetIterator
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.ndarray.rng import set_default_seed
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.optimize.listeners import CheckpointListener
    from deeplearning4j_tpu.parallel import (ParallelWrapper,
                                             ReduceScatterAccumulator,
                                             TrainingSupervisor)
    from deeplearning4j_tpu.util.checkpoint import (committed_checkpoints,
                                                    last_checkpoint)

    def fail(msg, **extra):
        faultinject.clear_plan()
        print(json.dumps({"error": msg, **extra}, default=str))
        sys.exit(1)

    workers = min(workers, len(jax.devices()))
    if workers < 4:
        fail("integrity-smoke needs >= 4 devices for an attributable "
             "majority vote (virtual CPU device request came too late?)",
             devices=len(jax.devices()))

    rng_np = np.random.RandomState(0)
    n = steps * batch
    x = rng_np.randn(n, 1, 28, 28).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng_np.randint(0, 10, n)]

    def make_it():
        return NDArrayDataSetIterator(x, y, batch_size=batch)

    prof = OpProfiler.get()
    prof.reset()
    faultinject.clear_plan()
    flightrec.reset()

    # ---- phase 1: A/B overhead at the tightest cadence ----------------
    wrappers = {}
    integ_lst = integrity.IntegrityListener(check_every=1)
    for name in ("off", "on"):
        set_default_seed(99)
        model = _lenet_model()
        pw = (ParallelWrapper.Builder(model).workers(workers)
              .gradients_accumulator(ReduceScatterAccumulator()).build())
        if name == "on":
            pw.set_listeners(integ_lst)
        wrappers[name] = (model, pw)

    def run(name, epochs=1):
        model, pw = wrappers[name]
        pw.fit(make_it(), epochs=epochs, batch_size=batch)
        float(model._score_dev)          # value fence

    # compile footprint: the fold and the vote ride the SAME jitted
    # step — ON and OFF each compile once, identically counted
    warm = {}
    for name in ("off", "on"):
        prof.reset()
        run(name)
        warm[name] = prof.trace_counts()
    if warm["on"] != warm["off"]:
        fail("fingerprinting changed the compile footprint (retrace "
             "delta)", off_traces=warm["off"], on_traces=warm["on"])

    def timed_epoch(name):
        t0 = time.perf_counter()
        run(name)
        return time.perf_counter() - t0

    timed_epoch("on")
    timed_epoch("off")                   # settle rounds, untimed
    prof.reset()
    try:
        # the ON config drains one 4-byte verdict per dispatch by
        # design — host syncs counted, traces policed
        with tracecheck.steady_state("integrity-smoke timed rounds",
                                     max_host_syncs=None):
            overhead, times, overhead_runs = _ab_overhead_gate(
                "integrity fingerprints", 0.05,
                lambda: _ab_rounds(timed_epoch, rounds=6), fail)
    except tracecheck.SteadyStateViolation as e:
        fail("train step retraced inside a timed window — the "
             "fingerprint fold must not destabilize shapes",
             violation=str(e).splitlines()[0])
    hot = prof.trace_counts()
    if any(hot.values()):
        fail("train step retraced inside a timed window", traces=hot)
    t_off = _stats.median(times["off"])
    t_on = _stats.median(times["on"])

    # clean window: every timed ON epoch checked at cadence 1 — the
    # exact-invariant gate is ZERO divergences, ever
    clean_checks = int(prof.counter_value("integrity/checks"))
    if not clean_checks or not integ_lst.fingerprints:
        fail("integrity checks did not run in the ON config",
             checks=clean_checks)
    if prof.counter_value("integrity/divergences") or integ_lst.divergences:
        fail("false positive: clean window counted a divergence",
             divergences=integ_lst.divergences)
    slo = next(s for s in watchtower.default_slos()
               if s.name == "replica-consistency")
    slo.sampler()                        # arming sample
    if slo.sampler():
        fail("replica-consistency SLO sampler tripped on a clean window")

    # ---- phase 2: bitflip -> quarantine -> one finalized incident -----
    def small_mlp():
        conf = (NeuralNetConfiguration.builder().seed(5)
                .updater(Adam(learning_rate=0.05)).activation("tanh")
                .list()
                .layer(L.DenseLayer(n_out=9))
                .layer(L.OutputLayer(n_out=3, loss="mcxent",
                                     activation="softmax"))
                .set_input_type(InputType.feed_forward(4)).build())
        return MultiLayerNetwork(conf).init()

    def small_iter():
        r = np.random.RandomState(7)
        xs = r.randn(96, 4).astype(np.float32)
        ys = np.eye(3, dtype=np.float32)[r.randint(0, 3, 96)]
        return NDArrayDataSetIterator(xs, ys, batch_size=24, shuffle=True,
                                      seed=3)

    inc_dir = tempfile.mkdtemp(prefix="dl4j_integrity_inc_")
    sup_dir = tempfile.mkdtemp(prefix="dl4j_integrity_sup_")
    scrub_dir = tempfile.mkdtemp(prefix="dl4j_integrity_scrub_")
    watchtower.uninstall()
    tower = watchtower.install(watchtower.Watchtower(
        [], incident_dir=inc_dir, interval_s=0.05,
        finalize_after_s=120.0))
    try:
        flightrec.reset()
        prof.reset()
        set_default_seed(99)
        m = small_mlp()
        pw = (ParallelWrapper.Builder(m).workers(4)
              .gradients_accumulator(ReduceScatterAccumulator()).build())
        pw.set_listeners(integrity.IntegrityListener(check_every=1))
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "integrity/fingerprint", "index": 5,
              "kind": "bitflip", "replica": 1}]))
        sup = TrainingSupervisor(pw, checkpoint_dir=sup_dir,
                                 elastic_grow=False)
        res = sup.fit(small_iter, epochs=3)
        faultinject.clear_plan()
        if res.status != "completed" or res.restarts != 0:
            fail("quarantine drill did not complete without a restart",
                 result=repr(res), history=res.history)
        if [h.get("policy") for h in res.history] \
                != ["quarantine_and_continue"] or pw.workers_count != 3:
            fail("divergent replica was not quarantined through the "
                 "elastic shrink", history=res.history,
                 workers=pw.workers_count)
        if prof.counter_value("supervisor/quarantines") != 1 or \
                prof.counter_value("integrity/divergences") != 1 or \
                prof.counter_value("integrity/bitflips_injected") != 1:
            fail("quarantine ledger mismatch",
                 ledger=prof.integrity_stats())
        if not slo.sampler():
            fail("replica-consistency SLO sampler missed the divergence")

        tower.evaluate_now()
        incs = tower.incidents()
        finalized = [i for i in incs if i.get("finalized")]
        if len(incs) != 1 or len(finalized) != 1:
            fail("expected exactly one finalized incident from the "
                 "bitflip drill", open=len(incs),
                 finalized=len(finalized))
        with open(finalized[0]["path"]) as f:
            report = json.load(f)
        chain = report["chain"]
        if not report["complete"] or \
                chain["cause"]["name"] != "fault/fired" or \
                chain["cause"]["attrs"].get("site") != \
                "integrity/fingerprint" or \
                chain["cause"]["attrs"].get("replica") != 1:
            fail("incident chain does not name the flipped replica as "
                 "cause", chain=chain)
        if chain["detection"]["name"] != "integrity/divergence" or \
                chain["mitigation"]["name"] != "integrity/quarantine":
            fail("incident detection/mitigation anchors wrong",
                 chain=chain)
        incident_id = report["id"]

        # ---- phase 3: checkpoint scrub drill ---------------------------
        set_default_seed(11)
        trainee = small_mlp()
        cl = CheckpointListener(scrub_dir, save_every_n_iterations=2,
                                keep_last=6)
        trainee.set_listeners(cl)
        trainee.fit(small_iter(), epochs=2)
        cl.close()
        paths = committed_checkpoints(scrub_dir)
        if len(paths) < 2:
            fail("scrub drill produced fewer than 2 retained "
                 "checkpoints", n=len(paths))
        scrub = integrity.CheckpointScrubber(scrub_dir, interval_s=60.0)
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "checkpoint/scrub", "index": 0,
              "kind": "transient"}]))
        s1 = scrub.scrub_now()
        if s1["skipped"] < 1 or \
                prof.counter_value("integrity/scrub_retries") != 1:
            fail("transient scrub fault did not skip-and-retry",
                 summary=s1)
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "checkpoint/scrub", "index": len(paths),
              "kind": "bitflip", "offset": 300, "bit": 2}]))
        s2 = scrub.scrub_now()
        faultinject.clear_plan()
        if s2["quarantined"] != 1 or scrub.passes != 2:
            fail("advisory bitflip did not quarantine the rotten "
                 "generation", summary=s2, passes=scrub.passes)
        q = flightrec.events("integrity/quarantine")[-1]
        rotten = q["attrs"].get("file")
        if not rotten or not os.path.exists(
                os.path.join(scrub_dir, rotten)):
            fail("quarantined checkpoint was deleted — evidence must "
                 "be retained", file=rotten)
        lc = last_checkpoint(scrub_dir)
        if lc is not None and os.path.basename(lc) == rotten:
            fail("restore path did not skip the quarantined generation",
                 restored=lc)
        if prof.counter_value("integrity/quarantined_checkpoints") != 1:
            fail("quarantined-checkpoint counter mismatch",
                 ledger=prof.integrity_stats())

        ledger = prof.integrity_stats()
        return {
            "metric": "integrity_smoke",
            "value": n / t_on,
            "unit": "images/sec",
            "batch": batch,
            "workers": workers,
            "platform": jax.devices()[0].platform,
            "check_every": 1,
            "traces": warm["on"],
            "fingerprint_overhead_frac": round(overhead, 4),
            "overhead_runs": overhead_runs,
            "epoch_s_off_median": round(t_off, 4),
            "epoch_s_on_median": round(t_on, 4),
            "clean_checks": clean_checks,
            "false_positives": 0,
            "quarantine_incident": incident_id,
            "quarantined_replica": 1,
            "workers_after_quarantine": pw.workers_count,
            "scrub": {"passes": scrub.passes, "quarantined_file": rotten,
                      "retries": 1},
            "integrity_ledger": {k: (round(v, 5) if isinstance(v, float)
                                     else v) for k, v in ledger.items()},
            "data": "LeNet A/B epochs with the in-graph fingerprint "
                    "fold at check_every=1 vs no listener; one injected "
                    "bitflip quarantined through the elastic shrink "
                    "with a finalized incident naming the replica; one "
                    "rotten retained zip quarantined by the scrubber",
        }
    finally:
        faultinject.clear_plan()
        watchtower.uninstall()
        for d in (inc_dir, sup_dir, scrub_dir):
            shutil.rmtree(d, ignore_errors=True)


def bench_obs_smoke(steps: int, batch: int = 64) -> dict:
    """CPU-friendly smoke of the observability layer (ISSUE 10). Three
    self-validating phases, every gate a hard fail:

    1. **Correlated supervised-restart drill** with the flight recorder
       ON: a deterministic crash mid-run, the supervisor heals it, and
       the exported Chrome trace must schema-validate AND contain spans
       (B/E pairs or profiler-section X slices) from >= 3 subsystems
       carrying the drill's ``incN.aM`` correlation ids; the black-box
       JSONL beside the checkpoints must reconstruct the
       fault → classify → restart → resume chain.
    2. **Interleaved A/B overhead** (recorder off vs on) inside a
       ``tracecheck.steady_state`` region: recorder-on step-time
       overhead > 5% (min-over-ratios, one automatic A/B re-run — the
       shared ``_ab_overhead_gate``) fails, any retrace delta fails.
    3. **``/api/metrics``** must parse as Prometheus text exposition
       (TYPE-before-samples, well-formed sample lines) and carry the
       counter/ledger/flight-recorder families.
    """
    import re
    import statistics as _stats
    import tempfile
    import urllib.request

    import jax

    from deeplearning4j_tpu.common import faultinject, flightrec, tracecheck
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import NDArrayDataSetIterator
    from deeplearning4j_tpu.parallel import TrainingSupervisor
    from deeplearning4j_tpu.ui.server import UIServer

    prof = OpProfiler.get()
    rng = np.random.RandomState(0)
    n = steps * batch + batch // 2      # partial tail like the other smokes
    x = rng.randn(n, 1, 28, 28).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)]

    def make_it():
        return NDArrayDataSetIterator(x, y, batch_size=batch)

    def fail(msg, **extra):
        print(json.dumps({"error": msg, **extra}))
        sys.exit(1)

    # ---- phase 1: correlated supervised-restart drill ------------------
    flightrec.configure(enabled=True)
    flightrec.reset()
    tmpdir = tempfile.mkdtemp(prefix="obs_smoke_ckpt_")
    faultinject.set_plan(faultinject.FaultPlan(
        [{"site": "train/step", "index": max(2, steps // 2),
          "kind": "crash"}]))
    model = _lenet_model()
    sup = TrainingSupervisor(model, tmpdir,
                             save_every_n_iterations=max(2, steps // 3),
                             backoff_base_s=0.01)
    res = sup.fit(make_it, epochs=1, resume="never")
    faultinject.clear_plan()
    if res.status != "completed" or res.restarts != 1:
        fail("supervised-restart drill did not heal as scripted",
             status=res.status, restarts=res.restarts)
    bb_path = sup.blackbox_path()
    if not os.path.exists(bb_path):
        fail("no black box beside the checkpoints after the drill",
             expected=bb_path)
    bb_names = [json.loads(l)["name"] for l in open(bb_path)]
    chain = ("fault/fired", "supervisor/attempt_failed",
             "supervisor/restart", "supervisor/attempt_start",
             "checkpoint/commit", "checkpoint/restore",
             "supervisor/completed")
    missing = [c for c in chain if c not in bb_names]
    if missing:
        fail("black box does not reconstruct the incident chain",
             missing=missing)

    trace_path = os.path.join(tmpdir, "drill_trace.json")
    flightrec.export_chrome_trace(trace_path)
    blob = json.load(open(trace_path))
    trace_events = blob.get("traceEvents")
    if not isinstance(trace_events, list) or not trace_events:
        fail("chrome trace export is empty or malformed")
    depth: dict = {}
    # B/E balance is only a valid invariant when the ring evicted
    # nothing — a long drill can legitimately drop a span's B while its
    # E survives (Perfetto tolerates the orphan; a gate must not)
    check_balance = flightrec.stats()["dropped"] == 0
    for ev in trace_events:
        if not {"ph", "pid", "tid", "name"} <= set(ev):
            fail("chrome trace event missing required keys", event=ev)
        if ev["ph"] != "M" and not isinstance(ev.get("ts"), (int, float)):
            fail("chrome trace event missing ts", event=ev)
        if ev["ph"] == "X" and not isinstance(ev.get("dur"), (int, float)):
            fail("X event without dur", event=ev)
        if not check_balance:
            continue
        if ev["ph"] == "B":
            depth[ev["tid"]] = depth.get(ev["tid"], 0) + 1
        elif ev["ph"] == "E":
            depth[ev["tid"]] = depth.get(ev["tid"], 0) - 1
            if depth[ev["tid"]] < 0:
                fail("unbalanced E before B in chrome trace",
                     tid=ev["tid"])
    if any(v != 0 for v in depth.values()):
        fail("unbalanced B/E pairs in chrome trace", depth=depth)
    corr_re = re.compile(r"inc\d+\.a\d+")
    drill_span_cats = {ev["cat"] for ev in trace_events
                      if ev["ph"] in ("B", "X")
                      and corr_re.fullmatch(
                          str(ev.get("args", {}).get("corr", "")))}
    if len(drill_span_cats) < 3:
        fail("chrome trace spans cover < 3 subsystems of the correlated "
             "drill", subsystems=sorted(drill_span_cats))

    # ---- phase 2: interleaved A/B recorder overhead --------------------
    models = {"off": _lenet_model(), "on": _lenet_model()}
    for m in models.values():       # warmup compile outside the region
        m.fit(make_it(), epochs=1)
        float(m._score_dev)
    prof.reset()

    def timed_epoch(name):
        m = models[name]
        flightrec.configure(enabled=(name == "on"))
        t0 = time.perf_counter()
        m.fit(make_it(), epochs=1)
        float(m._score_dev)         # value fence
        return time.perf_counter() - t0

    try:
        with tracecheck.steady_state("obs-smoke timed rounds",
                                     max_host_syncs=None):
            overhead, times, overhead_runs = _ab_overhead_gate(
                "flight-recorder", 0.05,
                lambda: _ab_rounds(timed_epoch, rounds=5), fail)
    except tracecheck.SteadyStateViolation as e:
        fail("train step retraced inside a timed window — the recorder "
             "must not destabilize shapes",
             violation=str(e).splitlines()[0])
    finally:
        flightrec.configure(enabled=True)
    t_off = _stats.median(times["off"])
    t_on = _stats.median(times["on"])

    # ---- phase 3: /api/metrics conformance -----------------------------
    ui = UIServer()
    port = ui.enable(0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/metrics", timeout=10) as r:
            metrics_text = r.read().decode()
    finally:
        ui.stop()
    families: dict = {}
    typed = None
    sample_re = re.compile(
        r'([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(-?[\d.eE+-]+)$')
    for line in metrics_text.splitlines():
        if not line.strip() or line.startswith("# HELP"):
            continue
        if line.startswith("# TYPE"):
            _h, _t, fam, mtype = line.split(None, 3)
            families[fam] = {"type": mtype, "samples": 0}
            typed = fam
            continue
        m = sample_re.match(line)
        if not m or m.group(1) not in families or m.group(1) != typed:
            fail("non-conformant /api/metrics line", line=line)
        families[m.group(1)]["samples"] += 1
    for fam in ("dl4j_counter_total", "dl4j_section_seconds_total",
                "dl4j_ledger", "dl4j_flightrec_events_total"):
        if families.get(fam, {}).get("samples", 0) < 1:
            fail(f"/api/metrics missing the {fam} family",
                 families=sorted(families))

    images = n + (batch - n % batch) % batch
    return {
        "metric": "obs_smoke",
        "value": images / t_on,
        "unit": "images/sec",
        "batch": batch,
        "platform": jax.devices()[0].platform,
        "recorder_overhead_frac": round(overhead, 4),
        "overhead_runs": overhead_runs,
        "epoch_s_off_median": round(t_off, 4),
        "epoch_s_on_median": round(t_on, 4),
        "drill_restarts": res.restarts,
        "blackbox_events": len(bb_names),
        "trace_events": len(trace_events),
        "drill_span_subsystems": sorted(drill_span_cats),
        "metrics_families": len(families),
        "flightrec": flightrec.stats(),
        "data": "synthetic LeNet batches; supervised crash drill with "
                "correlated chrome-trace/blackbox gates, recorder "
                "off/on interleaved A/B, /api/metrics conformance",
    }


def bench_xprof_smoke(steps: int, batch: int = 64) -> dict:
    """CPU-friendly smoke of the XLA performance observatory (ISSUE 15).
    Five self-validating phases, every gate a hard fail:

    1. **Census coverage**: a LeNet-class fit (per-step jit + infer jit)
       and a warmed ServingEngine bucket ladder; after
       ``xprof.analyze()`` every executable the smoke compiled must
       appear in the census with non-empty cost fields (flops/bytes) or
       an explicit counted fallback — a compiled-but-invisible
       executable is the bug class the census exists for.
    2. **Interleaved A/B census overhead** (census off vs on) inside a
       ``tracecheck.steady_state`` region: >5% min-over-ratios overhead
       (one automatic A/B re-run — the shared ``_ab_overhead_gate``)
       fails, any retrace delta fails (flipping the census must never
       rebuild a step).
    3. **Roofline ledger**: the ``xla`` entry of ``ledger_stats`` must
       carry per-executable flops/MFU/bound rows, and ``/api/metrics``
       (``prometheus_text``) must expose them.
    4. **Regression gate drill**: a deliberately-regressed synthetic
       record (step time +20%) against this run's own record must TRIP
       ``benchtrack.compare_records``; the clean copy must pass.
    5. **HBM watermarks**: the per-epoch ``fit`` phase must have
       sampled, and ``dump_memory_census`` must write a parseable
       census (the crash-blackbox companion).
    """
    import statistics as _stats
    import tempfile

    import jax

    from deeplearning4j_tpu.common import tracecheck, xprof
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.data import NDArrayDataSetIterator
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.parallel import ServingEngine
    from deeplearning4j_tpu.ui.server import prometheus_text
    from tools import benchtrack

    prof = OpProfiler.get()
    rng = np.random.RandomState(0)
    n = steps * batch + batch // 2
    x = rng.randn(n, 1, 28, 28).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)]

    def make_it():
        return NDArrayDataSetIterator(x, y, batch_size=batch)

    def fail(msg, **extra):
        print(json.dumps({"error": msg, **extra}, default=str))
        sys.exit(1)

    # ---- phase 1: census coverage (fit + infer + serving ladder) -------
    xprof.reset()
    xprof.configure(enabled=True)
    prof.reset()
    model = _lenet_model()
    model.fit(make_it(), epochs=1)
    float(model._score_dev)
    model.output(x[:batch])                      # mln/infer executable

    sconf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
             .activation("tanh").list()
             .layer(L.DenseLayer(n_out=32))
             .layer(L.OutputLayer(n_out=10))
             .set_input_type(InputType.feed_forward(16)).build())
    smodel = MultiLayerNetwork(sconf).init()
    eng = (ServingEngine.Builder(smodel)
           .buckets([1, 4, 8]).input_shape((16,))
           .workers(1).max_wait_ms(1.0).build())
    try:
        analyzed = xprof.analyze()
        census = xprof.census()
        compiled_here = ["mln/fit_step", "mln/infer", "serving/bucket"]
        missing = [name for name in compiled_here if name not in census]
        if missing:
            fail("compiled executables missing from the census",
                 missing=missing, census=sorted(census))
        for name in compiled_here:
            entry = census[name]
            cost = entry.get("cost") or {}
            if entry.get("cost_source") == "xla" and not cost:
                fail(f"census entry {name} claims xla analysis but "
                     "carries no cost fields", entry=entry)
            if entry.get("cost_source") is None:
                fail(f"census entry {name} was never analyzed (no cost, "
                     "no counted fallback)", entry=entry)
        if census["serving/bucket"]["variants"] \
                != len(eng.ladder.batch_sizes):
            fail("serving bucket census variants != ladder size",
                 variants=census["serving/bucket"]["variants"],
                 buckets=len(eng.ladder.batch_sizes))
    finally:
        eng.shutdown()

    # ---- phase 2: interleaved A/B census on/off ------------------------
    models = {"off": _lenet_model(), "on": _lenet_model()}
    for m in models.values():
        m.fit(make_it(), epochs=1)               # warmup compile
        float(m._score_dev)
    prof.reset()

    def timed_epoch(name):
        m = models[name]
        xprof.configure(enabled=(name == "on"))
        t0 = time.perf_counter()
        m.fit(make_it(), epochs=1)
        float(m._score_dev)
        return time.perf_counter() - t0

    try:
        with tracecheck.steady_state("xprof-smoke timed rounds",
                                     max_host_syncs=None):
            overhead, times, overhead_runs = _ab_overhead_gate(
                "executable-census", 0.05,
                lambda: _ab_rounds(timed_epoch, rounds=5), fail)
    except tracecheck.SteadyStateViolation as e:
        fail("train step retraced inside a timed window — flipping the "
             "census must not destabilize shapes",
             violation=str(e).splitlines()[0])
    finally:
        xprof.configure(enabled=True)
    t_off = _stats.median(times["off"])
    t_on = _stats.median(times["on"])

    # ---- phase 3: xla roofline ledger + Prometheus exposition ----------
    ledgers = prof.ledger_stats()
    xla = ledgers.get("xla", {})
    if not any(k.endswith("/flops") for k in xla):
        fail("xla ledger carries no per-executable flops rows",
             keys=sorted(xla)[:20])
    if not any(k.endswith("/compute_bound") for k in xla):
        fail("xla ledger carries no bound-classification rows",
             keys=sorted(xla)[:20])
    metrics_text = prometheus_text()
    if 'ledger="xla"' not in metrics_text:
        fail("/api/metrics exposition is missing the xla ledger family")

    # ---- phase 4: the --compare-to regression gate drill ---------------
    epoch_steps = -(-len(x) // batch)
    step_ms = t_on / epoch_steps * 1e3
    base_rec = {"metric": "xprof_smoke", "value": len(x) / t_on,
                "unit": "images/sec", "batch": batch,
                "platform": jax.devices()[0].platform,
                "step_ms_median": round(step_ms, 3),
                "step_ms_p10": round(step_ms * 0.97, 3)}
    regressed = dict(base_rec)
    regressed["step_ms_median"] = round(step_ms * 1.2, 3)
    regressed["step_ms_p10"] = round(step_ms * 1.18, 3)
    regressed["value"] = base_rec["value"] / 1.2
    tripped = benchtrack.compare_records(
        {"xprof_smoke": base_rec}, {"xprof_smoke": regressed})
    if not tripped["violations"]:
        fail("the regression gate FAILED to flag a 20% step-time "
             "regression", result=tripped)
    clean = benchtrack.compare_records(
        {"xprof_smoke": base_rec}, {"xprof_smoke": dict(base_rec)})
    if clean["violations"]:
        fail("the regression gate flagged an identical re-run",
             result=clean)

    # ---- phase 5: HBM watermarks + memory-census dump ------------------
    wms = xprof.watermarks()
    if "fit" not in wms or wms["fit"]["samples"] < 1:
        fail("per-epoch fit watermark never sampled", watermarks=wms)
    if wms["fit"]["peak_live_bytes"] <= 0:
        fail("fit watermark peak is zero", watermarks=wms)
    dump_path = os.path.join(tempfile.mkdtemp(prefix="xprof_smoke_"),
                             "memcensus.json")
    xprof.dump_memory_census(dump_path)
    blob = json.load(open(dump_path))
    if "watermarks" not in blob or "census" not in blob:
        fail("memory-census dump is malformed", keys=sorted(blob))

    images = n + (batch - n % batch) % batch
    return {
        "metric": "xprof_smoke",
        "value": images / t_on,
        "unit": "images/sec",
        "batch": batch,
        "platform": jax.devices()[0].platform,
        "census_overhead_frac": round(overhead, 4),
        "overhead_runs": overhead_runs,
        "epoch_s_off_median": round(t_off, 4),
        "epoch_s_on_median": round(t_on, 4),
        "census_executables": len(census),
        "analyzed": sorted(analyzed),
        "xla_ledger_rows": len(xla),
        "fit_watermark": wms.get("fit"),
        "gate_drill_violations": tripped["violations"],
        "data": "synthetic LeNet batches + a warmed 3-bucket serving "
                "ladder; census coverage, A/B census overhead, xla "
                "roofline/Prometheus, regression-gate drill, HBM "
                "watermark + memcensus dump",
    }


def _fleet_mlp(seed=7, n_in=64, n_out=10, hidden=32, lr=1e-3):
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import layers as L

    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=lr)).activation("tanh")
            .weight_init("xavier").list()
            .layer(L.DenseLayer(n_out=hidden))
            .layer(L.OutputLayer(n_out=n_out, loss="mse",
                                 activation="identity"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def bench_fleet_smoke(steps: int, batch: int = 64,
                      members: int = 8) -> dict:
    """CPU-friendly smoke of fleet training (parallel.fleet): an M-member
    stacked MLP population trained through ONE vmapped+jitted step.
    Self-validating hard gates:

    - **bitwise member parity**: member 3 of the fleet equals the same
      model trained SOLO with the same RNG stream (``solo_twin``), every
      param leaf bit-for-bit, after the full timed run;
    - **one compile for the whole fleet**: ``trace/fleet_step`` moves by
      exactly 1 per fleet instance, and the lifecycle phase — steps, a
      mid-run cull, a spawn, a per-member NaN injection, a telemetry
      drain — runs inside ``tracecheck.steady_state`` (any retrace
      fails the run; the drain's batched device_get is the declared
      sync budget);
    - **cull drill**: the culled member's params are bit-frozen while
      the rest keep training;
    - **per-member NaN drill**: a NaN batch fed to ONE member flips only
      that member's alive bit (``fleet/nan_cull``), and every OTHER
      member's params are bitwise identical to a clean control run;
    - **throughput**: the fleet trains M=8 members at >= 3x the summed
      per-model sequential baseline (one model's timed epoch x M).
    """
    import statistics as _stats

    import jax

    from deeplearning4j_tpu.common import flightrec, tracecheck
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.optimize import NanSentinelListener
    from deeplearning4j_tpu.parallel import FleetTrainer

    rng = np.random.RandomState(0)
    x = rng.randn(batch, 64).astype(np.float32)
    y = rng.randn(batch, 10).astype(np.float32)
    prof = OpProfiler.get()

    def fail(msg, **extra):
        print(json.dumps({"error": msg, **extra}))
        sys.exit(1)

    # ---- phase 1: parity + throughput (no telemetry, the hot shape) ----
    fleet = FleetTrainer(_fleet_mlp(), members, seed=7)
    solo = fleet.solo_twin(3)
    from deeplearning4j_tpu.data.dataset import DataSet

    ds = DataSet(x, y)
    t0 = prof.counter_value("trace/fleet_step")
    fleet.step(x, y)                      # warmup (the one compile)
    solo.fit(ds, epochs=1)
    jax.block_until_ready(fleet._params)

    t_start = time.perf_counter()
    for _ in range(steps):
        fleet.step(x, y)
    jax.block_until_ready(fleet._params)
    fleet_s = time.perf_counter() - t_start

    # first solo epoch lands on the SAME step count as the fleet — the
    # parity gate compares here; two more epochs refine the timing median
    solo_times = []
    t_start = time.perf_counter()
    for _ in range(steps):
        solo.fit(ds, epochs=1)
    jax.block_until_ready(solo._params)
    solo_times.append(time.perf_counter() - t_start)

    if prof.counter_value("trace/fleet_step") - t0 != 1:
        fail("fleet step traced more than once",
             traces=prof.trace_counts())
    p_f = jax.tree.leaves(jax.tree.map(lambda a: np.array(a[3]),
                                       fleet._params))
    p_s = jax.tree.leaves(jax.tree.map(np.array, solo._params))
    if not all(np.array_equal(a, b) for a, b in zip(p_f, p_s)):
        md = max(float(np.max(np.abs(a - b))) for a, b in zip(p_f, p_s))
        fail("fleet member 3 is not bitwise identical to its solo twin",
             max_abs_diff=md)

    for _ in range(2):
        t_start = time.perf_counter()
        for _ in range(steps):
            solo.fit(ds, epochs=1)
        jax.block_until_ready(solo._params)
        solo_times.append(time.perf_counter() - t_start)
    solo_s = _stats.median(solo_times)
    speedup = (members * solo_s) / fleet_s
    if speedup < 3.0:
        fail(f"fleet throughput {speedup:.2f}x the summed sequential "
             f"baseline (gate: >= 3x at M={members})",
             fleet_s=round(fleet_s, 4), solo_s=round(solo_s, 4))

    # ---- phase 2: lifecycle under steady_state (sweep + cull + NaN) ----
    def lifecycle(inject_nan: bool):
        """One deterministic lifecycle run; the drill and its clean
        control share everything but the poisoned batch."""
        fl = FleetTrainer.from_sweep(
            _fleet_mlp(), {"lr": [1e-3] * (members // 2)
                           + [3e-3] * (members - members // 2)},
            seed=7, drain_every_n=4)
        fl.set_listeners(NanSentinelListener("cull", check_every_n=4))
        # warmup: trace the step, warm the cull/spawn dispatch paths and
        # the per-member batch shape, then drain
        fl.step(x, y)
        xs = np.broadcast_to(x, (members,) + x.shape).copy()
        ys = np.broadcast_to(y, (members,) + y.shape).copy()
        fl.step(xs, ys, per_member=True)
        fl.cull(0, reason="warmup")
        fl.step(x, y)
        fl.spawn(0)
        fl.drain()
        return fl, xs, ys

    fl, xs, ys = lifecycle(False)
    ctrl, _, _ = lifecycle(False)
    flightrec.reset()
    try:
        with tracecheck.steady_state("fleet lifecycle",
                                     max_host_syncs=None):
            for s in range(6):
                fl.step(x, y)
                ctrl.step(x, y)
            # cull drill: member 5 freezes mid-run (both runs)
            fl.cull(5, reason="drill")
            ctrl.cull(5, reason="drill")
            frozen_at = jax.tree.map(lambda a: np.array(a[5]),
                                     fl._params)
            for s in range(4):
                fl.step(x, y)
                ctrl.step(x, y)
            # NaN drill: poison member 2's batch in the drill run only
            bad = xs.copy()
            bad[2] = np.nan
            fl.step(bad, ys, per_member=True)
            ctrl.step(xs, ys, per_member=True)
            for s in range(4):
                fl.step(x, y)
                ctrl.step(x, y)
            frozen_check = jax.tree.map(lambda a: np.array(a[5]),
                                        fl._params)
            fl.spawn(5)
            ctrl.spawn(5)
            fl.step(x, y)
            ctrl.step(x, y)
            fl.drain()
            ctrl.drain()
    except tracecheck.SteadyStateViolation as e:
        fail("fleet lifecycle retraced inside the steady-state region",
             violation=str(e).splitlines()[0])

    alive = fl.alive_mask()
    if alive[2] != 0:
        fail("per-member NaN drill did not cull the poisoned member",
             alive=alive.tolist())
    if not flightrec.events("fleet/nan_cull"):
        fail("no fleet/nan_cull event on the timeline")
    # cull drill: between its cull and its spawn, member 5's slice must
    # not have moved a single bit while the rest of the fleet trained on
    if not all(np.array_equal(a, b)
               for a, b in zip(jax.tree.leaves(frozen_at),
                               jax.tree.leaves(frozen_check))):
        fail("cull drill: the culled member's params moved")
    for m in range(members):
        if m == 2:
            continue
        a = jax.tree.leaves(jax.tree.map(lambda t: np.array(t[m]),
                                         fl._params))
        b = jax.tree.leaves(jax.tree.map(lambda t: np.array(t[m]),
                                         ctrl._params))
        if not all(np.array_equal(u, v) for u, v in zip(a, b)):
            fail(f"NaN drill perturbed member {m} (must be "
                 f"bit-unaffected)", member=m)

    images = steps * batch * members
    return {
        "metric": "fleet_smoke",
        "value": images / fleet_s,
        "unit": "member-images/sec",
        "batch": batch,
        "members": members,
        "platform": jax.devices()[0].platform,
        "fleet_epoch_s": round(fleet_s, 4),
        "solo_epoch_s": round(solo_s, 4),
        "speedup_vs_sequential": round(speedup, 2),
        "speedup_gate": 3.0,
        "traces": prof.trace_counts(),
        "bitwise_member_parity": True,
        "nan_cull_events": len(flightrec.events("fleet/nan_cull")),
        "cull_events": len(flightrec.events("fleet/cull")),
        "spawn_events": len(flightrec.events("fleet/spawn")),
        "alive_after_drills": alive.tolist(),
        "fleet_ledger": prof.fleet_stats(),
        "telemetry_drain": {k: (round(v, 5) if isinstance(v, float) else v)
                            for k, v in prof.telemetry_stats().items()},
        "data": "synthetic 64-feature MLP batches; M-member vmapped "
                "fleet vs solo-twin bitwise parity, cull/spawn/NaN "
                "drills inside one steady_state region",
    }


def bench_word2vec(steps: int) -> dict:
    """North-star config 4: Word2Vec skip-gram + negative sampling over a
    synthetic zipfian corpus; throughput = corpus words consumed / sec
    end-to-end (host pair-generation + fused device rounds), the number the
    reference logs at INFO during SequenceVectors.fit (SURVEY §3.6).
    ``steps`` scales the corpus: steps * 1000 sentences of 20 words.
    The word2vec default is 200 (a 4M-word corpus): throughput on this
    config is steady-state-dominated the way the reference's INFO number
    is; tiny corpora mostly measure per-process trace/executable-load."""
    import jax

    from deeplearning4j_tpu.nlp import Word2Vec

    rng = np.random.default_rng(123)
    vocab_size, n_sent, sent_len = 10_000, steps * 1000, 20
    p = 1.0 / np.arange(1, vocab_size + 1)
    p /= p.sum()
    words = np.array([f"w{i}" for i in range(vocab_size)])
    ids = rng.choice(vocab_size, size=(n_sent, sent_len), p=p)
    sents = [" ".join(row) for row in words[ids]]

    w2v = _w2v_model()
    w2v.set_sentence_iterator(sents)
    # Same methodology as the lenet/resnet/bert benches: compile excluded,
    # steady state timed. fit() #1 builds vocab + traces/compiles the block
    # and trains once (cold, recorded); fit() #2 reuses the compiled block
    # (resume semantics) — its words/sec is uploads + pair derivation +
    # device rounds + final value-fence, none of it compilation.
    w2v.fit()
    cold = w2v.words_per_sec
    w2v.fit()
    return {
        "metric": "word2vec_skipgram_train",
        "value": w2v.words_per_sec,
        "unit": "words/sec",
        "platform": jax.devices()[0].platform,
        "vocab": len(w2v.vocab),
        "corpus_words": n_sent * sent_len,
        "pairs_per_sec": round(w2v.pairs_per_sec),
        "cold_words_per_sec": round(cold),
        "layer_size": 100, "negative": 5, "window": 5,
        "data": "synthetic zipfian corpus (host RAM)",
        "final_loss": round(w2v.last_loss, 4),
    }


def _first_step_child(config: str) -> None:
    """ONE optimizer step end-to-end, meant to run in a FRESH process (the
    parent times the whole process: interpreter + imports + model build +
    trace + compile-or-cache-load + execute = time-to-first-step)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.data import DataSet

    rng = np.random.RandomState(0)
    if config == "lenet":
        model = _lenet_model()                 # shared flagship builder
        x = rng.randn(128, 1, 28, 28).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, 128)]
        model.fit(DataSet(x, y))
        loss = float(model._score_dev)
    elif config == "resnet50":
        # batch 64 (the cold ledger's recorded shape); the throughput
        # bench default is 128 — the MODEL is the shared builder either way
        model = _resnet50_model(224)
        x = rng.randn(64, 3, 224, 224).astype(np.float32)
        y = np.eye(1000, dtype=np.float32)[rng.randint(0, 1000, 64)]
        model.fit(DataSet(jnp.asarray(x), jnp.asarray(y)))
        loss = float(model._score_dev)
    elif config == "bert":
        step, params, upd, ph, _ = _bert_training(batch=32, seq=128)
        _, _, loss_dev = step(params, upd, ph, jax.random.PRNGKey(0),
                              jnp.asarray(0))
        loss = float(loss_dev)
    elif config == "word2vec":
        w2v = _w2v_model()
        w2v.set_sentence_iterator(_zipf_sentences(400_000))
        w2v.fit()
        loss = w2v.last_loss
    else:
        raise SystemExit(f"unknown first-step config {config}")
    assert np.isfinite(loss), f"non-finite first-step loss for {config}"
    print(f"FIRST_STEP_OK {config} loss={loss:.4f}", flush=True)


def _assert_no_live_backend() -> None:
    """One process per chip: a parent that has initialised a JAX backend
    holds the chip, and a child that needs it then fails or hangs."""
    from jax._src import xla_bridge

    assert not xla_bridge._backends, (
        "this process already initialised a JAX backend "
        f"({sorted(xla_bridge._backends)}); its children could not get "
        "the chip")


def cold_audit(configs=("lenet", "resnet50", "bert", "word2vec")) -> None:
    """Time-to-first-step ledger (round-5 item 6; SURVEY §5.6, §7.3 item
    8 compile-cost honesty): for each flagship, spawn a FRESH process
    against an empty persistent compile cache (cold) and a second fresh
    process against the now-populated cache (warm). Emits one JSON line
    per config with both wall times. The cache is a fixed sub-directory
    of the process's own cache (the path is part of the cache key),
    emptied first and handed to the children through
    ``JAX_COMPILATION_CACHE_DIR``."""
    import shutil
    import subprocess
    import sys

    from deeplearning4j_tpu.common.environment import enable_compilation_cache

    here = os.path.dirname(os.path.abspath(__file__))
    cache = os.path.join(enable_compilation_cache(), "cold_audit")
    for config in configs:
        shutil.rmtree(cache, ignore_errors=True)
        os.makedirs(cache)
        times = []
        for run in ("cold", "warm_cache"):
            env = dict(os.environ)
            env["JAX_COMPILATION_CACHE_DIR"] = cache
            _assert_no_live_backend()
            t0 = time.perf_counter()
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--first-step", config],
                env=env, cwd=here, capture_output=True, text=True)
            dt = time.perf_counter() - t0
            if proc.returncode != 0 or "FIRST_STEP_OK" not in proc.stdout:
                raise RuntimeError(
                    f"first-step {config} ({run}) failed rc="
                    f"{proc.returncode}:\n{proc.stdout}\n{proc.stderr}")
            times.append(dt)
        print(json.dumps({
            "metric": f"time_to_first_step_{config}",
            "value": round(times[1], 2), "unit": "seconds",
            "vs_baseline": 1.0,
            "cold_s": round(times[0], 2),
            "warm_cache_s": round(times[1], 2),
            "speedup": round(times[0] / max(times[1], 1e-9), 2),
            "note": "fresh process each; cold = empty persistent "
                    "compile cache, warm = same cache populated by the "
                    "cold run; time includes interpreter+imports+build+"
                    "trace+compile-or-load+one optimizer step",
        }), flush=True)


def _zipf_sentences(n_words: int, vocab_size: int = 10_000,
                    sent_len: int = 20, seed: int = 123):
    rng = np.random.default_rng(seed)
    n_sent = max(1, n_words // sent_len)
    p = 1.0 / np.arange(1, vocab_size + 1)
    p /= p.sum()
    words = np.array([f"w{i}" for i in range(vocab_size)])
    ids = rng.choice(vocab_size, size=(n_sent, sent_len), p=p)
    return [" ".join(row) for row in words[ids]]


def bench_word2vec_variant(steps: int, algorithm: str = "cbow",
                           hs: bool = False) -> dict:
    """CBOW / hierarchical-softmax driver-visible lines (VERDICT r4 item
    7): same corpus/methodology as bench_word2vec, different training
    path."""
    import jax

    from deeplearning4j_tpu.nlp import Word2Vec

    sents = _zipf_sentences(steps * 1000 * 20)
    w2v = Word2Vec(min_word_frequency=5, layer_size=100, window=5,
                   negative=0 if hs else 5, use_hierarchic_softmax=hs,
                   sampling=1e-3, epochs=1, batch_size=8192, seed=42,
                   algorithm=algorithm)
    w2v.set_sentence_iterator(sents)
    w2v.fit()
    cold = w2v.words_per_sec
    w2v.fit()
    name = f"word2vec_{algorithm}{'_hs' if hs else ''}_train"
    return {
        "metric": name, "value": w2v.words_per_sec, "unit": "words/sec",
        "platform": jax.devices()[0].platform, "vocab": len(w2v.vocab),
        "corpus_words": len(sents) * 20,
        "cold_words_per_sec": round(cold),
        "layer_size": 100, "window": 5,
        "negative": w2v.negative, "hs": hs,
        "data": "synthetic zipfian corpus (host RAM)",
        "final_loss": round(w2v.last_loss, 4),
    }


def bench_paragraph_vectors(steps: int) -> dict:
    """PV-DBOW on the device-windowed machinery (VERDICT r4 weak #1 /
    round-5 item 2): 40k docs x 100 words; words/sec includes the
    interleaved word-vector pass (reference default
    trainElementsRepresentation=true)."""
    import jax

    from deeplearning4j_tpu.nlp import ParagraphVectors
    from deeplearning4j_tpu.nlp.text import LabelAwareIterator

    doc_len = 100
    n_docs = max(10, steps * 1000 * 20 // doc_len)
    docs = _zipf_sentences(n_docs * doc_len, sent_len=doc_len)
    labels = [f"DOC_{i}" for i in range(len(docs))]
    pv = (ParagraphVectors.builder().min_word_frequency(5).layer_size(100)
          .epochs(1).negative_sample(5).batch_size(8192).seed(42)
          .sampling(1e-3)
          .iterate(LabelAwareIterator(docs, labels)).build())
    pv.fit()
    cold = pv.words_per_sec
    pv.fit()
    return {
        "metric": "paragraph_vectors_dbow_train",
        "value": pv.words_per_sec, "unit": "words/sec",
        "platform": jax.devices()[0].platform,
        "vocab": len(pv.vocab), "n_docs": n_docs,
        "corpus_words": n_docs * doc_len,
        "cold_words_per_sec": round(cold),
        "train_word_vectors": True,
        "data": "synthetic zipfian docs (host RAM)",
        "final_loss": round(pv.last_loss, 4),
    }


def bench_glove(n_words: int = 1_000_000) -> dict:
    import jax

    from deeplearning4j_tpu.nlp import Glove

    sents = _zipf_sentences(n_words)
    g = (Glove.builder().min_word_frequency(5).layer_size(100)
         .window_size(5).epochs(5).batch_size(8192).seed(42)
         .iterate(sents).build())
    g.fit()
    return {
        "metric": "glove_train", "value": g.words_per_sec,
        "unit": "words/sec", "platform": jax.devices()[0].platform,
        "vocab": len(g.vocab), "corpus_words": n_words, "epochs": 5,
        "data": "synthetic zipfian corpus (host RAM); includes host "
                "co-occurrence accumulation",
    }


def bench_fasttext(n_words: int = 1_000_000) -> dict:
    import jax

    from deeplearning4j_tpu.nlp import FastText

    sents = _zipf_sentences(n_words)
    ft = (FastText.builder().min_word_frequency(5).layer_size(100)
          .negative_sample(5).epochs(1).batch_size(8192).seed(42)
          .iterate(sents).build())
    ft.fit()
    cold = ft.words_per_sec
    ft.fit()
    return {
        "metric": "fasttext_train", "value": ft.words_per_sec,
        "unit": "words/sec", "platform": jax.devices()[0].platform,
        "vocab": len(ft.vocab), "corpus_words": n_words,
        "cold_words_per_sec": round(cold),
        "data": "synthetic zipfian corpus (host RAM); round-5 "
                "device-windowed subword path (subword windows gathered "
                "on device)",
    }


def main() -> None:
    # zero1-smoke / elastic-smoke need a multi-replica mesh: request
    # virtual CPU devices BEFORE anything imports jax (the library import
    # just below does). The flag only affects the host platform —
    # harmless on TPU runs.
    if ({"zero1-smoke", "elastic-smoke", "pipeline-parallel-smoke",
         "soak-smoke", "integrity-smoke"}
            & set(sys.argv)) and "jax" not in sys.modules:
        _flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in _flags:
            os.environ["XLA_FLAGS"] = (
                _flags + " --xla_force_host_platform_device_count=8").strip()
    # Persistent executable cache: compile each bench module once per
    # MACHINE, not once per process (the reference ships pre-built libnd4j
    # kernels; this is the XLA analog). First-ever run still pays the
    # compile; every later run loads the serialized executable. The one
    # placement rule lives in the library: $JAX_COMPILATION_CACHE_DIR where
    # set, else <checkout>/.jax_cache.
    from deeplearning4j_tpu.common.environment import enable_compilation_cache
    enable_compilation_cache()

    parser = argparse.ArgumentParser()
    parser.add_argument("--first-step", default=None,
                        help="internal: run ONE optimizer step of the named "
                             "config and exit (spawned by --cold-audit)")
    parser.add_argument("--cold-audit", nargs="?", const="all", default=None,
                        help="time-to-first-step ledger: fresh process per "
                             "flagship, cold vs populated compile cache; "
                             "optionally a comma-separated config subset")
    parser.add_argument("--config", default="flagships",
                        choices=["flagships", "lenet", "resnet50", "bert",
                                 "word2vec", "word2vec-cbow", "word2vec-hs",
                                 "paragraph-vectors", "glove", "fasttext",
                                 "resnet50-disk", "resnet50-predecoded",
                                 "pipeline-smoke", "telemetry-smoke",
                                 "fault-smoke", "supervisor-smoke",
                                 "zero1-smoke", "elastic-smoke",
                                 "cluster-smoke",
                                 "pipeline-parallel-smoke",
                                 "serving-smoke", "autoscale-smoke",
                                 "mfu-smoke", "obs-smoke", "fleet-smoke",
                                 "xprof-smoke", "remat-smoke",
                                 "soak-smoke", "integrity-smoke"])
    parser.add_argument("--steps", type=int, default=None)
    parser.add_argument("--batch", type=int, default=None,
                        help="per-config default: resnet50=128, bert=32")
    parser.add_argument("--image-size", type=int, default=None,
                        help="resnet50 input resolution (default 224; "
                             "smaller sizes make CPU re-baselines "
                             "tractable — the emitted record pins it)")
    parser.add_argument("--compare-to", default=None, metavar="ROUND",
                        help="regression gate: after the run, hold every "
                             "emitted record against the same metric in "
                             "this BENCH_r*.json round (tools/benchtrack "
                             "min-over-rounds gates: step time, "
                             "throughput, MFU, compile counts, state "
                             "bytes); exit non-zero on any violation")
    parser.add_argument("--with-listener", action="store_true",
                        help="attach a ScoreIterationListener during the timed "
                             "run (validates the listener bus does not tax the "
                             "hot loop)")
    args = parser.parse_args()

    if args.first_step:
        # the cold-audit parent placed this child's cache through
        # JAX_COMPILATION_CACHE_DIR, which the rule above left alone
        _first_step_child(args.first_step)
        return
    if args.cold_audit:
        if args.cold_audit == "all":
            cold_audit()
        else:
            cold_audit(tuple(args.cold_audit.split(",")))
        return

    if args.config.endswith("-smoke"):
        # dirty lint refuses to bench: the smoke configs assert hot-loop
        # invariants (no retraces, no host syncs, fault sites firing) —
        # running them over a package that fails the static versions of
        # those same invariants produces numbers nobody should trust
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from tools import graftlint

        lint = graftlint.lint(os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "deeplearning4j_tpu"))
        if not lint.clean:
            for f in lint.findings:
                print(f.render(), file=sys.stderr)
            print(json.dumps({"error": "graftlint preflight failed — fix "
                              "or suppress (with a reason) before "
                              "benching",
                              "findings": len(lint.findings)}))
            sys.exit(1)

    steps = args.steps or 30
    emitted: list = []

    def emit(result: dict) -> None:
        base = BASELINES.get(result["metric"], {}).get("value")
        vs = (result["value"] / base) if base else 1.0
        ordered = {"metric": result.pop("metric"),
                   "value": round(result.pop("value"), 2),
                   "unit": result.pop("unit"),
                   "vs_baseline": round(vs, 3)}
        ordered.update(result)
        emitted.append(ordered)
        print(json.dumps(ordered), flush=True)

    def finish() -> None:
        """The --compare-to regression gate (ISSUE 15): every emitted
        record is held against the baseline round's same-metric record;
        any violation is a hard non-zero exit. Cross-platform records
        are skipped (reported, never failed)."""
        if not args.compare_to:
            return
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from tools import benchtrack

        baseline = benchtrack.parse_round(args.compare_to)
        current = {r["metric"]: r for r in emitted}
        result = benchtrack.compare_records(baseline["records"], current)
        print(json.dumps({"compare_to": args.compare_to, **result}),
              flush=True)
        if result["violations"]:
            sys.exit(1)

    if args.config == "flagships":
        # The default run tells the WHOLE flagship story (round-3 verdict
        # item 5): BERT (the matmul-dominated model, 48.7% MFU class) and
        # Word2Vec print first, ResNet-50 LAST for drivers that parse the
        # final line (the bandwidth-bound model whose 25-30% MFU band the
        # round-3 audit pinned to BatchNorm/HBM, not code). --steps scales
        # all three; --batch applies to ResNet-50 only (BERT's 32 is its
        # measured plateau and its vs_baseline anchor is batch-32).
        emit(bench_bert(args.steps or 80, batch=32))
        emit(bench_word2vec(args.steps or 200))
        # NLP family (round-5 items 2+7): CBOW + HS driver-visible w2v
        # variants, PV-DBOW on the device-windowed path, GloVe + FastText
        emit(bench_word2vec_variant(args.steps or 200, "cbow"))
        emit(bench_word2vec_variant(args.steps or 200, "skipgram", hs=True))
        emit(bench_paragraph_vectors(args.steps or 200))
        emit(bench_glove())
        emit(bench_fasttext())
        emit(bench_resnet50(args.steps or 80, batch=args.batch or 128,
                            image_size=args.image_size or 224,
                            with_listener=args.with_listener))
        finish()
        return
    if args.config == "lenet":
        result = bench_lenet(steps, with_listener=args.with_listener)
    elif args.config == "bert":
        # batch 32 was the throughput plateau on r05's set-up (2026-07-31);
        # not measured on this chip
        result = bench_bert(steps, batch=args.batch or 32)
    elif args.config == "word2vec":
        result = bench_word2vec(args.steps or 200)
    elif args.config == "word2vec-cbow":
        result = bench_word2vec_variant(args.steps or 200, "cbow")
    elif args.config == "word2vec-hs":
        result = bench_word2vec_variant(args.steps or 200, "skipgram",
                                        hs=True)
    elif args.config == "paragraph-vectors":
        result = bench_paragraph_vectors(args.steps or 200)
    elif args.config == "glove":
        result = bench_glove(n_words=(args.steps or 50) * 20_000)
    elif args.config == "fasttext":
        result = bench_fasttext(n_words=(args.steps or 20) * 20_000)
    elif args.config == "pipeline-smoke":
        result = bench_pipeline_smoke(steps, batch=args.batch or 64)
    elif args.config == "telemetry-smoke":
        result = bench_telemetry_smoke(steps, batch=args.batch or 64)
    elif args.config == "fault-smoke":
        result = bench_fault_smoke(steps, batch=args.batch or 64)
    elif args.config == "supervisor-smoke":
        result = bench_supervisor_smoke(steps, batch=args.batch or 64)
    elif args.config == "zero1-smoke":
        result = bench_zero1_smoke(steps, batch=args.batch or 64)
    elif args.config == "mfu-smoke":
        result = bench_mfu_smoke(steps, batch=args.batch or 64)
    elif args.config == "remat-smoke":
        result = bench_remat_smoke(steps, batch=args.batch or 64)
    elif args.config == "elastic-smoke":
        result = bench_elastic_smoke(steps, batch=args.batch or 64)
    elif args.config == "cluster-smoke":
        result = bench_cluster_smoke(steps)
    elif args.config == "pipeline-parallel-smoke":
        result = bench_pipeline_parallel_smoke(steps, batch=args.batch or 64)
    elif args.config == "serving-smoke":
        result = bench_serving_smoke(steps, batch=args.batch or 32)
    elif args.config == "autoscale-smoke":
        result = bench_autoscale_smoke(steps, batch=args.batch or 32)
    elif args.config == "soak-smoke":
        result = bench_soak_smoke(steps, batch=args.batch or 32)
    elif args.config == "integrity-smoke":
        result = bench_integrity_smoke(steps, batch=args.batch or 64)
    elif args.config == "obs-smoke":
        result = bench_obs_smoke(steps, batch=args.batch or 64)
    elif args.config == "fleet-smoke":
        result = bench_fleet_smoke(steps, batch=args.batch or 64)
    elif args.config == "xprof-smoke":
        result = bench_xprof_smoke(steps, batch=args.batch or 64)
    elif args.config == "resnet50-disk":
        result = bench_resnet50_disk(steps, batch=args.batch or 64,
                                     image_size=args.image_size or 224)
    elif args.config == "resnet50-predecoded":
        result = bench_resnet50_predecoded(
            steps, batch=args.batch or 64,
            image_size=args.image_size or 224)
    else:
        result = bench_resnet50(steps, batch=args.batch or 128,
                                image_size=args.image_size or 224,
                                with_listener=args.with_listener)
    emit(result)
    finish()


if __name__ == "__main__":
    main()
