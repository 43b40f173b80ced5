"""Driver ``train_fit``: a training job that a user runs by calling ``fit``.

The configuration's module gives the job (``build``), its weights from the
seed (``make_weights``) and its plain reference (``reference``); the mix's
generator gives host batches from the seed. This file keeps the order:

- ``setup``: build ONE job, hand it the benchmark's weights, drive its own
  ``fit`` through the first steps on rows that all differ and read what the
  comparison needs, then warm up with one pass over all batches;
- ``window``: the same job, ``fit(data, epochs=epochs_per_call)`` called
  again and again until the clock passes ``--seconds``, one fence at the
  end. The rate is every example dispatched over the whole window;
- ``traced`` (a ``--trace 1`` run): one more such call with the profiler on,
  after the window, so that the window's own clock never pays for it;
- ``check``: once the window has closed and the peak has been read, free the
  job and run the reference over the same first steps.
"""

from __future__ import annotations

import gc
import time

import compare


def setup(ctx: dict) -> None:
    import jax

    cfg, sizes, mix, conf = ctx["cfg"], ctx["sizes"], ctx["mix"], ctx["conf"]
    seed = ctx["args"].seed
    job = conf.build(cfg, sizes, ctx["chips"], mix)
    ctx["phase"]("build", **ctx["meter"].snapshot())
    batches = ctx["generator"].make(mix, sizes, seed, mix["batches"])
    w0 = conf.make_weights(cfg, sizes, seed, mix)
    w0_host = jax.device_get(w0)    # the check keeps nothing on the device
    job.reset(w0)
    del w0
    first = batches[: mix["first_steps"]]
    ctx["program_readings"] = compare.drive_first_steps(job, first, w0_host)
    del w0_host
    ctx["phase"]("first_steps", loss=ctx["program_readings"]["loss"],
                 **ctx["meter"].snapshot())
    data = job.feed(batches)
    job.fit(data, epochs=1)
    job.fence()
    ctx["phase"]("warmup", **ctx["meter"].snapshot())
    ctx.update(job=job, data=data, first_batches=first,
               examples_per_epoch=ctx["generator"].examples(mix),
               steps_per_epoch=mix["batches"])
    del batches
    gc.collect()


def window(ctx: dict) -> None:
    import jax

    job, data, epochs = ctx["job"], ctx["data"], ctx["mix"]["epochs_per_call"]
    seconds = ctx["args"].seconds
    calls = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with jax.profiler.TraceAnnotation("bench/fit_call"):
            job.fit(data, epochs=epochs)
        calls += 1
    with jax.profiler.TraceAnnotation("bench/final_fence"):
        job.fence()
    ctx["window_s"] = time.perf_counter() - t0
    ctx["fit_calls"] = calls
    ctx["steps"] = calls * epochs * ctx["steps_per_epoch"]
    ctx["examples"] = calls * epochs * ctx["examples_per_epoch"]
    ctx["attempted"], ctx["failed"] = ctx["steps"], 0


def traced(ctx: dict) -> None:
    """The traced window: one more call like the window's own, fenced. A mix
    whose calls are long names a shorter one (``traced_epochs``): a step is
    thousands of device events, and the trace has to be read inside the
    run's time."""
    import jax

    mix = ctx["mix"]
    with jax.profiler.TraceAnnotation("bench/fit_call"):
        ctx["job"].fit(ctx["data"], epochs=mix.get("traced_epochs",
                                                   mix["epochs_per_call"]))
    with jax.profiler.TraceAnnotation("bench/final_fence"):
        ctx["job"].fence()


def check(ctx: dict) -> tuple:
    cfg, sizes, conf = ctx["cfg"], ctx["sizes"], ctx["conf"]
    ctx.pop("job").free()
    ctx.pop("data")
    gc.collect()
    t0 = time.perf_counter()
    ref = compare.reference_norms(conf.reference(
        cfg, sizes, ctx["args"].seed, ctx["first_batches"]))
    found = compare.gaps(ctx["program_readings"], ref)
    # the chip's limits are for the cell's own size; a rehearsal at the
    # tiny sizes is held to limits read at those
    ok, rows = compare.judge(
        found, cfg["limits_tiny" if ctx["rehearse"] else "limits"])
    ctx["phase"]("compare", seconds=round(time.perf_counter() - t0, 2),
                 reference_loss=ref["loss"])
    return ok, rows
