#!/usr/bin/env python3
"""The benchmark's command: one cell, one seed, one window, one result line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one kind of
cell or one metric is a file that this program finds by the name in
``BENCHMARK.json`` (see ``benchmarks/README.md``). The program itself only
parses, checks the device, keeps the clocks and the profiler, and prints.

One process; it is the only one that touches JAX. Without a TPU holding the
chips the cell asks for it exits non-zero and prints no result —
``--rehearse`` instead walks the same code at the files' ``tiny`` sizes on
whatever JAX has (the CPU), says ``"platform": "cpu"`` and prints no number
under a device metric's name.

Phase lines (``setup``, ``first_steps``, ``warmup``, ``window``,
``compare``) are JSON objects on earlier lines of standard output; the last
line is the result. The numbers compared for ``correct`` are also the last
lines of standard error, each beside its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse                      # noqa: E402
import importlib.util                # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402
import tempfile                      # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest as manifest_mod      # noqa: E402  (benchmarks/manifest.py)


class NoChip(RuntimeError):
    """JAX has no TPU, or fewer chips than the cell asks for."""


class Meter:
    """What JAX itself reports: seconds spent tracing, lowering and
    compiling (or loading from the persistent cache), the cache's hits and
    misses, and the number of backend compilations. Copied from
    ``chip_smoke.Meter``."""

    _COMPILE = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration",
                "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        import jax.monitoring

        self.compile_s, self.hits, self.misses, self.backend = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in self._COMPILE:
            self.compile_s += secs
        if event == self._COMPILE[2]:
            self.backend += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s, "cache_hits": self.hits,
                "cache_misses": self.misses, "backend_compiles": self.backend}


def load_module(kind: str, name: str):
    """The file ``benchmarks/<kind>/<name>.py`` as a module."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def phase(name: str, **fields) -> None:
    print(json.dumps({"phase": name,
                      "t_s": round(time.perf_counter() - T_START, 3),
                      **fields}), flush=True)


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))["peaks"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmarks/peaks.json: add it with its source")
    return table[device_kind]


def device_memory(used: list, step_program: str | None) -> dict:
    """The peak of device memory, in two parts that the runtime keeps apart.
    ``memory_stats()["peak_bytes_in_use"]`` is the allocator's peak of live
    buffers (arguments, results, what the process holds between calls) since
    the process began; on this TPU runtime it does NOT hold what an
    executable takes while it runs (a program with 2 GiB of temporaries over
    a 1 GiB argument leaves it at 1.08 GB: ``PERF.md`` section 4). That
    scratch is ``get_compiled_memory_stats().temp_size_in_bytes`` of the
    loaded executable: the largest among those named ``step_program`` (all of
    them, where the configuration names none). The peak is their sum: the
    step runs while the buffers are live."""
    live = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    scratch = 0
    for exe in used[0].client.live_executables():
        names = [m.name for m in exe.hlo_modules()]
        if step_program and not any(n.startswith(step_program) for n in names):
            continue
        scratch = max(scratch, exe.get_compiled_memory_stats().temp_size_in_bytes)
    return {"memory_peak_bytes": live + scratch,
            "memory_live_peak_bytes": live,
            "memory_step_scratch_bytes": scratch}


def load_cell(manifest: dict, name: str, rehearse: bool) -> tuple:
    """(cell, configuration, mix) of a workload as its files state them; a
    rehearsal takes the mix's ``tiny`` parameters."""
    cell = manifest_mod.cell(manifest, name)
    cfg = load_json(os.path.join(
        ROOT, manifest_mod.config(manifest, cell["config"])["file"]))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    if rehearse:
        mix = {**mix, **mix.get("tiny", {})}
    return cell, cfg, mix


def start_jax(rehearse: bool):
    """Import JAX with the one compile-cache rule: where the environment
    names a directory, that one; else a fixed directory inside the checkout.
    A rehearsal runs on the CPU unless the environment says otherwise."""
    if rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(HERE, ".jax_cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever JAX has; no device metric")
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another BENCHMARK.json (the harness's own tests)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    manifest = load_json(args.manifest)
    manifest_mod.check(manifest)
    cell, cfg, mix = load_cell(manifest, args.workload, args.rehearse)
    jax = start_jax(args.rehearse)
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and (dev.platform != "tpu"
                              or len(devices) < cell["chips"]):
        raise NoChip(f"cell {cell['name']} asks for {cell['chips']} TPU "
                     f"chip(s); JAX has {len(devices)} x {dev.platform}")
    if len(devices) < cell["chips"]:
        raise NoChip(f"the rehearsal of {cell['name']} needs "
                     f"{cell['chips']} devices (XLA_FLAGS="
                     f"--xla_force_host_platform_device_count)")
    used = devices[:cell["chips"]]

    meter = Meter()
    conf_mod = load_module("configs", cell["config"])
    ctx = {
        "args": args, "cell": cell, "cfg": cfg, "mix": mix,
        "conf": conf_mod, "sizes": conf_mod.sizes_of(cfg, args.rehearse),
        "generator": load_module("traffic", mix["generator"]),
        "chips": cell["chips"], "meter": meter,
        "rehearse": args.rehearse, "phase": phase,
        "peaks": None if args.rehearse else peaks_for(dev.device_kind),
        "trace": None,
    }
    driver = load_module("drivers", mix["driver"])

    driver.setup(ctx)
    ctx["setup_s"] = time.perf_counter() - T_START
    phase("setup_done", setup_s=round(ctx["setup_s"], 3), **meter.snapshot())

    kind = "per_layer" if args.trace else "end_to_end"
    readers = {
        m["name"]: load_module("metrics", m["name"]) for m in manifest[kind]
        if ("workloads" not in m or cell["name"] in m["workloads"])
        # a CPU run gives no time, rate or share of a peak
        and (not args.rehearse or m["source"] == "program_counter")}
    for reader in readers.values():
        if hasattr(reader, "start"):    # a counter's reading before the window
            reader.start(ctx)

    before = meter.snapshot()
    driver.window(ctx)
    for reader in readers.values():
        if hasattr(reader, "stop"):     # and its reading as the window closes
            reader.stop(ctx)
    memory = device_memory(used, getattr(conf_mod, "STEP_PROGRAM", None))
    ctx.update(memory)
    phase("window", window_s=round(ctx["window_s"], 4),
          attempted=ctx["attempted"], failed=ctx["failed"], **memory)

    if args.trace:
        # The profiler runs over a window of its own, after the measured one
        # and of the same work (``driver.traced``: one more call), so that the
        # host-clock metrics of a traced run are taken with the profiler off
        # and the trace stays small enough to read inside the run's time.
        import trace_reduce

        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # TraceMe spans only: small files
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                driver.traced(ctx)
            finally:
                jax.profiler.stop_trace()
            xplane = trace_reduce.find_xplane(trace_dir)
            ctx["trace"] = trace_reduce.reduce(
                xplane, step_program=getattr(conf_mod, "STEP_PROGRAM", None),
                on_device=not args.rehearse)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        phase("trace", busy_s=ctx["trace"]["busy_s"],
              window_s=ctx["trace"]["window_s"])
    after = meter.snapshot()
    ctx["compile_in_window"] = (
        after["backend_compiles"] - before["backend_compiles"]
        + after["cache_misses"] - before["cache_misses"])
    if ctx["compile_in_window"]:
        raise RuntimeError(f"{ctx['compile_in_window']} program(s) compiled "
                           "inside the measured window: warm-up is incomplete")

    correct, compared = driver.check(ctx)

    metrics = {}
    for m in manifest[kind]:
        if m["name"] in readers:
            value = readers[m["name"]].read(ctx)
            if value is not None:   # a reader that found nothing to read
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), **memory}
    result = {"correct": bool(correct), "attempted": ctx["attempted"],
              "failed": ctx["failed"], "metrics": metrics, "device": device}
    if ctx["trace"] is not None and not args.rehearse:
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = {"device_ops": ctx["trace"]["device_ops"][:10],
                               "idle_gaps": ctx["trace"]["idle_gaps"][:10]}
    result["compared"] = compared       # last: each number beside its limit
    sys.stdout.flush()
    for name, row in compared.items():
        print(f"compared {name}: value {row['value']!r} limit "
              f"{row['limit']!r} {row.get('where', '')}", file=sys.stderr)
    print(f"correct: {bool(correct)}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except NoChip as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        sys.exit(3)
