#!/usr/bin/env python3
"""A benchmark cell's training step compiled for a DESCRIBED TPU v5e, here, with
no chip: what the step's scratch will be, and how XLA fused the stochastic
rounding of the updater state.

    JAX_PLATFORMS=cpu python3 tools/compile_step.py <workload> [--root CHECKOUT]
                                                    [--scope NAME] [--dump FILE]

It builds the cell's job at its real sizes (the model is initialised on the
CPU: a few GB for the decoder cells), traces ``nn.train_step``'s step with
shapes placed on a described ``v5e:2x2`` device and compiles it with the
TPU's compiler (``phi4_mini_flash.train_s8k`` and ``lfm2_moe.train_b2_s8k``:
70-90 s each on 8 cores). ``jax.default_backend`` is made to answer ``"tpu"``
for the process, so the ops take their Pallas lowerings as they do on the chip.
``temp`` is ``memory_analysis().temp_size_in_bytes``: the benchmark's
``memory_step_scratch_bytes`` (PR 33: 4,142,870,528 here against 4,142,902,784
on the chip for ``lfm2_moe.train_b2_s8k``), so a change's effect on
``hbm_peak_gb`` can be read before any chip call; ``sequence_stats`` are the
``seq/*`` counters that tracing the step bumped (call sites on a kernel or on
the XLA path, the attention band's pairs and the forward's grid steps).
Nothing runs: no time, no rate. One process at a time (libtpu's lock).
``--root`` takes an unpacked parent commit, to compare. ``--scope`` lists what
the compiled step runs under a named scope apart from its Pallas calls (PR
37: ``--scope moe_experts`` shows whether a buffer-sized fusion, copy or
convert stands between the routed layer's kernels), largest result first;
``--dump`` writes the compiled module's text. Only ``ComputationGraph.fit``
cells.
"""

import argparse
import collections
import math
import os
import re
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--scope", default="")
    ap.add_argument("--dump", default="")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.chdir(root)
    sys.path[:0] = [os.path.join(root, "benchmarks"), root]
    import run                                  # benchmarks/run.py

    cell, cfg, mix = run.load_cell(
        run.load_json(os.path.join(root, "BENCHMARK.json")), args.workload,
        False)
    jax = run.start_jax(True)
    # an executable for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    device = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    jax.default_backend = lambda: "tpu"         # the kernels' switch
    conf = run.load_module("configs", cell["config"])
    gen = run.load_module("traffic", mix["generator"])
    sizes = conf.sizes_of(cfg, False)
    job = conf.build(cfg, sizes, 1, mix)
    model = job.model
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.nn.train_step import make_core, step_program

    batch = model._bind(job.feed(gen.make(mix, sizes, 1, 1)))

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=device), tree)

    updater = model.conf.global_conf.updater
    step = step_program(make_core(model, None), "trace/compile_step",
                        batch_len=3)
    t0 = time.time()
    compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
        described(model._params), described(model._states),
        described(jax.eval_shape(updater.init, model._params)),
        *described(tuple(batch[:3])),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=device),
        jax.ShapeDtypeStruct((), jnp.int64, sharding=device,
                             weak_type=True)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    rounding = collections.Counter(
        line.split(" fusion(")[0].count("bf16[")
        for line in entry.splitlines()
        if "is-finite_select_fusion" in line and " fusion(" in line)
    print({"workload": args.workload, "root": root,
           "compile_s": round(time.time() - t0, 1),
           "temp": mem.temp_size_in_bytes,
           "arguments": mem.argument_size_in_bytes,
           "outputs": mem.output_size_in_bytes,
           "aliased": mem.alias_size_in_bytes,
           "pallas_calls": text.count("tpu_custom_call"),
           "rounding_fusions_by_bf16_outputs": sorted(rounding.items()),
           # what tracing the step bumped, the kernels' switch on
           "sequence_stats": OpProfiler.get().sequence_stats(),
           "moe_stats": OpProfiler.get().moe_stats()})
    if args.scope:
        print_scope(entry, args.scope)
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(text)


def print_scope(entry: str, scope: str) -> None:
    """The entry computation's instructions whose ``op_name`` holds
    ``scope``, Pallas calls left out: how many, the elements of their
    (largest) results together, then the twelve largest."""
    found = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?(\S+) = (.*?) ([\w\-]+)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if not m or not name or scope not in name.group(1) \
                or "tpu_custom_call" in line:
            continue
        size = max((math.prod(map(int, dims.split(",")))
                    for dims in re.findall(r"\[([\d,]+)\]", m.group(2))),
                   default=1)
        found.append((size, m.group(3), m.group(1), m.group(2)[:70],
                      name.group(1)[-70:]))
    found.sort(reverse=True)
    print({"scope": scope, "instructions": len(found),
           "elements_of_results": sum(f[0] for f in found)})
    for row in found[:12]:
        print("  ", row)


if __name__ == "__main__":
    main()
