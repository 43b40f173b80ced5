#!/usr/bin/env python3
"""Readings for the limits of a training cell's comparison where the
program's job and the f32 reference do not fit on the chip together.

    python3 benchmarks/tests/freed_readings.py --workload <cell> \
        --seeds 1,2,3 --control-seed 1 [--faults half_batch,<name>] \
        [--rehearse]

``readings.py`` keeps the program's job on the device while each reference
runs. Here the program's first steps run on every seed through its own
``fit`` (one build, the norms fetched to the host), the job is freed, and
only then does each seed's exact reference run, and for ``--control-seed``
the reference in the configuration's ``control_precision`` and with each of
``--faults`` (``half_batch`` is every configuration's; a configuration's
``reference`` docstring names its others), each put in the program's place.
The numbers are ``compare``'s, as ``run.py`` and ``readings.py`` take them.

One JSON line per reading, each with the verdict of ``compare.judge`` under
the cell's own limits; the last line gathers, for each number, the largest
program reading and the smallest control and fault readings, and names the
control and faults that pass the limits (``unseen``). The exit code is 1
where a program reading fails the limits. Runs on the chip at the cell's own
size; the benchmark's own runs never call it.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
HERE = os.path.dirname(TESTS)
sys.path[:0] = [TESTS, HERE, os.path.dirname(HERE)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seed", type=int, required=True)
    ap.add_argument("--faults", default="half_batch")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import compare
    import precisions
    import run

    cell, cfg, mix = run.load_cell(
        run.load_json(os.path.join(run.ROOT, "BENCHMARK.json")),
        args.workload, args.rehearse)
    jax = run.start_jax(args.rehearse)
    conf = run.load_module("configs", cell["config"])
    gen = run.load_module("traffic", mix["generator"])
    sizes = conf.sizes_of(cfg, args.rehearse)
    limits = cfg["limits_tiny" if args.rehearse else "limits"]
    n = mix["first_steps"]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    if args.control_seed not in seeds:
        seeds.append(args.control_seed)

    def note(kind, seed, found):
        ok, _ = compare.judge(found, limits)
        print(json.dumps({"kind": kind, "seed": seed, "passes": ok,
                          **{k: v[0] for k, v in found.items()},
                          "where": {k: v[1] for k, v in found.items() if v[1]}}),
              flush=True)
        return ok, {k: v[0] for k, v in found.items()}

    job = conf.build(cfg, sizes, 1, mix)
    progs = {}
    for seed in seeds:
        w0 = conf.make_weights(cfg, sizes, seed, mix)
        w0_host = jax.device_get(w0)
        job.reset(w0)
        del w0
        progs[seed] = compare.drive_first_steps(
            job, gen.make(mix, sizes, seed, n), w0_host)
        del w0_host
    job.free()
    del job
    gc.collect()

    kinds = [("control", {"lower": precisions.get(cfg["control_precision"])})]
    kinds += [(f, {"fault": f}) for f in args.faults.split(",") if f]
    rows, failed, unseen = {}, [], []
    for seed in seeds:
        batches = gen.make(mix, sizes, seed, n)
        ref = compare.reference_norms(conf.reference(cfg, sizes, seed, batches))
        ok, row = note("lower", seed, compare.gaps(progs[seed], ref))
        rows.setdefault("lower", []).append(row)
        if not ok:
            failed.append(seed)
        if seed == args.control_seed:
            for kind, kw in kinds:
                bad = compare.reference_norms(
                    conf.reference(cfg, sizes, seed, batches, **kw))
                ok, row = note(kind, seed, compare.gaps(bad, ref))
                rows.setdefault(kind, []).append(row)
                if ok:
                    unseen.append(kind)
        del batches
        gc.collect()
    print(json.dumps({
        "summary": {kind: {k: (max if kind == "lower" else min)(r[k] for r in rs)
                           for k in rs[0]} for kind, rs in rows.items()},
        "limits": limits, "program_fails": failed, "unseen": unseen}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
