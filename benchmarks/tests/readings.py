#!/usr/bin/env python3
"""Readings for the limits of a training cell's comparison, in one process.

    python3 benchmarks/tests/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] \
        [--witness <precision>[,<precision>]] [--program compute_dtype=float32] \
        [--rehearse]

For every seed: the program's first steps through its own ``fit`` against the
plain reference (the lower readings). For the control seeds: the reference
computed in the configuration's ``control_precision``, put in the program's
place (the upper readings), and beside it each ``--witness`` precision of
``precisions.py``. For the fault seeds: the reference with half of the batch
left out, put in the program's place. ``--program key=value`` builds the
program with that key of its configuration changed (a witness for a gap that
is put down to the configuration's precision); its readings are printed as
``program_witness`` and set no limit.

One JSON line per reading, each with the verdict of ``compare.judge`` under
the cell's own limits; the last line gathers, for each number, the largest
lower reading and the smallest control and fault readings. The exit code is 1
where a sound run of the program fails the limits, or a control or a fault
passes them. Runs on the chip at the cell's own size; the benchmark's own
runs never call it.
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
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--witness", default="")
    ap.add_argument("--program", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    ints = lambda s: [int(x) for x in s.split(",") if x]     # noqa: E731

    import compare
    import precisions
    import run

    cell, cfg, mix = run.load_cell(
        run.load_json(os.path.join(run.ROOT, "BENCHMARK.json")),
        args.workload, args.rehearse)
    jax = run.start_jax(args.rehearse)

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU")
    conf = run.load_module("configs", cell["config"])
    gen = run.load_module("traffic", mix["generator"])
    sizes = conf.sizes_of(cfg, args.rehearse)
    limits = cfg["limits_tiny" if args.rehearse else "limits"]
    n = mix["first_steps"]
    program_cfg, kind = dict(cfg), "lower"
    if args.program:
        key, _, value = args.program.partition("=")
        if key not in cfg:
            raise SystemExit(f"the configuration has no key {key!r}")
        program_cfg[key], kind = value, "program_witness"
    seeds = ints(args.seeds)
    job = conf.build(program_cfg, sizes, cell["chips"], mix) if seeds else None
    summary, wrong = {}, []

    def note(kind, seed, found, has_to_pass, **extra):
        ok, _ = compare.judge(found, limits)
        row = {k: v[0] for k, v in found.items()}
        print(json.dumps({"kind": kind, "seed": seed, "passes": ok, **row,
                          "where": {k: v[1] for k, v in found.items() if v[1]},
                          **extra}), flush=True)
        if has_to_pass is not None and ok != has_to_pass:
            wrong.append(f"{kind} seed {seed} "
                         f"{'passes' if ok else 'fails'} the limits")
        for k, v in row.items():
            summary.setdefault(kind, {}).setdefault(k, []).append(v)

    for seed in sorted(set(seeds) | set(ints(args.control_seeds))
                       | set(ints(args.fault_seeds))):
        batches = gen.make(mix, sizes, seed, n)
        ref = compare.reference_norms(conf.reference(cfg, sizes, seed, batches))
        if seed in seeds:
            w0 = conf.make_weights(cfg, sizes, seed, mix)
            w0_host = jax.device_get(w0)
            job.reset(w0)
            del w0
            prog = compare.drive_first_steps(job, batches, w0_host)
            del w0_host
            note(kind, seed, compare.gaps(prog, ref),
                 None if args.program else True, loss=prog["loss"],
                 reference_loss=ref["loss"], program=args.program)
        if seed in ints(args.control_seeds):
            for name in [cfg["control_precision"],
                         *(w for w in args.witness.split(",") if w)]:
                is_control = name == cfg["control_precision"]
                low = compare.reference_norms(conf.reference(
                    cfg, sizes, seed, batches, lower=precisions.get(name)))
                note("control" if is_control else "witness:" + name, seed,
                     compare.gaps(low, ref), False if is_control else None,
                     loss=low["loss"])
        if seed in ints(args.fault_seeds):
            flt = compare.reference_norms(conf.reference(
                cfg, sizes, seed, batches, fault="half_batch"))
            note("half_batch", seed, compare.gaps(flt, ref), False,
                 loss=flt["loss"])
        del batches
        gc.collect()
    pick = {"lower": max, "program_witness": max}
    print(json.dumps({"summary": {
        f"{kind}_{pick.get(kind, min).__name__}":
            {k: pick.get(kind, min)(v) for k, v in rows.items()}
        for kind, rows in summary.items()}, "limits": limits, "wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
