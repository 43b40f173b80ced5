"""What decides ``correct``, tested at the files' ``tiny`` sizes on the CPU
(by hand: ``python3 -m pytest benchmarks/tests/test_correct.py -q``).

- the control: the plain reference computed in the configuration's
  ``control_precision`` and put in the program's place has to come out as
  not correct;
- the faults a training cell can have, planted in the timed path underneath
  a whole run of ``run.py`` (only the look for a chip is skipped, by
  ``--rehearse``): a step that returns its state unchanged, and half of the
  batch left out with the mean taken over the rest. ``correct`` has to come
  out false, and true for the same run with nothing planted.

The limits at these sizes are the configuration's ``limits_tiny`` (set from
CPU readings of ``readings.py --rehearse``; the chip's limits are for the
cell's own size and would say nothing here).
"""

from __future__ import annotations

import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [HERE, BENCH, ROOT]

import compare  # noqa: E402
import run  # noqa: E402
import precisions  # noqa: E402  (benchmarks/tests/precisions.py)

CELLS = ["resnet50.train", "bert_base.finetune"]


def _files(cell_name: str):
    cell, cfg, mix = run.load_cell(
        run.load_json(os.path.join(ROOT, "BENCHMARK.json")), cell_name, True)
    conf = run.load_module("configs", cell["config"])
    gen = run.load_module("traffic", mix["generator"])
    return cfg, mix, conf, gen


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [21, 22, 23])
def test_control_comes_out_not_correct(cell, seed):
    cfg, mix, conf, gen = _files(cell)
    sizes = conf.sizes_of(cfg, True)
    batches = gen.make(mix, sizes, seed, mix["first_steps"])
    ref = compare.reference_norms(conf.reference(cfg, sizes, seed, batches))
    ctl = compare.reference_norms(conf.reference(
        cfg, sizes, seed, batches,
        lower=precisions.get(cfg["control_precision"])))
    ok, rows = compare.judge(compare.gaps(ctl, ref), cfg["limits_tiny"])
    assert not ok, rows
    same, rows = compare.judge(compare.gaps(ref, ref), cfg["limits_tiny"])
    assert same, rows


class _Broken:
    """A job with a fault planted in its timed path; everything else is the
    job's own."""

    def __init__(self, job, fault: str):
        self._job, self._fault = job, fault

    def __getattr__(self, name):
        return getattr(self._job, name)

    def feed(self, batches):
        if self._fault == "half_batch":
            # rows of the second half replaced by the first half's: the loss,
            # the gradient and BatchNorm's statistics are then those of the
            # first half alone, at the shapes the step was compiled for
            def halved(a):
                h = a.shape[0] // 2
                out = a.copy()
                out[h:2 * h] = a[:h]
                return out

            batches = [{k: halved(v) for k, v in b.items()} for b in batches]
        return self._job.feed(batches)

    def fit(self, data, epochs):
        if self._fault != "state_unchanged":
            return self._job.fit(data, epochs)
        import jax
        import jax.numpy as jnp

        job = self._job
        copy = lambda t: jax.tree.map(jnp.copy, t)      # noqa: E731
        if hasattr(job, "sd"):                          # bert_base
            before = {v: jnp.copy(jnp.asarray(job.sd._vars[v].value))
                      for v in job.names.values()}
            job.fit(data, epochs)
            for v, value in before.items():
                job.sd._vars[v].value = value
            job.sd._updater_state = jax.tree.map(
                jnp.zeros_like, job.sd._updater_state)
        else:                                           # resnet50
            m = job.model
            before = copy(m._params), copy(m._states)
            job.fit(data, epochs)
            m._params, m._states = before
            m._updater_state = jax.tree.map(jnp.zeros_like, m._updater_state)


def _whole_run(cell: str, fault: str, capsys, monkeypatch) -> dict:
    real = run.load_module

    def load_module(kind, name):
        mod = real(kind, name)
        if kind == "configs" and fault:
            build = mod.build
            mod.build = lambda *a: _Broken(build(*a), fault)
        return mod

    monkeypatch.setattr(run, "load_module", load_module)
    assert run.main(["--workload", cell, "--seed", "31", "--seconds", "0.5",
                     "--trace", "0", "--rehearse"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["", "state_unchanged", "half_batch"])
def test_fault_in_the_timed_path_reads_not_correct(cell, fault, capsys,
                                                   monkeypatch):
    result = _whole_run(cell, fault, capsys, monkeypatch)
    assert result["correct"] is (fault == ""), result["compared"]
