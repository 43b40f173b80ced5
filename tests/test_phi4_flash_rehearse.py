"""The benchmark's cell ``phi4_mini_flash.train_s8k`` walks end to end at its
files' tiny sizes on the CPU: ``benchmarks/run.py --rehearse`` exits 0, is
``correct`` under the configuration's ``limits_tiny``, and reads the
sequence kernels' fallback count (on the CPU: what the XLA paths took); the
manifest with the new entries passes its own checks."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "phi4_mini_flash.train_s8k"


def _run(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), *args],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(trace):
    p = _run("--workload", CELL, "--rehearse", "--seed", "1", "--seconds", "1",
             "--trace", str(trace))
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    held = {k for k, row in result["compared"].items()
            if row["limit"] is not None}
    assert held == {"first_gradient_median_leaf", "param_change_median_leaf"}
    if trace:
        # two scans and three attention layers, counted once each as the
        # step is traced
        got = result["metrics"]["seq_kernel_fallbacks"]
        assert got["unit"] == "count" and got["value"] >= 5
        assert result["metrics"]["compile_in_window"]["value"] == 0
        # a CPU run gives no share of a roofline
        assert not any("roofline" in name for name in result["metrics"])


def test_manifest_with_the_new_entries():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "manifest.py")],
        capture_output=True, text=True, cwd=ROOT, timeout=120)
    assert p.returncode == 0, p.stderr
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi4_mini_flash", "lm_stream_b1_s8192", 1)
    names = {"scan_roofline_share", "attention_fwd_roofline_share",
             "seq_kernel_fallbacks"}
    new = {x["name"]: x for x in m["per_layer"] if x["name"] in names}
    assert set(new) == names
    assert all(x["workloads"] == [CELL] and x["layer"] == "kernels"
               and x["moves"] == "examples_per_s" for x in new.values())
