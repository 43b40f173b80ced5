"""Harness tests, run by hand (``python3 -m pytest benchmarks/tests -q``);
they are not part of the tier-1 suite. Everything here runs on the CPU at the
files' ``tiny`` sizes; nothing it prints is a device number."""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import manifest  # noqa: E402


def _manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _run(args, env=None, manifest_path=None):
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), *args]
    if manifest_path:
        cmd += ["--manifest", manifest_path]
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                       env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})
    return p, (json.loads(p.stdout.strip().splitlines()[-1])
               if p.returncode == 0 else None)


# -- BENCHMARK.json -----------------------------------------------------------

def test_manifest_as_committed_is_well_formed():
    manifest.check(_manifest())


@pytest.mark.parametrize("path,value", [
    (("per_layer", 0, "name"), "dispatch ms"),          # a space
    (("per_layer", 0, "unit"), "tokens per second"),    # spaces, too long
    (("end_to_end", 0, "unit"), "µs"),             # the Greek letter
    (("workloads", 0, "name"), "a/b"),                  # a slash
    (("end_to_end", 0, "bound"), 0.5),                  # over 0.1
    (("per_layer", 0, "moves"), "no_such_metric"),
])
def test_malformed_manifest_is_caught_before_a_run(path, value):
    m = copy.deepcopy(_manifest())
    node = m
    for k in path[:-1]:
        node = node[k]
    node[path[-1]] = value
    with pytest.raises(manifest.Malformed):
        manifest.check(m)


def test_unknown_device_kind_raises():
    import run

    with pytest.raises(KeyError):
        run.peaks_for("TPU v9 imaginary")
    assert run.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12


# -- the trace reduction --------------------------------------------------------

def test_union():
    import trace_reduce as tr

    merged = tr.union([(0, 10), (5, 12), (20, 30), (30, 31), (40, 41)])
    assert merged == [[0, 12], [20, 31], [40, 41]]


def test_reducer_on_the_recorded_trace():
    import trace_reduce as tr

    with open(os.path.join(BENCH, "testdata", "expected.json")) as f:
        want = json.load(f)
    got = tr.reduce(os.path.join(BENCH, "testdata", want["file"]),
                    step_program=want["step_program"])
    for key in ("busy_s", "window_s", "step_executions", "step_busy_s",
                "mxu_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
    assert got["device_ops"][0][0] == want["top_op"]
    assert got["idle_gaps"][0][0] == want["longest_gap_span"]
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["step_busy_s"] <= got["busy_s"] * (1 + 1e-9)
    assert got["mxu_s"] <= got["step_busy_s"]


SYNTHETIC = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 60000000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 40000000 duration_ps: 20000000 }
    events { metadata_id: 1 offset_ps: 80000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, calls=%c" } }
  event_metadata { key: 2 value { id: 2 name: "%copy.3 = f32[8]{0} copy(f32[8]{0} %p)" } }
  event_metadata { key: 3 value { id: 3 name: "%convolution.2 = f32[8]{0} convolution(f32[8]{0} %a, f32[8]{0} %b)" } }
  event_metadata { key: 9 value { id: 9 name: "jit_step(123)" } } }
planes { name: "/host:CPU"
  lines { name: "main" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 16000000 duration_ps: 22000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench/fit_call" } }
  event_metadata { key: 2 value { id: 2 name: "TransferToDevice" } } }
"""


def test_reducer_on_a_trace_written_by_hand(monkeypatch):
    """Known numbers: ops at 0-10, 5-15 and 40-60 us inside one execution of
    the step (0-60 us) and one at 80-85 us outside it, in a 100 us window."""
    import trace_reduce as tr
    from jax.profiler import ProfileData

    monkeypatch.setattr(tr, "load", lambda _: ProfileData.from_text_proto(
        SYNTHETIC))
    got = tr.reduce("by hand", step_program="jit_step")
    us = 1e-6
    assert got["window_s"] == pytest.approx(100 * us)
    assert got["busy_s"] == pytest.approx((15 + 20 + 5) * us)   # the union
    assert got["step_executions"] == 1
    assert got["step_busy_s"] == pytest.approx(35 * us)
    # the kOutput fusion inside the step and the convolution; not the copy,
    # and not the fusion's second execution outside the step
    assert got["mxu_s"] == pytest.approx((10 + 20) * us)
    assert got["device_ops"][0] == ["convolution", pytest.approx(20 * us)]
    assert got["device_ops"][1] == ["fusion[kOutput]", pytest.approx(15 * us)]
    # the longest gap, 15-40 us, is named after the runtime span that covers
    # most of it; the last, 85-100 us, after the benchmark's own span
    assert got["idle_gaps"][0] == ["TransferToDevice", pytest.approx(25 * us)]
    assert got["idle_gaps"][1][1] == pytest.approx(20 * us)
    assert got["idle_gaps"][2] == [
        "bench/fit_call: Python between runtime calls", pytest.approx(15 * us)]


# -- the command, end to end at tiny sizes ---------------------------------------

@pytest.mark.parametrize("cell", ["resnet50.train", "bert_base.finetune"])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearse_end_to_end(cell, trace):
    p, result = _run(["--workload", cell, "--seed", "2147483999",
                      "--seconds", "1", "--trace", trace, "--rehearse"])
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, result["compared"]
    assert list(result)[-1] == "compared"
    # a rehearsal prints no time, rate or share under a metric's name
    assert set(result["metrics"]) <= {"compile_in_window"}
    assert "busy_s" not in result["device"]
    # the peak is the allocator's live peak plus the scratch of the step's
    # executable, which was found by the configuration's STEP_PROGRAM
    d = result["device"]
    assert d["memory_step_scratch_bytes"] > 0
    assert d["memory_peak_bytes"] == (d["memory_live_peak_bytes"]
                                      + d["memory_step_scratch_bytes"])


def test_rehearse_four_devices(tmp_path):
    """A four-chip cell is an entry with ``chips: 4``: ``build`` wraps the
    model in ParallelWrapper and the same files run it."""
    m = _manifest()
    assert m["workloads"][0]["name"] == "resnet50.train"
    m["workloads"][0]["chips"] = 4      # a pair of config and mix appears once
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    p, result = _run(
        ["--workload", "resnet50.train", "--seed", "5", "--seconds", "1",
         "--trace", "0", "--rehearse"], manifest_path=str(path),
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["device"]["count"] == 4


def test_no_chip_no_result():
    p, _ = _run(["--workload", "resnet50.train", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and p.stdout.strip() == ""
