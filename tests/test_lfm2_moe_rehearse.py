"""The benchmark's cell ``lfm2_moe.train_b2_s8k`` walks end to end at its
files' tiny sizes on the CPU: ``benchmarks/run.py --rehearse`` exits 0, is
``correct`` under the configuration's ``limits_tiny``, and reads the expert
and attention kernels' fallback count (on the CPU: what the XLA paths took);
the manifest with the new entries passes its own checks."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "lfm2_moe.train_b2_s8k"


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
    assert held == {"first_gradient_median_leaf", "param_change_median_leaf",
                    "buffer_change"}
    # the routed layers' expert_load is compared with the reference's counts
    assert result["compared"]["buffer_change"]["where"].endswith("expert_load")
    if trace:
        # four routed layers' two grouped products and one attention layer,
        # forward and backward, counted as the step is traced
        got = result["metrics"]["moe_kernel_fallbacks"]
        assert got["unit"] == "count" and got["value"] >= 9
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
        "lfm2_moe", "lm_stream_b2_s8192", 1)
    conf = next(c for c in m["configs"] if c["name"] == "lfm2_moe")
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert "LiquidAI/LFM2-24B-A2B" in conf["source"]
    names = {"moe_gmm_roofline_share", "moe_kernel_fallbacks"}
    new = {x["name"]: x for x in m["per_layer"] if x["name"] in names}
    assert set(new) == names
    assert all(x["workloads"] == [CELL] and x["layer"] == "kernels"
               and x["moves"] == "examples_per_s" for x in new.values())
    # the accepted closed lists stay the accepted cells' (PR 36 appended
    # its six ``scope_*`` entries, whose lists name the cell)
    assert all(CELL not in x.get("workloads", []) for x in m["per_layer"]
               if x["name"] not in names
               and not x["name"].startswith("scope_"))


def test_the_file_states_the_published_config_and_the_cut():
    """Every number of the catalog row's config is in the file under its
    key, but for the three keys of ``reduced``."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2_moe.json")) as f:
        cfg = json.load(f)
    published = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
                 "intermediate_size": 11776, "max_position_embeddings": 128000,
                 "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
                 "norm_eps": 1e-05, "norm_topk_prob": True,
                 "num_attention_heads": 32, "num_dense_layers": 2,
                 "num_experts_per_tok": 4, "num_key_value_heads": 8,
                 "routed_scaling_factor": 1, "use_expert_bias": True}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_parameters"] == {"rope_theta": 1000000,
                                      "rope_type": "default"}
    assert len(cfg["layer_types"]) == 40
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 8, 8192)
    assert cfg["published"]["num_experts"] == cfg["router_width"] == 64
    assert cfg["experts_held"] == [0, 8] and cfg["layers_kept"] == [1, 2, 3, 4, 5]


def test_gmm_roofline_share_reads_rows_and_seconds_of_the_traced_call():
    """The held experts' load grows while the cell trains, so the share's
    rows are the traced call's own: the load as the window closes (``stop``)
    against the reading ``Job.free`` keeps after the traced call; and a job
    without the state reads nothing."""
    import importlib.util
    import types

    import numpy as np

    def load(kind, name):
        spec = importlib.util.spec_from_file_location(
            "bench_" + name, os.path.join(ROOT, "benchmarks", kind,
                                          name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    metric = load("metrics", "moe_gmm_roofline_share")
    conf = load("configs", "lfm2_moe")
    sizes = {"experts_held": [0, 8], "hidden_size": 2048,
             "moe_intermediate_size": 1536}
    counts = lambda held: {f"l{l}_ffn": {"expert_load": np.concatenate(  # noqa: E731
        [np.full(8, held / 8.0), np.full(56, 7.0)]).astype(np.float32)}
        for l in (2, 3, 4, 5)}
    job = types.SimpleNamespace(buffers=lambda: counts(1000.0))
    ctx = {"job": job, "sizes": sizes, "conf": conf, "cfg": {}, "chips": 1,
           "peaks": {"flops_per_s": 197e12}, "trace": None}
    metric.stop(ctx)
    assert metric.read(ctx) is None             # an untraced run
    ctx["trace"] = {"step_executions": 8, "device_ops": [
        ("fusion[kOutput]", 1.0), ("moe_gmm", 0.08), ("moe_gmm_dw", 0.02)]}
    assert metric.read(ctx) is None             # the job is still alive
    job.last_buffers = counts(1000.0 + 8 * 8192.0)     # what free() keeps
    # 4 layers x 8,192 rows a step for 8 steps; 12.5 ms of kernels a step
    least = 18.0 * 4 * 8192 * 2048 * 1536 / 197e12
    assert abs(metric.read(ctx) - 100.0 * least / 0.0125) < 1e-9
    ctx["trace"]["device_ops"] = [("fusion[kOutput]", 1.0)]
    assert metric.read(ctx) is None             # the XLA path ran
    bare = {**ctx, "job": types.SimpleNamespace()}
    bare.pop("moe_job")
    metric.stop(bare)
    assert "moe_job" not in bare and metric.read(bare) is None
