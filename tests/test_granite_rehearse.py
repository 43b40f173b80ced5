"""The benchmark's cell ``granite_4_h_micro.train_s16k`` walks end to end at
its files' tiny sizes on the CPU: ``benchmarks/run.py --rehearse`` exits 0,
is ``correct`` under the configuration's ``limits_tiny``, and reads the
kernels' fallback count (on the CPU: what the XLA paths took); the manifest
with the new entries passes its own checks, the four new metrics have their
files (the two that take an accepted definition take it from that file) and
read nothing where there is nothing to read, and the configuration's file
states the catalog's ``config`` and the cut."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "granite_4_h_micro.train_s16k"
NEW = ("ssd_roofline_share", "ssd_kernel_fallbacks", "scope_ms.ssm_mixer",
       "granite_scope_unattributed_share")


def _run(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), *args],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=900)


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"),
        os.path.join(ROOT, "benchmarks", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite_4_h_micro.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(trace):
    p = _run("--workload", CELL, "--rehearse", "--seed", "3000000019",
             "--seconds", "1", "--trace", str(trace))
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    held = {k for k, row in result["compared"].items()
            if row["limit"] is not None}
    assert held == set(_cfg()["limits_tiny"])
    if trace:
        # on the CPU the nine scans and the attention's forward and
        # backward take their XLA paths, counted as the step is traced
        got = result["metrics"]["ssd_kernel_fallbacks"]
        assert got == {"value": 9 + 1 + 1, "unit": "count"}
        assert result["metrics"]["compile_in_window"]["value"] == 0
        # a CPU run gives no share of a roofline and no device time
        assert not any("roofline" in name or "scope" in name
                       for name in result["metrics"])


def test_freed_readings_rehearsal():
    """``benchmarks/tests/freed_readings.py``, which read the cell's limits on
    the chip, at the tiny sizes: the program passes the tiny limits, the
    float8 control fails them, and the dropped chunk state reads under them
    (PERF.md Open questions 30)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "tests",
                                      "freed_readings.py"),
         "--workload", CELL, "--seeds", "3000000019",
         "--control-seed", "3000000019", "--faults", "drop_chunk_state",
         "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    passes = {r["kind"]: r["passes"] for r in lines if "kind" in r}
    assert passes == {"lower": True, "control": False,
                      "drop_chunk_state": True}
    last = lines[-1]
    assert last["program_fails"] == [] and last["unseen"] == [
        "drop_chunk_state"]
    assert set(last["summary"]) == {"lower", "control", "drop_chunk_state"}


def test_manifest_with_the_new_entries():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "manifest.py")],
        capture_output=True, text=True, cwd=ROOT, timeout=120)
    assert p.returncode == 0, p.stderr
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite_4_h_micro", "lm_stream_b1_s16384", 1)
    assert m["workloads"][-1] is cell
    conf = next(c for c in m["configs"] if c["name"] == "granite_4_h_micro")
    assert m["configs"][-1] is conf
    assert conf["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert conf["source"] == ("https://huggingface.co/ibm-granite/"
                              "granite-4.0-h-micro/blob/main/config.json")
    # the four were appended together, the scope readers last (their
    # profiled call comes after every other reader's stop)
    names = [x["name"] for x in m["per_layer"]]
    assert names[-4:] == list(NEW)
    assert all(x["workloads"] == [CELL] and x["moves"] == "examples_per_s"
               for x in m["per_layer"][-4:])
    assert [x["layer"] for x in m["per_layer"][-4:]] == [
        "kernels", "kernels", "kernels", "device"]
    # the accepted closed lists stay the accepted cells'
    assert all(CELL not in x.get("workloads", [])
               for x in m["per_layer"][:-4])


@pytest.mark.parametrize("name", NEW)
def test_new_metric_reads_nothing_where_there_is_nothing(name):
    mine = _metric(name)
    accepted = {"granite_scope_unattributed_share": "scope_unattributed_share",
                "scope_ms.ssm_mixer": "scope_ms.update"}
    if name in accepted:
        assert mine.stop.__code__.co_filename.endswith(
            "scope_ms.update.py")
    if name == "granite_scope_unattributed_share":
        assert mine.read.__code__.co_filename.endswith(
            accepted[name] + ".py")
    # nothing to read without a trace, a table, the counters or the counts
    ctx = {"trace": None, "conf": object(), "sizes": {}, "cfg": {}, "mix": {}}
    assert mine.read(ctx) is None


def test_the_readers_read_the_table_and_the_trace():
    """``scope_ms.ssm_mixer`` sums the Mamba-2 vertices' rows; the roofline
    share takes the kernel's seconds from the trace where its name is among
    the reducer's ten, else from the table's rows of that op."""
    rows = [{"vertex": "l0_mamba", "kind": "Mamba2Layer", "ms": 5.0,
             "op": "ssd_scan", "phase": "backward", "inner": "mamba2/ssd"},
            {"vertex": "l0_mamba", "kind": "Mamba2Layer", "ms": 3.0,
             "op": "fusion[kOutput]", "phase": "forward", "inner": "mamba2"},
            {"vertex": "l5_attn", "kind": "RotaryAttentionLayer", "ms": 2.0,
             "op": "flash_attention_fwd", "phase": "forward", "inner": ""}]
    table = {"rows": rows, "step_ms": 10.0, "unattributed_ms": 0.5}
    spec = importlib.util.spec_from_file_location(
        "bench_conf_granite_rehearse",
        os.path.join(ROOT, "benchmarks", "configs", "granite_4_h_micro.py"))
    conf = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(conf)
    cfg = _cfg()
    ctx = {"scope_table": table, "conf": conf, "cfg": cfg,
           "sizes": conf.sizes_of(cfg, False), "mix": {"seq": 16384},
           "examples": 16, "steps": 16, "chips": 1,
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "trace": {"step_executions": 2,
                     "device_ops": [["fusion_kOutput_", 1.0]]}}
    assert _metric("scope_ms.ssm_mixer").read(ctx) == 8.0
    assert _metric("granite_scope_unattributed_share").read(ctx) == 5.0
    least = conf.ssd_bytes(cfg, ctx["sizes"], ctx["mix"]) / 819e9
    share = _metric("ssd_roofline_share").read(ctx)
    assert share == pytest.approx(100 * least / 5e-3)
    ctx["trace"]["device_ops"].append(["ssd_scan", 0.02])
    assert _metric("ssd_roofline_share").read(ctx) == pytest.approx(
        100 * least / 1e-2)


def test_the_file_states_the_published_config_and_the_cut():
    """Every key of the catalog row's config is in the file under its key
    with its value, but for the two keys of ``reduced``."""
    cfg = _cfg()
    mamba, attention = "mamba", "attention"
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "layer_types": ([mamba] * 5 + [attention] + [mamba] * 4) * 4,
        "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_key_value_heads": 8,
        "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (10, 25088)
    assert cfg["published"]["vocab_size"] == 4 * cfg["vocab_size"]
    assert cfg["published"]["num_hidden_layers"] == 40
    assert cfg["layers_kept"] == list(range(10))
    assert [cfg["layer_types"][l] for l in cfg["layers_kept"]] == [
        mamba] * 5 + [attention] + [mamba] * 4
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert set(cfg["limits"]) <= {
        "loss_step1", "loss_step2", "loss_step3", "first_gradient",
        "first_gradient_median_leaf", "param_change",
        "param_change_median_leaf"}
    for key in ("embedding", "blocks", "mlp", "mamba", "attention", "head",
                "optimizer", "precision", "init", "data"):
        assert cfg["assumed"][key]
