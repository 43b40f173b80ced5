"""The benchmark's cell ``joyai_llm_flash.train_b2_s8k`` walks end to end at
its files' tiny sizes on the CPU: ``benchmarks/run.py --rehearse`` exits 0, is
``correct`` under the configuration's ``limits_tiny``, and reads the expert
and attention kernels' fallback count (on the CPU: what the XLA paths took);
the manifest with the new entries passes its own checks, the four new metrics
have their files, and the configuration's file states the catalog's
``config``."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "joyai_llm_flash.train_b2_s8k"
NEW = {"mla_attention_fwd_roofline_share": "attention_fwd_roofline_share",
       "mla_attention_bwd_roofline_share": "attention_bwd_roofline_share",
       "mla_moe_gmm_roofline_share": "moe_gmm_roofline_share",
       "mla_moe_kernel_fallbacks": "moe_kernel_fallbacks"}


def _run(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), *args],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=900)


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name,
        os.path.join(ROOT, "benchmarks", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
    assert held == {"first_gradient", "first_gradient_median_leaf",
                    "param_change_median_leaf", "buffer_change"}
    # the routed layers' expert_load is compared with the reference's counts
    assert result["compared"]["buffer_change"]["where"].endswith("expert_load")
    if trace:
        # five routed layers' two grouped products and six attention blocks,
        # forward and backward, counted as the step is traced
        got = result["metrics"]["mla_moe_kernel_fallbacks"]
        assert got["unit"] == "count" and got["value"] >= 22
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
        "joyai_llm_flash", "lm_stream_b2_s8192", 1)
    conf = next(c for c in m["configs"] if c["name"] == "joyai_llm_flash")
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert conf["source"] == ("https://huggingface.co/jdopensource/"
                              "JoyAI-LLM-Flash/blob/main/config.json")
    new = {x["name"]: x for x in m["per_layer"] if x["name"] in NEW}
    assert set(new) == set(NEW)
    assert all(x["workloads"] == [CELL] and x["layer"] == "kernels"
               and x["moves"] == "examples_per_s" for x in new.values())
    # the four were appended together; PR 36 appended its six ``scope_*``
    # entries after them (their lists name the cell), later PRs their own
    accepted = [x for x in m["per_layer"]
                if not x["name"].startswith("scope_")]
    first = [x["name"] for x in accepted].index(next(iter(NEW)))
    assert [x["name"] for x in accepted[first:first + 4]] == list(NEW)
    # the accepted closed lists stay the accepted cells'
    assert all(CELL not in x.get("workloads", []) for x in accepted
               if x["name"] not in NEW)


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_takes_its_definition_from_the_accepted_file(name):
    mine, accepted = _metric(name), _metric(NEW[name])
    assert mine.read.__code__.co_filename.endswith(NEW[name] + ".py")
    assert hasattr(mine, "stop") == hasattr(accepted, "stop")
    # nothing to read without a trace, a job or the program's counters
    ctx = {"trace": None, "conf": object(), "sizes": {}, "cfg": {}, "mix": {},
           "job": object()}
    if hasattr(mine, "stop") and "fallbacks" not in name:
        mine.stop(ctx)
    assert mine.read(ctx) is None


def test_the_file_states_the_published_config_and_the_cut():
    """Every number of the catalog row's config is in the file under its
    key, but for the three keys of ``reduced``."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "joyai_llm_flash.json")) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 16, 16160)
    assert cfg["published"]["n_routed_experts"] == cfg["router_width"] == 256
    assert cfg["published"]["vocab_size"] == 8 * cfg["vocab_size"]
    assert cfg["experts_held"] == [0, 16]
    assert cfg["layers_kept"] == [0, 1, 2, 3, 4]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert set(cfg["limits"]) <= {
        "loss_step1", "loss_step2", "loss_step3", "first_gradient", "first_gradient_median_leaf", "param_change",
        "param_change_median_leaf", "buffer_change"}
