"""The benchmark's cell ``trinity_mini.train_s16k`` walks end to end at its
files' tiny sizes on the CPU: ``benchmarks/run.py --rehearse`` exits 0, is
``correct`` under the configuration's ``limits_tiny``, and reads the kernels'
fallback count (on the CPU: what the XLA paths took); the manifest with the
new entries passes its own checks, the five new metrics have their files and
take their definitions from the accepted ones, and the configuration's file
states the catalog's ``config`` and the cut."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "trinity_mini.train_s16k"
# new metric: the accepted file whose definition it takes
NEW = {"trinity_attention_fwd_roofline_share": "attention_fwd_roofline_share",
       "trinity_moe_gmm_roofline_share": "moe_gmm_roofline_share",
       "trinity_kernel_fallbacks": "moe_kernel_fallbacks",
       "trinity_scope_ms.window_attention": "scope_ms.update",
       "trinity_scope_ms.full_attention": "scope_ms.update"}


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
    assert held == set(json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "trinity_mini.json")))["limits_tiny"])
    # the routed layers' bias and expert_load are compared with the
    # reference's own
    assert result["compared"]["buffer_change"]["where"].startswith("l")
    if trace:
        # on the CPU every attention forward and backward and every grouped
        # product takes its XLA path, counted as the step is traced
        got = result["metrics"]["trinity_kernel_fallbacks"]
        assert got["unit"] == "count" and got["value"] >= 5 + 5 + 8
        assert result["metrics"]["compile_in_window"]["value"] == 0
        # a CPU run gives no share of a roofline and no device time
        assert not any("roofline" in name or "scope_ms" in name
                       for name in result["metrics"])


def test_manifest_with_the_new_entries():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "manifest.py")],
        capture_output=True, text=True, cwd=ROOT, timeout=120)
    assert p.returncode == 0, p.stderr
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity_mini", "lm_stream_b1_s16384", 1)
    conf = next(c for c in m["configs"] if c["name"] == "trinity_mini")
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert conf["source"] == ("https://huggingface.co/arcee-ai/Trinity-Mini/"
                              "blob/main/config.json")
    # the five were appended together, the scope readers last (their
    # profiled call comes after every other reader's stop); no entry after
    # them lists the cell
    names = [x["name"] for x in m["per_layer"]]
    first = names.index(next(iter(NEW)))
    mine = m["per_layer"][first:first + 5]
    assert [x["name"] for x in mine] == list(NEW)
    assert all(x["workloads"] == [CELL] and x["layer"] == "kernels"
               and x["moves"] == "examples_per_s" for x in mine)
    # the accepted closed lists stay the accepted cells'
    assert all(CELL not in x.get("workloads", [])
               for x in m["per_layer"][:first] + m["per_layer"][first + 5:])


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_takes_its_definition_from_the_accepted_file(name):
    mine, accepted = _metric(name), _metric(NEW[name])
    assert mine.stop.__code__.co_filename.endswith(NEW[name] + ".py") \
        if hasattr(accepted, "stop") else not hasattr(mine, "stop")
    if "scope_ms" not in name:
        assert mine.read.__code__.co_filename.endswith(NEW[name] + ".py")
    # nothing to read without a trace, a table, a job or the counters
    ctx = {"trace": None, "conf": object(), "sizes": {}, "cfg": {}, "mix": {},
           "job": object()}
    if name == "trinity_moe_gmm_roofline_share":
        mine.stop(ctx)
    assert mine.read(ctx) is None


def test_scope_readers_split_the_attention_vertices_by_layer_type():
    """The window and full readers take the configuration's layer table:
    four window layers, one full layer, and no vertex twice."""
    spec = importlib.util.spec_from_file_location(
        "bench_conf_trinity_rehearse",
        os.path.join(ROOT, "benchmarks", "configs", "trinity_mini.py"))
    conf = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(conf)
    cfg = json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                      "trinity_mini.json")))
    sizes = conf.sizes_of(cfg, False)
    rows = [{"vertex": v, "ms": 1.0, "phase": "forward", "op": "x"}
            for v in ("l1_attn", "l2_attn", "l3_attn", "l4_attn", "l5_attn",
                      "l2_ffn", "")]
    ctx = {"conf": conf, "sizes": sizes, "scope_table": {"rows": rows}}
    assert _metric("trinity_scope_ms.window_attention").read(ctx) == 4.0
    assert _metric("trinity_scope_ms.full_attention").read(ctx) == 1.0
    assert conf.attention_nodes(sizes, False) == ["l3_attn"]


def test_the_file_states_the_published_config_and_the_cut():
    """Every key of the catalog row's config is in the file under its key
    with its value, but for the three keys of ``reduced``."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "trinity_mini.json")) as f:
        cfg = json.load(f)
    sliding, full = "sliding_attention", "full_attention"
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144,
        "layer_types": ([sliding] * 3 + [full]) * 8,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "model_type": "afmoe", "moe_intermediate_size": 1024,
        "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_expert_groups": 1,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_mm": True}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 16, 25024)
    assert cfg["published"]["num_experts"] == cfg["router_width"] == 128
    assert cfg["published"]["vocab_size"] == 8 * cfg["vocab_size"]
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["experts_held"] == [0, 16]
    assert cfg["layers_kept"] == [1, 2, 3, 4, 5]
    assert [cfg["layer_types"][l] for l in cfg["layers_kept"]] == [
        sliding, sliding, full, sliding, sliding]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert set(cfg["limits"]) <= {
        "loss_step1", "loss_step2", "loss_step3", "first_gradient",
        "first_gradient_median_leaf", "param_change",
        "param_change_median_leaf", "buffer_change",
        "buffer_change_median_leaf"}
    for key in ("embedding", "blocks", "attention", "router", "balance_rule",
                "experts", "head", "optimizer", "precision", "init"):
        assert cfg["assumed"][key]
