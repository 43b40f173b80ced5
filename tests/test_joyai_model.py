"""``models.JoyAILLMFlash`` at the benchmark files' tiny sizes on the CPU,
against the plain reference of ``benchmarks/configs/joyai_llm_flash.py``
(which imports nothing of the package): the two-head loss ``L_main + 0.3
L_mtp`` and its gradients — the embedding's (the trunk's lookup and the
prediction module's) and ``W_head``'s (two heads) equal to the reference's
sums —, the first steps through ``ComputationGraph.fit`` on a two-input,
two-label ``MultiDataSet`` with ``batch_size``, and the layer table's counts.
Tolerances as ``tests/test_moe_layers.py``.
"""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.common.profiler import OpProfiler
from deeplearning4j_tpu.data import MultiDataSet
from deeplearning4j_tpu.models import JoyAILLMFlash
from deeplearning4j_tpu.nn.conf import layers as L

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
for p in (BENCH, os.path.join(BENCH, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

import compare  # noqa: E402  (benchmarks/compare.py)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CONF = _load(os.path.join(BENCH, "configs", "joyai_llm_flash.py"),
             "bench_conf_joyai_model")
CFG = json.load(open(os.path.join(BENCH, "configs", "joyai_llm_flash.json")))
SIZES = CONF.sizes_of(CFG, True)
F32_CFG = {**CFG, "compute_dtype": "", "updater_state_dtype": ""}
SEQ, SEED = 32, 11
MIX = {"batch": 2, "seq": SEQ, "batches": 3, "first_steps": 3}


def _close(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-30), \
        np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)


def _batches():
    gen = _load(os.path.join(BENCH, "traffic", "token_stream.py"), "bench_gen")
    return gen.make(MIX, SIZES, SEED, 3)


def _job(cfg):
    job = CONF.build(cfg, SIZES, 1, MIX)
    job.reset(CONF.make_weights(cfg, SIZES, SEED))
    return job


# --- the two-head loss ----------------------------------------------------------

@pytest.fixture(scope="module")
def two_heads():
    """(program's gradients and score, reference's loss, gradients, main loss
    alone and its gradients) on one batch, float32."""
    job, b = _job(F32_CFG), _batches()[0]
    w0 = jax.tree.map(jnp.copy, job.params())
    grads, score = job.model.compute_gradient_and_score(job.feed([b]))
    ids, labels = jnp.asarray(b["ids"]), jnp.asarray(b["labels"])
    ref = {fault: CONF._ref_grad(
        json.dumps(F32_CFG, sort_keys=True), json.dumps(SIZES, sort_keys=True),
        compare.EXACT, fault)(w0, ids, labels) for fault in ("", "no_mtp")}
    return {"grads": {n: g for n, g in grads.items() if g}, "score": score,
            "ref": ref, "labels": np.asarray(labels)}


def test_two_head_loss_is_main_plus_weighted_mtp(two_heads):
    (loss, _), _ = two_heads["ref"][""]
    (main, _), _ = two_heads["ref"]["no_mtp"]
    assert abs(two_heads["score"] - float(loss)) <= 1e-5 * float(loss)
    # ids are uniform, so each head reads about ln(vocabulary)
    ln_v = np.log(SIZES["vocab_size"])
    assert abs(float(main) - ln_v) < 0.05 * ln_v
    assert abs(float(loss) - float(main) - 0.3 * ln_v) < 0.05 * ln_v
    assert CFG["mtp_loss_weight"] == 0.3


def test_gradients_of_leaves_used_twice_are_the_references_sums(two_heads):
    """Every leaf's gradient is the reference's; the embedding's (looked up
    by the trunk and by the prediction module) and the head's (two heads)
    are more than the main loss alone gives them."""
    _, want = two_heads["ref"][""]
    _, main_only = two_heads["ref"]["no_mtp"]
    got = two_heads["grads"]
    assert sorted(got) == sorted(want)
    for node in want:
        for leaf in want[node]:
            _close(got[node][leaf], want[node][leaf], 2e-5)
    for node in ("embed", "head"):
        extra = np.asarray(want[node]["W"] - main_only[node]["W"])
        assert np.max(np.abs(extra)) > 0.05 * np.max(np.abs(
            np.asarray(want[node]["W"])))
    # the prediction module's own leaves take no gradient from the main loss
    assert not np.asarray(main_only["mtp_merge"]["W_eh"]).any()
    assert np.asarray(want["mtp_merge"]["W_eh"]).any()


def test_last_position_of_the_second_head_is_masked(two_heads):
    """The second head's labels are the first's shifted by one; its last
    position has no label and changing it changes nothing."""
    job, b = _job(F32_CFG), _batches()[0]
    features, labels, masks = CONF.two_heads(b["ids"], b["labels"])
    assert np.array_equal(labels[1][:, :-1], b["labels"][:, 1:])
    assert masks[0] is None and not masks[1][:, -1].any() \
        and masks[1][:, :-1].all()
    a = job.model.score(MultiDataSet(features, labels, labels_masks=masks))
    labels[1][:, -1] = 7
    c = job.model.score(MultiDataSet(features, labels, labels_masks=masks))
    assert a == c
    assert abs(a - two_heads["score"]) <= 1e-6 * a


def test_loss_weight_scales_a_loss_layers_score():
    assert L.LossLayer().loss_weight == 1.0
    assert L.TiedOutputLayer(tied_to="head", loss_weight=0.3).loss_weight == 0.3
    job = _job(F32_CFG)
    conf = job.model.conf
    assert conf.nodes["mtp_head"].layer.borrowed_params() == {
        "W": ("head", "W")}
    assert conf.nodes["mtp_merge"].layer.borrowed_params() == {
        "E": ("embed", "W")}
    assert job.model._params["mtp_head"] == {}
    assert sorted(job.model._params["head"]) == ["W"]
    data = job.feed([_batches()[0]])
    whole = job.model.score(data)
    conf.nodes["mtp_head"].layer.loss_weight = 0.0
    main = job.model.score(data)
    conf.nodes["mtp_head"].layer.loss_weight = 1.0
    both = job.model.score(data)
    assert abs((both - main) * 0.3 - (whole - main)) < 1e-5


# --- the whole tiny model through ComputationGraph.fit ---------------------------

def _drive(cfg):
    job = CONF.build(cfg, SIZES, 1, MIX)
    batches = _batches()
    w0 = CONF.make_weights(cfg, SIZES, SEED)
    w0_host = jax.device_get(w0)
    job.reset(w0)
    traced = OpProfiler.get().counter_value("trace/graph_fit_step")
    prog = compare.drive_first_steps(job, batches, w0_host)
    traced = OpProfiler.get().counter_value("trace/graph_fit_step") - traced
    ref = compare.reference_norms(CONF.reference(cfg, SIZES, SEED, batches))
    return {"job": job, "prog": prog, "ref": ref, "batches": batches,
            "traced": traced}


@pytest.fixture(scope="module")
def float32_run():
    return _drive(F32_CFG)


@pytest.fixture(scope="module")
def bfloat16_run():
    return _drive(CFG)


def test_fit_three_steps_float32_matches_reference(float32_run):
    found = compare.gaps(float32_run["prog"], float32_run["ref"])
    assert set(found) >= {"loss_step3", "first_gradient", "param_change",
                          "buffer_change"}
    for name, (gap, where) in found.items():
        assert gap <= 1e-4, (name, gap, where)
    assert found["buffer_change"][0] <= 1e-6    # the same selections
    assert float32_run["traced"] == 1


def test_fit_three_steps_bfloat16_inside_limits_that_float8_and_the_fault_fail(
        bfloat16_run):
    import precisions

    r = bfloat16_run
    limits = CFG["limits_tiny"]
    ok, rows = compare.judge(compare.gaps(r["prog"], r["ref"]), limits)
    assert ok, rows
    low = compare.reference_norms(CONF.reference(
        CFG, SIZES, SEED, r["batches"],
        lower=precisions.get(CFG["control_precision"])))
    ok8, rows8 = compare.judge(compare.gaps(low, r["ref"]), limits)
    assert not ok8, rows8
    for fault in ("no_mtp", "half_batch"):
        bad = compare.reference_norms(CONF.reference(
            CFG, SIZES, SEED, r["batches"], fault=fault))
        okf, rowsf = compare.judge(compare.gaps(bad, r["ref"]), limits)
        assert not okf, (fault, rowsf)


def test_fit_walks_the_staged_feed_and_counts_load_once_a_step(bfloat16_run):
    """A ``MultiDataSet`` with ``batch_size`` is cut into batches alike for
    every array; five routed layers (the prediction module's among them)
    count ``steps x tokens x 8`` selections each."""
    job = bfloat16_run["job"]
    m = job.model
    loads = m.expert_load()
    assert sorted(loads) == ["l1_ffn", "l2_ffn", "l3_ffn", "l4_ffn", "mtp_ffn"]
    for load in loads.values():
        assert load.shape == (256,) and load.sum() == 3 * 2 * SEQ * 8
    data = job.feed(bfloat16_run["batches"])
    cut = list(data.batch_by(2))
    assert len(cut) == 3 and all(c.num_examples() == 2 for c in cut)
    assert cut[1].labels_masks[0] is None
    assert np.array_equal(cut[1].labels[1].to_numpy(),
                          data.labels[1].to_numpy()[2:4])
    before = m._iteration
    dispatches = OpProfiler.get().counter_value("trace/graph_fit_step")
    job.fit(data, epochs=2)
    assert m._iteration == before + 6
    assert OpProfiler.get().counter_value("trace/graph_fit_step") == dispatches
    stats = OpProfiler.get().sequence_stats()
    assert stats["mla_layers"] >= 6 and stats["mtp_modules"] >= 1


# --- the layer table and the zoo model -------------------------------------------

def test_param_tree_is_the_layer_table_and_counts_680m():
    full = CONF.sizes_of(CFG, False)
    shapes = CONF.param_shapes(CFG, full)
    count = lambda nodes: sum(int(np.prod(s)) for n in nodes  # noqa: E731
                              for s in shapes[n].values())
    assert count(shapes) == 680_439_808
    assert count(["l0_ln1", "l0_attn", "l0_ln2", "l0_ffn"]) == 70_391_808
    assert count(["l1_ln1", "l1_attn", "l1_ln2", "l1_ffn",
                  "l1_shared"]) == 107_091_968
    assert count([n for n in shapes if n.startswith("mtp")]) == 115_486_720
    assert count(["embed"]) == count(["head"]) == 33_095_680
    mix = {"seq": 8192}
    assert CONF._dense_matmul_params(CFG, full) == 302_907_392
    assert CONF.attention_fwd_flops(CFG, full, mix) == \
        6 * 32 * 2.0 * (8192 * 8193 // 2) * 320
    assert CONF.expert_flops(CFG, full, CONF.balanced_rows(full, 8192)) \
        == 18.0 * 5 * 4096 * 2048 * 768
    total = CONF.model_flops(CFG, full, mix)
    assert 27.7e12 < total < 27.9e12
    assert 0.43 < 3 * CONF.attention_fwd_flops(CFG, full, mix) / total < 0.46


def test_zoo_model_defaults_are_the_published_sizes():
    z = JoyAILLMFlash()
    a = z.attention
    assert (z.d, z.ff, z.moe_ff, z.experts, z.shared, z.top_k, z.scale,
            z.dense_layers, z.vocab_rows, z.eps, len(z.layers)) == (
        2048, 7168, 768, 256, 1, 8, 2.5, 1, 129280, 1e-6, 40)
    assert (a["n_heads"], a["q_lora_rank"], a["kv_lora_rank"],
            a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"],
            a["rope_theta"]) == (32, 1536, 512, 128, 64, 128, 3.2e7)
    assert z.mtp and z.mtp_loss_weight == 0.3
    with pytest.raises(ValueError, match="one group"):
        JoyAILLMFlash(n_group=8, topk_group=4)


def test_model_without_mtp_has_one_input_and_one_head():
    m = JoyAILLMFlash(
        layers=[0, 1], vocab_rows=96, experts_held=(0, 4), mtp=False,
        hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=16, num_experts_per_tok=4, seq_len=16,
        compute_dtype=None, state_dtype=None).init()
    assert m.conf.network_inputs == ["ids"]
    assert m.conf.network_outputs == ["head"]
    assert "mtp_merge" not in m.conf.nodes and "l1_shared" in m.conf.nodes
    ids = np.random.default_rng(0).integers(1, 96, (2, 17)).astype(np.int32)
    probs = m.output(ids[:, :16])[0].to_numpy()
    assert probs.shape == (2, 16, 96)
    np.testing.assert_allclose(probs.sum(-1), 1.0, atol=1e-5)
