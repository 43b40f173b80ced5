"""``models.TrinityMini`` at the benchmark files' tiny sizes on the CPU,
against the plain reference of ``benchmarks/configs/trinity_mini.py`` (which
imports nothing of the package): the log-probabilities, the loss and the
gradient of every leaf on one batch, three AdamW steps through
``ComputationGraph.fit`` (every weight and the selection bias that the
balance rule moved), and what Trinity-Mini adds to the shared layers — a
full layer without rotation (NoPE) beside a window layer with it, the
attention's output gate, the balance rule, and the chip's share of 128
experts. Tolerances as ``tests/test_joyai_model.py``.
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
from deeplearning4j_tpu.models import TrinityMini
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.inputs import RNNInput

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


CONF = _load(os.path.join(BENCH, "configs", "trinity_mini.py"),
             "bench_conf_trinity_model")
CFG = json.load(open(os.path.join(BENCH, "configs", "trinity_mini.json")))
SIZES = CONF.sizes_of(CFG, True)
F32_CFG = {**CFG, "compute_dtype": "", "updater_state_dtype": ""}
SEQ, SEED = 64, 13
MIX = {"batch": 1, "seq": SEQ, "batches": 3, "first_steps": 3}


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


def _zero_biases():
    return {n: jnp.zeros((SIZES["router_width"],), jnp.float32)
            for n in CONF.routed_nodes(SIZES)}


# --- one batch in float32 --------------------------------------------------------

@pytest.fixture(scope="module")
def one_batch():
    """(program's log-probabilities, gradients and score, the reference's
    trunk, loss and gradients) on one batch, float32."""
    job, b = _job(F32_CFG), _batches()[0]
    w0 = jax.tree.map(jnp.copy, job.params())
    probs = job.model.output(b["ids"])[0].to_numpy()
    grads, score = job.model.compute_gradient_and_score(job.feed([b]))
    ids, labels = jnp.asarray(b["ids"]), jnp.asarray(b["labels"])
    (loss, _), want = CONF._ref_grad(
        json.dumps(F32_CFG, sort_keys=True), json.dumps(SIZES, sort_keys=True),
        compare.EXACT, "")(w0, _zero_biases(), ids, labels)
    h, _ = CONF.ref_trunk(SIZES, compare.EXACT, "", w0, _zero_biases(), ids)
    logits = jnp.einsum("btd,vd->btv", h, w0["head"]["W"],
                        precision=jax.lax.Precision.HIGHEST)
    return {"log_probs": np.log(probs), "grads": {n: g for n, g in
                                                  grads.items() if g},
            "score": score, "loss": float(loss), "want": want,
            "ref_log_probs": np.asarray(jax.nn.log_softmax(logits, -1))}


def test_log_probabilities_and_loss_match_the_reference(one_batch):
    _close(one_batch["log_probs"], one_batch["ref_log_probs"], 2e-5)
    assert abs(one_batch["score"] - one_batch["loss"]) <= 1e-5 * one_batch["loss"]
    # ids are uniform, so the loss reads about ln(vocabulary)
    ln_v = np.log(SIZES["vocab_size"])
    assert abs(one_batch["loss"] - ln_v) < 0.05 * ln_v


def test_gradient_of_every_leaf_matches_the_reference(one_batch):
    got, want = one_batch["grads"], one_batch["want"]
    assert sorted(got) == sorted(want)
    for node in want:
        assert sorted(got[node]) == sorted(want[node]), node
        for leaf in want[node]:
            _close(got[node][leaf], want[node][leaf], 5e-5)


# --- three steps through ComputationGraph.fit ------------------------------------

def _drive(cfg):
    job = CONF.build(cfg, SIZES, 1, MIX)
    batches = _batches()
    w0 = CONF.make_weights(cfg, SIZES, SEED)
    w0_host = jax.device_get(w0)
    job.reset(w0)
    prog = compare.drive_first_steps(job, batches, w0_host)
    raw = CONF.reference(cfg, SIZES, SEED, batches)
    return {"job": job, "prog": prog, "raw": raw, "w0": w0_host,
            "ref": compare.reference_norms(raw), "batches": batches}


@pytest.fixture(scope="module")
def float32_run():
    return _drive(F32_CFG)


@pytest.fixture(scope="module")
def bfloat16_run():
    return _drive(CFG)


def test_three_adamw_steps_float32_match_weights_and_moved_bias(float32_run):
    r = float32_run
    for name, (gap, where) in compare.gaps(r["prog"], r["ref"]).items():
        assert gap <= 1e-4, (name, gap, where)
    # Adam moves an element whose gradient is all but zero by up to lr
    # whatever its sign's rounding, so the weights are held by leaf norms
    params = jax.device_get(r["job"].params())
    for node, leaves in r["raw"]["param_change"].items():
        for leaf, change in leaves.items():
            miss = params[node][leaf] - r["w0"][node][leaf] - change
            assert np.linalg.norm(miss) <= 1e-3 * np.linalg.norm(change), (
                node, leaf)
    buffers = jax.device_get(r["job"].buffers())
    for node, want in r["raw"]["buffer_change"].items():
        # the same selections, so the same counts and the same bias
        np.testing.assert_array_equal(buffers[node]["expert_load"],
                                      want["expert_load"])
        _close(buffers[node]["bias"], want["bias"], 1e-6)
        assert np.abs(want["bias"]).max() > 0      # the rule moved it
        assert abs(float(np.mean(want["bias"]))) < 1e-6


def test_fit_three_steps_bfloat16_inside_limits_that_float8_and_faults_fail(
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
    for fault in ("half_batch", "rope_everywhere", "no_gate", "no_bias_rule"):
        bad = compare.reference_norms(CONF.reference(
            CFG, SIZES, SEED, r["batches"], fault=fault))
        okf, rowsf = compare.judge(compare.gaps(bad, r["ref"]), limits)
        assert not okf, (fault, rowsf)


def test_fit_counts_the_new_parts_as_the_step_is_traced(bfloat16_run):
    """Three routed layers of 128 selections' width count ``steps x tokens
    x 8``; the step holds one full layer without rotation, five gated
    attention layers and three layers that apply the balance rule."""
    job = bfloat16_run["job"]
    loads = job.model.expert_load()
    assert sorted(loads) == ["l2_ffn", "l3_ffn", "l4_ffn", "l5_ffn"]
    for load in loads.values():
        assert load.shape == (128,) and load.sum() == 3 * SEQ * 8
    before = {k: OpProfiler.get().counter_value("seq/" + k)
              for k in ("attn_nope_layers", "attn_gated_layers")}
    rule = OpProfiler.get().counter_value("moe/bias_rule_layers")
    job.model._fit_step = None      # trace the step once more
    job.fit(job.feed(bfloat16_run["batches"]), epochs=1)
    seq = OpProfiler.get()
    # the forward is traced once and again under remat: at least once each
    assert seq.counter_value("seq/attn_nope_layers") - before[
        "attn_nope_layers"] >= 1
    assert seq.counter_value("seq/attn_gated_layers") - before[
        "attn_gated_layers"] >= 5
    assert seq.counter_value("moe/bias_rule_layers") - rule >= 4


# --- the layer table and the zoo model -------------------------------------------

def test_param_tree_is_the_layer_table_and_counts_705m():
    full = CONF.sizes_of(CFG, False)
    shapes = CONF.param_shapes(CFG, full)
    count = lambda nodes: sum(int(np.prod(s)) for n in nodes  # noqa: E731
                              for s in shapes[n].values())
    assert count(shapes) == 705_473_792
    assert count(["l1_attn"]) == 27_263_232
    assert count([n for n in shapes if n.startswith("l1_")]) == 65_020_160
    assert count([n for n in shapes if n.startswith("l2_")]) == 134_488_320
    assert count(["embed", "head"]) == 102_498_304
    mix = {"seq": 16384}
    assert [s for _, _, s in CONF.blocks(full)] == [True, True, False, True,
                                                    True]
    window = 2048 * 2049 // 2 + (16384 - 2048) * 2048
    assert CONF.attention_fwd_flops(CFG, full, mix) == \
        32 * 2.0 * (16384 * 16385 // 2 + 4 * window) * 256
    total = CONF.model_flops(CFG, full, mix)
    assert 39.9e12 < total < 40.1e12
    assert 0.3 < 3 * CONF.attention_fwd_flops(CFG, full, mix) / total < 0.33
    assert CONF.expert_flops(CFG, full, CONF.balanced_rows(full, 16384)) \
        == 18.0 * 4 * 16384 * 8 * 16 / 128 * 2048 * 1024


def test_zoo_model_defaults_are_the_published_sizes():
    z = TrinityMini()
    assert (z.d, z.ff, z.moe_ff, z.heads, z.kv_heads, z.head_dim, z.experts,
            z.shared, z.top_k, z.scale, z.dense_layers, z.window,
            z.vocab_rows, z.eps, z.theta, z.mup, z.balance_rate,
            len(z.layers)) == (2048, 6144, 1024, 32, 4, 128, 128, 1, 8,
                               2.826, 2, 2048, 200192, 1e-5, 10000.0, True,
                               0.001, 32)
    assert z.layer_types == CFG["layer_types"]


def test_model_wires_sandwich_norms_the_scale_and_the_layer_kinds():
    m = _job(F32_CFG).model
    nodes = m.conf.nodes
    assert nodes["embed_scale"].vertex.scale == pytest.approx(
        SIZES["hidden_size"] ** 0.5)
    assert nodes["l2_add1"].inputs == ["l1_add2", "l2_post_ln1"]
    assert nodes["l2_post_ln1"].inputs == ["l2_attn"]
    assert nodes["l2_moe"].inputs == ["l2_ffn", "l2_shared"]
    assert nodes["l2_post_ln2"].inputs == ["l2_moe"]
    assert nodes["l1_post_ln2"].inputs == ["l1_ffn"]
    for l in SIZES["layers_kept"]:
        att = nodes[f"l{l}_attn"].layer
        sliding = SIZES["layer_types"][l] == "sliding_attention"
        assert (att.rope, att.window, att.output_gate) == (
            sliding, SIZES["sliding_window"] if sliding else None, True)
    assert nodes["l3_ffn"].layer.bias_update_rate == 0.001


# --- the shared layers' new parts ------------------------------------------------

def _attention_layer(**kw):
    layer = L.RotaryAttentionLayer(n_heads=8, n_kv_heads=1, head_dim=16,
                                   **kw)
    layer.set_input_type(RNNInput(64, 24))
    return layer


def _x(seed=0, T=24):
    return jax.random.normal(jax.random.key(seed), (1, T, 64), jnp.float32)


def test_a_full_layer_has_no_position_and_a_window_layer_has():
    """The last query of a layer without rotation sees its keys as a set:
    shuffling the earlier tokens leaves its output alone. With rotation (a
    window as wide as the sequence, so that the mask is the same) it does
    not."""
    x = _x()
    perm = np.concatenate([np.random.default_rng(1).permutation(23), [23]])
    for rope, same in ((False, True), (True, False)):
        layer = _attention_layer(rope=rope, window=None if not rope else 24,
                                 output_gate=True)
        p = layer.init_params(jax.random.key(2))
        a, _ = layer.apply(p, x, {}, False, None)
        b, _ = layer.apply(p, x[:, perm], {}, False, None)
        moved = float(jnp.max(jnp.abs(a[:, -1] - b[:, -1])))
        assert (moved < 1e-5) is same, (rope, moved)


def test_the_window_hides_keys_further_back():
    layer = _attention_layer(window=4)
    p = layer.init_params(jax.random.key(3))
    x = _x(4)
    y, _ = layer.apply(p, x, {}, False, None)
    far = x.at[:, :10].set(_x(5)[:, :10])     # keys beyond every window of
    z, _ = layer.apply(p, far, {}, False, None)   # queries 14 on
    assert float(jnp.max(jnp.abs(y[:, 14:] - z[:, 14:]))) < 1e-6
    assert float(jnp.max(jnp.abs(y[:, :14] - z[:, :14]))) > 1e-3


def test_the_gate_at_zero_weight_halves_the_output():
    gated, plain = _attention_layer(output_gate=True), _attention_layer()
    p = gated.init_params(jax.random.key(6))
    assert sorted(p) == sorted([*plain.init_params(jax.random.key(6)),
                                "W_gate"])
    p = {**p, "W_gate": jnp.zeros_like(p["W_gate"])}
    x = _x(7)
    y, _ = gated.apply(p, x, {}, False, None)
    z, _ = plain.apply({k: v for k, v in p.items() if k != "W_gate"}, x, {},
                       False, None)
    _close(y, 0.5 * z, 1e-6)


def test_defaults_leave_the_layers_as_they_were():
    """``RotaryAttentionLayer`` and ``RoutedExpertsLayer`` at their defaults
    draw the same parameters as before these fields and trace the same
    program with or without them named."""
    plain = _attention_layer()
    p = plain.init_params(jax.random.key(8))
    assert sorted(p) == ["Wk", "Wo", "Wq", "Wv", "k_norm", "q_norm"]
    named = _attention_layer(window=None, rope=True, output_gate=False)
    x = _x(9)
    jp = lambda layer: str(jax.make_jaxpr(                 # noqa: E731
        lambda p, x: layer.apply(p, x, {}, True, None)[0])(p, x))
    assert jp(plain) == jp(named)


def _routed(first=0, held=128, rate=0.0):
    layer = L.RoutedExpertsLayer(n_routed=128, n_experts=held,
                                 first_expert=first, n_ff=32, top_k=8,
                                 scale=2.826, norm_eps=1e-20,
                                 bias_update_rate=rate)
    layer.set_input_type(RNNInput(64, 32))
    return layer


def test_bias_rule_rate_zero_leaves_the_bias_and_a_rate_moves_it():
    x = _x(10, 32)
    still, moving = _routed(), _routed(rate=0.001)
    p = still.init_params(jax.random.key(11))
    state = {**still.init_state(), "bias": jnp.linspace(-0.01, 0.01, 128)}
    _, kept = still.apply(p, x, state, True, None)
    np.testing.assert_array_equal(kept["bias"], state["bias"])
    _, moved = moving.apply(p, x, state, True, None)
    c = np.asarray(kept["expert_load"])
    np.testing.assert_allclose(
        moved["bias"], CONF.balance_rule(SIZES, state["bias"], c), atol=1e-8)
    delta = np.asarray(moved["bias"] - state["bias"])
    assert np.abs(delta).max() > 0 and abs(delta.mean()) < 1e-7
    # under the mean, the bias rises; over it, it falls
    assert np.all(delta[c < c.mean()] > delta[c > c.mean()].max())
    # outside training nothing moves
    _, frozen = moving.apply(p, x, state, False, None)
    np.testing.assert_array_equal(frozen["bias"], state["bias"])


def test_eight_shares_of_128_experts_add_up_to_the_uncut_layer():
    """At top-8 of 128, eight chips of 16 experts each compute their part;
    the shared expert, which every chip computes alike, is counted once; the
    sum is the reference's whole layer."""
    x = _x(12, 32)
    whole = _routed()
    p = whole.init_params(jax.random.key(13))
    state = {**whole.init_state(), "bias": 0.02 * jnp.cos(
        jnp.arange(128) * 0.7)}
    shared = L.GatedMLPLayer(n_ff=32, scope="shared_expert")
    shared.set_input_type(RNNInput(64, 32))
    ps = shared.init_params(jax.random.key(14))
    total = shared.apply(ps, x, {}, False, None)[0]
    for s in range(8):
        share = _routed(16 * s, 16)
        mine = {"Wg": p["Wg"], "W1": p["W1"][16 * s:16 * s + 16],
                "W2": p["W2"][16 * s:16 * s + 16]}
        total = total + share.apply(mine, x, state, False, None)[0]
    ops = CONF.ref_ops({**SIZES, "experts_held": [0, 128]}, compare.EXACT)
    xt = x.reshape(-1, 64)
    experts, weights, _ = ops.route(p, state["bias"], xt)
    want = ops.experts_of(p, xt, experts, weights, held=(0, 128)) + ops.mlp(
        ps, xt)
    _close(total.reshape(-1, 64), want, 2e-5)
