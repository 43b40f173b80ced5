"""``models.GraniteHybrid`` at the benchmark files' tiny sizes on the CPU,
against the plain reference of ``benchmarks/configs/granite_4_h_micro.py``
(which imports nothing of the package; its scan is the recurrence one step
at a time): the log-probabilities, the loss and the gradient of every leaf
on one batch, three AdamW steps through ``ComputationGraph.fit``, bfloat16
``fit`` inside the tiny limits that the float8 control and a fault fail, the
parameter count at the cut, the zoo's published defaults, the wiring of
``layer_types`` and the family's multipliers, and the attention layer's two
new fields at their defaults. Tolerances as ``tests/test_trinity_model.py``.
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
from deeplearning4j_tpu.models import GraniteHybrid
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


CONF = _load(os.path.join(BENCH, "configs", "granite_4_h_micro.py"),
             "bench_conf_granite_model")
CFG = json.load(open(os.path.join(BENCH, "configs", "granite_4_h_micro.json")))
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


# --- one batch in float32 --------------------------------------------------------

@pytest.fixture(scope="module")
def one_batch():
    """(program's log-probabilities, gradients and score, the reference's
    loss, gradients and log-probabilities) on one batch, float32."""
    job, b = _job(F32_CFG), _batches()[0]
    w0 = jax.tree.map(jnp.copy, job.params())
    probs = job.model.output(b["ids"])[0].to_numpy()
    grads, score = job.model.compute_gradient_and_score(job.feed([b]))
    ids, labels = jnp.asarray(b["ids"]), jnp.asarray(b["labels"])
    loss, want = CONF._ref_grad(
        json.dumps(F32_CFG, sort_keys=True), json.dumps(SIZES, sort_keys=True),
        compare.EXACT, "")(w0, ids, labels)
    h = CONF.ref_trunk(SIZES, compare.EXACT, "", w0, ids)
    logits = jnp.einsum("btd,vd->btv", h, w0["embed"]["W"],
                        precision=jax.lax.Precision.HIGHEST) / SIZES[
                            "logits_scaling"]
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
    """``A_log``'s gradient is a millionth of the matrices' at these weights
    (the scan is a small part of the mixer, and a decay's gradient sums
    terms of both signs, in another order in the chunked form than in the
    recurrence), so it is held at float32's rounding of that sum."""
    got, want = one_batch["grads"], one_batch["want"]
    assert sorted(got) == sorted(want)
    for node in want:
        assert sorted(got[node]) == sorted(want[node]), node
        for leaf in want[node]:
            _close(got[node][leaf], want[node][leaf],
                   2e-4 if leaf == "A_log" else 5e-5)


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


def test_three_adamw_steps_float32_match_the_weights(float32_run):
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


def test_fit_three_steps_bfloat16_inside_limits_that_float8_and_a_fault_fail(
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
    bad = compare.reference_norms(CONF.reference(
        CFG, SIZES, SEED, r["batches"], fault="half_batch"))
    okf, rowsf = compare.judge(compare.gaps(bad, r["ref"]), limits)
    assert not okf, rowsf


def test_the_planted_fault_drops_one_chunks_carried_state():
    """``drop_chunk_state`` zeroes the state carried into the chunk at the
    middle of the sequence, in the reference's first Mamba-2 layer: the scan
    is the same before that step and differs from it on. The comparison's
    norms cannot tell it from rounding (PERF.md): the states of these
    initial weights decay within a few steps and a 0.02-wide convolution
    keeps the scan a small part of the mixer beside D X."""
    ops = CONF.ref_ops(SIZES, compare.EXACT)
    r = np.random.RandomState(3)
    H, P = SIZES["mamba_n_heads"], SIZES["mamba_d_head"]
    G, N = SIZES["mamba_n_groups"], SIZES["mamba_d_state"]
    x = jnp.asarray(r.randn(1, SEQ, H, P), jnp.float32)
    dt = jnp.asarray(np.log1p(np.exp(r.randn(1, SEQ, H) - 2)), jnp.float32)
    A = -jnp.arange(1, H + 1, dtype=jnp.float32) / H
    B = jnp.asarray(r.randn(1, SEQ, G, N), jnp.float32)
    C = jnp.asarray(r.randn(1, SEQ, G, N), jnp.float32)
    t0 = CONF.drop_step(SIZES, SEQ)
    assert t0 == SEQ // 2 and t0 % SIZES["mamba_chunk_size"] == 0
    y, bad = ops.scan(x, dt, A, B, C), ops.scan(x, dt, A, B, C, drop_at=t0)
    np.testing.assert_array_equal(y[:, :t0], bad[:, :t0])
    assert float(jnp.max(jnp.abs(y[:, t0] - bad[:, t0]))) > 1e-2


def test_fit_counts_the_mixers_and_their_kernels_as_the_step_is_traced(
        bfloat16_run):
    """Nine Mamba-2 layers and one attention layer without position: each
    call site of the scan counts once, on its XLA path on the CPU."""
    job = bfloat16_run["job"]
    prof = OpProfiler.get()
    names = ("seq/mamba2_layers", "seq/ssd_fallback", "seq/ssd_kernel",
             "seq/attn_nope_layers")
    before = {k: prof.counter_value(k) for k in names}
    job.model._fit_step = None      # trace the step once more
    job.fit(job.feed(bfloat16_run["batches"]), epochs=1)
    moved = {k: prof.counter_value(k) - before[k] for k in names}
    assert moved["seq/mamba2_layers"] >= 9
    assert moved["seq/ssd_fallback"] >= 9 and moved["seq/ssd_kernel"] == 0
    assert moved["seq/attn_nope_layers"] >= 1


# --- the layer table and the zoo model -------------------------------------------

def test_param_tree_is_the_layer_table_and_counts_797m():
    full = CONF.sizes_of(CFG, False)
    shapes = CONF.param_shapes(CFG, full)
    count = lambda nodes: sum(int(np.prod(s)) for n in nodes  # noqa: E731
                              for s in shapes[n].values())
    assert count(shapes) == 797_850_560
    assert count([n for n in shapes if n.startswith("l0_")]) == 76_182_976
    assert count([n for n in shapes if n.startswith("l5_")]) == 60_821_504
    assert count(["l0_mamba"]) == 25_847_232
    assert count(["embed"]) == 51_380_224 == 25088 * 2048
    assert CONF.matrix_params(CFG, full) == 797_573_120
    assert [attn for _, _, attn in CONF.blocks(full)] == [
        False] * 5 + [True] + [False] * 4
    mix = {"seq": 16384}
    assert CONF.attention_fwd_flops(CFG, full, mix) == \
        32 * 2.0 * (16384 * 16385 // 2) * 2 * 64
    total = CONF.model_flops(CFG, full, mix)
    assert 83.0e12 < total < 83.2e12
    assert 0.46e12 < CONF.ssd_fwd_flops(CFG, full, mix) < 0.48e12
    # ~7 ms of products and ~9 ms of bytes a step at the v5e's peaks: a
    # little bandwidth-bound
    assert 7.0e-3 < CONF.ssd_flops(CFG, full, mix) / 197e12 < 7.3e-3
    assert CONF.ssd_bytes(CFG, full, mix) == 9 * (
        4 * 2 * 16384 * 4096 + 4 * 2 * 16384 * 128 + 2 * 4 * 16384 * 64
        + 2 * 4 * 64 * 64 * 128 * 64)
    assert 9.0e-3 < CONF.ssd_bytes(CFG, full, mix) / 819e9 < 9.3e-3
    assert CONF.mxu_flops(CFG, full, mix) == 6.0 * 797_573_120 * 16384


def test_zoo_model_defaults_are_the_published_sizes():
    z = GraniteHybrid()
    assert (z.d, z.ff, z.heads, z.kv_heads, z.attention_multiplier,
            z.embedding_multiplier, z.residual_multiplier, z.logits_scaling,
            z.vocab_rows, z.eps, len(z.layers)) == (
        2048, 8192, 32, 8, 0.015625, 12.0, 0.22, 8.0, 100352, 1e-5, 40)
    assert z.mamba == {"d_inner": 4096, "n_heads": 64, "d_state": 128,
                       "n_groups": 1, "d_conv": 4, "chunk": 256, "eps": 1e-5}
    assert z.layer_types == CFG["layer_types"]
    full = CONF.sizes_of(CFG, False)
    assert (full["mamba_n_heads"] * full["mamba_d_head"]
            == CFG["mamba_expand"] * CFG["hidden_size"])


def test_model_wires_the_layer_types_and_the_multipliers():
    m = _job(F32_CFG).model
    nodes = m.conf.nodes
    assert nodes["embed_scale"].vertex.scale == 12.0
    assert nodes["head_scale"].vertex.scale == 1 / 8
    assert nodes["head_scale"].inputs == ["final_ln"]
    assert nodes["head"].inputs == ["head_scale"]
    assert nodes["head"].layer.tied_to == "embed"
    assert nodes["l0_ln1"].inputs == ["embed_scale"]
    for l in SIZES["layers_kept"]:
        attn = SIZES["layer_types"][l] == "attention"
        mixer = f"l{l}_attn" if attn else f"l{l}_mamba"
        assert nodes[f"l{l}_add1"].inputs[1] == mixer
        assert nodes[f"l{l}_add2"].inputs == [f"l{l}_add1", f"l{l}_mlp"]
        for add in ("add1", "add2"):
            assert nodes[f"l{l}_{add}"].vertex.branch_scale == 0.22
        layer = nodes[mixer].layer
        if attn:
            assert (type(layer).__name__, layer.rope, layer.qk_norm,
                    layer.sm_scale, layer.window) == (
                "RotaryAttentionLayer", False, False, 0.015625, None)
        else:
            assert type(layer).__name__ == "Mamba2Layer"
            assert (layer.n_heads, layer.d_state, layer.n_groups,
                    layer.chunk) == (8, 16, 1, 16)
    assert [l for l in SIZES["layers_kept"]
            if SIZES["layer_types"][l] == "attention"] == [5]


def test_the_residual_add_scales_its_branch_and_nothing_else():
    from deeplearning4j_tpu.nn.graph import ElementWiseVertex

    a, b, c = (jnp.full((2, 3), v) for v in (1.0, 2.0, 4.0))
    _close(ElementWiseVertex("add", branch_scale=0.22).apply(a, b, c),
           jnp.full((2, 3), 1.0 + 0.22 * 6.0))
    _close(ElementWiseVertex("add").apply(a, b, c), jnp.full((2, 3), 7.0))


# --- the shared attention layer's new fields -------------------------------------

def _attention_layer(**kw):
    layer = L.RotaryAttentionLayer(n_heads=8, n_kv_heads=2, head_dim=16, **kw)
    layer.set_input_type(RNNInput(64, 24))
    return layer


def test_qk_norm_and_sm_scale_defaults_leave_the_layer_as_it_was():
    """At their defaults the layer draws the same leaves and traces the same
    program with or without the fields named."""
    plain = _attention_layer()
    p = plain.init_params(jax.random.key(8))
    assert sorted(p) == ["Wk", "Wo", "Wq", "Wv", "k_norm", "q_norm"]
    named = _attention_layer(qk_norm=True, sm_scale=None)
    x = jax.random.normal(jax.random.key(9), (1, 24, 64), jnp.float32)
    jp = lambda layer: str(jax.make_jaxpr(                 # noqa: E731
        lambda p, x: layer.apply(p, x, {}, True, None)[0])(p, x))
    assert jp(plain) == jp(named)


def test_no_qk_norm_drops_the_leaves_and_sm_scale_sets_the_softmax():
    """Without per-head norms the layer has no q_norm / k_norm; with the
    norms' gains at 1 and the queries pre-scaled, the default scale gives
    the same output as an explicit one (the softmax's argument is q k^T
    times the scale)."""
    bare = _attention_layer(qk_norm=False, rope=False)
    p = bare.init_params(jax.random.key(10))
    assert sorted(p) == ["Wk", "Wo", "Wq", "Wv"]
    x = jax.random.normal(jax.random.key(11), (1, 24, 64), jnp.float32)
    scaled = _attention_layer(qk_norm=False, rope=False, sm_scale=0.015625)
    y, _ = scaled.apply(p, x, {}, False, None)
    # the same softmax argument from the default 1/sqrt(16) and Wq scaled
    p2 = {**p, "Wq": p["Wq"] * (0.015625 * 4.0)}
    z, _ = bare.apply(p2, x, {}, False, None)
    _close(y, z, 1e-5)
    w, _ = bare.apply(p, x, {}, False, None)
    assert float(jnp.max(jnp.abs(w - y))) > 1e-4


def test_the_mamba2_layer_is_its_reference():
    """One ``Mamba2Layer`` against the reference's mixer on the same
    parameters, float32: projections, convolution with bias, the scan, the
    D skip and the gated norm."""
    layer = L.Mamba2Layer(d_inner=128, n_heads=8, d_state=16, n_groups=1,
                          chunk=16)
    layer.set_input_type(RNNInput(64, 40))
    p = layer.init_params(jax.random.key(12))
    f32 = jnp.float32
    p = {**p, "conv_b": 0.1 * jax.random.normal(jax.random.key(13),
                                                p["conv_b"].shape, f32),
         "conv_w": 0.3 * jax.random.normal(jax.random.key(14),
                                           p["conv_w"].shape, f32)}
    x = jax.random.normal(jax.random.key(15), (2, 40, 64), f32)
    y, _ = layer.apply(p, x, {}, True, None)
    ops = CONF.ref_ops(SIZES, compare.EXACT)
    with jax.default_matmul_precision("highest"):
        want = ops.mamba(p, x)
    _close(y, want, 2e-5)
