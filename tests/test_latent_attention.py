"""Latent attention (``LatentAttentionLayer``), the interleaved rotary
embedding (``ops.moe.rotary_embedding(interleaved=True)``), the banded
attention kernels at a head width that is not the value's (192 / 128, in
interpret mode) and the routed layer in the DeepSeek-V3 family's form (top-8,
scale 2.5, epsilon 1e-20, a shared expert beside it), against the plain
reference of ``benchmarks/configs/joyai_llm_flash.py``, which imports nothing
of the package.

Tolerances as ``tests/test_moe_layers.py``: float32 against float32 at
``highest`` differ by rounding alone (another order of the same sums): 1e-5 of
the largest magnitude of what is compared, 2e-5 for gradients.
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
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf import layers_seq
from deeplearning4j_tpu.nn.conf.inputs import RNNInput
from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.ops import pallas_attention as pa

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import compare  # noqa: E402  (benchmarks/compare.py)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CONF = _load(os.path.join(BENCH, "configs", "joyai_llm_flash.py"),
             "bench_conf_joyai")
CFG = json.load(open(os.path.join(BENCH, "configs", "joyai_llm_flash.json")))
SIZES = CONF.sizes_of(CFG, True)    # d=64, 4 heads of 16+8 / 16, experts 32
REF = CONF.ref_ops(SIZES, compare.EXACT)
D, T, B = SIZES["hidden_size"], 32, 2
E, K, FF = (SIZES["router_width"], SIZES["num_experts_per_tok"],
            SIZES["moe_intermediate_size"])
F32 = jnp.float32
BIAS = jnp.asarray(SIZES["expert_bias"], F32)


def _close(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-30), \
        np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)


def _wide(layer, t=T):
    """The layer with its input type set and its matrices drawn wide enough
    (std 0.3) that every term of its output matters."""
    layer.set_input_type(RNNInput(D, t))
    params = layer.init_params(jax.random.PRNGKey(3))
    return layer, jax.tree.map(
        lambda a: a * 15.0 if a.ndim >= 2 and a.shape[-2] > 8 else a, params)


def _x(seed=0, t=T):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, t, D), F32)


def _mla():
    layer, p = _wide(L.LatentAttentionLayer(
        n_heads=SIZES["num_attention_heads"],
        q_lora_rank=SIZES["q_lora_rank"], kv_lora_rank=SIZES["kv_lora_rank"],
        qk_nope_head_dim=SIZES["qk_nope_head_dim"],
        qk_rope_head_dim=SIZES["qk_rope_head_dim"],
        v_head_dim=SIZES["v_head_dim"], rope_theta=100.0,
        eps=SIZES["rms_norm_eps"]))
    for i, g in enumerate(("q_norm", "kv_norm")):
        p[g] = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(6 + i),
                                             p[g].shape, F32)
    ref = CONF.ref_ops({**SIZES, "rope_theta": 100.0}, compare.EXACT)
    return layer, p, ref.attention


@pytest.mark.parametrize("seed", [1, 2])
def test_latent_attention_matches_reference(seed):
    """Output, the input's gradient and every leaf's; a small theta so that
    the rotation matters at 32 positions."""
    layer, p, ref = _mla()
    x = _x(seed)
    w = jax.random.normal(jax.random.PRNGKey(2), x.shape, F32)

    def prog(p, x):
        return layer.apply(p, x, {}, True, None)[0]

    _close(prog(p, x), ref(p, x))
    got = jax.grad(lambda p, x: jnp.sum(prog(p, x) * w), (0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(ref(p, x) * w), (0, 1))(p, x)
    assert sorted(got[0]) == ["W_kva", "W_kvb", "W_o", "W_qa", "W_qb",
                              "kv_norm", "q_norm"]
    for leaf in want[0]:
        assert np.asarray(want[0][leaf]).any()
        _close(got[0][leaf], want[0][leaf], 2e-5)
    _close(got[1], want[1], 2e-5)


def test_latent_attention_is_causal_and_shares_one_rotated_key_slice():
    """Position t's output does not move when later positions change; and
    the shared key slice is rotated by position: shifting the sequence by one
    changes the scores only through the nope parts (relative positions are
    kept), so a model with W_kvb's key columns zeroed gives shifted outputs."""
    layer, p, _ = _mla()
    x = _x(3)
    y = layer.apply(p, x, {}, True, None)[0]
    x2 = x.at[:, 20:].set(_x(4)[:, 20:])
    y2 = layer.apply(p, x2, {}, True, None)[0]
    _close(y2[:, :20], y[:, :20])
    assert np.max(np.abs(np.asarray(y2[:, 20:] - y[:, 20:]))) > 1e-3
    assert OpProfiler.get().sequence_stats()["mla_layers"] >= 2


def test_interleaved_rotary_is_the_pairwise_rotation():
    rng = np.random.RandomState(10)
    x = rng.randn(2, 3, 7, 8).astype(np.float32)
    pos = np.arange(7)
    got = np.asarray(moe.rotary_embedding(jnp.asarray(x), jnp.asarray(pos),
                                          50.0, interleaved=True))
    want = np.zeros_like(x)
    for t in range(7):
        for i in range(4):
            ang = pos[t] * 50.0 ** (-2.0 * i / 8)
            a, b = x[..., t, 2 * i], x[..., t, 2 * i + 1]
            want[..., t, 2 * i] = a * np.cos(ang) - b * np.sin(ang)
            want[..., t, 2 * i + 1] = b * np.cos(ang) + a * np.sin(ang)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # norms kept; the reference's own rotation is the same
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    ref = CONF.ref_ops({**SIZES, "rope_theta": 50.0}, compare.EXACT).rotary
    np.testing.assert_allclose(got, np.asarray(ref(jnp.asarray(x))),
                               atol=2e-6)
    # de-interleaving q and k alike and rotating halves gives the same scores
    perm = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    half = np.asarray(moe.rotary_embedding(jnp.asarray(x[..., perm]),
                                           jnp.asarray(pos), 50.0))
    np.testing.assert_allclose(half, got[..., perm], atol=2e-6)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
def test_band_kernels_at_192_wide_keys_and_128_wide_values(dtype, tol):
    """``flash_attention_fwd`` / ``flash_attention_bwd`` in interpret mode at
    the published head widths (d 192, dv 128, one query head a key head)
    against the XLA loops."""
    assert pa.supports_band_kernel(8192, 192, 128, pa.BAND_BLOCK)
    assert pa.supports_band_bwd_kernel(8192, 192, 1, 2)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(ks[i], (1, 2, 256, 192), F32).astype(dtype)
            for i in range(2))
    v = jax.random.normal(ks[2], (1, 2, 256, 128), F32).astype(dtype)
    w = jax.random.normal(ks[3], (1, 2, 256, 128), F32)
    before = OpProfiler.get().sequence_stats().get("attn_bwd_kernel", 0)

    def run(interpret):
        def f(q, k, v):
            o = pa.causal_attention(q, k, v, block=128, interpret=interpret)
            return jnp.sum(o.astype(F32) * w), o
        (_, o), g = jax.value_and_grad(f, (0, 1, 2), has_aux=True)(q, k, v)
        return (o, *g)

    for got, want in zip(run(True), run(False)):
        assert got.dtype == jnp.dtype(dtype)
        _close(got.astype(F32), want.astype(F32), tol)
    assert OpProfiler.get().sequence_stats()["attn_bwd_kernel"] == before + 1


# --- the routed layer in this family's form ------------------------------------

def _routed(first=0, held=SIZES["n_routed_experts"]):
    return _wide(L.RoutedExpertsLayer(
        n_routed=E, n_experts=held, first_expert=first, n_ff=FF, top_k=K,
        scale=SIZES["routed_scaling_factor"],
        norm_eps=SIZES["route_norm_eps"],
        selection_bias=SIZES["expert_bias"]))


def test_routing_epsilon_reaches_route_topk(monkeypatch):
    """The layer's ``norm_eps`` is the one in the weights' denominator: with
    scores of about 1e-7 the default 1e-6 would halve the weights, the
    family's 1e-20 leaves them summing to the scale."""
    layer, _ = _routed(held=E)
    assert layer.norm_eps == 1e-20
    xt = 1.0 + 0.1 * _x(8).reshape(-1, D)
    wg = (-16.0 / D) * (1.0 + 0.05 * jax.random.normal(
        jax.random.PRNGKey(9), (D, E), F32))
    experts, weights, _ = REF.route({"Wg": wg}, BIAS, xt)
    seen = {}
    real = moe.route_topk

    def spy(x, w, b, k, scale=1.0, norm_eps=1e-6):
        seen.update(scale=scale, norm_eps=norm_eps)
        return real(x, w, b, k, scale, norm_eps)

    monkeypatch.setattr(layers_seq, "route_topk", spy)
    p = layer.init_params(jax.random.PRNGKey(1))
    layer.apply({**p, "Wg": wg}, xt.reshape(B, T, D), layer.init_state(),
                True, None)
    assert seen == {"scale": 2.5, "norm_eps": 1e-20}
    got_experts, got, _ = real(xt, wg, BIAS, K, 2.5, layer.norm_eps)
    assert np.array_equal(np.asarray(got_experts), np.asarray(experts))
    _close(got, weights)
    _close(jnp.sum(got, -1), jnp.full((xt.shape[0],), 2.5), 1e-5)
    _, loose, _ = real(xt, wg, BIAS, K, 2.5)
    assert float(jnp.max(jnp.sum(loose, -1))) < 2.0
