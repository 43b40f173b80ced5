"""Flash-attention Pallas kernel conformance (interpret mode on the CPU
test mesh; the same kernel lowers through Mosaic on TPU —
tests/test_tpu_compile.py compiles it for the chip, chip_smoke.py runs it
there). Parity target: ops/nn.dot_product_attention, the dense reference
implementation."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.nn import dot_product_attention
from deeplearning4j_tpu.ops.pallas_attention import (flash_attention,
                                                     supports_flash)

rng = np.random.RandomState(3)


def _qkv(b=2, h=2, t=256, d=64):
    return (rng.randn(b, h, t, d).astype(np.float32) * 0.3,
            rng.randn(b, h, t, d).astype(np.float32) * 0.3,
            rng.randn(b, h, t, d).astype(np.float32) * 0.3)


def _dense(q, k, v, causal=False):
    if not causal:
        return dot_product_attention(q, k, v)
    t = q.shape[-2]
    mask = np.tril(np.ones((t, t), bool))
    return dot_product_attention(q, k, v, mask=mask)


class TestFlashForward:
    def test_matches_dense(self):
        from deeplearning4j_tpu.ops import exec_op

        q, k, v = _qkv()
        got = np.asarray(exec_op("flash_attention", q, k, v,
                                 interpret=True))
        ref = np.asarray(_dense(q, k, v))
        np.testing.assert_allclose(got, ref, atol=2e-5)

    def test_causal_matches_dense(self):
        q, k, v = _qkv(t=256)
        got = np.asarray(flash_attention(q, k, v, causal=True,
                                         interpret=True))
        ref = np.asarray(_dense(q, k, v, causal=True))
        np.testing.assert_allclose(got, ref, atol=2e-5)

    def test_causal_is_the_band_kernel(self):
        """No second causal kernel: the call is ``causal_attention``'s, and
        counted as one."""
        from deeplearning4j_tpu.common.profiler import OpProfiler

        prof = OpProfiler.get()
        before = prof.counter_value("seq/attn_kernel")
        q, k, v = _qkv(b=1, h=2, t=256)
        flash_attention(q, k, v, causal=True, block_q=128, interpret=True)
        assert prof.counter_value("seq/attn_kernel") == before + 1

    def test_multiple_k_blocks(self):
        q, k, v = _qkv(b=1, h=1, t=512, d=32)
        got = np.asarray(flash_attention(q, k, v, block_q=128, block_k=128,
                                         interpret=True))
        ref = np.asarray(_dense(q, k, v))
        np.testing.assert_allclose(got, ref, atol=2e-5)

    def test_three_dim_single_head(self):
        q, k, v = (a[:, 0] for a in _qkv(b=2, h=1, t=128, d=32))
        got = np.asarray(flash_attention(q, k, v, interpret=True))
        ref = np.asarray(_dense(q[:, None], k[:, None], v[:, None]))[:, 0]
        np.testing.assert_allclose(got, ref, atol=2e-5)

    def test_unsupported_length_raises(self):
        assert not supports_flash(100, 64)
        q, k, v = _qkv(t=128)
        with pytest.raises(ValueError, match="fall back"):
            flash_attention(q[:, :, :100], k[:, :, :100], v[:, :, :100],
                            interpret=True)


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_dense(self, causal):
        q, k, v = _qkv(b=1, h=2, t=256, d=32)
        tgt = rng.randn(1, 2, 256, 32).astype(np.float32)

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=causal, interpret=True)
            return jnp.mean((out - tgt) ** 2)

        def loss_dense(q, k, v):
            return jnp.mean((_dense(q, k, v, causal=causal) - tgt) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, err_msg=f"d{name}")

    def test_causal_with_bias_matches_dense(self):
        """The mask joins the bias; the bias is differentiated."""
        q, k, v = _qkv(b=1, h=2, t=256, d=32)
        bias = rng.randn(1, 2, 256, 256).astype(np.float32) * 0.5
        tril = np.tril(np.ones((256, 256), bool))

        def dense(q, k, v, bias):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(32.0) + bias
            w = jax.nn.softmax(jnp.where(tril, s, -jnp.inf), axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", w, v)

        def flash(q, k, v, bias):
            return flash_attention(q, k, v, causal=True, bias=bias,
                                   block_q=128, block_k=128, interpret=True)

        np.testing.assert_allclose(np.asarray(flash(q, k, v, bias)),
                                   np.asarray(dense(q, k, v, bias)),
                                   atol=2e-5)
        tgt = rng.randn(1, 2, 256, 32).astype(np.float32)
        g = lambda f: jax.grad(                              # noqa: E731
            lambda *a: jnp.mean((f(*a) - tgt) ** 2), (0, 1, 2, 3))(q, k, v,
                                                                   bias)
        for a, b, name in zip(g(flash), g(dense), ["q", "k", "v", "bias"]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, err_msg=f"d{name}")

    def test_trains_toward_target(self):
        q, k, v = _qkv(b=1, h=1, t=128, d=16)
        tgt = np.asarray(_dense(q, k, v)) * 0.5

        @jax.jit
        def step(params):
            def loss(p):
                out = flash_attention(p["q"], p["k"], p["v"],
                                      interpret=True)
                return jnp.mean((out - tgt) ** 2)

            l, g = jax.value_and_grad(loss)(params)
            return jax.tree.map(lambda a, b: a - 5.0 * b, params, g), l

        params = {"q": jnp.asarray(q), "k": jnp.asarray(k),
                  "v": jnp.asarray(v)}
        losses = []
        for _ in range(60):
            params, l = step(params)
            losses.append(float(l))
        assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])
