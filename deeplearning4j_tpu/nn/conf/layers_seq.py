"""Sequence-model layers of hybrid decoders.

``MambaLayer`` (Gu & Dao, arXiv:2312.00752, Alg. 2), ``DifferentialAttentionLayer``
(Ye et al., arXiv:2410.05258, causal, grouped-query, optionally windowed, or
reading another layer's keys and values), ``GatedMemoryUnit`` (Ren et al.,
arXiv:2507.06607 section 2), ``GatedMLPLayer`` and ``TiedOutputLayer`` (the
head that reads the embedding table and computes its loss in token blocks):
``models.Phi4MiniFlash``. ``RMSNormLayer``, ``ShortConvLayer`` (a gated
depthwise causal convolution of a few taps), ``RotaryAttentionLayer``
(grouped-query attention with per-head RMSNorm on queries and keys and rotary
positions) and ``RoutedExpertsLayer`` (a dropless top-k expert layer that
holds a share of the experts; the second kind of layer with state, after
BatchNorm: a selection bias and the accumulated load of each expert):
``models.Lfm2Moe`` (the ``lfm2_moe`` family of Hugging Face
``transformers``); the same two with a window, an output gate and full
layers without rotation, and a selection bias moved by a balance rule:
``models.TrinityMini`` (the ``afmoe`` family). ``LatentAttentionLayer``
(attention through low-rank latents, a rotated slice of each head, keys
wider than values),
``MTPMergeLayer`` (the entry of a multi-token-prediction module) and
``LMHeadLayer`` (the head that owns its matrix; ``TiedOutputLayer`` is the
same head reading another node's): ``models.JoyAILLMFlash`` (the DeepSeek-V3
family). ``Mamba2Layer`` (the Mamba-2 mixer over ``ops.ssm.ssd_scan``) and
``RotaryAttentionLayer`` without rotation or per-head norm and with the
family's softmax scale: ``models.GraniteHybrid`` (the ``granitemoehybrid``
family). All take and give ``[B, T, F]``. They are plain layer
configurations: a ``ComputationGraph`` wires them with a norm layer and
``ElementWiseVertex(add)`` into pre-norm residual blocks.

Three things here that the older layers do not use, each read by
``ComputationGraph``:

- ``multi_input``: the layer's ``x`` is the tuple of its node's inputs;
- ``extra_outputs()``: names of further tensors that ``apply`` returns after
  ``y``; another node reads one as ``"<node>.<name>"``;
- ``full_precision_params``: leaves that stay float32 under a reduced
  ``compute_dtype`` (a decay rate, a step bias), and ``borrowed_params()``:
  leaves that belong to another node (the tied head reads the embedding's
  table: one leaf, one gradient, one optimizer state; a leaf may be borrowed
  by several nodes, and its gradient is the sum over its uses).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...common.profiler import OpProfiler
from ...ops.moe import (GMM_ROW_TILE, grouped_gated_mlp, rotary_embedding,
                        route_topk)
from ...ops.pallas_attention import causal_attention
from ...ops.ssm import SSD_CHUNK, selective_scan, ssd_scan
from ..losses import LossSparseMCXENT
from .inputs import RNNInput
from .layers import Layer, LossLayer

_STD = 0.02     # N(0, 0.02) on every matrix, as GPT-2 and the Phi family
HEAD_TOKEN_BLOCK = 1024     # positions whose logits the head's loss holds at once


def _normal(key, shape, dtype, std=_STD):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _f32(a):
    """At least float32 (float64 stays: gradient checks)."""
    return a.astype(jnp.promote_types(a.dtype, jnp.float32))


@dataclass
class GatedMLPLayer(Layer):
    """``[g, u] = x W1``; ``y = (u * silu(g)) W2``; no bias. ``scope``: the
    ``jax.named_scope`` its ops carry in a trace (a shared expert beside a
    routed layer is the same layer under another name)."""

    n_ff: int = 0
    scope: str = "gated_mlp"

    def set_input_type(self, input_type):
        self.n_in = input_type.size
        return input_type

    def init_params(self, key, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        return {"W1": _normal(k1, (self.n_in, 2 * self.n_ff), dtype),
                "W2": _normal(k2, (self.n_ff, self.n_in), dtype)}

    def apply(self, params, x, state, training, rng):
        with jax.named_scope(self.scope):
            g, u = jnp.split(x @ params["W1"], 2, axis=-1)
            return (u * jax.nn.silu(g)) @ params["W2"], state


@dataclass
class MambaLayer(Layer):
    """Mamba-1 mixer. ``emit_memory`` also exposes the scan's output before
    the gate (``[B, T, d_inner]``) as ``"<node>.memory"``."""

    d_inner: int = 0
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0
    emit_memory: bool = False

    full_precision_params = ("A_log", "b_dt", "D")

    def set_input_type(self, input_type):
        self.n_in = input_type.size
        if not self.dt_rank:
            self.dt_rank = math.ceil(self.n_in / 16)
        return input_type

    def extra_outputs(self) -> Tuple[str, ...]:
        return ("memory",) if self.emit_memory else ()

    def extra_output_types(self, input_type) -> Tuple:
        return ((RNNInput(self.d_inner, input_type.timesteps),)
                if self.emit_memory else ())

    def init_params(self, key, dtype=jnp.float32):
        ks = jax.random.split(key, 6)
        di, n, r = self.d_inner, self.d_state, self.dt_rank
        # dt bias: softplus^-1 of a step drawn log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(ks[4], (di,), jnp.float32)
                     * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return {
            "W_in": _normal(ks[0], (self.n_in, 2 * di), dtype),
            "conv_w": _normal(ks[1], (self.d_conv, di), dtype),
            "conv_b": jnp.zeros((di,), dtype),
            "W_x": _normal(ks[2], (di, r + 2 * n), dtype),
            "W_dt": _normal(ks[3], (r, di), dtype),
            "b_dt": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                (di, n)).astype(dtype),
            "D": jnp.ones((di,), dtype),
            "W_out": _normal(ks[5], (di, self.n_in), dtype),
        }

    def apply(self, params, x, state, training, rng):
        n, r = self.d_state, self.dt_rank
        with jax.named_scope("mamba"):
            u, z = jnp.split(x @ params["W_in"], 2, axis=-1)
            u = jax.nn.silu(params["conv_b"]
                            + _causal_conv(u, params["conv_w"]))
            proj = u @ params["W_x"]
            delta, Bm, Cm = (proj[..., :r], proj[..., r:r + n],
                             proj[..., r + n:])
            dt = jax.nn.softplus(_f32(delta @ params["W_dt"])
                                 + _f32(params["b_dt"]))
            y = selective_scan(u, dt, -jnp.exp(_f32(params["A_log"])),
                               Bm, Cm)
            y = y + (_f32(params["D"]) * _f32(u)).astype(y.dtype)
            out = (y * jax.nn.silu(z)) @ params["W_out"]
        return ((out, y) if self.emit_memory else out), state


@dataclass
class Mamba2Layer(Layer):
    """Mamba-2 mixer (Dao & Gu, arXiv:2405.21060; the ``granitemoehybrid``
    family's ``mamba`` layer). ``[z | xBC | dt] = x W_in`` (``d_inner``,
    ``d_inner + 2 n_groups d_state``, ``n_heads`` wide; no bias); ``xBC <-
    silu(causal depthwise conv of d_conv taps + conv_b)``; ``xBC = [X | B |
    C]`` with X ``n_heads`` heads of ``d_inner / n_heads`` and B, C
    ``n_groups`` groups of ``d_state`` (head ``h`` reads group ``h //
    (n_heads / n_groups)``); ``dt = softplus(dt + dt_bias)`` and ``A =
    -exp(A_log)``, one each a head; ``Y = ssd_scan(X, dt, A, B, C) + D X``;
    ``out = (RMSNorm(Y * silu(z)) * norm) W_out``, the gated norm over all
    ``d_inner`` channels in float32. Scopes: ``mamba2``, and inside it
    ``ssd`` (the scan) and ``gated_norm``; ``seq/mamba2_layers`` counts the
    layers as a step is traced."""

    d_inner: int = 0
    n_heads: int = 0
    d_state: int = 128
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = SSD_CHUNK
    eps: float = 1e-5

    full_precision_params = ("A_log", "dt_bias", "D", "norm")

    def set_input_type(self, input_type):
        self.n_in = input_type.size
        return input_type

    def init_params(self, key, dtype=jnp.float32):
        ks = jax.random.split(key, 3)
        di, h = self.d_inner, self.n_heads
        conv = di + 2 * self.n_groups * self.d_state
        return {
            "W_in": _normal(ks[0], (self.n_in, di + conv + h), dtype),
            "conv_w": _normal(ks[1], (self.d_conv, conv), dtype),
            "conv_b": jnp.zeros((conv,), dtype),
            "dt_bias": jnp.ones((h,), dtype),
            "A_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)).astype(
                dtype),
            "D": jnp.ones((h,), dtype),
            "norm": jnp.ones((di,), dtype),
            "W_out": _normal(ks[2], (di, self.n_in), dtype),
        }

    def apply(self, params, x, state, training, rng):
        di, h, gn = self.d_inner, self.n_heads, self.n_groups * self.d_state
        OpProfiler.get().count("seq/mamba2_layers")
        with jax.named_scope("mamba2"):
            b, T, _ = x.shape
            proj = x @ params["W_in"]
            z, xbc, dt = (proj[..., :di], proj[..., di:2 * di + 2 * gn],
                          proj[..., 2 * di + 2 * gn:])
            xbc = jax.nn.silu(params["conv_b"]
                              + _causal_conv(xbc, params["conv_w"]))
            # X stays token-major, [b, T, di]: the scan's kernels read it so
            xs = xbc[..., :di]
            groups = lambda a: a.reshape(b, T, self.n_groups,  # noqa: E731
                                         self.d_state)
            dt = jax.nn.softplus(_f32(dt) + _f32(params["dt_bias"]))
            with jax.named_scope("ssd"):
                y = ssd_scan(xs.reshape(b, T, h, di // h), dt,
                             -jnp.exp(_f32(params["A_log"])),
                             groups(xbc[..., di:di + gn]),
                             groups(xbc[..., di + gn:]),
                             chunk=self.chunk).reshape(b, T, di)
            y = _f32(y) + jnp.repeat(_f32(params["D"]), di // h) * _f32(xs)
            with jax.named_scope("gated_norm"):
                g = y * jax.nn.silu(_f32(z))
                g = _rms(g, params["norm"], self.eps).astype(x.dtype)
            return g @ params["W_out"], state


@dataclass
class DifferentialAttentionLayer(Layer):
    """Causal differential attention with grouped-query heads.

    ``q`` is viewed as ``[T, n_heads/2, 2, head_dim]`` (two query maps a
    pair-head), ``k`` as ``[T, n_kv_heads/2, 2, head_dim]`` and ``v`` as
    ``[T, n_kv_heads/2, 2*head_dim]``; pair-head ``h`` reads key/value group
    ``h // (n_heads/n_kv_heads)``. ``o_h = (1 - lambda_init) *
    RMSNorm(A_1 - lambda A_2)`` with ``A_i = softmax(q_i k_i^T /
    sqrt(head_dim) + mask) v`` and ``lambda = exp(lq1.lk1) - exp(lq2.lk2) +
    lambda_init``. ``window`` bands the mask (query i sees i-window < j <= i).
    ``emit_kv`` exposes the projected ``k`` and ``v`` (``"<node>.k"``,
    ``"<node>.v"``); ``cross=True`` makes the layer own only its queries,
    lambdas, norm and output projection and take ``(x, k, v)`` as inputs."""

    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 64
    window: Optional[int] = None
    cross: bool = False
    emit_kv: bool = False
    lambda_init: float = 0.8
    eps: float = 1e-5

    full_precision_params = ("lambda_q1", "lambda_k1", "lambda_q2",
                             "lambda_k2")

    @property
    def multi_input(self) -> bool:
        return self.cross

    def set_input_type(self, input_type):
        t = input_type[0] if isinstance(input_type, (tuple, list)) else input_type
        self.n_in = t.size
        return t

    def extra_outputs(self) -> Tuple[str, ...]:
        return ("k", "v") if self.emit_kv else ()

    def extra_output_types(self, input_type) -> Tuple:
        kv = RNNInput(self.n_kv_heads * self.head_dim, input_type.timesteps)
        return (kv, kv) if self.emit_kv else ()

    def init_params(self, key, dtype=jnp.float32):
        ks = jax.random.split(key, 8)
        d, hd = self.n_in, self.head_dim
        nq, nkv = self.n_heads * hd, self.n_kv_heads * hd
        p = {"Wq": _normal(ks[0], (d, nq), dtype), "bq": jnp.zeros((nq,), dtype),
             "Wo": _normal(ks[3], (nq, d), dtype), "bo": jnp.zeros((d,), dtype),
             "subln": jnp.ones((2 * hd,), dtype)}
        for i, name in enumerate(self.full_precision_params):
            p[name] = _normal(ks[4 + i], (hd,), dtype, std=0.1)
        if not self.cross:
            p.update(Wk=_normal(ks[1], (d, nkv), dtype),
                     bk=jnp.zeros((nkv,), dtype),
                     Wv=_normal(ks[2], (d, nkv), dtype),
                     bv=jnp.zeros((nkv,), dtype))
        return p

    def apply(self, params, x, state, training, rng):
        hd, hp, gp = self.head_dim, self.n_heads // 2, self.n_kv_heads // 2
        with jax.named_scope("diff_attn"):
            if self.cross:
                x, k, v = x
            else:
                k = x @ params["Wk"] + params["bk"]
                v = x @ params["Wv"] + params["bv"]
            b, T, _ = x.shape
            q = (x @ params["Wq"] + params["bq"]).reshape(b, T, hp, 2, hd)
            k2 = k.reshape(b, T, gp, 2, hd)
            # the two maps side by side on the batch axis: one call
            heads = lambda a: jnp.concatenate(          # noqa: E731
                [a[:, :, :, 0], a[:, :, :, 1]], 0).transpose(0, 2, 1, 3)
            vv = v.reshape(b, T, gp, 2 * hd).transpose(0, 2, 1, 3)
            a = causal_attention(heads(q), heads(k2),
                                 jnp.concatenate([vv, vv], 0),
                                 window=self.window)
            f = lambda name: _f32(params[name])         # noqa: E731
            lam = (jnp.exp(jnp.sum(f("lambda_q1") * f("lambda_k1")))
                   - jnp.exp(jnp.sum(f("lambda_q2") * f("lambda_k2")))
                   + self.lambda_init)
            o = _f32(a[:b]) - lam * _f32(a[b:])         # [B, hp, T, 2*hd]
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + self.eps)
            o = o * f("subln") * (1.0 - self.lambda_init)
            o = o.astype(x.dtype).transpose(0, 2, 1, 3).reshape(b, T, hp * 2 * hd)
            out = o @ params["Wo"] + params["bo"]
        return ((out, k, v) if self.emit_kv else out), state


@dataclass
class GatedMemoryUnit(Layer):
    """Inputs ``(x, m)``: ``y = (m * silu(x W1)) W2`` with ``m`` the memory
    that an earlier ``MambaLayer(emit_memory=True)`` exposed; no bias."""

    d_mem: int = 0
    multi_input = True

    def set_input_type(self, input_type):
        x, m = input_type
        self.n_in, self.d_mem = x.size, m.size
        return x

    def init_params(self, key, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        return {"W1": _normal(k1, (self.n_in, self.d_mem), dtype),
                "W2": _normal(k2, (self.d_mem, self.n_in), dtype)}

    def apply(self, params, x, state, training, rng):
        x, m = x
        with jax.named_scope("gmu"):
            return (m * jax.nn.silu(x @ params["W1"])) @ params["W2"], state


def _causal_conv(x, w):
    """Causal depthwise convolution over the time axis of ``x`` ``[B, T, F]``
    with taps ``w`` ``[k, F]``: tap ``j`` reads ``k - 1 - j`` steps back, ``x``
    zero before the sequence."""
    T, k = x.shape[1], w.shape[0]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + T] * w[j] for j in range(k))


def _rms(x, gain, eps):
    """``x * rsqrt(mean(x^2) + eps) * gain`` over the last axis in float32,
    back in ``x``'s dtype."""
    xw = _f32(x)
    y = xw * jax.lax.rsqrt(jnp.mean(xw * xw, -1, keepdims=True) + eps)
    return (y * _f32(gain)).astype(x.dtype)


@dataclass
class RMSNormLayer(Layer):
    """``y = x * rsqrt(mean(x^2) + eps) * gain`` over the feature axis, in
    float32 whatever the compute dtype; no bias."""

    eps: float = 1e-5
    full_precision_params = ("gain",)

    def set_input_type(self, input_type):
        self.n_in = input_type.size
        return input_type

    def init_params(self, key, dtype=jnp.float32):
        return {"gain": jnp.ones((self.n_in,), dtype)}

    def apply(self, params, x, state, training, rng):
        with jax.named_scope("rms_norm"):
            return _rms(x, params["gain"], self.eps), state


@dataclass
class ShortConvLayer(Layer):
    """Gated short convolution: ``[B, C, u] = x W_in``; ``v = B * u``;
    ``c_t = sum_j w_j * v_{t-(taps-1)+j}`` (depthwise, causal, ``v`` zero
    before the sequence; no bias, no activation); ``y = (C * c) W_out``."""

    taps: int = 3

    def set_input_type(self, input_type):
        self.n_in = input_type.size
        return input_type

    def init_params(self, key, dtype=jnp.float32):
        k1, k2, k3 = jax.random.split(key, 3)
        d = self.n_in
        return {"W_in": _normal(k1, (d, 3 * d), dtype),
                "conv_w": _normal(k2, (self.taps, d), dtype),
                "W_out": _normal(k3, (d, d), dtype)}

    def apply(self, params, x, state, training, rng):
        with jax.named_scope("short_conv"):
            b, c, u = jnp.split(x @ params["W_in"], 3, axis=-1)
            conv = _causal_conv(b * u, params["conv_w"])
            return (c * conv) @ params["W_out"], state


@dataclass
class RotaryAttentionLayer(Layer):
    """Causal grouped-query attention with rotary positions: ``q = x Wq``
    (``n_heads`` of ``head_dim``), ``k = x Wk``, ``v = x Wv`` (``n_kv_heads``
    each), no bias; RMSNorm over each head of q and of k with one learned
    gain each (``q_norm``, ``k_norm``); rotate-half rotary embedding at
    positions 0..T-1; query head ``h`` reads key/value head ``h //
    (n_heads / n_kv_heads)``; ``softmax(q k^T / sqrt(head_dim) + causal
    mask) v``; heads concatenated into ``Wo``.

    ``window``: query ``i`` sees keys ``i - window < j <= i`` (all ``j <=
    i`` when None). ``rope`` False: no rotation, no position at all (NoPE:
    the full layers of the ``afmoe`` family). ``output_gate``: the heads'
    output is gated before ``Wo``, ``o * sigmoid(x W_gate)`` with ``W_gate``
    ``[d, n_heads * head_dim]`` from the same ``x`` (scope ``attn_gate``).
    ``qk_norm`` False: no per-head norm and no ``q_norm`` / ``k_norm``
    leaves. ``sm_scale``: the softmax's scale in place of ``1 /
    sqrt(head_dim)`` (the ``granitemoehybrid`` family's
    ``attention_multiplier``). As a step is traced, ``seq/attn_nope_layers``
    and ``seq/attn_gated_layers`` count the layers that skip the rotation and
    that gate."""

    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 64
    rope_theta: float = 10000.0
    eps: float = 1e-5
    window: Optional[int] = None
    rope: bool = True
    output_gate: bool = False
    qk_norm: bool = True
    sm_scale: Optional[float] = None

    full_precision_params = ("q_norm", "k_norm")

    def set_input_type(self, input_type):
        self.n_in = input_type.size
        return input_type

    def init_params(self, key, dtype=jnp.float32):
        ks = jax.random.split(key, 4)
        d, hd = self.n_in, self.head_dim
        nq, nkv = self.n_heads * hd, self.n_kv_heads * hd
        params = {"Wq": _normal(ks[0], (d, nq), dtype),
                  "Wk": _normal(ks[1], (d, nkv), dtype),
                  "Wv": _normal(ks[2], (d, nkv), dtype),
                  "Wo": _normal(ks[3], (nq, d), dtype)}
        if self.qk_norm:
            params.update(q_norm=jnp.ones((hd,), dtype),
                          k_norm=jnp.ones((hd,), dtype))
        if self.output_gate:
            params["W_gate"] = _normal(jax.random.fold_in(key, 4), (d, nq),
                                       dtype)
        return params

    def apply(self, params, x, state, training, rng):
        hd = self.head_dim
        prof = OpProfiler.get()
        with jax.named_scope("rope_attn"):
            b, T, _ = x.shape
            pos = jnp.arange(T)
            if not self.rope:
                prof.count("seq/attn_nope_layers")

            def heads(w, n, gain=None):     # -> [B, n, T, hd]
                a = (x @ params[w]).reshape(b, T, n, hd)
                if gain is not None and self.qk_norm:
                    a = _rms(a, params[gain], self.eps)
                a = a.transpose(0, 2, 1, 3)
                return (a if gain is None or not self.rope
                        else rotary_embedding(a, pos, self.rope_theta))

            o = causal_attention(heads("Wq", self.n_heads, "q_norm"),
                                 heads("Wk", self.n_kv_heads, "k_norm"),
                                 heads("Wv", self.n_kv_heads),
                                 window=self.window, sm_scale=self.sm_scale)
            o = o.transpose(0, 2, 1, 3).reshape(b, T, self.n_heads * hd)
            if self.output_gate:
                prof.count("seq/attn_gated_layers")
                with jax.named_scope("attn_gate"):
                    o = o * jax.nn.sigmoid(x @ params["W_gate"])
            return o @ params["Wo"], state


@dataclass
class LatentAttentionLayer(Layer):
    """Multi-head latent attention (DeepSeek-V2/V3: arXiv:2405.04434 section
    2.1, arXiv:2412.19437 section 2.1.1), causal, as it is trained: queries
    and keys/values come through low-rank latents, and only a slice of each
    head is rotated. ``c_q = RMSNorm(x W_qa)`` (``q_lora_rank`` wide); ``q =
    c_q W_qb`` -> ``n_heads`` heads of ``[q_nope (qk_nope_head_dim) ; q_rope
    (qk_rope_head_dim)]``. ``x W_kva`` -> ``[c_kv (kv_lora_rank) ; k_rope
    (qk_rope_head_dim)]``; ``c_kv = RMSNorm(c_kv)``; ``c_kv W_kvb`` ->
    ``n_heads`` heads of ``[k_nope (qk_nope_head_dim) ; v (v_head_dim)]``.
    Rotary positions 0..T-1 on ``q_rope`` of every head and on the one
    ``k_rope`` a token, which all heads share, in interleaved pairs ``(2i,
    2i+1)`` (the family's ``rope_interleave``). ``k_h = [k_nope_h ;
    k_rope]``; ``softmax(q_h k_h^T / sqrt(qk_nope_head_dim +
    qk_rope_head_dim) + causal mask) v_h``; heads concatenated into ``W_o``.
    No bias. The keys are expanded per head and go through
    ``causal_attention`` with a head width that is not the value's
    (``[B, H, T, 192]`` / ``[B, H, T, 128]`` at the published sizes); the
    latent cache and the absorbed products of decoding are serving's."""

    n_heads: int = 0
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    eps: float = 1e-6

    full_precision_params = ("q_norm", "kv_norm")

    def set_input_type(self, input_type):
        self.n_in = input_type.size
        return input_type

    def init_params(self, key, dtype=jnp.float32):
        ks = jax.random.split(key, 5)
        d, h = self.n_in, self.n_heads
        nope, rope, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
        return {"W_qa": _normal(ks[0], (d, self.q_lora_rank), dtype),
                "q_norm": jnp.ones((self.q_lora_rank,), dtype),
                "W_qb": _normal(ks[1], (self.q_lora_rank, h * (nope + rope)),
                                dtype),
                "W_kva": _normal(ks[2], (d, self.kv_lora_rank + rope), dtype),
                "kv_norm": jnp.ones((self.kv_lora_rank,), dtype),
                "W_kvb": _normal(ks[3], (self.kv_lora_rank, h * (nope + dv)),
                                 dtype),
                "W_o": _normal(ks[4], (h * dv, d), dtype)}

    def apply(self, params, x, state, training, rng):
        h, nope, r = self.n_heads, self.qk_nope_head_dim, self.kv_lora_rank
        b, T, _ = x.shape
        OpProfiler.get().count("seq/mla_layers")

        def rope(a):        # [B, heads, T, qk_rope_head_dim]
            return rotary_embedding(a, jnp.arange(T), self.rope_theta,
                                    interleaved=True)

        with jax.named_scope("mla_q"):
            c_q = _rms(x @ params["W_qa"], params["q_norm"], self.eps)
            q = (c_q @ params["W_qb"]).reshape(b, T, h, -1).transpose(
                0, 2, 1, 3)
            q = jnp.concatenate([q[..., :nope], rope(q[..., nope:])], -1)
        with jax.named_scope("mla_kv"):
            kva = x @ params["W_kva"]
            c_kv = _rms(kva[..., :r], params["kv_norm"], self.eps)
            kv = (c_kv @ params["W_kvb"]).reshape(b, T, h, -1).transpose(
                0, 2, 1, 3)
            k_rope = rope(kva[:, None, :, r:])      # one a token
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                k_rope, (b, h, T, k_rope.shape[-1]))], -1)
        with jax.named_scope("mla_attn"):
            o = causal_attention(q, k, kv[..., nope:])
        with jax.named_scope("mla_out"):
            o = o.transpose(0, 2, 1, 3).reshape(b, T, -1)
            return o @ params["W_o"], state


@dataclass
class MTPMergeLayer(Layer):
    """The entry of a multi-token-prediction module (DeepSeek-V3,
    arXiv:2412.19437 section 2.2). Inputs ``(h, ids)``: the trunk's output
    ``[B, T, d]`` and the ids ``[B, T]`` of each position's NEXT token; ``m =
    [RMSNorm_e(Emb(ids)) ; RMSNorm_h(h)] W_eh`` (``2d -> d``) with ``Emb`` the
    table of node ``embed`` itself (a borrowed leaf). The module's block, its
    norm and a head that borrows the trunk's follow as graph nodes."""

    embed: str = ""
    eps: float = 1e-6
    multi_input = True
    full_precision_params = ("e_norm", "h_norm")

    def set_input_type(self, input_type):
        self.n_in = input_type[0].size
        return input_type[0]

    def borrowed_params(self) -> Dict[str, Tuple[str, str]]:
        return {"E": (self.embed, "W")}

    def init_params(self, key, dtype=jnp.float32):
        d = self.n_in
        return {"e_norm": jnp.ones((d,), dtype),
                "h_norm": jnp.ones((d,), dtype),
                "W_eh": _normal(key, (2 * d, d), dtype)}

    def apply(self, params, x, state, training, rng):
        h, ids = x
        OpProfiler.get().count("mtp/modules")
        with jax.named_scope("mtp_merge"):
            e = jnp.take(params["E"], ids.astype(jnp.int32), axis=0)
            both = jnp.concatenate(
                [_rms(e, params["e_norm"], self.eps),
                 _rms(h, params["h_norm"], self.eps)], -1)
            return both @ params["W_eh"], state


@jax.custom_vjp
def _take_rows(x, src, dst, live):
    """The dispatch: ``x`` ``[n, d]`` -> the buffer's rows ``[cap, d]``, row
    ``r`` the token of pair ``src[r]`` (pairs are slot-major: pair ``j*n +
    t`` is token ``t``'s ``j``-th selection). ``dst`` ``[k*n]`` is each
    pair's row in the buffer and ``live`` whether the pair's expert is held
    (then ``dst < cap``): the backward gathers by them and sums a token's
    ``k`` slots, where autodiff would scatter-add."""
    return x[src % x.shape[0]]


def _take_rows_fwd(x, src, dst, live):
    return _take_rows(x, src, dst, live), (dst, live, x.shape[0])


def _take_rows_bwd(res, g):
    dst, live, n = res
    back = jnp.where(live[:, None], g[jnp.minimum(dst, g.shape[0] - 1)], 0)
    return (back.reshape(-1, n, g.shape[-1]).sum(0).astype(g.dtype),
            None, None, None)


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def _combine(out, p, src, dst, live):
    """The buffer's rows back to their tokens: ``y[t] = sum_j p[j, t] *
    out[dst[j*n + t]]`` over the live pairs, float32. ``out`` ``[cap, d]``,
    ``p`` ``[k, n]``. The backward stays the buffer's size: a row's
    cotangent is its token's times its weight."""
    k, n = p.shape
    back = jnp.where(live[:, None],
                     out[jnp.minimum(dst, out.shape[0] - 1)], 0)
    return jnp.sum(_f32(back).reshape(k, n, -1) * p[..., None], axis=0)


def _combine_fwd(out, p, src, dst, live):
    return _combine(out, p, src, dst, live), (out, p, src, dst, live)


def _combine_bwd(res, dy):
    out, p, src, dst, live = res
    rows = dy[src % p.shape[1]]                            # [cap, d]
    w = jnp.where(live[src], p.reshape(-1)[src], 0)
    per_row = jnp.sum(_f32(out) * rows, axis=-1)           # [cap]
    dp = jnp.where(live, per_row[jnp.minimum(dst, out.shape[0] - 1)], 0)
    return ((rows * w[:, None]).astype(out.dtype),
            dp.reshape(p.shape).astype(p.dtype), None, None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


@dataclass
class RoutedExpertsLayer(Layer):
    """A share of a top-k routed expert layer, dropless. ``n_routed`` is the
    router's width (the published expert count); the layer holds the experts
    ``first_expert <= e < first_expert + n_experts`` (all of them where
    ``n_experts`` is 0). Scores ``s = sigmoid(x Wg)`` over all ``n_routed``
    in float32; selection ``S = top_k(s + bias)``; weights ``p_e = s_e /
    (sum_{S} s + norm_eps) * scale`` (``ops.moe.route_topk``); expert ``e``:
    ``E_e(x) = (silu(x W1_e[:, :ff]) * x W1_e[:, ff:]) W2_e``. Returns ``sum
    over e in S and held of p_e E_e(x)``: the weights' denominator runs over
    all selected experts, held or not, what the experts that are not held
    would add is left out, and a token that selects no held expert gets
    zero. No capacity: every (token, held expert) pair is computed, for any
    routing. The pairs are sorted by expert into a buffer that one op takes
    through both products and the activation between them
    (``ops.moe.grouped_gated_mlp``: on the TPU six kernels, forward and
    backward, over the tiles that hold routed rows and no other, so the
    buffer's rows beyond the routed total are never written and nothing here
    reads them). The buffer holds ``k * tokens`` rows, the worst case (every
    selection held), so no routing drops a token; the two gathers, into the
    buffer and back, and their backward run over all of it, whatever was
    routed.

    A layer that holds a share of the experts and trains ALONE sees its load
    grow: its router is a replica whose gradient the deployment sums over
    the chips that share the layer, and the one part that comes through the
    held experts pulls every token towards them (``PERF.md``, PR 32: the
    held experts' load grew 5.3-fold in 96 steps of AdamW).

    State: ``bias`` ``[n_routed]``, the selection bias, which takes no
    gradient, and ``expert_load`` ``[n_routed]`` float32, the tokens that
    selected each expert, accumulated over the training steps since it was
    last cleared. ``bias_update_rate`` γ: auxiliary-loss-free balancing
    (DeepSeek-V3, arXiv:2412.19437 section 2.1.2), applied in the step after
    its selection: with ``c`` this step's selections of each of the
    ``n_routed`` experts, ``δ = γ sign(mean(c) - c)`` and ``bias += δ -
    mean(δ)`` (scope ``moe_bias_rule``; ``moe/bias_rule_layers`` counts the
    layers that apply it as a step is traced). On a share the counts are
    this chip's, as the router's gradient is. 0: a constant bias."""

    n_routed: int = 0
    n_experts: int = 0
    first_expert: int = 0
    n_ff: int = 0
    top_k: int = 1
    scale: float = 1.0
    norm_eps: float = 1e-6      # in the weights' denominator
    selection_bias: Optional[Sequence[float]] = None    # zeros when None
    bias_update_rate: float = 0.0

    full_precision_params = ("Wg",)

    def set_input_type(self, input_type):
        self.n_in = input_type.size
        if not self.n_experts:
            self.n_experts = self.n_routed
        return input_type

    def init_params(self, key, dtype=jnp.float32):
        k1, k2, k3 = jax.random.split(key, 3)
        d, e, ff = self.n_in, self.n_experts, self.n_ff
        return {"Wg": _normal(k1, (d, self.n_routed), dtype),
                "W1": _normal(k2, (e, d, 2 * ff), dtype),
                "W2": _normal(k3, (e, ff, d), dtype)}

    def init_state(self):
        bias = (jnp.zeros((self.n_routed,), jnp.float32)
                if self.selection_bias is None
                else jnp.asarray(self.selection_bias, jnp.float32))
        return {"bias": bias,
                "expert_load": jnp.zeros((self.n_routed,), jnp.float32)}

    def apply(self, params, x, state, training, rng):
        b, T, d = x.shape
        n, k, held = b * T, self.top_k, self.n_experts
        xt = x.reshape(n, d)
        with jax.named_scope("moe_router"):
            experts, weights, load = route_topk(
                xt, params["Wg"], state["bias"], k, self.scale,
                self.norm_eps)
            # pairs slot-major: pair j*n + t is token t's j-th selection
            local = (experts - self.first_expert).T             # [k, n]
            mine = (local >= 0) & (local < held)
            p = jnp.where(mine, weights.T, 0.0)
        with jax.named_scope("moe_dispatch"):
            # pairs of experts held elsewhere sort behind every group
            key = jnp.where(mine, local, held).reshape(-1)
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            dst = jnp.argsort(order).astype(jnp.int32)
            live = mine.reshape(-1)
            sizes = load[self.first_expert:self.first_expert + held].astype(
                jnp.int32)
            cap = -(-n * k // GMM_ROW_TILE) * GMM_ROW_TILE
            OpProfiler.get().count("moe/dispatch_rows", cap)
            src = jnp.pad(order, (0, cap - n * k))
            rows = _take_rows(xt, src, dst, live)
        with jax.named_scope("moe_experts"):
            # on the kernel path the rows from the routed total on are
            # not defined: ``_combine`` and both backward gathers index a
            # live pair's row, which lies below it
            out = grouped_gated_mlp(rows, params["W1"], params["W2"], sizes)
        with jax.named_scope("moe_combine"):
            y = _combine(out, p, src, dst, live).astype(xt.dtype)
        y = y.reshape(b, T, d)
        if training:
            state = {**state, "expert_load": state["expert_load"] + load}
            if self.bias_update_rate:
                OpProfiler.get().count("moe/bias_rule_layers")
                with jax.named_scope("moe_bias_rule"):
                    delta = self.bias_update_rate * jnp.sign(
                        jnp.mean(load) - load)
                    state["bias"] = state["bias"] + delta - jnp.mean(delta)
        return y, state


class HeadInput(NamedTuple):
    """What a head that computes its own loss hands to ``_loss``."""
    x: jnp.ndarray
    params: Dict[str, jnp.ndarray]


@dataclass
class LMHeadLayer(LossLayer):
    """Language-model head that owns its matrix: ``logits = x W^T`` with
    ``W`` ``[n_out, n_in]`` (a row a vocabulary entry, as an embedding
    table's), no bias. Labels are ``[B, T]`` integer ids; the loss is the
    sparse softmax cross-entropy in float32, the mean over a sequence's
    (unmasked) positions, then over sequences, as every head here, times
    ``loss_weight`` in the network's total. In training the loss is computed
    ``HEAD_TOKEN_BLOCK`` positions at a time under ``jax.checkpoint``, so the
    ``[B*T, vocabulary]`` logits are never whole."""

    n_out: int = 0

    def __post_init__(self):
        self.loss = LossSparseMCXENT()
        if self.activation is None:
            self.activation = "softmax"

    def set_input_type(self, input_type):
        self.n_in = input_type.size
        return input_type

    @property
    def has_params(self):
        return True

    def init_params(self, key, dtype=jnp.float32):
        return {"W": _normal(key, (self.n_out, self.n_in), dtype)}

    def pre_output(self, params, x):
        with jax.named_scope("head"):
            return jnp.einsum("btd,vd->btv", x, params["W"],
                              preferred_element_type=jnp.float32)

    def apply(self, params, x, state, training, rng):
        return jax.nn.softmax(self.pre_output(params, x), axis=-1), state

    def fused_score(self, params, x, labels, weights):
        """sum over positions of ``weights * cross-entropy``; x ``[B, T,
        d]``, labels and weights ``[B, T]``."""
        E = params["W"]
        d = x.shape[-1]
        xs = x.reshape(-1, d)
        ys = labels.reshape(-1).astype(jnp.int32)
        ws = _f32(weights).reshape(-1)

        def block(xb, yb, wb):
            logits = jax.lax.dot_general(
                xb, E, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
            return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * wb)

        with jax.named_scope("head"):
            n, tb = xs.shape[0], HEAD_TOKEN_BLOCK
            if n <= tb:
                return block(xs, ys, ws)
            pad = -n % tb
            if pad:
                xs = jnp.pad(xs, ((0, pad), (0, 0)))
                ys, ws = jnp.pad(ys, (0, pad)), jnp.pad(ws, (0, pad))
            step = jax.checkpoint(block)
            total, _ = jax.lax.scan(
                lambda acc, b: (acc + step(*b), None),
                jnp.zeros((), jnp.float32),
                (xs.reshape(-1, tb, d), ys.reshape(-1, tb),
                 ws.reshape(-1, tb)))
            return total


@dataclass
class TiedOutputLayer(LMHeadLayer):
    """``LMHeadLayer`` whose ``W`` is node ``tied_to``'s own leaf: an
    embedding's table (a tied head) or another head's matrix (a
    multi-token-prediction module's head). One leaf, one gradient (the sum
    over its uses), one optimizer state."""

    tied_to: str = ""

    @property
    def has_params(self):
        return False

    def borrowed_params(self) -> Dict[str, Tuple[str, str]]:
        return {"W": (self.tied_to, "W")}
