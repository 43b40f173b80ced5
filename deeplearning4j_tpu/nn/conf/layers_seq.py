"""Sequence-model layers of a hybrid state-space / attention decoder.

``MambaLayer`` (Gu & Dao, arXiv:2312.00752, Alg. 2), ``DifferentialAttentionLayer``
(Ye et al., arXiv:2410.05258, causal, grouped-query, optionally windowed, or
reading another layer's keys and values), ``GatedMemoryUnit`` (Ren et al.,
arXiv:2507.06607 section 2), ``GatedMLPLayer`` and ``TiedOutputLayer`` (the
head that reads the embedding table and computes its loss in token blocks).
All take and give ``[B, T, F]``. They are plain layer configurations: a
``ComputationGraph`` wires them with ``LayerNormalization`` and
``ElementWiseVertex(add)`` into pre-norm residual blocks
(``models.Phi4MiniFlash``).

Three things here that the older layers do not use, each read by
``ComputationGraph``:

- ``multi_input``: the layer's ``x`` is the tuple of its node's inputs;
- ``extra_outputs()``: names of further tensors that ``apply`` returns after
  ``y``; another node reads one as ``"<node>.<name>"``;
- ``full_precision_params``: leaves that stay float32 under a reduced
  ``compute_dtype`` (a decay rate, a step bias), and ``borrowed_params()``:
  leaves that belong to another node (the tied head reads the embedding's
  table: one leaf, one gradient, one optimizer state).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ...ops.pallas_attention import causal_attention
from ...ops.ssm import selective_scan
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
    """``[g, u] = x W1``; ``y = (u * silu(g)) W2``; no bias."""

    n_ff: int = 0

    def set_input_type(self, input_type):
        self.n_in = input_type.size
        return input_type

    def init_params(self, key, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        return {"W1": _normal(k1, (self.n_in, 2 * self.n_ff), dtype),
                "W2": _normal(k2, (self.n_ff, self.n_in), dtype)}

    def apply(self, params, x, state, training, rng):
        with jax.named_scope("gated_mlp"):
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
            # causal depthwise convolution: tap k reads d_conv-1-k steps back
            T = u.shape[1]
            padded = jnp.pad(u, ((0, 0), (self.d_conv - 1, 0), (0, 0)))
            u = params["conv_b"] + sum(
                padded[:, k:k + T] * params["conv_w"][k]
                for k in range(self.d_conv))
            u = jax.nn.silu(u)
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


class HeadInput(NamedTuple):
    """What a head that computes its own loss hands to ``_loss``."""
    x: jnp.ndarray
    params: Dict[str, jnp.ndarray]


@dataclass
class TiedOutputLayer(LossLayer):
    """Language-model head tied to an embedding: ``logits = x E^T`` with
    ``E`` the table of node ``tied_to`` itself. Labels are ``[B, T]`` integer
    ids; the loss is the sparse softmax cross-entropy in float32, the mean
    over a sequence's positions (then over sequences, as every head here).
    In training the loss is computed ``HEAD_TOKEN_BLOCK`` positions at a
    time under ``jax.checkpoint``, so the ``[B*T, vocabulary]`` logits are
    never whole."""

    tied_to: str = ""

    def __post_init__(self):
        self.loss = LossSparseMCXENT()
        if self.activation is None:
            self.activation = "softmax"

    def set_input_type(self, input_type):
        self.n_in = input_type.size
        return input_type

    def borrowed_params(self) -> Dict[str, Tuple[str, str]]:
        return {"W": (self.tied_to, "W")}

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
