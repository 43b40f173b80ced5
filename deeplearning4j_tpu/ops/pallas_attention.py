"""Flash attention: a hand-written Pallas TPU kernel for the hot op.

Reference: the reference's attention is a dense libnd4j kernel
(``generic/nn/multi_head_dot_product_attention.cpp``) materializing the
full [T, T] score matrix. On TPU the memory-bound way to run long-sequence
attention is the blockwise online-softmax construction (Flash Attention /
Rabe-Staats), tiled for VMEM with Pallas/Mosaic — this module implements
it natively (forward kernel + memory-efficient blockwise backward), the
"pallas kernels for the hot ops" role in this framework's layer map.

Shapes: q, k, v ``[B, H, T, D]``. The kernel grid is (B·H, T/block_q);
each program holds one q block in VMEM and streams k/v blocks with an
online max/denominator, so nothing of size T×T ever materializes. The
backward pass is the standard FA recipe (recompute p per block from the
saved row max/denominator) expressed as an XLA ``lax.scan`` over k blocks
— also free of T×T buffers.

``interpret=True`` runs the kernel in Pallas interpret mode (used by the
CPU test mesh); on the TPU the same kernel lowers through Mosaic
(``chip_smoke.py`` runs it there). Sequence lengths must divide the block
sizes — callers fall back to the dense op otherwise
(``ops/nn.dot_product_attention``).
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .registry import op

# Chosen at T=4096 on a v5e set-up that is gone (r05-era, 2026-07); not
# measured on this chip. Blocks auto-shrink to T.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024


def _fa_kernel(*refs, scale: float, causal: bool, block_q: int,
               block_k: int, n_k: int, has_bias: bool = False):
    # NOTE (Mosaic, this jax version — pinned empirically on the real
    # chip): the kernel must trace in the 32-bit world. This framework
    # enables jax_enable_x64 globally (NDArray fp64 parity), under which
    # weak python ints become i64 — Mosaic then fails muli verification,
    # and its i64→i32 convert fallback recurses. _fa_forward therefore
    # traces the pallas_call under enable_x64(False); in-kernel integer
    # scalars are strong jnp.int32, floats weak python scalars, and no
    # dtype casts appear inside the kernel (inputs are pre-cast f32).
    #
    # Grid is (B·H, n_q, n_k) with the k axis innermost: k/v stream
    # through VMEM one block at a time (T never resides whole), while the
    # online-softmax state (m, l, acc) lives in VMEM scratch that
    # persists across the k iterations of one q block.
    if has_bias:
        q_ref, k_ref, v_ref, b_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        b_ref = None
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == jnp.int32(0))
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    def _compute():
        q = q_ref[0] * scale                              # [bq, d]
        k = k_ref[0]                                      # [bk, d]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        if has_bias:
            # additive logits bias (BERT attention mask / relative-pos
            # bias), streamed block-by-block like k/v — the [T, T] bias
            # never resides whole in VMEM
            s = s + b_ref[0]
        if causal:
            qpos = (qi * jnp.int32(block_q)
                    + lax.broadcasted_iota(jnp.int32,
                                           (block_q, block_k), 0))
            kpos = (kj * jnp.int32(block_k)
                    + lax.broadcasted_iota(jnp.int32,
                                           (block_q, block_k), 1))
            s = jnp.where(qpos >= kpos, s, jnp.float32(-jnp.inf))
        m_prev = jnp.max(m_scr[...], axis=1, keepdims=True)   # [bq, 1]
        l_prev = jnp.max(l_scr[...], axis=1, keepdims=True)
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        f0 = jnp.float32(0.0)
        safe = jnp.where(jnp.isfinite(m_new), m_new, f0)
        p = jnp.exp(s - safe)
        p = jnp.where(jnp.isfinite(s), p, f0)
        alpha = jnp.where(jnp.isfinite(m_prev),
                          jnp.exp(m_prev - safe), f0)        # [bq, 1]
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        ones = jnp.ones((1, m_scr.shape[1]), jnp.float32)
        m_scr[...] = m_new * ones
        l_scr[...] = l_new * ones

    if causal:
        # whole k block above the diagonal → nothing to do
        pl.when(kj * jnp.int32(block_k)
                <= qi * jnp.int32(block_q)
                + jnp.int32(block_q - 1))(_compute)
    else:
        _compute()

    @pl.when(kj == jnp.int32(n_k - 1))
    def _finalize():
        l = jnp.max(l_scr[...], axis=1, keepdims=True)
        o_ref[0] = acc_scr[...] / jnp.maximum(l, jnp.float32(1e-30))


def _fa_forward(q, k, v, scale, causal, block_q, block_k, interpret,
                bias=None):
    from jax.experimental.pallas import tpu as pltpu

    bh, T, d = q.shape
    n_q = T // block_q
    n_k = T // block_k
    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, n_k=n_k,
                               has_bias=bias is not None)
    scratch = [
        pltpu.VMEM((block_q, 128), jnp.float32),   # running row max
        pltpu.VMEM((block_q, 128), jnp.float32),   # running denominator
        pltpu.VMEM((block_q, d), jnp.float32),     # unnormalized out
    ]
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
    ]
    args = (q, k, v)
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, block_q, block_k),
                                     lambda b, i, j: (b, i, j)))
        args = (q, k, v, bias)
    with jax.enable_x64(False):
        o = pl.pallas_call(
            kernel,
            grid=(bh, n_q, n_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, T, d), q.dtype),
            scratch_shapes=scratch,
            interpret=interpret,
        )(*args)
    return o


def _row_stats(q, k, scale, causal, block_k, bias=None):
    """Blockwise recomputation of the softmax row max/denominator
    (the stats the kernel keeps in registers), as an XLA scan."""
    bh, T, d = q.shape
    n_k = T // block_k
    qf = q.astype(jnp.float32)
    qpos = jnp.arange(T)

    def blk(carry, i):
        m, l = carry
        ks = lax.dynamic_slice_in_dim(k, i * block_k, block_k, 1) \
            .astype(jnp.float32)
        s = jnp.einsum("bqd,bkd->bqk", qf, ks) * scale
        if bias is not None:
            s = s + lax.dynamic_slice_in_dim(bias, i * block_k, block_k,
                                             2).astype(jnp.float32)
        if causal:
            kpos = i * block_k + jnp.arange(block_k)
            s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe[..., None]), 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
        return (m_new, l * alpha + p.sum(-1)), None

    m0 = jnp.full((bh, T), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bh, T), jnp.float32)
    (m, l), _ = lax.scan(blk, (m0, l0), jnp.arange(n_k))
    return jnp.where(jnp.isfinite(m), m, 0.0), l


def _fa_backward(q, k, v, o, do, scale, causal, block_k, bias=None,
                 need_dbias=False):
    """Blockwise FA backward (XLA scan over k blocks, no T×T buffers).

    p_ij = exp(s_ij - m_i) / l_i;  D_i = Σ_d dO_id O_id;
    dV_j = Σ_i p_ij dO_i;  dS = p ∘ (dO·Vᵀ − D);  dQ += dS·K·scale;
    dK_j = Σ_i dS_ij q_i · scale;  dBias = dS (the bias adds to the
    post-scale logits, so its cotangent is dS verbatim — stacked back to
    [bh, T, T] only when ``need_dbias``; with the usual broadcast bias
    the sum back to the small shape happens OUTSIDE the custom_vjp
    through the broadcast's own VJP).
    """
    bh, T, d = q.shape
    m, l = _row_stats(q, k, scale, causal, block_k, bias=bias)
    n_k = T // block_k
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    D = jnp.sum(dof * o.astype(jnp.float32), axis=-1)       # [bh, T]
    qpos = jnp.arange(T)

    def blk(carry, i):
        dq_acc = carry
        ks = lax.dynamic_slice_in_dim(k, i * block_k, block_k, 1) \
            .astype(jnp.float32)                             # [bh, bk, d]
        vs = lax.dynamic_slice_in_dim(v, i * block_k, block_k, 1) \
            .astype(jnp.float32)
        s = jnp.einsum("bqd,bkd->bqk", qf, ks) * scale
        if bias is not None:
            s = s + lax.dynamic_slice_in_dim(bias, i * block_k, block_k,
                                             2).astype(jnp.float32)
        if causal:
            kpos = i * block_k + jnp.arange(block_k)
            s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        p = jnp.exp(s - m[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0) \
            / jnp.maximum(l, 1e-30)[..., None]               # [bh, T, bk]
        dv = jnp.einsum("bqk,bqd->bkd", p, dof)
        dp = jnp.einsum("bqd,bkd->bqk", dof, vs)
        ds = p * (dp - D[..., None])
        dq_acc = dq_acc + jnp.einsum("bqk,bkd->bqd", ds, ks) * scale
        dk = jnp.einsum("bqk,bqd->bkd", ds, qf) * scale
        outs = (dk, dv, ds) if need_dbias else (dk, dv)
        return dq_acc, outs

    dq0 = jnp.zeros_like(qf)
    dq, outs = lax.scan(blk, dq0, jnp.arange(n_k))
    if need_dbias:
        dks, dvs, dss = outs
    else:
        dks, dvs = outs
    dk = jnp.moveaxis(dks, 0, 1).reshape(bh, T, d)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(bh, T, d)
    grads = (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))
    if need_dbias:
        # [n_k, bh, T, bk] -> [bh, T, n_k, bk] -> [bh, T, T]
        dbias = jnp.moveaxis(dss, 0, 2).reshape(bh, T, T)
        grads = grads + (dbias.astype(bias.dtype),)
    return grads


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash3(q, k, v, scale, causal, block_q, block_k, interpret):
    return _fa_forward(q, k, v, scale, causal, block_q, block_k, interpret)


def _flash3_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    o = _fa_forward(q, k, v, scale, causal, block_q, block_k, interpret)
    return o, (q, k, v, o)


def _flash3_bwd(scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, o = res
    return _fa_backward(q, k, v, o, do, scale, causal, block_k)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash3b(q, k, v, bias, scale, causal, block_q, block_k, interpret):
    return _fa_forward(q, k, v, scale, causal, block_q, block_k,
                       interpret, bias=bias)


def _flash3b_fwd(q, k, v, bias, scale, causal, block_q, block_k,
                 interpret):
    o = _fa_forward(q, k, v, scale, causal, block_q, block_k, interpret,
                    bias=bias)
    return o, (q, k, v, bias, o)


def _flash3b_bwd(scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, bias, o = res
    return _fa_backward(q, k, v, o, do, scale, causal, block_k,
                        bias=bias, need_dbias=True)


_flash3b.defvjp(_flash3b_fwd, _flash3b_bwd)


def pick_blocks(T: int, block_q: Optional[int] = None,
                block_k: Optional[int] = None):
    bq = block_q or min(DEFAULT_BLOCK_Q, T)
    bk = block_k or min(DEFAULT_BLOCK_K, T)
    return bq, bk


def supports_flash(T: int, d: int, block_q: Optional[int] = None,
                   block_k: Optional[int] = None) -> bool:
    bq, bk = pick_blocks(T, block_q, block_k)
    # Mosaic tiling: q-block sublane dim % 8, k-block (and the [bq, bk]
    # score tile's lane dim) % 128
    return (T % bq == 0 and T % bk == 0 and T >= bq
            and bq % 8 == 0 and bk % 128 == 0)


@op("flash_attention", "nn")
def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    bias=None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Blockwise fused attention. q, k, v: [B, H, T, D] (or [B, T, D] for
    a single head); returns the same shape. T must divide the block sizes
    (``supports_flash``); use ``dot_product_attention`` otherwise.

    ``bias``: additive logits bias, broadcastable to [B, H, T, T] — the
    full attention+bias+softmax path BERT runs (padding mask as
    ``where(mask, 0, -1e9)``, or a learned relative-position bias: it is
    differentiated, with the cotangent summed back through the broadcast).
    The bias streams through VMEM one [block_q, block_k] tile at a time,
    same as k/v — no [T, T] residency."""
    squeeze = q.ndim == 3
    if squeeze:
        q, k, v = q[:, None], k[:, None], v[:, None]
    b, h, T, d = q.shape
    block_q, block_k = pick_blocks(T, block_q, block_k)
    if not supports_flash(T, d, block_q, block_k):
        raise ValueError(
            f"flash_attention needs T % block == 0 (T={T}, blocks "
            f"{block_q}/{block_k}); fall back to dot_product_attention")
    if interpret is None:
        interpret = jax.default_backend() not in ("tpu",)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(d)
    in_dtype = q.dtype
    qf = q.reshape(b * h, T, d).astype(jnp.float32)
    kf = k.reshape(b * h, T, d).astype(jnp.float32)
    vf = v.reshape(b * h, T, d).astype(jnp.float32)
    if bias is not None:
        if squeeze and bias.ndim == 3:
            bias = bias[:, None]
        # broadcast OUTSIDE the custom_vjp: dbias sums back to the
        # caller's small shape through the broadcast's own VJP
        bf = jnp.broadcast_to(bias.astype(jnp.float32),
                              (b, h, T, T)).reshape(b * h, T, T)
        o = _flash3b(qf, kf, vf, bf, float(scale), bool(causal),
                     int(block_q), int(block_k), bool(interpret))
    else:
        o = _flash3(qf, kf, vf, float(scale), bool(causal), int(block_q),
                    int(block_k), bool(interpret))
    o = o.reshape(b, h, T, d).astype(in_dtype)
    return o[:, 0] if squeeze else o
