"""Flash attention: hand-written Pallas TPU kernels for the hot op.

Reference: the reference's attention is a dense libnd4j kernel
(``generic/nn/multi_head_dot_product_attention.cpp``) materializing the
full [T, T] score matrix. On TPU the memory-bound way to run long-sequence
attention is the blockwise online-softmax construction (Flash Attention /
Rabe-Staats), tiled for VMEM with Pallas/Mosaic. Two kernels, one for each
shape of the mask:

- ``flash_attention`` (``flash_attention_dense_fwd``): every key block of the
  square, with an optional additive bias (a padding mask, a relative-position
  bias) streamed tile by tile. q, k, v ``[B, H, T, D]``, float32 in the
  kernel. Grid (B·H, T/block_q, T/block_k); the backward is the standard FA
  recipe (recompute p per block from the row max/denominator) as an XLA
  ``lax.scan`` over key blocks. A causal mask with a bias rides in the bias;
  a causal mask without one is ``causal_attention``;
- ``causal_attention`` (``flash_attention_fwd``, ``flash_attention_bwd``):
  causal, optionally banded by a window, grouped-query heads, a value wider
  than the head, operands in the inputs' dtype with float32 accumulation. Both
  kernels walk the band as a scalar-prefetched list of (query block, key
  block) pairs — the forward query block first; the backward key block first
  where a group's dq fits VMEM, else query block first with the key/value
  head's dk and dv resident — with a key/value head's query heads inside one
  grid step: no grid step is issued, and nothing fetched or computed, for a
  pair outside the band. ``seq/attn_fwd_grid_steps`` counts the steps the
  forward's calls issue.

Nothing of size T×T ever materializes in either. ``interpret=True`` runs a
kernel in Pallas interpret mode (the CPU tests); on the TPU the same kernel
lowers through Mosaic (``tests/test_tpu_compile*.py`` compile them for the
chip). ``flash_attention`` needs sequence lengths that divide the block
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

from ..common.profiler import OpProfiler
from .registry import op

# Chosen at T=4096 on a v5e set-up that is gone (r05-era, 2026-07); not
# measured on this chip. Blocks auto-shrink to T.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024


def _fa_kernel(*refs, scale: float, n_k: int, has_bias: bool = False):
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
    kj = pl.program_id(2)

    @pl.when(kj == jnp.int32(0))
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    q = q_ref[0] * scale                              # [bq, d]
    k = k_ref[0]                                      # [bk, d]
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
    if has_bias:
        # additive logits bias (BERT attention mask / relative-pos bias, a
        # causal mask as -inf), streamed block-by-block like k/v — the
        # [T, T] bias never resides whole in VMEM
        s = s + b_ref[0]
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

    @pl.when(kj == jnp.int32(n_k - 1))
    def _finalize():
        l = jnp.max(l_scr[...], axis=1, keepdims=True)
        o_ref[0] = acc_scr[...] / jnp.maximum(l, jnp.float32(1e-30))


def _fa_forward(q, k, v, scale, block_q, block_k, interpret, bias=None):
    from jax.experimental.pallas import tpu as pltpu

    bh, T, d = q.shape
    n_q = T // block_q
    n_k = T // block_k
    kernel = functools.partial(_fa_kernel, scale=scale, n_k=n_k,
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
            name="flash_attention_dense_fwd",
        )(*args)
    return o


def _row_stats(q, k, scale, block_k, bias=None):
    """Blockwise recomputation of the softmax row max/denominator
    (the stats the kernel keeps in registers), as an XLA scan."""
    bh, T, d = q.shape
    n_k = T // block_k
    qf = q.astype(jnp.float32)

    def blk(carry, i):
        m, l = carry
        ks = lax.dynamic_slice_in_dim(k, i * block_k, block_k, 1) \
            .astype(jnp.float32)
        s = jnp.einsum("bqd,bkd->bqk", qf, ks) * scale
        if bias is not None:
            s = s + lax.dynamic_slice_in_dim(bias, i * block_k, block_k,
                                             2).astype(jnp.float32)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe[..., None]), 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
        return (m_new, l * alpha + p.sum(-1)), None

    m0 = jnp.full((bh, T), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bh, T), jnp.float32)
    (m, l), _ = lax.scan(blk, (m0, l0), jnp.arange(n_k))
    return jnp.where(jnp.isfinite(m), m, 0.0), l


def _fa_backward(q, k, v, o, do, scale, block_k, bias=None,
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
    m, l = _row_stats(q, k, scale, block_k, bias=bias)
    n_k = T // block_k
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    D = jnp.sum(dof * o.astype(jnp.float32), axis=-1)       # [bh, T]

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash3(q, k, v, scale, block_q, block_k, interpret):
    return _fa_forward(q, k, v, scale, block_q, block_k, interpret)


def _flash3_fwd(q, k, v, scale, block_q, block_k, interpret):
    o = _fa_forward(q, k, v, scale, block_q, block_k, interpret)
    return o, (q, k, v, o)


def _flash3_bwd(scale, block_q, block_k, interpret, res, do):
    q, k, v, o = res
    return _fa_backward(q, k, v, o, do, scale, block_k)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash3b(q, k, v, bias, scale, block_q, block_k, interpret):
    return _fa_forward(q, k, v, scale, block_q, block_k, interpret,
                       bias=bias)


def _flash3b_fwd(q, k, v, bias, scale, block_q, block_k, interpret):
    o = _fa_forward(q, k, v, scale, block_q, block_k, interpret, bias=bias)
    return o, (q, k, v, bias, o)


def _flash3b_bwd(scale, block_q, block_k, interpret, res, do):
    q, k, v, bias, o = res
    return _fa_backward(q, k, v, o, do, scale, block_k, bias=bias,
                        need_dbias=True)


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
    same as k/v — no [T, T] residency.

    ``causal`` without a bias is ``causal_attention`` (one block size,
    ``block_q``; the key blocks above the diagonal are neither fetched nor
    computed, forward or backward); with a bias the mask joins the bias and
    every block is computed."""
    squeeze = q.ndim == 3
    if squeeze:
        q, k, v = q[:, None], k[:, None], v[:, None]
    b, h, T, d = q.shape
    block_q, block_k = pick_blocks(T, block_q, block_k)
    if not supports_flash(T, d, block_q, block_k):
        raise ValueError(
            f"flash_attention needs T % block == 0 (T={T}, blocks "
            f"{block_q}/{block_k}); fall back to dot_product_attention")
    if causal and bias is None:
        # False asks for the compiled kernel here and for the XLA loops
        # there: None lets the backend decide
        o = causal_attention(q, k, v, sm_scale=sm_scale, block=block_q,
                             interpret=interpret or None)
        return o[:, 0] if squeeze else o
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
        if causal:
            bias = bias + jnp.where(jnp.tril(jnp.ones((T, T), bool)),
                                    jnp.float32(0.0), -jnp.inf)
        # broadcast OUTSIDE the custom_vjp: dbias sums back to the
        # caller's small shape through the broadcast's own VJP
        bf = jnp.broadcast_to(bias.astype(jnp.float32),
                              (b, h, T, T)).reshape(b * h, T, T)
        o = _flash3b(qf, kf, vf, bf, float(scale), int(block_q),
                     int(block_k), bool(interpret))
    else:
        o = _flash3(qf, kf, vf, float(scale), int(block_q), int(block_k),
                    bool(interpret))
    o = o.reshape(b, h, T, d).astype(in_dtype)
    return o[:, 0] if squeeze else o


# --- causal attention over a band of key blocks ------------------------------
#
# Grouped-query heads, an optional sliding window, operands in the inputs'
# dtype with float32 accumulation. Query block i meets key blocks
# lo(i)..i only (lo = 0 without a window), forward and backward: what the
# mask empties is neither fetched nor computed, and no temporary is larger
# than one [heads, block, block] tile. On the TPU both directions are Pallas
# kernels whose grid is (key/value head, block pair of the band): the pairs
# come as two scalar-prefetched index lists (``_band_pairs``) and a group's
# query heads share one step.
# ``flash_attention_fwd`` walks the pairs query block first (two products a
# pair, the running statistics in scratch while the query block stays) and
# also hands back each row's log-sum-exp, laid out ``[head, group, query
# block, row]``; ``flash_attention_bwd`` makes one pass from that
# log-sum-exp (five products a pair, the score tile never leaving VMEM) in
# one of two walks, chosen by shape (``_bwd_walk``): key block first, the
# group's dq resident (``supports_band_bwd_kernel``), or, where that dq
# outgrows VMEM, query block first with the key/value head's dk and dv
# resident (``supports_band_bwd_kv_resident``: a group of 8 heads of 128 at
# 8k or 16k). ``seq/attn_fwd_grid_steps`` counts the grid steps the forward's call
# issues: the band's pairs a key/value head, ``attn_key_blocks_run / g``
# (``b·hq·n·`` the band's width on the clamped rectangle this grid replaced).
# The XLA loops below are the path of every other case (the CPU, a shape
# ``supports_band_kernel`` refuses, a backward that neither walk fits) and
# what the tests hold the kernels to.

BAND_BLOCK = 512
_MASKED = -1e30     # finite: a row whose first block is all masked stays finite
_BWD_VMEM_LIMIT = 64 * 1024 * 1024      # of the v5e's 128 MiB, as ops/ssm.py


def _band_lo(i, bs: int, window: Optional[int]):
    """First key block that query block ``i`` can see."""
    if not window:
        return i * 0
    return jnp.maximum(i * bs - (window - 1), 0) // bs


def _band_pairs(n: int, bs: int, window: Optional[int],
                query_first: bool = False) -> tuple:
    """The band's block pairs as two int32 arrays (key blocks, query
    blocks), in the order a kernel walks them: key block first (the
    backward: dk and dv stay while the key block stays) or query block first
    (the forward: the accumulator stays while the query block stays); the
    other index ascends inside."""
    lo = [max(i * bs - (window - 1), 0) // bs if window else 0
          for i in range(n)]
    pairs = [(j, i) for j in range(n) for i in range(j, n) if lo[i] <= j]
    if query_first:
        pairs.sort(key=lambda ji: ji[::-1])
    return tuple(np.asarray(a, np.int32) for a in zip(*pairs))


def band_blocks(T: int, bs: int, window: Optional[int]) -> tuple:
    """(block pairs computed, block pairs of the square left out)."""
    n = T // bs
    run = len(_band_pairs(n, bs, window)[0])
    return run, n * n - run


def _band_mask(i, j, bs: int, window: Optional[int], keys_first=False):
    """[query, key] tile of block pair (i, j), or [key, query]."""
    q_dim, k_dim = (1, 0) if keys_first else (0, 1)
    qpos = i * bs + lax.broadcasted_iota(jnp.int32, (bs, bs), q_dim)
    kpos = j * bs + lax.broadcasted_iota(jnp.int32, (bs, bs), k_dim)
    ok = kpos <= qpos
    if window:
        ok = ok & (qpos - kpos < window)
    return ok


def _blk(a, i, bs, axis):
    return lax.dynamic_slice_in_dim(a, i * bs, bs, axis)


def _band_fwd_xla(q, k, v, scale, window, bs):
    """q [B, Hk, G, T, D]; k [B, Hk, T, D]; v [B, Hk, T, Dv] ->
    (o [B, Hk, G, T, Dv] float32, lse [B, Hk, G, T])."""
    f32 = jnp.float32
    b, hk, g, T, d = q.shape
    n = T // bs

    def q_block(i):
        qi = _blk(q, i, bs, 3)

        def k_block(j, carry):
            m, l, acc = carry
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qi, _blk(k, j, bs, 2),
                           preferred_element_type=f32) * scale
            s = jnp.where(_band_mask(i, j, bs, window), s, _MASKED)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            vj = _blk(v, j, bs, 2)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(vj.dtype), vj,
                preferred_element_type=f32)
            return m_new, l * alpha + p.sum(-1), acc

        m, l, acc = lax.fori_loop(
            _band_lo(i, bs, window), i + 1, k_block,
            (jnp.full((b, hk, g, bs), _MASKED, f32),
             jnp.zeros((b, hk, g, bs), f32),
             jnp.zeros((b, hk, g, bs, v.shape[-1]), f32)))
        return acc / l[..., None], m + jnp.log(l)

    o, lse = lax.map(q_block, jnp.arange(n, dtype=jnp.int32))
    o = jnp.moveaxis(o, 0, 3).reshape(b, hk, g, T, v.shape[-1])
    lse = jnp.moveaxis(lse, 0, 3).reshape(b, hk, g, T)
    return o, lse


def _band_bwd_xla(q, k, v, o, lse, do, scale, window, bs):
    """The backward over the same band: one pass over (query block, key
    block) pairs; dk and dv are accumulated in place, block by block."""
    f32 = jnp.float32
    b, hk, g, T, d = q.shape
    n = T // bs
    delta = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1)   # [B,Hk,G,T]

    def q_block(carry, i):
        qi, doi = _blk(q, i, bs, 3), _blk(do, i, bs, 3)
        lse_i, delta_i = _blk(lse, i, bs, 3), _blk(delta, i, bs, 3)

        def k_block(j, carry):
            dq, dk, dv = carry
            kj, vj = _blk(k, j, bs, 2), _blk(v, j, bs, 2)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qi, kj,
                           preferred_element_type=f32) * scale
            s = jnp.where(_band_mask(i, j, bs, window), s, _MASKED)
            p = jnp.exp(s - lse_i[..., None])
            dp = jnp.einsum("bhgqd,bhkd->bhgqk", doi, vj,
                            preferred_element_type=f32)
            ds = (p * (dp - delta_i[..., None]) * scale).astype(q.dtype)
            dq = dq + jnp.einsum("bhgqk,bhkd->bhgqd", ds, kj,
                                 preferred_element_type=f32)
            dk_j = jnp.einsum("bhgqk,bhgqd->bhkd", ds, qi,
                              preferred_element_type=f32)
            dv_j = jnp.einsum("bhgqk,bhgqd->bhkd", p.astype(do.dtype), doi,
                              preferred_element_type=f32)
            dk = lax.dynamic_update_slice_in_dim(
                dk, _blk(dk, j, bs, 2) + dk_j, j * bs, 2)
            dv = lax.dynamic_update_slice_in_dim(
                dv, _blk(dv, j, bs, 2) + dv_j, j * bs, 2)
            return dq, dk, dv

        dq, dk, dv = lax.fori_loop(
            _band_lo(i, bs, window), i + 1, k_block,
            (jnp.zeros((b, hk, g, bs, d), f32),) + carry)
        return (dk, dv), dq

    (dk, dv), dq = lax.scan(
        q_block, (jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32)),
        jnp.arange(n, dtype=jnp.int32))
    dq = jnp.moveaxis(dq, 0, 3).reshape(q.shape)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _band_fwd_kernel(pj_ref, pi_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                     m_scr, l_scr, acc_scr,
                     *, scale: float, bs: int, window: Optional[int]):
    # One grid step is one block pair of the band (``_band_pairs``, query
    # block i outer, the key blocks j it sees inner) for the G query heads of
    # one key/value head: the k and v tiles are fetched once for the group,
    # the heads' running maximum, denominator and unnormalised output stay in
    # scratch while i stays, and o and the log-sum-exp are written as i
    # changes. Tiles are [query, key]; the row statistics are kept as
    # [block, 128] tiles whose lanes all hold the row's value, so that they
    # meet the score tile and the accumulator lane for lane and nothing is
    # reduced across lanes but the tile itself. Traced in the 32-bit world,
    # like _fa_kernel.
    f32 = jnp.float32
    nt = (((1,), (1,)), ((), ()))
    p, last_p = pl.program_id(1), pl.num_programs(1) - 1
    j, i = pj_ref[p], pi_ref[p]
    dv = v_ref.shape[-1]

    def lanes(stat, width):
        """A [block, 128] statistic against a tile ``width`` lanes wide."""
        reps = -(-width // 128)
        wide = stat if reps == 1 else jnp.tile(stat, (1, reps))
        return wide if width == reps * 128 else wide[:, :width]

    @pl.when((p == 0) | (pi_ref[jnp.maximum(p - 1, 0)] != i))
    def _new_query_block():
        m_scr[...] = jnp.full_like(m_scr, _MASKED)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # one tile body, masked on every pair: the chip read a second, unmasked
    # body under ``pl.when`` (as the backward has) no faster at any shape
    k, v = k_ref[0], v_ref[0]
    ok = _band_mask(i, j, bs, window)
    for g in range(q_ref.shape[0]):
        s = lax.dot_general(q_ref[g], k, nt,
                            preferred_element_type=f32) * scale
        s = jnp.where(ok, s, _MASKED)
        m_prev = m_scr[g]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pr = jnp.exp(s - lanes(m_new, bs))
        alpha = jnp.exp(m_prev - m_new)
        l_scr[g] = l_scr[g] * alpha + jnp.sum(pr, axis=1, keepdims=True)
        acc_scr[g] = acc_scr[g] * lanes(alpha, dv) + jnp.dot(
            pr.astype(v.dtype), v, preferred_element_type=f32)
        m_scr[g] = m_new

    @pl.when((p == last_p) | (pi_ref[jnp.minimum(p + 1, last_p)] != i))
    def _query_block_done():
        for g in range(q_ref.shape[0]):
            l = l_scr[g]
            o_ref[g] = (acc_scr[g] / lanes(l, dv)).astype(o_ref.dtype)
            # the rows' log-sum-exp as one row of the head's [n, block]
            lse_ref[0, g, pl.ds(i, 1), :] = (m_scr[g] + jnp.log(l)).T[:1]


@functools.lru_cache(maxsize=None)
def _band_fwd_call(h, g, T, d, dv, bs, window, scale, dtype, interpret):
    """The forward's ``pallas_call`` for one shape, kept as
    ``_band_bwd_call`` is: a model's layers of one shape trace the unrolled
    body once."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    n = T // bs
    # A group of 8 query heads of 128 (g·d 1024) outgrows the 16 MiB of
    # VMEM Mosaic grants a kernel that asks none (its blocks, scratch and
    # tiles: 19 MiB at T 16,384) and asks the backward's limit; the smaller
    # groups of the other cells (g·d ≤ 256) ask nothing, as they always did.
    limit = _BWD_VMEM_LIMIT if g * d >= 1024 else None
    # q and o stay [query head, T, ·], a key/value head's g heads one block
    rows_map = lambda h, p, pj, pi: (h, pi[p], 0)           # noqa: E731
    kv_map = lambda h, p, pj, pi: (h, pj[p], 0)             # noqa: E731
    return pl.pallas_call(
        functools.partial(_band_fwd_kernel, scale=scale, bs=bs,
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h, len(_band_pairs(n, bs, window)[0])),
            in_specs=[pl.BlockSpec((g, bs, d), rows_map),
                      pl.BlockSpec((1, bs, d), kv_map),
                      pl.BlockSpec((1, bs, dv), kv_map)],
            out_specs=[pl.BlockSpec((g, bs, dv), rows_map),
                       pl.BlockSpec((1, g, n, bs),
                                    lambda h, p, pj, pi: (h, 0, 0, 0))],
            scratch_shapes=[pltpu.VMEM((g, bs, 128), f32),
                            pltpu.VMEM((g, bs, 128), f32),
                            pltpu.VMEM((g, bs, dv), f32)]),
        out_shape=[jax.ShapeDtypeStruct((h * g, T, dv), dtype),
                   jax.ShapeDtypeStruct((h, g, n, bs), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=limit),
        interpret=interpret, name="flash_attention_fwd")


def _band_fwd_pallas(q, k, v, scale, window, bs, interpret):
    """Same contract as ``_band_fwd_xla``, as one kernel over the band's
    block pairs, query block first; k and v are never repeated for a group's
    heads, and the log-sum-exp leaves the kernel as the backward reads it."""
    b, hk, g, T, d = q.shape
    dv = v.shape[-1]
    h = b * hk
    pj, pi = _band_pairs(T // bs, bs, window, query_first=True)
    with jax.enable_x64(False):
        o, lse = _band_fwd_call(
            h, g, T, d, dv, bs, window, scale, q.dtype, interpret,
        )(jnp.asarray(pj), jnp.asarray(pi), q.reshape(h * g, T, d),
          k.reshape(h, T, d), v.reshape(h, T, dv))
    return o.reshape(b, hk, g, T, dv), lse.reshape(b, hk, g, T)


def _band_bwd_kernel(pj_ref, pi_ref, *refs, scale: float, bs: int,
                     window: Optional[int], kv_resident: bool = False):
    # One grid step is one block pair of the band for the G query heads of
    # one key/value head, in one of two walks. Tiles are [key, query], so the
    # per-query log-sum-exp and delta broadcast as rows. Key block first
    # (``_band_pairs``: key block j outer, the query blocks i that see it
    # inner): dk_j and dv_j gather in scratch while j stays, the heads' dq
    # stays in VMEM while the key/value head's pairs run; dk and dq are
    # gathered transposed ([D, block]) from q and k that come transposed
    # too: with a narrow head as the streamed side of their products the
    # matrix unit takes half the passes, and nothing is transposed in here.
    # ``kv_resident``, query block first (the forward's list): the heads' dq
    # of block i gathers in scratch while i stays and leaves as i changes, dk
    # and dv of the whole key/value head stay in VMEM while its pairs run —
    # state of T·(D + Dv), where the first walk's is G·T·D; q, k, dq and dk
    # as they come (at the 128-wide heads this walk serves, XLA's turns of
    # q and dq around the call cost more than the narrow side saves: on a
    # v5e at 16k tokens the turned form took 6% longer). Traced in the
    # 32-bit world, like _fa_kernel.
    if kv_resident:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, kt_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr) = refs
    f32 = jnp.float32
    nt = (((1,), (1,)), ((), ()))
    tn = (((0,), (0,)), ((), ()))
    p, last_p = pl.program_id(1), pl.num_programs(1) - 1
    j, i = pj_ref[p], pi_ref[p]
    if kv_resident:
        kv_at = j           # the key block's dk / dv tile in scratch
        first_i = (p == 0) | (pi_ref[jnp.maximum(p - 1, 0)] != i)
        last_i = (p == last_p) | (pi_ref[jnp.minimum(p + 1, last_p)] != i)

        @pl.when(p == 0)
        def _new_head():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

        @pl.when(first_i)
        def _new_query_block():
            dq_scr[...] = jnp.zeros_like(dq_scr)
    else:
        kv_at = ...         # the one tile of the key block that stays

        @pl.when(p == 0)
        def _new_head():
            dq_scr[...] = jnp.zeros_like(dq_scr)

        @pl.when((p == 0) | (pj_ref[jnp.maximum(p - 1, 0)] != j))
        def _new_key_block():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

    def pair(masked: bool):
        k = k_ref[0]
        kt = None if kv_resident else kt_ref[0]
        v = v_ref[0]
        if masked:
            ok = _band_mask(i, j, bs, window, keys_first=True)
        for g in range(q_ref.shape[1]):
            q, do = q_ref[0, g], do_ref[0, g]     # q [bq, D], or [D, bq]
            if kv_resident:
                s = lax.dot_general(k, q, nt, preferred_element_type=f32)
            else:
                s = jnp.dot(k, q, preferred_element_type=f32)
            s = s * scale
            if masked:
                s = jnp.where(ok, s, _MASKED)
            pr = jnp.exp(s - lse_ref[0, g, pl.ds(i, 1), :])
            dv_scr[kv_at] += jnp.dot(pr.astype(do.dtype), do,
                                     preferred_element_type=f32)
            dp = lax.dot_general(v, do, nt, preferred_element_type=f32)
            ds = (pr * (dp - delta_ref[0, g, pl.ds(i, 1), :])
                  * scale).astype(q.dtype)
            if kv_resident:
                dk_scr[kv_at] += jnp.dot(ds, q, preferred_element_type=f32)
                dq_scr[g] += lax.dot_general(ds, k, tn,
                                             preferred_element_type=f32)
            else:
                dk_scr[...] += lax.dot_general(q, ds, nt,
                                               preferred_element_type=f32)
                dq_scr[g, i] += jnp.dot(kt, ds, preferred_element_type=f32)

    # the mask only empties part of a tile on the diagonal and at the
    # window's far edge
    edge = i == j
    if window:
        edge = edge | ((i - j + 1) * bs - 1 >= window)
    pl.when(edge)(functools.partial(pair, True))
    pl.when(jnp.logical_not(edge))(functools.partial(pair, False))

    if kv_resident:
        @pl.when(last_i)
        def _query_block_done():
            dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)

        @pl.when(p == last_p)
        def _head_done():
            dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)
        return

    @pl.when((p == last_p) | (pj_ref[jnp.minimum(p + 1, last_p)] != j))
    def _key_block_done():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(p == last_p)
    def _head_done():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


@functools.lru_cache(maxsize=None)
def _band_bwd_call(h, g, T, d, dv, bs, window, scale, dtypes, interpret,
                   kv_resident=False):
    """The backward's ``pallas_call`` for one shape and walk. Kept: what
    ``pallas_call`` hands back is a ``jit``, so a model's layers of one
    shape trace the kernel's body once."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    n = T // bs
    rows_map = lambda h, p, pj, pi: (h, 0, pi[p], 0)        # noqa: E731
    qt_map = lambda h, p, pj, pi: (h, 0, 0, pi[p])          # noqa: E731
    kv_map = lambda h, p, pj, pi: (h, pj[p], 0)             # noqa: E731
    kt_map = lambda h, p, pj, pi: (h, 0, pj[p])             # noqa: E731
    head4 = lambda h, p, pj, pi: (h, 0, 0, 0)               # noqa: E731
    if kv_resident:
        # q, k, dq and dk as they come: dq a query block; dk and dv the
        # whole key/value head, a [block, ·] tile a key block
        in_specs = [pl.BlockSpec((1, g, bs, d), rows_map),
                    pl.BlockSpec((1, bs, d), kv_map)]
        out_specs = [pl.BlockSpec((1, g, bs, d), rows_map),
                     pl.BlockSpec((1, n, bs, d), head4),
                     pl.BlockSpec((1, n, bs, dv), head4)]
        scratch = [(g, bs, d), (n, bs, d), (n, bs, dv)]
        out_shapes = [(h, g, T, d), (h, n, bs, d), (h, n, bs, dv)]
    else:
        in_specs = [pl.BlockSpec((1, g, d, bs), qt_map),
                    pl.BlockSpec((1, bs, d), kv_map),
                    pl.BlockSpec((1, d, bs), kt_map)]
        out_specs = [pl.BlockSpec((1, g, n, d, bs),
                                  lambda h, p, pj, pi: (h, 0, 0, 0, 0)),
                     pl.BlockSpec((1, d, bs), kt_map),
                     pl.BlockSpec((1, bs, dv), kv_map)]
        scratch = [(g, n, d, bs), (d, bs), (bs, dv)]
        out_shapes = [(h, g, n, d, bs), (h, d, T), (h, T, dv)]
    return pl.pallas_call(
        functools.partial(_band_bwd_kernel, scale=scale, bs=bs,
                          window=window, kv_resident=kv_resident),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h, len(_band_pairs(n, bs, window)[0])),
            in_specs=in_specs + [pl.BlockSpec((1, bs, dv), kv_map),
                                 pl.BlockSpec((1, g, bs, dv), rows_map),
                                 pl.BlockSpec((1, g, n, bs), head4),
                                 pl.BlockSpec((1, g, n, bs), head4)],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM(s, f32) for s in scratch]),
        out_shape=[jax.ShapeDtypeStruct(s, t)
                   for s, t in zip(out_shapes, dtypes)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        interpret=interpret, name="flash_attention_bwd")


def _band_bwd_pallas(q, k, v, o, lse, do, scale, window, bs, interpret,
                     kv_resident=False):
    """Same contract as ``_band_bwd_xla``, as one kernel: five products a
    block pair, a group's heads summed into dk and dv inside it, k and v
    never repeated; the pairs key block first, or query block first where
    ``kv_resident`` (``_band_bwd_kernel``). In the first walk q and k go in
    transposed as well, and dq and dk leave it as [.., D, block] tiles:
    turned here, by XLA; the second takes and gives them as they are."""
    f32 = jnp.float32
    b, hk, g, T, d = q.shape
    dv = v.shape[-1]
    h, n = b * hk, T // bs
    pj, pi = _band_pairs(n, bs, window, query_first=kv_resident)
    delta = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1)   # [B,Hk,G,T]
    q3, k3 = q.reshape(h, g, T, d), k.reshape(h, T, d)
    qk = ((q3, k3) if kv_resident
          else (q3.swapaxes(-1, -2), k3, k3.swapaxes(-1, -2)))
    with jax.enable_x64(False):
        dq, dk, dv_ = _band_bwd_call(
            h, g, T, d, dv, bs, window, scale, (q.dtype, k.dtype, v.dtype),
            interpret, kv_resident,
        )(jnp.asarray(pj), jnp.asarray(pi), *qk, v.reshape(h, T, dv),
          do.reshape(h, g, T, dv), lse.reshape(h, g, n, bs),
          delta.reshape(h, g, n, bs))
    if kv_resident:
        return dq.reshape(q.shape), dk.reshape(k.shape), dv_.reshape(v.shape)
    return (dq.swapaxes(-1, -2).reshape(q.shape),
            dk.swapaxes(-1, -2).reshape(k.shape), dv_.reshape(v.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _band(q, k, v, scale, window, bs, kernel, interpret):
    return _band_fwd(q, k, v, scale, window, bs, kernel, interpret)[0]


def _band_fwd(q, k, v, scale, window, bs, kernel, interpret):
    if kernel:
        o, lse = _band_fwd_pallas(q, k, v, scale, window, bs, interpret)
    else:
        o, lse = _band_fwd_xla(q, k, v, scale, window, bs)
        o = o.astype(q.dtype)
    return o, (q, k, v, o, lse)


def _band_bwd(scale, window, bs, kernel, interpret, res, do):
    q, v = res[0], res[2]
    _, _, g, T, d = q.shape
    walk = kernel and _bwd_walk(T, d, v.shape[-1], g, q.dtype.itemsize)
    prof = OpProfiler.get()
    prof.count("seq/attn_bwd_kernel" if walk else "seq/attn_bwd_fallback")
    if walk == "kv_resident":
        prof.count("seq/attn_bwd_kv_resident")
    if walk:
        return _band_bwd_pallas(*res, do, scale, window, bs, interpret,
                                walk == "kv_resident")
    return _band_bwd_xla(*res, do, scale, window, bs)


_band.defvjp(_band_fwd, _band_bwd)


def supports_band_kernel(T: int, d: int, dv: int, bs: int) -> bool:
    # the [bs, bs] score tile's lane dim % 128; head and value widths as
    # compiled for the chip (tests/test_tpu_compile_seq.py)
    return (T % bs == 0 and bs % 128 == 0 and d % 32 == 0 and dv % 32 == 0)


def supports_band_bwd_kernel(T: int, d: int, g: int, itemsize: int) -> bool:
    """Beside ``supports_band_kernel``: the backward kernel's key-block-first
    walk keeps the dq of a key/value head's ``g`` query heads in VMEM (a
    float32 scratch and the double-buffered output block) and leaves half of
    its limit to the tiles. Where that does not fit,
    ``supports_band_bwd_kv_resident`` says whether the query-block-first
    walk does; where neither does, the backward is the XLA loops."""
    return g * T * d * (4 + 2 * itemsize) <= _BWD_VMEM_LIMIT // 2


def supports_band_bwd_kv_resident(T: int, d: int, dv: int,
                                  itemsize: int) -> bool:
    """The backward kernel's query-block-first walk keeps dk and dv of the
    whole key/value head in VMEM (float32 scratch and the double-buffered
    output blocks), whatever the group: taken where a group's dq does not
    fit (``supports_band_bwd_kernel``), as 8 heads of 128 at 8k or 16k."""
    return T * (d + dv) * (4 + 2 * itemsize) <= _BWD_VMEM_LIMIT // 2


def _bwd_walk(T: int, d: int, dv: int, g: int, itemsize: int):
    """The backward kernel's walk for a shape the forward kernel takes:
    ``"key_first"`` where a group's dq fits, else ``"kv_resident"`` where
    the key/value head's dk and dv do, else None (the XLA loops)."""
    if supports_band_bwd_kernel(T, d, g, itemsize):
        return "key_first"
    if supports_band_bwd_kv_resident(T, d, dv, itemsize):
        return "kv_resident"
    return None


@op("causal_attention", "nn")
def causal_attention(q, k, v, window: Optional[int] = None,
                     sm_scale: Optional[float] = None,
                     block: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """Causal softmax attention with grouped-query heads: q ``[B, Hq, T,
    D]``, k ``[B, Hk, T, D]``, v ``[B, Hk, T, Dv]`` with ``Hq`` a multiple of
    ``Hk`` (query head ``h`` reads key/value head ``h // (Hq/Hk)``); returns
    ``[B, Hq, T, Dv]`` in q's dtype. ``window``: query i sees keys j with
    ``i - window < j <= i``; None sees every ``j <= i``. Products take the
    operands as they come (bfloat16 stays bfloat16) and accumulate in
    float32. ``block``: query and key block (``BAND_BLOCK``, shrunk to T); a
    ``T`` it does not divide is padded at the end, where causality hides the
    padding. ``interpret`` as in ``ops.ssm.selective_scan``.

    Where the forward is the Pallas kernel (a TPU, ``allow_pallas``,
    ``supports_band_kernel``; or ``interpret=True``) the backward is one too
    (``flash_attention_bwd``: the same band, the same casts of ``p`` and
    ``ds`` before their products as the XLA loops): key block first where a
    group's dq fits VMEM (``supports_band_bwd_kernel``), else query block
    first with the key/value head's dk and dv resident where those fit
    (``supports_band_bwd_kv_resident``), else the XLA loops; everywhere else
    both are XLA loops. ``seq/attn_kernel`` / ``seq/attn_fallback`` count the
    forward's call sites, ``seq/attn_bwd_kernel`` / ``seq/attn_bwd_fallback``
    the backward's (``seq/attn_bwd_kv_resident`` those of the kernel's
    second walk), as a step is traced; ``seq/attn_key_blocks_run`` /
    ``seq/attn_key_blocks_skipped`` the band's block pairs a query head and
    what the square has beside them; ``seq/attn_fwd_grid_steps`` the grid
    steps the forward kernel's call issues — a pair for each key/value head,
    ``attn_key_blocks_run / (Hq/Hk)``, and 0 on the XLA path."""
    from ..common.environment import Environment

    b, hq, T, d = q.shape
    hk, dv = k.shape[1], v.shape[-1]
    g = hq // hk
    bs = min(int(block or BAND_BLOCK), T)
    pad = -T % bs
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for a in (q, k, v))
    Tp = T + pad
    fits = supports_band_kernel(Tp, d, dv, bs)
    if interpret is None:
        kernel = (Environment.get().allow_pallas()
                  and jax.default_backend() == "tpu" and fits)
    else:
        kernel = bool(interpret) and fits
    prof = OpProfiler.get()
    prof.count("seq/attn_kernel" if kernel else "seq/attn_fallback")
    run, skipped = band_blocks(Tp, bs, window)
    prof.count("seq/attn_key_blocks_run", run * b * hq)
    prof.count("seq/attn_key_blocks_skipped", skipped * b * hq)
    prof.count("seq/attn_fwd_grid_steps", run * b * hk if kernel else 0)
    scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))
    o = _band(q.reshape(b, hk, g, Tp, d), k, v, scale,
              int(window) if window else None, bs, kernel, bool(interpret))
    return o.reshape(b, hq, Tp, dv)[:, :, :T]
