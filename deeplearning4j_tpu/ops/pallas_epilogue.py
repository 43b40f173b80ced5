"""Fused BN+activation(+residual-add) inference epilogue (Pallas).

The next-hottest fusion XLA misses on the resnet blocks (ROADMAP item 3,
the TVM argument again): inference-mode BatchNormalization collapses to a
per-channel affine ``y = x*scale + shift``, and the resnet block tail is
exactly ``relu(bn(x) + residual)`` — three HBM round-trips (normalize,
add, activate) that one kernel does in a single x/residual read and one
write. Training-mode BN is NOT fused here: it computes batch statistics
(a reduction) behind a hand-written VJP (ops/nn.batchnorm_train) and
stays on that path untouched.

Layout: the kernel streams the tensor as channels-last 2-D ``[rows, C]``
(NCHW transposes around the call — XLA fuses the transposes into the
neighbouring ops), per-channel scale/shift ride along as a ``(1, C)``
row indexed by the lane-program. Shape gate (:func:`fusable`): float
inputs, relu/identity activation, channel count a multiple of 128 (the
TPU lane width — resnet block channels 256/512/1024/2048 pass, the
7x7-stem's 64 falls back to the dense ops). Refusals return ``None`` and
are ledgered (``precision/epilogue_fallbacks``); callers keep their
dense path.

Modes mirror ``ops/pallas_update``: ``pallas`` (real Mosaic kernel, TPU
default), ``interpret`` (CPU test mesh), ``xla`` (non-TPU default: the
same affine+act expression broadcast in the original layout — one fused
XLA elementwise kernel, no transposes). All modes share one math
expression; scale/shift are computed ONCE in f32 outside the kernel, so
mode-to-mode agreement is elementwise-exact up to XLA's fma contraction
of ``x*scale + shift`` when it compiles the kernel body (≤2 ulp, pinned
by tests/test_precision.py). Against the UNFUSED dense
ops the epilogue is a reassociation — ``(x-mean)*inv*gamma+beta`` vs
``x*(gamma*inv) + (beta-mean*gamma*inv)`` — so parity is
tolerance-bounded (documented, tested), not bitwise; that is why the
fusion is opt-in (``GlobalConf.fused_epilogue``), never silent.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..common.profiler import OpProfiler
from .pallas_update import LANES, default_mode

BLOCK_ROWS = 256


def _act_fn(act: str):
    if act == "relu":
        return lambda y: jnp.maximum(y, jnp.zeros((), y.dtype))
    return lambda y: y


def fusable(x, axis: int, act: Optional[str]) -> bool:
    """Shape gate: can :func:`bn_act` fuse this epilogue?"""
    act = (act or "identity").lower()
    if act not in ("relu", "identity"):
        return False
    if not (hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)):
        return False
    nd = getattr(x, "ndim", 0)
    if nd == 4 and axis % 4 == 1:
        c = x.shape[1]
    elif nd == 2 and axis % 2 == 1:
        c = x.shape[1]
    else:
        return False
    return c % LANES == 0


def _kernel(act, has_res, x_ref, scale_ref, shift_ref, *rest):
    res_ref, out_ref = (rest[0], rest[1]) if has_res else (None, rest[0])
    y = x_ref[...] * scale_ref[...] + shift_ref[...]
    if has_res:
        y = y + res_ref[...]
    out_ref[...] = _act_fn(act)(y)


def _launch(x2d, scale, shift, res2d, act, interpret):
    rows, C = x2d.shape
    pad = -(-rows // BLOCK_ROWS) * BLOCK_ROWS - rows
    if pad:
        z = jnp.zeros((pad, C), x2d.dtype)
        x2d = jnp.concatenate([x2d, z])
        if res2d is not None:
            res2d = jnp.concatenate([res2d, z])
    grid = (x2d.shape[0] // BLOCK_ROWS, C // LANES)
    blk = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i, j: (i, j))
    vec = pl.BlockSpec((1, LANES), lambda i, j: (0, j))
    ins = [x2d, scale.reshape(1, C), shift.reshape(1, C)]
    in_specs = [blk, vec, vec]
    if res2d is not None:
        ins.append(res2d)
        in_specs.append(blk)
    kernel = functools.partial(_kernel, act, res2d is not None)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=blk,
            out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
            interpret=interpret,
        )(*ins)
    return out[:rows] if pad else out


def bn_act(x, mean, var, gamma=None, beta=None, *, epsilon: float = 1e-5,
           axis: int = 1, act: Optional[str] = None, residual=None,
           mode: Optional[str] = None):
    """Fused inference epilogue ``act(bn(x) [+ residual])`` — or ``None``
    when the shape gate refuses (caller falls back to its dense path;
    the refusal is ledgered).

    ``mean``/``var``/``gamma``/``beta``: per-channel ``(C,)`` f32 (the BN
    layer's running stats and affine params; gamma/beta may be None).
    ``residual`` must match ``x``'s shape. scale/shift are folded in f32
    then cast to ``x.dtype`` — identical across all three modes.
    """
    act = (act or "identity").lower()
    prof = OpProfiler.get()
    if residual is not None and residual.shape != x.shape:
        prof.count("precision/epilogue_fallbacks")
        return None
    if not fusable(x, axis, act):
        prof.count("precision/epilogue_fallbacks")
        return None
    if mode is None:
        mode = default_mode()
    if mode not in ("pallas", "interpret", "xla"):
        raise ValueError(f"unknown epilogue mode {mode!r}")
    f32 = jnp.float32
    inv = lax.rsqrt(var.astype(f32) + jnp.asarray(epsilon, f32))
    scale = inv if gamma is None else gamma.astype(f32) * inv
    shift = -mean.astype(f32) * scale
    if beta is not None:
        shift = beta.astype(f32) + shift
    scale, shift = scale.astype(x.dtype), shift.astype(x.dtype)
    if residual is not None:
        residual = residual.astype(x.dtype)
    prof.count("precision/epilogue_hits")
    if residual is not None:
        prof.count("precision/epilogue_residual_hits")
    if mode == "xla":
        shape = [1] * x.ndim
        shape[1] = x.shape[1]
        y = x * scale.reshape(shape) + shift.reshape(shape)
        if residual is not None:
            y = y + residual
        return _act_fn(act)(y)
    if x.ndim == 4:
        to2d = lambda a: a.transpose(0, 2, 3, 1).reshape(-1, x.shape[1])
        n, c, h, w = x.shape
        back = lambda a: a.reshape(n, h, w, c).transpose(0, 3, 1, 2)
    else:
        to2d = back = lambda a: a
    out = _launch(to2d(x), scale, shift,
                  None if residual is None else to2d(residual),
                  act, interpret=(mode == "interpret"))
    return back(out)
