"""Selective state-space scan (Mamba-1, Gu & Dao arXiv:2312.00752 Alg. 2).

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * u_t) (x) B_t,   h_0 = 0
    y_t = h_t . C_t

``u``, ``dt``: ``[B, T, D]`` (``dt`` after its softplus); ``A``: ``[D, N]``
(negative); ``Bm``, ``Cm``: ``[B, T, N]``; ``y``: ``[B, T, D]``. The skip
``D * u`` and the gate stay with the layer. State, ``dt`` and ``A`` are
float32 whatever the inputs are; ``y`` comes back in ``u``'s dtype.

Time runs in chunks of ``chunk`` steps with the state carried between
them. The forward keeps only the state at each chunk's start
(``[T/chunk, B, D, N]`` float32); the backward walks the chunks in
reverse, recomputes one chunk's states from its start and differentiates
that chunk alone, so nothing of size ``T x D x N`` is ever stored.

Two implementations behind one ``custom_vjp``:

- a Pallas TPU kernel each way, both under the one name ``selective_scan``
  (a kernel's name is all that a device trace keeps of it, and a reader of
  the ten most expensive names sees the scan whole or not at all, never the
  slower kernel alone). A block is 1024 channels as one ``[8, 128]``
  tile per state index, so every op in the time loop is a whole-vreg op
  and ``exp(dt*A)`` and ``dt*u (x) B`` are formed in the kernel; ``B_t``
  and ``C_t`` arrive broadcast along lanes (``[T, N, 128]``) and meet the
  channel tile by a sublane broadcast. The backward's sums over channels
  (``dB``, ``dC``) leave the kernel reduced over sublanes only and are
  finished by XLA;
- plain XLA (``lax.scan`` over chunks of a ``lax.scan`` over steps): the
  path off the TPU, and for widths that are not a multiple of 1024.

Which one ran is counted when the step is traced (``seq/scan_kernel``,
``seq/scan_fallback``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..common.profiler import OpProfiler
from .registry import op

DEFAULT_CHUNK = 64      # steps a chunk; the backward holds chunk+1 states in VMEM
LANE_BLOCK = 1024       # channels a kernel block: one [8, 128] float32 tile


def supports_scan_kernel(d_inner: int, d_state: int) -> bool:
    return d_inner % LANE_BLOCK == 0 and 1 <= d_state <= 64


# --- plain XLA -------------------------------------------------------------


def _chunk_xla(h, u, dt, A, Bm, Cm):
    """One chunk, time-major: u, dt [L, B, D]; Bm, Cm [L, B, N]; h [B, D, N]
    -> (h after the chunk, y [L, B, D])."""

    def step(h, x):
        u_t, dt_t, b_t, c_t = x
        a = jnp.exp(dt_t[..., None] * A)
        h = a * h + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    return lax.scan(step, h, (u, dt, Bm, Cm))


def _chunks(a, L):
    """[B, T, F] -> [T/L, L, B, F]."""
    b, t, f = a.shape
    return jnp.moveaxis(a, 1, 0).reshape(t // L, L, b, f)


def _unchunk(a):
    """[T/L, L, B, F] -> [B, T, F]."""
    c, L, b, f = a.shape
    return jnp.moveaxis(a.reshape(c * L, b, f), 0, 1)


def _fwd_xla(u, dt, A, Bm, Cm, L):
    b, _, d = u.shape
    h0 = jnp.zeros((b, d, A.shape[1]), A.dtype)

    def chunk(h, xs):
        h_new, y = _chunk_xla(h, *xs[:2], A, *xs[2:])
        return h_new, (y, h)

    _, (y, starts) = lax.scan(
        chunk, h0, (_chunks(u, L), _chunks(dt, L), _chunks(Bm, L),
                    _chunks(Cm, L)))
    return _unchunk(y), starts          # starts: [T/L, B, D, N]


def _bwd_xla(u, dt, A, Bm, Cm, starts, dy, L):
    def chunk(carry, xs):
        gh, dA = carry
        h, u_c, dt_c, b_c, c_c, dy_c = xs
        _, vjp = jax.vjp(_chunk_xla, h, u_c, dt_c, A, b_c, c_c)
        gh, du, ddt, dA_c, db, dc = vjp((gh, dy_c))
        return (gh, dA + dA_c), (du, ddt, db, dc)

    (_, dA), (du, ddt, db, dc) = lax.scan(
        chunk, (jnp.zeros_like(starts[0]), jnp.zeros_like(A)),
        (starts, _chunks(u, L), _chunks(dt, L), _chunks(Bm, L),
         _chunks(Cm, L), _chunks(dy, L)), reverse=True)
    return _unchunk(du), _unchunk(ddt), dA, _unchunk(db), _unchunk(dc)


# --- the Pallas kernels ----------------------------------------------------
#
# Both trace in the 32-bit world (``jax.enable_x64(False)`` around the
# pallas_call: the framework turns x64 on, and Mosaic refuses i64 scalars,
# see ops/pallas_attention.py). Per grid step (batch row, channel block,
# chunk) the chunk axis is innermost and sequential; the state lives in VMEM
# scratch across it.


def _scan_fwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, start_ref,
                     h_scr, *, L: int, N: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    start_ref[0, 0, 0] = h_scr[...]

    def step(t, carry):
        dt = dt_ref[0, t, 0]                        # [8, 128]
        dtu = dt * u_ref[0, t, 0]
        y = jnp.zeros_like(dt)
        for n in range(N):
            h = (jnp.exp(dt * a_ref[0, n]) * h_scr[n]
                 + dtu * b_ref[0, t, pl.ds(n, 1), :])
            h_scr[n] = h
            y = y + h * c_ref[0, t, pl.ds(n, 1), :]
        y_ref[0, t, 0] = y
        return carry

    lax.fori_loop(0, L, step, 0)


def _scan_bwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, start_ref, dy_ref,
                     du_ref, ddt_ref, db_ref, dc_ref, dA_ref,
                     hist, ga, dA_acc, *, L: int, N: int):
    """Chunks arrive last first. ``hist[t*N + n]`` is h_{t-1}[n] of this
    chunk (``hist[0..N)`` its start); ``ga`` carries a_{t+1} * dL/dh_{t+1}
    from the step, and the chunk, after."""

    @pl.when(pl.program_id(2) == 0)
    def _():
        ga[...] = jnp.zeros_like(ga)
        dA_acc[...] = jnp.zeros_like(dA_acc)

    for n in range(N):
        hist[n] = start_ref[0, 0, 0, n]

    def forward(t, carry):
        dt = dt_ref[0, t, 0]
        dtu = dt * u_ref[0, t, 0]
        for n in range(N):
            hist[(t + 1) * N + n] = (
                jnp.exp(dt * a_ref[0, n]) * hist[t * N + n]
                + dtu * b_ref[0, t, pl.ds(n, 1), :])
        return carry

    lax.fori_loop(0, L, forward, 0)

    def backward(i, carry):
        t = L - 1 - i
        dt = dt_ref[0, t, 0]
        u = u_ref[0, t, 0]
        dy = dy_ref[0, t, 0]
        dtu = dt * u
        du = jnp.zeros_like(dt)
        ddt = jnp.zeros_like(dt)
        for n in range(N):
            A_n = a_ref[0, n]
            b_n = b_ref[0, t, pl.ds(n, 1), :]
            g = dy * c_ref[0, t, pl.ds(n, 1), :] + ga[n]    # dL/dh_t[n]
            a = jnp.exp(dt * A_n)
            ga[n] = a * g
            gah = a * g * hist[t * N + n]                   # dL/da_t * a_t
            ddt = ddt + gah * A_n + g * (u * b_n)
            du = du + g * (dt * b_n)
            dA_acc[n] = dA_acc[n] + gah * dt
            db_ref[0, 0, t, pl.ds(n, 1), :] = jnp.sum(
                g * dtu, axis=0, keepdims=True)
            dc_ref[0, 0, t, pl.ds(n, 1), :] = jnp.sum(
                hist[(t + 1) * N + n] * dy, axis=0, keepdims=True)
        du_ref[0, t, 0] = du
        ddt_ref[0, t, 0] = ddt
        return carry

    lax.fori_loop(0, L, backward, 0)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        dA_ref[0, 0] = dA_acc[...]


def _tiles(a):
    """[B, T, D] -> [B, T, D/1024, 8, 128] (free: D is contiguous)."""
    b, t, d = a.shape
    return a.reshape(b, t, d // LANE_BLOCK, 8, 128)


def _lanes(a):
    """[B, T, N] -> [B, T, N, 128]: each scalar along a row of lanes."""
    return jnp.broadcast_to(a[..., None], a.shape + (128,))


def _a_tiles(A):
    """[D, N] -> [D/1024, N, 8, 128]."""
    d, n = A.shape
    return jnp.moveaxis(A.reshape(d // LANE_BLOCK, 8, 128, n), 3, 1)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)


def _fwd_pallas(u, dt, A, Bm, Cm, L, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, t, d = u.shape
    n, g, nc = A.shape[1], d // LANE_BLOCK, t // L
    f32 = jnp.float32
    seq = pl.BlockSpec((1, L, 1, 8, 128), lambda i, j, c: (i, c, j, 0, 0))
    row = pl.BlockSpec((1, L, n, 128), lambda i, j, c: (i, c, 0, 0))
    with jax.enable_x64(False):
        y, starts = pl.pallas_call(
            functools.partial(_scan_fwd_kernel, L=L, N=n),
            grid=(b, g, nc),
            in_specs=[seq, seq,
                      pl.BlockSpec((1, n, 8, 128), lambda i, j, c: (j, 0, 0, 0)),
                      row, row],
            out_specs=[seq, pl.BlockSpec((1, 1, 1, n, 8, 128),
                                         lambda i, j, c: (c, i, j, 0, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((b, t, g, 8, 128), f32),
                       jax.ShapeDtypeStruct((nc, b, g, n, 8, 128), f32)],
            scratch_shapes=[pltpu.VMEM((n, 8, 128), f32)],
            compiler_params=None if interpret else _compiler_params(),
            interpret=interpret, name="selective_scan",
        )(_tiles(u), _tiles(dt), _a_tiles(A), _lanes(Bm), _lanes(Cm))
    return y.reshape(b, t, d), starts


def _bwd_pallas(u, dt, A, Bm, Cm, starts, dy, L, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, t, d = u.shape
    n, g, nc = A.shape[1], d // LANE_BLOCK, t // L
    f32 = jnp.float32
    # grid step c works on chunk nc-1-c
    seq = pl.BlockSpec((1, L, 1, 8, 128),
                       lambda i, j, c: (i, nc - 1 - c, j, 0, 0))
    row = pl.BlockSpec((1, L, n, 128), lambda i, j, c: (i, nc - 1 - c, 0, 0))
    part = pl.BlockSpec((1, 1, L, n, 128),
                        lambda i, j, c: (i, j, nc - 1 - c, 0, 0))
    with jax.enable_x64(False):
        du, ddt, db, dc, dA = pl.pallas_call(
            functools.partial(_scan_bwd_kernel, L=L, N=n),
            grid=(b, g, nc),
            in_specs=[seq, seq,
                      pl.BlockSpec((1, n, 8, 128), lambda i, j, c: (j, 0, 0, 0)),
                      row, row,
                      pl.BlockSpec((1, 1, 1, n, 8, 128),
                                   lambda i, j, c: (nc - 1 - c, i, j, 0, 0, 0)),
                      seq],
            out_specs=[seq, seq, part, part,
                       pl.BlockSpec((1, 1, n, 8, 128),
                                    lambda i, j, c: (i, j, 0, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((b, t, g, 8, 128), f32),
                       jax.ShapeDtypeStruct((b, t, g, 8, 128), f32),
                       jax.ShapeDtypeStruct((b, g, t, n, 128), f32),
                       jax.ShapeDtypeStruct((b, g, t, n, 128), f32),
                       jax.ShapeDtypeStruct((b, g, n, 8, 128), f32)],
            scratch_shapes=[pltpu.VMEM(((L + 1) * n, 8, 128), f32),
                            pltpu.VMEM((n, 8, 128), f32),
                            pltpu.VMEM((n, 8, 128), f32)],
            compiler_params=None if interpret else _compiler_params(),
            interpret=interpret, name="selective_scan",
        )(_tiles(u), _tiles(dt), _a_tiles(A), _lanes(Bm), _lanes(Cm),
          starts, _tiles(dy))
    dA = jnp.moveaxis(dA.sum(0), 1, 3).reshape(d, n)    # [g,n,8,128] -> [D,N]
    return (du.reshape(b, t, d), ddt.reshape(b, t, d), dA,
            db.sum(axis=(1, 4)), dc.sum(axis=(1, 4)))


# --- one op ------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(u, dt, A, Bm, Cm, L, kernel, interpret):
    return _scan_fwd(u, dt, A, Bm, Cm, L, kernel, interpret)[0]


def _scan_fwd(u, dt, A, Bm, Cm, L, kernel, interpret):
    if kernel:
        y, starts = _fwd_pallas(u, dt, A, Bm, Cm, L, interpret)
    else:
        y, starts = _fwd_xla(u, dt, A, Bm, Cm, L)
    return y, (u, dt, A, Bm, Cm, starts)


def _scan_bwd(L, kernel, interpret, res, dy):
    if kernel:
        return _bwd_pallas(*res, dy, L, interpret)
    return _bwd_xla(*res, dy, L)


_scan.defvjp(_scan_fwd, _scan_bwd)


@op("selective_scan", "nn")
def selective_scan(u, dt, A, Bm, Cm, chunk: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """The scan of the module's docstring. ``chunk``: steps between saved
    states (``DEFAULT_CHUNK``); a ``T`` it does not divide is padded with
    steps of ``dt = 0``, which leave the state as it is. ``interpret``: True
    runs the Pallas kernels in interpret mode whatever the backend (the CPU
    tests), False never; None takes the kernels on a TPU where the widths
    allow them and the XLA path otherwise."""
    from ..common.environment import Environment

    b, t, d = u.shape
    n = A.shape[1]
    L = int(chunk or DEFAULT_CHUNK)
    # float32 whatever comes in, but float64 stays (gradient checks)
    wide = jnp.promote_types(u.dtype, jnp.float32)
    fits = supports_scan_kernel(d, n) and wide == jnp.float32
    if interpret is None:
        kernel = (Environment.get().allow_pallas()
                  and jax.default_backend() == "tpu" and fits)
    else:
        kernel = bool(interpret) and fits
    OpProfiler.get().count("seq/scan_kernel" if kernel
                           else "seq/scan_fallback")
    args = [a.astype(wide) for a in (u, dt, A, Bm, Cm)]
    pad = -t % L
    if pad:
        for i in (0, 1, 3, 4):
            args[i] = jnp.pad(args[i], ((0, 0), (0, pad), (0, 0)))
    y = _scan(*args, L, kernel, bool(interpret))
    return y[:, :t].astype(u.dtype)
