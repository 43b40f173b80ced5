"""State-space scans: Mamba-1's selective scan and Mamba-2's state-space dual.

``selective_scan`` (Mamba-1, Gu & Dao arXiv:2312.00752 Alg. 2; called by
``nn.conf.layers_seq.MambaLayer``, ``models.Phi4MiniFlash``):

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * u_t) (x) B_t,   h_0 = 0
    y_t = h_t . C_t

with a decay for every channel and state index. ``ssd_scan`` (Mamba-2, Dao &
Gu arXiv:2405.21060; called by ``nn.conf.layers_seq.Mamba2Layer``,
``models.GraniteHybrid``) is the same recurrence with ONE decay a head,
heads of ``P`` channels and a state ``[P, N]`` a head, and ``B``, ``C`` shared
by the heads of a group:

    H_t^h = exp(dt_t^h A^h) H_{t-1}^h + dt_t^h X_t^h (x) B_t^g,   H_0 = 0
    Y_t^h = H_t^h C_t^g                                 (g = h // (H/G))

A scalar decay a head is what makes a chunk of the recurrence a masked
matrix product (the "dual" form, section 6 of the paper): with ``s_t`` the
cumulative log-decay inside a chunk, ``Y_t = sum_{j<=t} (C_t . B_j)
exp(s_t - s_j) dt_j X_j + exp(s_t) C_t H_in``, and the chunk's end state is
``exp(s_L) H_in + sum_j exp(s_L - s_j) dt_j X_j (x) B_j``. That is matrix
unit work, where the selective scan is the vector unit's, a step at a time.

Both scans run time in chunks of ``chunk`` steps with the state carried
between them. The forward keeps only the state at each chunk's start; the
backward walks the chunks in reverse, recomputes one chunk from its start
and differentiates that chunk alone, carrying the state's cotangent, so
nothing of size ``T x state`` is ever stored. A ``T`` the chunk does not
divide is padded with steps of ``dt = 0``, which leave the state as it is.

The selective scan: ``u``, ``dt``: ``[B, T, D]`` (``dt`` after its
softplus); ``A``: ``[D, N]`` (negative); ``Bm``, ``Cm``: ``[B, T, N]``;
``y``: ``[B, T, D]``. The skip ``D * u`` and the gate stay with the layer.
State, ``dt`` and ``A`` are float32 whatever the inputs are; ``y`` comes
back in ``u``'s dtype. Chunk starts are ``[T/chunk, B, D, N]`` float32.

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

The state-space dual: ``x``: ``[B, T, H, P]``; ``dt``: ``[B, T, H]`` (after
its softplus); ``A``: ``[H]`` (negative); ``B``, ``C``: ``[B, T, G, N]``;
``y``: ``[B, T, H, P]`` in ``x``'s dtype, without the ``D`` skip (the layer's,
with the gate). Decays, their cumulative sums, ``exp`` and the carried state
are float32; the products take ``x``'s dtype as operands (bfloat16 in a
bfloat16 layer) and accumulate in float32. Two implementations behind one
``custom_vjp``:

- a Pallas TPU kernel each way, both under the one name ``ssd_scan``; grid
  (batch row, group, chunk), the chunk axis sequential, every head of the
  group in one grid step with the group's ``[N, hg*P]`` float32 state in
  VMEM scratch. x, y, dy and dx stay token-major: the kernels view them as
  ``[B, T, H*P]``, a chunk of a group one ``[L, hg*P]`` block, and the
  heads are walked a pack of 128 lanes at a time (two heads of 64; one of
  128 or wider), each head's products taking the pack's tile and a lane
  select keeping the head's lanes. ``C B^T`` is formed once a chunk for the
  group, ``C H_in`` once a pack; a head's decay mask, its intra-chunk
  product and the state update follow. Chunk starts are ``[B, T/chunk, N,
  H*P]`` float32 (a head's state kept as ``H^T``, side by side along the
  lanes). The cumulative log-decays are XLA's (``s``, handed in beside
  ``dt``, both ``[B, H, T]``), and the backward hands back ``ds``, whose map
  to ``dA`` and ``ddt`` (a reverse cumulative sum in the chunk) is XLA's
  too. ``dB`` and ``dC`` leave the kernel summed over the group's heads;
- plain XLA (``lax.scan`` over chunks of the dual form, the backward by
  ``jax.vjp`` of one chunk; chunk starts ``[B, T/chunk, H, N, P]``): the
  path off the TPU, for shapes that ``supports_ssd_kernel`` refuses, and
  the tests' oracle for the kernel.

``seq/ssd_kernel`` / ``seq/ssd_fallback`` count the call sites as a step is
traced.
"""

from __future__ import annotations

import functools
import math
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
    """[B, T, ...] -> [T/L, L, B, ...]."""
    b, t = a.shape[:2]
    return jnp.moveaxis(a, 1, 0).reshape(t // L, L, b, *a.shape[2:])


def _unchunk(a):
    """[T/L, L, B, ...] -> [B, T, ...]."""
    c, L, b = a.shape[:3]
    return jnp.moveaxis(a.reshape(c * L, b, *a.shape[3:]), 0, 1)


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


# --- the state-space dual (Mamba-2) ------------------------------------------

SSD_CHUNK = 256         # steps a chunk: Mamba-2's ``chunk_size``
_SSD_VMEM_LIMIT = 64 * 1024 * 1024


def supports_ssd_kernel(H: int, G: int, P: int, N: int, L: int,
                        itemsize: int) -> bool:
    """The kernels' shapes: a group's heads in whole sublane tiles, a head
    and a state as wide as the lanes the products want, a group of whole
    128-lane tiles (a chunk of it is one token-major block ``[L, hg*P]``), a
    chunk of whole lane tiles; the backward's blocks (x, dy and dx
    double-buffered, the chunk starts double-buffered, the carried
    cotangent) and its ``[L, L]`` temporaries, as counted here, within three
    quarters of the VMEM it asks for. Mosaic allocates within 2 MiB of the
    count at 16,384 steps (24 MiB where it counts 23 for 64 heads of 64 in
    bfloat16, 37 where it counts 35 in float32, 67 where it counts 65 for 128
    heads in float32, which is refused); the quarter left over is for what
    the count leaves out (``tests/test_tpu_compile_seq.py`` compiles the
    widest groups it takes)."""
    if H % G:
        return False
    hg = H // G
    blocks = (2 * 3 * hg * L * P * itemsize + 3 * hg * N * P * 4
              + 16 * L * L * 4 + 8 * L * N * 4)
    return (hg % 8 == 0 and P % 64 == 0 and (hg * P) % 128 == 0
            and N % 128 == 0 and L % 128 == 0
            and blocks <= _SSD_VMEM_LIMIT * 3 // 4)


def _ssd_chunk_xla(h, x, dt, A, B, C):
    """One chunk of the dual form, time-major. h ``[b, H, N, P]`` (a head's
    state as ``H^T``); x ``[L, b, H, P]``; dt ``[L, b, H]``; B, C ``[L, b,
    G, N]`` -> (h after the chunk, y ``[L, b, H, P]``)."""
    L, H, G = x.shape[0], x.shape[2], B.shape[2]
    Bh = jnp.repeat(B, H // G, axis=2)
    Ch = jnp.repeat(C, H // G, axis=2)
    s = jnp.cumsum(dt * A, axis=0)                          # [L, b, H]
    causal = jnp.tril(jnp.ones((L, L), bool))[:, :, None, None]
    decay = jnp.exp(jnp.where(causal, s[:, None] - s[None], -jnp.inf))
    scores = jnp.einsum("tbhn,jbhn->tjbh", Ch, Bh) * decay * dt[None]
    y = (jnp.einsum("tjbh,jbhp->tbhp", scores, x)
         + jnp.exp(s)[..., None] * jnp.einsum("tbhn,bhnp->tbhp", Ch, h))
    w = jnp.exp(s[-1:] - s) * dt                            # [L, b, H]
    h = (jnp.exp(s[-1])[..., None, None] * h
         + jnp.einsum("jbhn,jbhp->bhnp", Bh * w[..., None], x))
    return h, y


def _ssd_fwd_xla(x, dt, A, B, C, L):
    b, _, H, P = x.shape
    wide = jnp.promote_types(x.dtype, jnp.float32)
    h0 = jnp.zeros((b, H, B.shape[-1], P), wide)

    def chunk(h, xs):
        xc, dtc, bc, cc = xs
        h_new, y = _ssd_chunk_xla(h, xc.astype(wide), dtc, A,
                                  bc.astype(wide), cc.astype(wide))
        return h_new, (y.astype(x.dtype), h)

    _, (y, starts) = lax.scan(chunk, h0, (
        _chunks(x, L), _chunks(dt, L), _chunks(B, L), _chunks(C, L)))
    return _unchunk(y), jnp.moveaxis(starts, 0, 1).astype(jnp.float32)


def _ssd_bwd_xla(x, dt, A, B, C, starts, dy, L):
    wide = jnp.promote_types(x.dtype, jnp.float32)

    def chunk(carry, xs):
        gh, dA = carry
        h, xc, dtc, bc, cc, dyc = xs
        _, vjp = jax.vjp(_ssd_chunk_xla, h.astype(wide), xc.astype(wide),
                         dtc, A, bc.astype(wide), cc.astype(wide))
        gh, dx, ddt, dA_c, dB, dC = vjp((gh, dyc.astype(wide)))
        return (gh, dA + dA_c), (dx.astype(x.dtype), ddt, dB.astype(B.dtype),
                                 dC.astype(C.dtype))

    (_, dA), (dx, ddt, dB, dC) = lax.scan(
        chunk, (jnp.zeros(starts.shape[:1] + starts.shape[2:], wide),
                jnp.zeros_like(A)),
        (jnp.moveaxis(starts, 1, 0), _chunks(x, L), _chunks(dt, L),
         _chunks(B, L), _chunks(C, L), _chunks(dy, L)), reverse=True)
    return (_unchunk(dx), _unchunk(ddt), dA, _unchunk(dB), _unchunk(dC))


# The kernels trace in the 32-bit world as the selective scan's. They read
# and write x, y, dy and dx in the caller's token-major layout ``[b, T, H*P]``:
# a chunk of a group is one ``[L, hg*P]`` block, and the chunk starts are
# ``[N, hg*P]`` a group. Per grid step (batch row, group, chunk) the heads of
# the group are walked in blocks of ``sub`` (a sublane tile of their ``dt``
# and ``s`` rows), a pack at a time: a pack is the fewest heads that fill
# whole 128-lane tiles (two heads of 64, one of 128 or wider). Each head's
# products take the pack's whole tile as their operand, which costs the
# matrix unit no more passes than the head's own lanes, and a lane select
# keeps the head's lanes of the result; the products that every head of a
# pack shares (``C H_in``, ``dC``, ``C^T`` times the cotangent) run once a
# pack. ``s`` is the chunk's cumulative log-decay as a row ``[1, L]``; its
# column ``[L, 1]`` is taken on the diagonal (a select and a lane sum), so
# nothing is transposed in the kernel.

_NT = (((1,), (1,)), ((), ()))     # a @ b^T
_TN = (((0,), (0,)), ((), ()))     # a^T @ b


def _ssd_masks(L):
    rows = lax.broadcasted_iota(jnp.int32, (L, L), 0)
    cols = lax.broadcasted_iota(jnp.int32, (L, L), 1)
    return cols <= rows, cols == rows


def _ssd_head(s_row, eye, causal):
    """(s as a column, the chunk's decay mask exp(s_t - s_j) [j <= t], its
    last log-decay ``[1, 1]``) of one head."""
    L = s_row.shape[-1]
    s_col = jnp.sum(jnp.where(eye, s_row, 0.0), axis=1, keepdims=True)
    decay = jnp.exp(jnp.where(causal, s_col - s_row, -jnp.inf))
    lane = lax.broadcasted_iota(jnp.int32, (1, L), 1)
    s_last = jnp.sum(jnp.where(lane == L - 1, s_row, 0.0), axis=1,
                     keepdims=True)
    return s_col, decay, s_last


def _ssd_pack(hg, P):
    """Heads a pack: the fewest whole heads that fill whole 128-lane tiles
    (two of 64, one of 128 or wider), at most the group's (the tests' narrow
    heads in interpret mode)."""
    return min(hg, 128 // math.gcd(P, 128))


def _ssd_walk(hg, P):
    """(heads a block of the walk, heads a pack): a block is a sublane tile
    of ``dt`` and ``s`` rows, or the whole group where 8 do not divide it,
    and whole packs."""
    k = _ssd_pack(hg, P)
    sub = max(8, k)
    return (sub if hg % sub == 0 else hg), k


def _ssd_lanes(base, j, P, k):
    """The lanes of pack ``j`` of the block whose first head is ``base``."""
    return pl.ds(pl.multiple_of(base * P + j * k * P, k * P), k * P)


def _ssd_fwd_kernel(x_ref, dt_ref, s_ref, bt_ref, c_ref, y_ref, start_ref,
                    h_scr, *, sub: int, P: int, k: int):
    f32 = jnp.float32
    hg, L = dt_ref.shape[1], dt_ref.shape[2]
    op = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    start_ref[0, 0] = h_scr[...]
    bt, c = bt_ref[0, 0], c_ref[0, 0]                       # [N, L], [L, N]
    cb = jnp.dot(c, bt, preferred_element_type=f32)         # [L, L]
    causal, eye = _ssd_masks(L)
    owner = lax.broadcasted_iota(jnp.int32, (1, k * P), 1) // P

    def block(q, carry):
        base = pl.multiple_of(q * sub, sub)
        s_blk = s_ref[0, pl.ds(base, sub), :]
        dt_blk = dt_ref[0, pl.ds(base, sub), :]
        for j in range(sub // k):
            lanes = _ssd_lanes(base, j, P, k)
            xp, h0 = x_ref[0, :, lanes], h_scr[:, lanes]    # [L, W], [N, W]
            z = jnp.dot(c, h0.astype(op), preferred_element_type=f32)
            y = h1 = None
            for i in range(k):
                r = j * k + i
                s_row, dt_row = s_blk[r:r + 1], dt_blk[r:r + 1]
                s_col, decay, s_last = _ssd_head(s_row, eye, causal)
                y_i = (jnp.dot((cb * decay * dt_row).astype(op), xp,
                               preferred_element_type=f32)
                       + jnp.exp(s_col) * z)
                w = jnp.exp(s_last - s_row) * dt_row         # [1, L]
                h1_i = jnp.exp(s_last) * h0 + jnp.dot(
                    (bt * w).astype(op), xp, preferred_element_type=f32)
                if i == 0:
                    y, h1 = y_i, h1_i
                else:
                    y = jnp.where(owner == i, y_i, y)
                    h1 = jnp.where(owner == i, h1_i, h1)
            y_ref[0, :, lanes] = y.astype(y_ref.dtype)
            h_scr[:, lanes] = h1
        return carry

    lax.fori_loop(0, hg // sub, block, 0)


def _ssd_bwd_kernel(x_ref, dt_ref, s_ref, bt_ref, c_ref, start_ref, dy_ref,
                    dx_ref, ddt_ref, ds_ref, dbt_ref, dc_ref,
                    dh_scr, dcb_scr, dc_scr, dbt_scr, *, sub: int, P: int,
                    k: int):
    """Chunks arrive last first; ``dh_scr`` carries each head's cotangent of
    the state the chunk hands on. ``dcb_scr``, ``dc_scr``, ``dbt_scr`` sum
    the group's heads' parts of ``d(C B^T)``, ``dC`` and ``dB^T``. A head's
    contractions over its own lanes (``dY X^T``, ``dH X^T``) take its lanes
    of ``dy`` and ``dh`` with the pack's other lanes zeroed."""
    f32 = jnp.float32
    hg, L = dt_ref.shape[1], dt_ref.shape[2]
    op = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_scr[...] = jnp.zeros_like(dh_scr)

    dcb_scr[...] = jnp.zeros_like(dcb_scr)
    dc_scr[...] = jnp.zeros_like(dc_scr)
    dbt_scr[...] = jnp.zeros_like(dbt_scr)
    bt, c = bt_ref[0, 0], c_ref[0, 0]
    cb = jnp.dot(c, bt, preferred_element_type=f32)
    causal, eye = _ssd_masks(L)
    lane = lax.broadcasted_iota(jnp.int32, (1, L), 1)
    row_of = lax.broadcasted_iota(jnp.int32, (sub, L), 0)
    owner = lax.broadcasted_iota(jnp.int32, (1, k * P), 1) // P
    only = lambda a, i: a if k == 1 else jnp.where(      # noqa: E731
        owner == i, a, jnp.zeros_like(a))

    def block(q, carry):
        base = pl.multiple_of(q * sub, sub)
        s_blk = s_ref[0, pl.ds(base, sub), :]
        dt_blk = dt_ref[0, pl.ds(base, sub), :]
        ds_rows = jnp.zeros((sub, L), f32)
        ddt_rows = jnp.zeros((sub, L), f32)
        for j in range(sub // k):
            lanes = _ssd_lanes(base, j, P, k)
            xp, dyp = x_ref[0, :, lanes], dy_ref[0, :, lanes]     # [L, W]
            h0, dh1 = start_ref[0, 0, :, lanes], dh_scr[:, lanes]  # [N, W]
            z = jnp.dot(c, h0.astype(op), preferred_element_type=f32)
            dh1_op, dh1_h0 = dh1.astype(op), dh1 * h0
            dx = ey = e_last_w = None
            for i in range(k):
                r = j * k + i
                s_row, dt_row = s_blk[r:r + 1], dt_blk[r:r + 1]
                s_col, decay, s_last = _ssd_head(s_row, eye, causal)
                e_last = jnp.exp(s_last)
                cbm = cb * decay
                scores = cbm * dt_row
                w = jnp.exp(s_last - s_row) * dt_row       # [1, L]
                dyh = only(dyp, i)
                # the intra-chunk product and its scores
                dsc = lax.dot_general(dyh, xp, _NT, preferred_element_type=f32)
                btw = (bt * w).astype(op)
                dx_i = (lax.dot_general(scores.astype(op), dyp, _TN,
                                        preferred_element_type=f32)
                        + lax.dot_general(btw, dh1_op, _TN,
                                          preferred_element_type=f32))
                both = dsc * scores
                ds_col = jnp.sum(both, axis=1, keepdims=True)
                ds_row = -jnp.sum(both, axis=0, keepdims=True)
                ddt_row = jnp.sum(dsc * cbm, axis=0, keepdims=True)
                dcb_scr[...] += dsc * decay * dt_row
                # the state coming in: exp(s_t) C_t H_in
                e_col = jnp.exp(s_col)
                ds_col = ds_col + e_col * jnp.sum(dyh.astype(f32) * z, axis=1,
                                                  keepdims=True)
                ey_i = e_col * dyh
                # the state going out: exp(s_L) H_in + (B^T w) X
                v = lax.dot_general(only(dh1_op, i), xp, _NT,
                                    preferred_element_type=f32)  # [N, L]
                dbt_scr[...] += w * v
                dw = jnp.sum(bt.astype(f32) * v, axis=0, keepdims=True)
                ddt_row = ddt_row + dw * jnp.exp(s_last - s_row)
                ds_row = ds_row - dw * w
                ds_last = (jnp.sum(dw * w, axis=1, keepdims=True)
                           + e_last * jnp.sum(jnp.sum(only(dh1_h0, i), axis=1,
                                                      keepdims=True),
                                              axis=0, keepdims=True))
                ds_row = (ds_row
                          + jnp.sum(jnp.where(eye, ds_col, 0.0), axis=0,
                                    keepdims=True)
                          + jnp.where(lane == L - 1, ds_last, 0.0))
                ds_rows = jnp.where(row_of == r, ds_row, ds_rows)
                ddt_rows = jnp.where(row_of == r, ddt_row, ddt_rows)
                if i == 0:
                    dx, ey, e_last_w = dx_i, ey_i, e_last
                else:
                    dx = jnp.where(owner == i, dx_i, dx)
                    ey = ey + ey_i
                    e_last_w = jnp.where(owner == i, e_last, e_last_w)
            dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
            # the pack's heads at once: their lanes of e_col dY are disjoint
            ey = ey.astype(op)
            dc_scr[...] += lax.dot_general(ey, h0.astype(op), _NT,
                                           preferred_element_type=f32)
            dh_scr[:, lanes] = e_last_w * dh1 + lax.dot_general(
                c, ey, _TN, preferred_element_type=f32)
        ds_ref[0, pl.ds(base, sub), :] = ds_rows
        ddt_ref[0, pl.ds(base, sub), :] = ddt_rows
        return carry

    lax.fori_loop(0, hg // sub, block, 0)
    dcb = dcb_scr[...].astype(op)
    dc_ref[0, 0] = dc_scr[...] + lax.dot_general(
        dcb, bt, _NT, preferred_element_type=f32)
    dbt_ref[0, 0] = dbt_scr[...] + lax.dot_general(
        c, dcb, _TN, preferred_element_type=f32)


def _ssd_layouts(x, dt, A, B, C, L):
    """The kernels' operands: x as ``[b, T, H*P]`` (a view of the caller's
    row-major array: no copy); dt and the cumulative log-decay within each
    chunk ``[b, H, T]`` float32; ``B^T`` ``[b, G, N, T]`` and C ``[b, G, T,
    N]`` in x's dtype."""
    b, T, H, P = x.shape
    a = (dt * A).reshape(b, T // L, L, H)
    s = jnp.cumsum(a, axis=2).reshape(b, T, H)
    return (x.reshape(b, T, H * P), dt.transpose(0, 2, 1),
            s.transpose(0, 2, 1), B.astype(x.dtype).transpose(0, 2, 3, 1),
            C.astype(x.dtype).transpose(0, 2, 1, 3))


def _ssd_compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_SSD_VMEM_LIMIT)


def _ssd_fwd_pallas(x, dt, A, B, C, L, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, T, H, P = x.shape
    G, N = B.shape[2:]
    hg, nc = H // G, T // L
    sub, k = _ssd_walk(hg, P)
    f32 = jnp.float32
    chunk = pl.BlockSpec((1, L, hg * P), lambda i, g, c: (i, c, g))
    rows = pl.BlockSpec((1, hg, L), lambda i, g, c: (i, g, c))
    with jax.enable_x64(False):
        y, starts = pl.pallas_call(
            functools.partial(_ssd_fwd_kernel, sub=sub, P=P, k=k),
            grid=(b, G, nc),
            in_specs=[chunk, rows, rows,
                      pl.BlockSpec((1, 1, N, L), lambda i, g, c: (i, g, 0, c)),
                      pl.BlockSpec((1, 1, L, N), lambda i, g, c: (i, g, c, 0))],
            out_specs=[chunk, pl.BlockSpec((1, 1, N, hg * P),
                                           lambda i, g, c: (i, c, 0, g))],
            out_shape=[jax.ShapeDtypeStruct((b, T, H * P), x.dtype),
                       jax.ShapeDtypeStruct((b, nc, N, H * P), f32)],
            scratch_shapes=[pltpu.VMEM((N, hg * P), f32)],
            compiler_params=None if interpret else _ssd_compiler_params(),
            interpret=interpret, name="ssd_scan",
        )(*_ssd_layouts(x, dt, A, B, C, L))
    return y.reshape(b, T, H, P), starts


def _ssd_bwd_pallas(x, dt, A, B, C, starts, dy, L, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, T, H, P = x.shape
    G, N = B.shape[2:]
    hg, nc = H // G, T // L
    sub, k = _ssd_walk(hg, P)
    f32 = jnp.float32
    # grid step c works on chunk nc-1-c
    chunk = pl.BlockSpec((1, L, hg * P), lambda i, g, c: (i, nc - 1 - c, g))
    rows = pl.BlockSpec((1, hg, L), lambda i, g, c: (i, g, nc - 1 - c))
    bt_spec = pl.BlockSpec((1, 1, N, L), lambda i, g, c: (i, g, 0, nc - 1 - c))
    c_spec = pl.BlockSpec((1, 1, L, N), lambda i, g, c: (i, g, nc - 1 - c, 0))
    with jax.enable_x64(False):
        dx, ddt, ds, dbt, dc = pl.pallas_call(
            functools.partial(_ssd_bwd_kernel, sub=sub, P=P, k=k),
            grid=(b, G, nc),
            in_specs=[chunk, rows, rows, bt_spec, c_spec,
                      pl.BlockSpec((1, 1, N, hg * P),
                                   lambda i, g, c: (i, nc - 1 - c, 0, g)),
                      chunk],
            out_specs=[chunk, rows, rows, bt_spec, c_spec],
            out_shape=[jax.ShapeDtypeStruct((b, T, H * P), x.dtype),
                       jax.ShapeDtypeStruct((b, H, T), f32),
                       jax.ShapeDtypeStruct((b, H, T), f32),
                       jax.ShapeDtypeStruct((b, G, N, T), f32),
                       jax.ShapeDtypeStruct((b, G, T, N), f32)],
            scratch_shapes=[pltpu.VMEM((N, hg * P), f32),
                            pltpu.VMEM((L, L), f32),
                            pltpu.VMEM((L, N), f32),
                            pltpu.VMEM((N, L), f32)],
            compiler_params=None if interpret else _ssd_compiler_params(),
            interpret=interpret, name="ssd_scan",
        )(*_ssd_layouts(x, dt, A, B, C, L), starts,
          dy.astype(x.dtype).reshape(b, T, H * P))
    # s_t = sum_{i<=t} dt_i A in the chunk: ds flows back to every earlier
    # step of the chunk, and from there to dt and A
    da = ds.transpose(0, 2, 1).reshape(b, nc, L, H)
    da = jnp.flip(jnp.cumsum(jnp.flip(da, 2), axis=2), 2).reshape(b, T, H)
    return (dx.reshape(b, T, H, P), ddt.transpose(0, 2, 1) + da * A,
            jnp.sum(da * dt, axis=(0, 1)),
            dbt.transpose(0, 3, 1, 2).astype(B.dtype),
            dc.transpose(0, 2, 1, 3).astype(C.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ssd(x, dt, A, B, C, L, kernel, interpret):
    return _ssd_fwd(x, dt, A, B, C, L, kernel, interpret)[0]


def _ssd_fwd(x, dt, A, B, C, L, kernel, interpret):
    if kernel:
        y, starts = _ssd_fwd_pallas(x, dt, A, B, C, L, interpret)
    else:
        y, starts = _ssd_fwd_xla(x, dt, A, B, C, L)
    return y, (x, dt, A, B, C, starts)


def _ssd_bwd(L, kernel, interpret, res, dy):
    if kernel:
        return _ssd_bwd_pallas(*res, dy, L, interpret)
    return _ssd_bwd_xla(*res, dy, L)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


@op("ssd_scan", "nn")
def ssd_scan(x, dt, A, B, C, chunk: Optional[int] = None,
             interpret: Optional[bool] = None):
    """The state-space dual of the module's docstring. ``chunk``: steps a
    chunk (``SSD_CHUNK``); a ``T`` it does not divide is padded with steps of
    ``dt = 0``. ``interpret`` as ``selective_scan``'s: True runs the Pallas
    kernels in interpret mode whatever the backend, False never, None takes
    them on a TPU where ``supports_ssd_kernel`` allows and the XLA path
    otherwise. ``dt`` and ``A`` are taken in float32 (float64 stays: gradient
    checks), ``B`` and ``C`` in ``x``'s dtype on the kernel path."""
    from ..common.environment import Environment

    b, t, H, P = x.shape
    G, N = B.shape[2:]
    L = int(chunk or SSD_CHUNK)
    wide = jnp.promote_types(x.dtype, jnp.float32)
    fits = (supports_ssd_kernel(H, G, P, N, L, x.dtype.itemsize)
            and wide == jnp.float32)
    if interpret is None:
        kernel = (Environment.get().allow_pallas()
                  and jax.default_backend() == "tpu" and fits)
    else:
        kernel = (bool(interpret) and H % G == 0 and wide == jnp.float32
                  and (H // G) % _ssd_pack(H // G, P) == 0)
    OpProfiler.get().count("seq/ssd_kernel" if kernel else "seq/ssd_fallback")
    dt, A = dt.astype(wide), A.astype(wide)
    pad = -t % L
    if pad:
        widen = lambda a: jnp.pad(                          # noqa: E731
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        x, dt, B, C = widen(x), widen(dt), widen(B), widen(C)
    return _ssd(x, dt, A, B, C, L, kernel, bool(interpret))[:, :t]
