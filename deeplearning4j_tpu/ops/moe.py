"""Routed experts: the router, the grouped matrix product, rotary positions.

``route_topk``: sigmoid scores over all experts, a selection bias that picks
and does not weigh, top-k, weights normalised over the selected, and the
count of tokens that selected each expert.

``grouped_matmul``: ``y[r] = x[r] @ w[g(r)]`` for rows sorted by group, the
group sizes a device array (``lax.ragged_dot``'s contract: groups lie one
after the other from row 0; rows beyond their total give zeros and take no
gradient). Two implementations:

- Pallas TPU kernels, all under the one name ``moe_gmm`` (a device trace
  keeps a kernel's name only, and a reader of the ten most expensive names
  sees the grouped products whole or not at all). The rows run in tiles of
  ``GMM_ROW_TILE``; a work item is a (group, row tile) pair, a tile that two
  groups share is one item for each, and the list of items is built from
  the sizes on the device and handed over as scalar prefetch. The grid is as
  long as the list can get (``M / tile + G - 1``) and items beyond the
  realised count are skipped, so the work follows the rows that were routed,
  not the buffer. The contraction is whole in one block: no accumulator
  across grid steps, and each group's matrix is fetched once a sweep; a
  tile that lies inside one group (most do) is stored without a mask. The
  input gradient is the same kernel over the matrices' other axis; the
  weight gradient accumulates a group's row tiles in float32 scratch and
  writes the group's matrix when the list moves to the next group;
- ``lax.ragged_dot``, differentiated by JAX: the path off the TPU and of
  widths off the 128-lane tiling (``supports_gmm_kernel``).

Which one ran is counted when the step is traced (``moe/gmm_kernel``,
``moe/gmm_fallback``).

``rotary_embedding``: rotary position embedding, the pairs a half apart
(rotate-half) or interleaved.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ..common.profiler import OpProfiler
from .registry import op

GMM_ROW_TILE = 256                      # rows a work item
_VMEM_LIMIT = 96 * 1024 * 1024          # of the v5e's 128 MiB
_RHS_BLOCK_BYTES = 16 * 1024 * 1024     # one group's matrix block, single
_ACC_BLOCK_BYTES = 8 * 1024 * 1024      # the weight gradient's float32 tile


# --- the router --------------------------------------------------------------


@op("route_topk", "nn")
def route_topk(x, w_gate, bias, k: int, scale: float = 1.0,
               norm_eps: float = 1e-6):
    """``x`` ``[N, d]``, ``w_gate`` ``[d, E]``, ``bias`` ``[E]`` ->
    ``(experts [N, k] int32, weights [N, k], load [E])``. Scores are
    ``sigmoid(x w_gate)`` in float32 with the product at ``highest``; the
    selection is ``top_k(scores + bias)``; a selected expert's weight is its
    score (the bias selects and is not in the weight) over the sum of the
    selected scores plus ``norm_eps``, times ``scale``; ``load[e]`` counts
    the tokens that selected expert ``e``. The gradient reaches ``x`` and
    ``w_gate`` through the selected scores."""
    f32 = jnp.promote_types(x.dtype, jnp.float32)
    scores = jax.nn.sigmoid(jnp.dot(x.astype(f32), w_gate.astype(f32),
                                    precision=lax.Precision.HIGHEST))
    _, experts = lax.top_k(scores + bias.astype(f32), k)
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    weights = picked / (jnp.sum(picked, -1, keepdims=True) + norm_eps) * scale
    # a comparison against every expert, not a scatter: E bins
    load = jnp.sum(experts[..., None] == jnp.arange(w_gate.shape[1]),
                   axis=(0, 1), dtype=jnp.float32)
    return experts.astype(jnp.int32), weights, load


# --- rotary positions --------------------------------------------------------


@op("rotary_embedding", "nn")
def rotary_embedding(x, positions, theta: float = 10000.0,
                     interleaved: bool = False):
    """Rotary position embedding over the whole last axis ``D`` (even) of
    ``x`` ``[..., T, D]`` at ``positions`` ``[T]``, ``inv_freq_i =
    theta^(-2i/D)``. Rotate-half (the default): the pair of frequency ``i``
    is ``(x_i, x_{i+D/2})``; ``cos`` and ``sin`` of ``positions * inv_freq``
    repeated over the two halves, ``x cos + rotate_half(x) sin`` with
    ``rotate_half([a, b]) = [-b, a]``. ``interleaved``: the pair is
    ``(x_{2i}, x_{2i+1})`` (``rope_interleave`` of the DeepSeek-V3 family):
    ``y_{2i} = x_{2i} cos_i - x_{2i+1} sin_i``, ``y_{2i+1} = x_{2i+1} cos_i +
    x_{2i} sin_i``, each element's partner fetched by a shift along the axis,
    so nothing is reshaped. Computed in float32 (float64 stays), returned in
    ``x``'s dtype."""
    d = x.shape[-1]
    wide = jnp.promote_types(x.dtype, jnp.float32)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=wide) / d))
    angles = positions.astype(wide)[:, None] * inv_freq[None, :]
    if interleaved:
        cos = jnp.repeat(jnp.cos(angles), 2, axis=-1)
        sin = jnp.repeat(jnp.sin(angles), 2, axis=-1)
        xw = x.astype(wide)
        partner = jnp.where(jnp.arange(d) % 2 == 0,
                            -jnp.roll(xw, -1, axis=-1),
                            jnp.roll(xw, 1, axis=-1))
        return (xw * cos + partner * sin).astype(x.dtype)
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    xw = x.astype(wide)
    a, b = xw[..., : d // 2], xw[..., d // 2:]
    return (xw * cos + jnp.concatenate([-b, a], axis=-1) * sin).astype(x.dtype)


# --- the grouped product: work items -----------------------------------------


def _work_items(group_sizes, m: int, tm: int):
    """The (group, row tile) pairs that hold rows, in order, as scalar
    arrays of the static length ``m // tm + G - 1``: ``(item_group,
    item_tile, starts, ends, count)``. Entries beyond ``count`` repeat the
    last real item (their grid steps fetch nothing new and are skipped)."""
    g = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // tm
    tiles = jnp.where(sizes > 0, (ends + tm - 1) // tm - first_tile, 0)
    first_item = jnp.cumsum(tiles) - tiles
    count = jnp.sum(tiles)
    length = m // tm + g - 1
    i = jnp.minimum(jnp.arange(length, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    # the group of item i: how many groups' items end at or before it
    item_group = jnp.minimum(
        jnp.sum(i[:, None] >= (first_item + tiles)[None, :], axis=1),
        g - 1).astype(jnp.int32)
    item_tile = (first_tile[item_group] + i - first_item[item_group])
    item_tile = jnp.clip(item_tile, 0, m // tm - 1).astype(jnp.int32)
    return (item_group, item_tile, starts.astype(jnp.int32),
            ends.astype(jnp.int32), count.reshape(1).astype(jnp.int32))


def _pick(n: int, most: int) -> int:
    """The largest divisor of ``n`` that is a multiple of 128 and at most
    ``most`` (``n`` itself where it fits)."""
    if n <= most:
        return n
    best = 128
    for t in range(128, most + 1, 128):
        if n % t == 0:
            best = t
    return best


def supports_gmm_kernel(k: int, n: int, itemsize: int) -> bool:
    """Both widths on the 128-lane tiling, and a 128-column block of a
    group's matrix (either way round) inside the block budget."""
    return (k % 128 == 0 and n % 128 == 0
            and max(k, n) * 128 * itemsize <= _RHS_BLOCK_BYTES)


def _compiler_params(semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


# --- the grouped product: kernels ---------------------------------------------


def _gmm_kernel(group_ref, tile_ref, start_ref, end_ref, count_ref,
                x_ref, w_ref, o_ref, *, tm: int, transposed: bool):
    i = pl.program_id(1)

    @pl.when(i < count_ref[0])
    def _():
        g, t = group_ref[i], tile_ref[i]
        dims = (((1,), (1,)), ((), ())) if transposed else (((1,), (0,)), ((), ()))
        acc = lax.dot_general(x_ref[...], w_ref[...], dims,
                              preferred_element_type=jnp.float32)
        # most tiles lie inside one group and are stored as they are
        whole = (start_ref[g] <= t * tm) & (end_ref[g] >= (t + 1) * tm)
        # a shared tile's first item clears the rows that are not its
        # group's (the block arrives uninitialised); a later group's item
        # keeps them
        first = (i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != t)

        def mine():
            rows = t * tm + lax.broadcasted_iota(jnp.int32, acc.shape, 0)
            return (rows >= start_ref[g]) & (rows < end_ref[g])

        @pl.when(whole)
        def _():
            o_ref[...] = acc.astype(o_ref.dtype)

        @pl.when(jnp.logical_not(whole) & first)
        def _():
            o_ref[...] = jnp.where(mine(), acc, 0.0).astype(o_ref.dtype)

        @pl.when(jnp.logical_not(whole | first))
        def _():
            o_ref[...] = jnp.where(mine(), acc.astype(o_ref.dtype), o_ref[...])


def _gmm_pallas(x, w, items, tm: int, transposed: bool, interpret: bool):
    """``x`` ``[M, C]``; ``w`` ``[G, C, O]``, or ``[G, O, C]`` where
    ``transposed``; -> ``[M, O]`` with the rows of no group's tile left as
    they come (the caller clears rows beyond the total)."""
    from jax.experimental.pallas import tpu as pltpu

    m, c = x.shape
    o = w.shape[1] if transposed else w.shape[2]
    to = _pick(o, max(128, _RHS_BLOCK_BYTES // (c * w.dtype.itemsize)
                      // 128 * 128))
    length = items[0].shape[0]
    if transposed:
        w_spec = pl.BlockSpec((None, to, c),
                              lambda j, i, grp, *_: (grp[i], j, 0))
    else:
        w_spec = pl.BlockSpec((None, c, to),
                              lambda j, i, grp, *_: (grp[i], 0, j))
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_gmm_kernel, tm=tm, transposed=transposed),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5, grid=(o // to, length),
                in_specs=[pl.BlockSpec((tm, c),
                                       lambda j, i, grp, tile, *_: (tile[i], 0)),
                          w_spec],
                out_specs=pl.BlockSpec(
                    (tm, to), lambda j, i, grp, tile, *_: (tile[i], j))),
            out_shape=jax.ShapeDtypeStruct((m, o), x.dtype),
            compiler_params=None if interpret else _compiler_params(
                ("parallel", "arbitrary")),
            interpret=interpret, name="moe_gmm",
        )(*items, x, w)


def _tgmm_kernel(group_ref, tile_ref, start_ref, end_ref, count_ref,
                 x_ref, dy_ref, o_ref, acc, *, tm: int):
    i = pl.program_id(1)
    last = pl.num_programs(1) - 1
    g = group_ref[i]
    active = i < count_ref[0]

    @pl.when(active & ((i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != g)))
    def _():
        acc[...] = jnp.zeros_like(acc)

    t = tile_ref[i]
    whole = (start_ref[g] <= t * tm) & (end_ref[g] >= (t + 1) * tm)

    def add(xs):
        acc[...] += lax.dot_general(xs, dy_ref[...], (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(active & whole)
    def _():
        add(x_ref[...])

    @pl.when(active & jnp.logical_not(whole))
    def _():
        rows = t * tm + lax.broadcasted_iota(jnp.int32, x_ref.shape, 0)
        mine = (rows >= start_ref[g]) & (rows < end_ref[g])
        add(jnp.where(mine, x_ref[...], jnp.zeros_like(x_ref)))

    @pl.when(active & ((i == count_ref[0] - 1)
                       | (group_ref[jnp.minimum(i + 1, last)] != g)))
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _tgmm_pallas(x, dy, items, groups: int, tm: int, dtype, interpret: bool):
    """``x`` ``[M, C]``, ``dy`` ``[M, O]`` -> ``[G, C, O]``: each group's
    ``x^T dy`` over its own rows; an empty group's matrix is left as it
    comes (the caller clears it)."""
    from jax.experimental.pallas import tpu as pltpu

    m, c = x.shape
    o = dy.shape[1]
    to = _pick(o, max(128, _ACC_BLOCK_BYTES // (c * 4) // 128 * 128))
    length = items[0].shape[0]
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_tgmm_kernel, tm=tm),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5, grid=(o // to, length),
                in_specs=[pl.BlockSpec(
                    (tm, c), lambda j, i, grp, tile, *_: (tile[i], 0)),
                    pl.BlockSpec(
                        (tm, to), lambda j, i, grp, tile, *_: (tile[i], j))],
                out_specs=pl.BlockSpec(
                    (None, c, to), lambda j, i, grp, *_: (grp[i], 0, j)),
                scratch_shapes=[pltpu.VMEM((c, to), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((groups, c, o), dtype),
            compiler_params=None if interpret else _compiler_params(
                ("parallel", "arbitrary")),
            interpret=interpret, name="moe_gmm",
        )(*items, x, dy)


# --- the grouped product: one op ----------------------------------------------


def _rows_below(total, a):
    """``a`` with the rows from ``total`` on cleared."""
    keep = lax.broadcasted_iota(jnp.int32, (a.shape[0], 1), 0) < total
    return jnp.where(keep, a, jnp.zeros_like(a))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(x, w, group_sizes, tm, interpret):
    return _gmm_fwd(x, w, group_sizes, tm, interpret)[0]


def _gmm_fwd(x, w, group_sizes, tm, interpret):
    items = _work_items(group_sizes, x.shape[0], tm)
    y = _gmm_pallas(x, w, items, tm, False, interpret)
    return _rows_below(items[3][-1], y), (x, w, group_sizes, items)


def _gmm_bwd(tm, interpret, res, dy):
    x, w, group_sizes, items = res
    dy = dy.astype(x.dtype)
    dx = _rows_below(items[3][-1],
                     _gmm_pallas(dy, w, items, tm, True, interpret))
    dw = _tgmm_pallas(x, dy, items, w.shape[0], tm, w.dtype, interpret)
    dw = jnp.where((group_sizes > 0)[:, None, None], dw, jnp.zeros_like(dw))
    return dx, dw, np.zeros(group_sizes.shape, jax.dtypes.float0)


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


@op("grouped_matmul", "nn")
def grouped_matmul(x, w, group_sizes, row_tile: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """``x`` ``[M, K]`` with its rows sorted by group, ``w`` ``[G, K, N]``,
    ``group_sizes`` ``[G]`` integers whose total is at most ``M`` ->
    ``[M, N]`` in ``x``'s dtype: row ``r`` of group ``g`` gives ``x[r] @
    w[g]``; rows beyond the total give zeros. Products take the operands as
    they come and accumulate in float32. ``row_tile``: rows a work item
    (``GMM_ROW_TILE``, shrunk to ``M``); an ``M`` it does not divide is
    padded. ``interpret`` as in ``ops.ssm.selective_scan``."""
    from ..common.environment import Environment

    m, k = x.shape
    n = w.shape[2]
    fits = (supports_gmm_kernel(k, n, x.dtype.itemsize)
            and x.dtype == w.dtype and x.dtype.itemsize <= 4)
    if interpret is None:
        kernel = (Environment.get().allow_pallas()
                  and jax.default_backend() == "tpu" and fits)
    else:
        kernel = bool(interpret) and fits
    OpProfiler.get().count("moe/gmm_kernel" if kernel else "moe/gmm_fallback")
    group_sizes = group_sizes.astype(jnp.int32)
    if not kernel:
        return lax.ragged_dot(x, w, group_sizes)
    tm = min(int(row_tile or GMM_ROW_TILE), -(-m // 8) * 8)
    pad = -m % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return _gmm(x, w, group_sizes, tm, bool(interpret))[:m]
