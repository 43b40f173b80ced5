"""Routed experts: the router, the grouped matrix product, the experts' gated
MLP over it, rotary positions.

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

``grouped_gated_mlp``: an expert's whole gated MLP over the dispatch buffer,
``(silu(h[:, :ff]) * h[:, ff:]) @ w2[g]`` with ``h = rows @ w1[g]``, as ONE op
with its own gradient — what ``RoutedExpertsLayer`` calls. On the TPU it is
six launches of the kernels above (two forward, four backward), still named
``moe_gmm`` and driven by one list of work items: the second product takes a
tile of ``h`` and activates it on the way in; the backward's first kernel
forms the activation's cotangent in fast memory, applies the activation's
derivative to it beside the same tile of ``h`` and writes ``d_h``; the weight
gradient of ``w2`` activates its tile of ``h`` likewise. So neither the
activation ``[M, ff]`` nor its cotangent exists in device memory, no XLA
fusion touches a buffer-sized array between the dispatch gather and the
combine gather, and the rows at or beyond the routed total are **not
defined** there (no kernel writes their tiles; every consumer masks by its
group's rows or gathers a live pair's row). Elsewhere the op is two
``grouped_matmul`` calls around ``jnp``'s activation. Counted as
``moe/gated_kernel`` / ``moe/gated_fallback``, and its two products as
``grouped_matmul``'s.

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


def _silu_mul(h):
    """A tile ``[tm, 2 ff]`` of the first product, gate beside up ->
    ``silu(gate) * up`` ``[tm, ff]`` in float32."""
    ff = h.shape[1] // 2
    gate = h[:, :ff].astype(jnp.float32)
    return h[:, ff:].astype(jnp.float32) * (gate * jax.nn.sigmoid(gate))


def _gmm_kernel(group_ref, tile_ref, start_ref, end_ref, count_ref,
                x_ref, w_ref, *refs, tm: int, transposed: bool, gated: bool):
    """One work item's product. ``gated`` and not ``transposed``: ``x`` is a
    tile of the first product and goes through ``silu * mul`` on its way in
    (float32, rounded once to the operands' dtype). ``gated`` and
    ``transposed``: the product is the activation's cotangent, ``refs[0]``
    is the same tile of the first product, and what is stored is that
    product's cotangent ``[tm, 2 ff]``, never the activation's."""
    o_ref = refs[-1]
    i = pl.program_id(1)

    @pl.when(i < count_ref[0])
    def _():
        g, t = group_ref[i], tile_ref[i]
        dims = (((1,), (1,)), ((), ())) if transposed else (((1,), (0,)), ((), ()))
        x = x_ref[...]
        if gated and not transposed:
            x = _silu_mul(x).astype(x.dtype)
        acc = lax.dot_general(x, w_ref[...], dims,
                              preferred_element_type=jnp.float32)
        # most tiles lie inside one group and are stored as they are
        whole = (start_ref[g] <= t * tm) & (end_ref[g] >= (t + 1) * tm)
        # a shared tile's first item clears the rows that are not its
        # group's (the block arrives uninitialised); a later group's item
        # keeps them
        first = (i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != t)

        def put(cols, val):
            def mine():
                rows = t * tm + lax.broadcasted_iota(jnp.int32, val.shape, 0)
                return (rows >= start_ref[g]) & (rows < end_ref[g])

            @pl.when(whole)
            def _():
                o_ref[:, cols] = val.astype(o_ref.dtype)

            @pl.when(jnp.logical_not(whole) & first)
            def _():
                o_ref[:, cols] = jnp.where(mine(), val, 0.0).astype(o_ref.dtype)

            @pl.when(jnp.logical_not(whole | first))
            def _():
                o_ref[:, cols] = jnp.where(mine(), val.astype(o_ref.dtype),
                                           o_ref[:, cols])

        if gated and transposed:
            ff = acc.shape[1]
            gate = refs[0][:, :ff].astype(jnp.float32)
            s = jax.nn.sigmoid(gate)
            put(slice(0, ff), acc * refs[0][:, ff:].astype(jnp.float32)
                * (s * (1.0 + gate * (1.0 - s))))
            put(slice(ff, 2 * ff), acc * (gate * s))
        else:
            put(slice(None), acc)


def _gmm_pallas(x, w, items, tm: int, transposed: bool, interpret: bool,
                h=None, gated: bool = False):
    """``x`` ``[M, C]``; ``w`` ``[G, C, O]``, or ``[G, O, C]`` where
    ``transposed``; -> ``[M, O]``. The tiles that hold no group's rows are
    never written: rows from the routed total's tile on are NOT DEFINED (the
    boundary tile's rows beyond the total are cleared). ``gated``: ``x`` is
    the first product ``[M, 2 C]`` and ``silu(gate) * up`` of its tile is
    what is multiplied. ``h`` (with ``transposed``): the first product
    ``[M, 2 O]``; the result is its cotangent ``[M, 2 O]``, the activation's
    gradient applied to ``x @ w[g]^T`` before anything is stored, and a
    group's whole matrix is one block."""
    from jax.experimental.pallas import tpu as pltpu

    m, c = x.shape[0], w.shape[2 if transposed else 1]
    o = w.shape[1] if transposed else w.shape[2]
    to = o if h is not None else _pick(
        o, max(128, _RHS_BLOCK_BYTES // (c * w.dtype.itemsize) // 128 * 128))
    wide, block = (o, to) if h is None else (h.shape[1], h.shape[1])
    length = items[0].shape[0]
    by_tile = lambda j, i, grp, tile, *_: (tile[i], 0)      # noqa: E731
    if transposed:
        w_spec = pl.BlockSpec((None, to, c),
                              lambda j, i, grp, *_: (grp[i], j, 0))
    else:
        w_spec = pl.BlockSpec((None, c, to),
                              lambda j, i, grp, *_: (grp[i], 0, j))
    in_specs = [pl.BlockSpec((tm, x.shape[1]), by_tile), w_spec]
    if h is not None:
        in_specs.append(pl.BlockSpec((tm, wide), by_tile))
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_gmm_kernel, tm=tm, transposed=transposed,
                              gated=gated or h is not None),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5, grid=(o // to, length),
                in_specs=in_specs,
                out_specs=pl.BlockSpec(
                    (tm, block), lambda j, i, grp, tile, *_: (tile[i], j))),
            out_shape=jax.ShapeDtypeStruct((m, wide), x.dtype),
            compiler_params=None if interpret else _compiler_params(
                ("parallel", "arbitrary")),
            interpret=interpret, name="moe_gmm",
        )(*items, x, w, *(() if h is None else (h,)))


def _tgmm_kernel(group_ref, tile_ref, start_ref, end_ref, count_ref,
                 x_ref, dy_ref, o_ref, acc, *, tm: int, gated: bool):
    i = pl.program_id(1)
    last = pl.num_programs(1) - 1
    g = group_ref[i]
    active = i < count_ref[0]

    @pl.when(active & ((i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != g)))
    def _():
        acc[...] = jnp.zeros_like(acc)

    t = tile_ref[i]
    whole = (start_ref[g] <= t * tm) & (end_ref[g] >= (t + 1) * tm)

    def rows_in():      # ``gated``: the first product's tile, activated
        x = x_ref[...]
        return _silu_mul(x).astype(x.dtype) if gated else x

    def add(xs, dys):
        acc[...] += lax.dot_general(xs, dys, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(active & whole)
    def _():
        add(rows_in(), dy_ref[...])

    @pl.when(active & jnp.logical_not(whole))
    def _():
        # both operands: a row of another group, or beyond the total, may
        # hold anything on either side
        rows = t * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (rows >= start_ref[g]) & (rows < end_ref[g])
        xs = rows_in()
        add(jnp.where(mine, xs, jnp.zeros_like(xs)),
            jnp.where(mine, dy_ref[...], jnp.zeros_like(dy_ref)))

    @pl.when(active & ((i == count_ref[0] - 1)
                       | (group_ref[jnp.minimum(i + 1, last)] != g)))
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _tgmm_pallas(x, dy, items, groups: int, tm: int, dtype, interpret: bool,
                 gated: bool = False):
    """``x`` ``[M, C]``, ``dy`` ``[M, O]`` -> ``[G, C, O]``: each group's
    ``x^T dy`` over its own rows; an empty group's matrix is left as it
    comes (the caller clears it). ``gated``: ``x`` is the first product
    ``[M, 2 C]`` and ``silu(gate) * up`` of its tile is what is multiplied."""
    from jax.experimental.pallas import tpu as pltpu

    m, c = x.shape[0], x.shape[1] // (2 if gated else 1)
    o = dy.shape[1]
    to = _pick(o, max(128, _ACC_BLOCK_BYTES // (c * 4) // 128 * 128))
    length = items[0].shape[0]
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_tgmm_kernel, tm=tm, gated=gated),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5, grid=(o // to, length),
                in_specs=[pl.BlockSpec(
                    (tm, x.shape[1]),
                    lambda j, i, grp, tile, *_: (tile[i], 0)),
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


def _on_kernels(fits: bool, interpret: Optional[bool]) -> bool:
    """Whether an op takes its Pallas kernels: where they fit, on the TPU by
    default, anywhere when asked for in interpret mode."""
    from ..common.environment import Environment

    if interpret is None:
        return (Environment.get().allow_pallas()
                and jax.default_backend() == "tpu" and fits)
    return bool(interpret) and fits


def _row_tiled(x, row_tile: Optional[int]):
    """``(x padded to whole row tiles, the tile)``: ``row_tile`` or
    ``GMM_ROW_TILE``, shrunk to the rows there are."""
    m = x.shape[0]
    tm = min(int(row_tile or GMM_ROW_TILE), -(-m // 8) * 8)
    pad = -m % tm
    return (jnp.pad(x, ((0, pad), (0, 0))) if pad else x), tm


def _held_only(group_sizes, dw):
    """``dw`` with the matrices of the empty groups cleared (the weight
    gradient's kernel never visits them)."""
    return jnp.where((group_sizes > 0)[:, None, None], dw, jnp.zeros_like(dw))


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
    return (dx, _held_only(group_sizes, dw),
            np.zeros(group_sizes.shape, jax.dtypes.float0))


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
    m, k = x.shape
    kernel = _on_kernels(
        supports_gmm_kernel(k, w.shape[2], x.dtype.itemsize)
        and x.dtype == w.dtype and x.dtype.itemsize <= 4, interpret)
    OpProfiler.get().count("moe/gmm_kernel" if kernel else "moe/gmm_fallback")
    group_sizes = group_sizes.astype(jnp.int32)
    if not kernel:
        return lax.ragged_dot(x, w, group_sizes)
    x, tm = _row_tiled(x, row_tile)
    return _gmm(x, w, group_sizes, tm, bool(interpret))[:m]


# --- the gated MLP of the routed experts: one op ---------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gated(rows, w1, w2, group_sizes, tm, interpret):
    return _gated_fwd(rows, w1, w2, group_sizes, tm, interpret)[0]


def _gated_fwd(rows, w1, w2, group_sizes, tm, interpret):
    items = _work_items(group_sizes, rows.shape[0], tm)
    h = _gmm_pallas(rows, w1, items, tm, False, interpret)
    out = _gmm_pallas(h, w2, items, tm, False, interpret, gated=True)
    return out, (rows, h, w1, w2, group_sizes, items)


def _gated_bwd(tm, interpret, res, d_out):
    rows, h, w1, w2, group_sizes, items = res
    d_out = d_out.astype(rows.dtype)
    d_h = _gmm_pallas(d_out, w2, items, tm, True, interpret, h=h)
    d_w2 = _tgmm_pallas(h, d_out, items, w2.shape[0], tm, w2.dtype,
                        interpret, gated=True)
    d_rows = _gmm_pallas(d_h, w1, items, tm, True, interpret)
    d_w1 = _tgmm_pallas(rows, d_h, items, w1.shape[0], tm, w1.dtype,
                        interpret)
    return (d_rows, _held_only(group_sizes, d_w1),
            _held_only(group_sizes, d_w2),
            np.zeros(group_sizes.shape, jax.dtypes.float0))


_gated.defvjp(_gated_fwd, _gated_bwd)


def supports_gated_kernel(d: int, ff: int, itemsize: int) -> bool:
    """``supports_gmm_kernel`` on both products' widths, and a group's whole
    second matrix in one block (the kernel that turns the output's cotangent
    into the first product's holds it so, a gate column beside its up
    column)."""
    return (supports_gmm_kernel(d, 2 * ff, itemsize)
            and supports_gmm_kernel(ff, d, itemsize)
            and ff * d * itemsize <= _RHS_BLOCK_BYTES)


@op("grouped_gated_mlp", "nn")
def grouped_gated_mlp(rows, w1, w2, group_sizes,
                      row_tile: Optional[int] = None,
                      interpret: Optional[bool] = None):
    """The routed experts' gated MLP over a dispatch buffer: ``rows`` ``[M,
    d]`` sorted by group, ``w1`` ``[G, d, 2 ff]`` (gate columns, then up
    columns), ``w2`` ``[G, ff, d]``, ``group_sizes`` ``[G]`` integers whose
    total is at most ``M`` -> ``[M, d]`` in ``rows``' dtype: row ``r`` of
    group ``g`` gives ``(silu(h[:ff]) * h[ff:]) @ w2[g]`` with ``h = rows[r]
    @ w1[g]``, ``h`` in ``rows``' dtype, the activation in float32 rounded
    once, products accumulated in float32.

    On the kernel path (the TPU, ``supports_gated_kernel``) **the rows at or
    beyond the total are not defined**, in the result and in the gradient to
    ``rows`` alike: the kernels run over the tiles that hold routed rows and
    write no other, so whoever reads the result indexes rows below the
    total (``RoutedExpertsLayer`` gathers by each live pair's row). One list
    of work items serves the forward's two kernels and the backward's four;
    the residuals are ``rows``, ``h``, the matrices and the list: the
    activation ``[M, ff]`` and its cotangent never exist in device memory.
    Elsewhere: two ``grouped_matmul`` calls around ``jnp``'s activation
    (zeros beyond the total). Which one ran is counted (``moe/gated_kernel``
    / ``moe/gated_fallback``), and the two products count as
    ``grouped_matmul``'s do. ``row_tile`` and ``interpret`` as there."""
    m, d = rows.shape
    kernel = _on_kernels(
        supports_gated_kernel(d, w2.shape[1], rows.dtype.itemsize)
        and rows.dtype == w1.dtype == w2.dtype and rows.dtype.itemsize <= 4,
        interpret)
    prof = OpProfiler.get()
    prof.count("moe/gated_kernel" if kernel else "moe/gated_fallback")
    if not kernel:
        g, u = jnp.split(
            grouped_matmul(rows, w1, group_sizes, row_tile, interpret), 2,
            axis=-1)
        return grouped_matmul(u * jax.nn.silu(g), w2, group_sizes, row_tile,
                              interpret)
    prof.count("moe/gmm_kernel", 2)
    rows, tm = _row_tiled(rows, row_tile)
    return _gated(rows, w1, w2, group_sizes.astype(jnp.int32), tm,
                  bool(interpret))[:m]
