#!/usr/bin/env python3
"""A first number for a row gather whose cost follows the rows (PR 37, for
``ROADMAP.md`` S13's ``moe_dispatch`` / ``moe_combine``): measurement only,
nothing a cell runs.

    chiprun -- python3 tools/row_gather_bench.py

On the chip it times, ns a gathered row, these ways of taking ``R`` rows
(8,192, 16,384, 36,864) of a ``[16384, 2048]`` bfloat16 matrix by an index
vector:

- ``xla``: ``table[idx]``, XLA's gather; also at ``R`` = 131,072, the
  dispatch buffer of ``joyai_llm_flash.train_b2_s8k`` (6.5 ns a row there
  and 23 / 11 / 6.8 at the three smaller counts, PR 37: a floor of ≈190 µs
  a call, the 64 MB matrix read once);
- ``dma_rows_vmem``: a Pallas kernel over ``[256, 2048]`` output tiles, the
  indices scalar-prefetched, one DMA a row from the matrix in HBM into the
  output's VMEM block (the pipeline writes the block back), all 256 in
  flight before the first wait; ``dma_rows_hbm``: the output left in HBM, a
  row's DMA going HBM to HBM. The kernels take the matrix as uint32 pairs
  (``[16384, 1024]``: a bfloat16 row is half a sublane of its (16, 128)
  tile, a 32-bit row a whole one) — and Mosaic still refuses both (PR 37, on
  the chip and compiled here for a described v5e): a DMA's slice of a tiled
  array has to be whole (8, 128) tiles, so a one-row slice does not lower;
- ``dma_flat_vmem`` / ``dma_flat_hbm``: the same two kernels over the matrix
  and the result as ONE-dimensional arrays, where a 32-bit tile is 1,024
  words — exactly one 2048-wide bfloat16 row — so a row is a whole tile and
  the DMA lowers (PR 37: 19-25 ns a row HBM to HBM, 32-34 into the VMEM
  block). The price is the layout: flattening ``[n, 1024]`` is a
  relayout on the chip (done here outside the timed call), and whoever
  consumes the rows has to take them flat or pay for another.

A call is timed as 30 back-to-back dispatches with one fence at the end
(the device's queue stays full: a call is 0.1-2 ms), the best of five such
batches. Off the TPU it runs one tiny case in interpret mode and prints no
time.
"""

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 256


def _kernel(idx_ref, table_ref, out_ref, sem, *, width: int, to_hbm: bool):
    """``width`` 0: the matrix and the result are ``[rows, words]`` and a
    row's DMA is a one-row slice of each. Else both are flat and a row is
    ``width`` words from ``index * width`` on."""
    base = pl.program_id(0) * TILE

    def copy(r):
        src, dst = idx_ref[base + r], (base + r if to_hbm else r)
        if width:
            src, dst = (pl.ds(pl.multiple_of(src * width, width), width),
                        pl.ds(pl.multiple_of(dst * width, width), width))
        else:
            src, dst = pl.ds(src, 1), pl.ds(dst, 1)
        return pltpu.make_async_copy(table_ref.at[src], out_ref.at[dst], sem)

    def start(r, _):
        copy(r).start()
        return _

    def wait(r, _):
        copy(r).wait()
        return _

    lax.fori_loop(0, TILE, start, 0)
    lax.fori_loop(0, TILE, wait, 0)


def dma_gather(table, idx, words: int, to_hbm: bool, interpret: bool = False):
    """``table`` uint32, ``[n, words]`` or flat ``[n * words]`` -> the rows
    ``idx`` in the same form."""
    rows, flat = idx.shape[0], table.ndim == 1
    shape = (rows * words,) if flat else (rows, words)
    if to_hbm:
        out_spec = pl.BlockSpec(memory_space=pl.ANY)
    elif flat:
        out_spec = pl.BlockSpec((TILE * words,), lambda i, idx: (i,))
    else:
        out_spec = pl.BlockSpec((TILE, words), lambda i, idx: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, width=words if flat else 0, to_hbm=to_hbm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // TILE,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=out_spec,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(shape, table.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="row_gather",
    )(idx, table)


def as_words(table):
    """bfloat16 ``[n, d]`` -> uint32 ``[n, d // 2]``, the same bytes."""
    n, d = table.shape
    return lax.bitcast_convert_type(table.reshape(n, d // 2, 2), jnp.uint32)


def seconds_a_call(fn, *args, calls=30, batches=5):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(batches):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return best


def main() -> None:
    on_chip = jax.default_backend() == "tpu"
    n, d = (16384, 2048) if on_chip else (64, 256)
    counts = (8192, 16384, 36864) if on_chip else (256,)
    rng = np.random.RandomState(0)
    table = jnp.asarray(rng.randn(n, d), jnp.bfloat16)
    words = jax.jit(as_words)(table)
    flat = jax.jit(lambda w: w.reshape(-1))(words)     # a relayout, not timed
    kernel = functools.partial(dma_gather, words=d // 2,
                               interpret=not on_chip)
    ways = {
        "xla": (jax.jit(lambda t, i: t[i]), table),
        "dma_rows_vmem": (jax.jit(functools.partial(kernel, to_hbm=False)),
                          words),
        "dma_rows_hbm": (jax.jit(functools.partial(kernel, to_hbm=True)),
                         words),
        "dma_flat_vmem": (jax.jit(functools.partial(kernel, to_hbm=False)),
                          flat),
        "dma_flat_hbm": (jax.jit(functools.partial(kernel, to_hbm=True)),
                         flat),
    }
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "table": [n, d], "tile": TILE}))
    for rows in counts + ((131072,) if on_chip else ()):
        idx = jnp.asarray(rng.randint(0, n, rows), jnp.int32)
        want = np.asarray(jax.jit(as_words)(table[idx]))
        for name, (fn, operand) in ways.items():
            if rows == 131072 and name != "xla":
                continue
            line = {"way": name, "rows": rows}
            try:
                got = fn(operand, idx)
                got = np.asarray(jax.jit(as_words)(got) if name == "xla"
                                 else got.reshape(rows, d // 2))
                line["equal"] = bool(np.array_equal(got, want))
                if on_chip:
                    s = seconds_a_call(fn, operand, idx)
                    line["us_a_call"] = round(s * 1e6, 2)
                    line["ns_a_row"] = round(s * 1e9 / rows, 3)
                    line["gb_s"] = round(2 * rows * d * 2 / s / 1e9, 1)
            except Exception as e:      # noqa: BLE001 - a way the compiler
                line["error"] = repr(e)[:400]   # refuses is a finding too
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
