"""Fused Pallas weight-update kernels over ZeRO-1 flat buckets.

This module applies SGD / Nesterovs / Adam / AdamW to a ``Zero1Plan``
flat per-dtype bucket in ONE Pallas kernel launch: params, grads and
moments stream HBM→VMEM once, the whole update (including the
bf16-state + stochastic-rounding path of ``learning/precision.py``)
happens in registers, and the new params/moments stream back out.

Buckets are for SHARDED state only. Under ZeRO-1 the bucket is the unit
of the reduce-scatter and the all-gather, so the flatten is paid for by
the collective and this kernel updates a replica's 1/N slice. Where
params and state are whole on the device as trees (the ``fit`` steps,
dense ``ParallelWrapper``) nothing calls this module: the argument it
was written on — "a ResNet-50's ~160 leaves become hundreds of small
per-leaf kernels whose launch overhead the compiler does not fuse away"
— was never measured on a chip, and is false there. On a TPU v5e XLA
fuses each leaf's update in the leaf's own tiling (BERT-base's f32 Adam
over 201 leaves runs near the HBM roofline, 5.4 ms for 2.6 GB), while a
rank-4 → rank-1 reshape is a physical copy out of the (8,128) tiling:
the flatten/unflatten round trip around this kernel cost 46 ms of a
93 ms ResNet-50 step for an update of under 1 ms (PERF.md, PR 27).

Three execution modes, one shared math function (``_update_math`` — the
SAME jnp expressions as ``learning/updaters.py``, so fp32 results are
bit-identical to the per-leaf reference):

- ``"pallas"`` (TPU default): the real Mosaic-compiled kernel;
- ``"interpret"``: the same kernel through the Pallas interpreter (CPU
  test mesh — exactly the ``ops/pallas_attention.py`` fallback recipe);
- ``"xla"`` (non-TPU default): the shared math applied directly to the
  flat bucket — still ONE fused XLA elementwise kernel per bucket
  instead of hundreds of per-leaf ops, and bitwise-identical to the
  per-leaf reference (same expressions through the same compiler).

Cross-mode parity (xla vs interpret/pallas) is ulp-bounded, not bitwise:
the kernel body gets its own compile, and whether XLA fma-contracts a
``p - lr*g`` style mul-add there is environment-dependent (observed to
flip with the device-count flags alone) — tests pin the drift ≤2 ulp.
The production invariant is mode-local and strict: the ``xla`` mode (the
non-TPU hot path) is BITWISE-identical to the per-leaf fp32 reference,
and with ``state_dtype`` set every mode consumes the same SR bits.

Stochastic rounding draws ride the step's existing RNG stream: one
uint32 per element per bucket, generated OUTSIDE the kernel with
``jax.random.bits`` (identical bits in every mode — that is what makes
the modes mutually bitwise-comparable); Adam spends the low halfword on
``m`` and the high halfword on ``v``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common.profiler import OpProfiler
from ..learning.updaters import Adam, AdamW, Nesterovs, Sgd, _lr_at


BLOCK_ROWS = 256          # f32 rows of 128 lanes per grid program (~128KB
LANES = 128               # per buffer in VMEM; 8 buffers stay well inside)

# exact-type match: AdaMax/Nadam/AMSGrad subclass Adam with DIFFERENT
# apply() math — isinstance would silently run the wrong update
_KINDS = {Sgd: "sgd", Nesterovs: "nesterovs", Adam: "adam", AdamW: "adamw"}
_SLOTS = {"sgd": (), "nesterovs": ("v",), "adam": ("m", "v"),
          "adamw": ("m", "v")}
# analytic flops per element for the census's counted sub-executable
# entry (rough op counts of _update_math, SR excluded — order-of-
# magnitude roofline inputs, not a cycle model)
_FLOPS_PER_ELEM = {"sgd": 2, "nesterovs": 5, "adam": 12, "adamw": 14}


def supports_fused(updater) -> bool:
    """True when ``updater`` has a fused flat-bucket kernel (exact type:
    Sgd / Nesterovs / Adam / AdamW)."""
    return type(updater) in _KINDS


def _scalars(updater, kind: str, iteration) -> Tuple[Any, ...]:
    """Hyperparameter scalars as f32, computed with the SAME expressions
    as the per-leaf updaters (the f32 cast matches the implicit cast XLA
    inserts when a weak scalar meets the f32 tensors)."""
    f32 = lambda v: jnp.asarray(v, jnp.float32)  # noqa: E731
    lr = _lr_at(updater.learning_rate, iteration)
    if kind == "sgd":
        return (f32(lr),)
    if kind == "nesterovs":
        # (1+mu) precomputed in python (f64) then cast — the per-leaf
        # path's weak scalars round to f32 the same way; deriving it from
        # an f32 mu INSIDE the kernel can land one ulp off
        return f32(lr), f32(updater.momentum), f32(1.0 + updater.momentum)
    t = iteration + 1
    bc1 = 1 - updater.beta1 ** t
    bc2 = 1 - updater.beta2 ** t
    sc = [f32(lr), f32(updater.beta1), f32(updater.beta2),
          f32(updater.epsilon), f32(bc1), f32(bc2),
          f32(1 - updater.beta1), f32(1 - updater.beta2)]
    if kind == "adamw":
        sc.append(f32(updater.weight_decay))
    return tuple(sc)


def _update_math(kind: str, sc, p, g, slots: Dict[str, Any],
                 bits, sr_dtype):
    """The one update-math definition every mode traces. ``slots`` holds
    the stored moments (possibly low-precision); math runs in f32; when
    ``sr_dtype`` is set the new moments are stochastically rounded back
    down with ``bits`` (low halfword first slot, high halfword second)."""
    from ..learning.precision import halfword, stochastic_round

    up = lambda a: a.astype(jnp.float32)  # noqa: E731

    def down(a, which: int):
        if sr_dtype is None:
            return a
        return stochastic_round(a, halfword(bits, which), sr_dtype)

    if kind == "sgd":
        return p - sc[0] * g, {}
    if kind == "nesterovs":
        lr, mu, opmu = sc
        v = up(slots["v"])
        v_new = mu * v - lr * g
        p_new = p + (-mu * v + opmu * v_new)
        return p_new, {"v": down(v_new, 0)}
    lr, b1, b2, eps, bc1, bc2, omb1, omb2 = sc[:8]
    m, v = up(slots["m"]), up(slots["v"])
    m_new = b1 * m + omb1 * g
    v_new = b2 * v + omb2 * jnp.square(g)
    if kind == "adamw":
        step = lr * ((m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
                     + sc[8] * p)
    else:
        step = lr * (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    return p - step, {"m": down(m_new, 0), "v": down(v_new, 1)}


# --------------------------------------------------------------------------
# the kernel
# --------------------------------------------------------------------------

def _kernel(kind, slot_names, has_bits, sr_dtype, n_sc, sc_ref, *refs):
    n_in = 2 + len(slot_names) + (1 if has_bits else 0)
    ins, outs = refs[:n_in], refs[n_in:]
    p, g = ins[0][...], ins[1][...]
    slots = {name: ins[2 + i][...]
             for i, name in enumerate(slot_names)}
    bits = ins[2 + len(slot_names)][...] if has_bits else None
    sc = tuple(sc_ref[0, i] for i in range(n_sc))
    new_p, new_slots = _update_math(kind, sc, p, g, slots, bits, sr_dtype)
    outs[0][...] = new_p
    for i, name in enumerate(slot_names):
        outs[1 + i][...] = new_slots[name]


def _pad2d(a, tile: int):
    L = a.shape[0]
    pad = -(-L // tile) * tile - L
    if pad:
        a = jnp.concatenate([a, jnp.zeros((pad,), a.dtype)])
    return a.reshape(-1, LANES)


def _launch_kernel(kind, sc, p, g, slots, bits, sr_dtype, interpret):
    """One pallas_call over the whole (padded) flat bucket. Zero padding
    is self-consistent for every supported kind: g=0 and zero moments
    leave the padded tail of p exactly unchanged, and the caller slices
    it off anyway."""
    L = p.shape[0]
    tile = BLOCK_ROWS * LANES
    slot_names = _SLOTS[kind]
    sc_arr = jnp.zeros((1, LANES), jnp.float32).at[0, :len(sc)].set(
        jnp.stack(sc))
    tensors = [p, g] + [slots[n] for n in slot_names]
    if bits is not None:
        tensors.append(bits)
    tensors = [_pad2d(t, tile) for t in tensors]
    rows = tensors[0].shape[0]
    grid = (rows // BLOCK_ROWS,)
    blk = lambda: pl.BlockSpec((BLOCK_ROWS, LANES),  # noqa: E731
                               lambda i: (i, 0))
    state_dt = sr_dtype if sr_dtype is not None else (
        tensors[2].dtype if slot_names else None)
    out_shape = [jax.ShapeDtypeStruct(tensors[0].shape, p.dtype)]
    out_shape += [jax.ShapeDtypeStruct(tensors[0].shape, state_dt)
                  for _ in slot_names]
    kernel = functools.partial(_kernel, kind, slot_names, bits is not None,
                               sr_dtype, len(sc))
    with jax.enable_x64(False):
        outs = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((1, LANES), lambda i: (0, 0))]
            + [blk() for _ in tensors],
            out_specs=tuple(blk() for _ in out_shape),
            out_shape=tuple(out_shape),
            interpret=interpret,
        )(sc_arr, *tensors)
    new_p = outs[0].reshape(-1)[:L]
    new_slots = {n: outs[1 + i].reshape(-1)[:L]
                 for i, n in enumerate(slot_names)}
    return new_p, new_slots


# --------------------------------------------------------------------------
# public entry
# --------------------------------------------------------------------------

def default_mode() -> str:
    """``pallas`` on real TPUs, ``xla`` elsewhere (the interpret-mode
    kernel is for parity tests — running it on the CPU hot path would be
    a de-optimization, exactly like ops/pallas_attention's gate)."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def fused_apply(updater, flat_params: Dict[str, Any],
                flat_grads: Dict[str, Any], state: Dict[str, Any],
                iteration, key, mode: Optional[str] = None
                ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Apply ``updater`` to ``Zero1Plan`` flat buckets in one fused kernel
    per float32 bucket (non-f32 buckets take the same shared math as a
    plain XLA expression — f32 arithmetic with round-to-storage
    write-back, dtype-stable but tolerance-level vs a per-leaf updater
    whose weak scalars would have kept the math in the narrow dtype).

    ``flat_params``/``flat_grads``: ``{"flat::<dtype>": [L]}``;
    ``state``: ``{slot: {"flat::<dtype>": [L]}}`` in the same layout
    (shard- or full-length — the updaters are elementwise, so any slice
    works). Returns ``(new_flat_params, new_state)`` in the same layout.

    fp32 state: bitwise-identical to ``updater.apply`` on the same
    buckets (and hence to the per-leaf dense path — the flat layout is a
    pure permutation). ``state_dtype`` set: moments upcast in-register,
    f32 math, stochastic rounding on ``key``'s fold_in-derived stream —
    one uint32 draw per element per bucket, identical across modes.
    """
    from ..learning.precision import (SR_STREAM_TAG, random_bits_for,
                                      state_dtype_of)

    kind = _KINDS.get(type(updater))
    if kind is None:
        raise NotImplementedError(
            f"no fused kernel for {type(updater).__name__}; gate on "
            "supports_fused() and fall back to apply_updater")
    if mode is None:
        mode = default_mode()
    if mode not in ("pallas", "interpret", "xla"):
        raise ValueError(f"unknown fused-update mode {mode!r}")
    sd = state_dtype_of(updater)
    sr_dtype = jnp.dtype(sd) if sd else None
    if sr_dtype is not None and key is None:
        raise ValueError("state_dtype set but no RNG key threaded to "
                         "fused_apply")
    slot_names = _SLOTS[kind]
    sc = _scalars(updater, kind, iteration)
    prof = OpProfiler.get()
    new_flat: Dict[str, Any] = {}
    new_state: Dict[str, Dict[str, Any]] = {n: {} for n in slot_names}
    for bi, (bkey, p) in enumerate(sorted(flat_params.items())):
        g = flat_grads[bkey].astype(p.dtype) \
            if flat_grads[bkey].dtype != p.dtype else flat_grads[bkey]
        slots = {n: state[n][bkey] for n in slot_names}
        bits = None
        # slot_names gate: a stateless updater (Sgd) with state_dtype set
        # has nothing to round — don't pay threefry for unused bits
        if sr_dtype is not None and slot_names:
            sub = jax.random.fold_in(jax.random.fold_in(key, SR_STREAM_TAG),
                                     bi)
            bits = random_bits_for(sub, p.shape, len(slot_names))
        if mode != "xla" and p.dtype == jnp.float32:
            prof.count("precision/fused_buckets_pallas")
            np_, ns = _launch_kernel(kind, sc, p, g, slots, bits, sr_dtype,
                                     interpret=(mode == "interpret"))
        else:
            prof.count("precision/fused_buckets_xla")
            np_, ns = _update_math(kind, sc, p, g, slots, bits, sr_dtype)
            # dtype stability: the f32 scalar arrays widen a non-f32
            # bucket's math to f32 — write back in the stored dtypes so
            # the param pytree never flips dtype (which would retrace
            # the step). For f32 buckets these casts are no-ops.
            np_ = np_.astype(p.dtype)
            if sr_dtype is None:
                ns = {k: v.astype(slots[k].dtype) for k, v in ns.items()}
        prof.count("precision/fused_hits")
        new_flat[bkey] = np_
        for n in slot_names:
            new_state[n][bkey] = ns[n]
    # executable census, counted sub-executable: the fused kernels
    # dispatch INSIDE the parent step, so their cost rides the parent's
    # measured time — record analytic flops/bytes here at trace time
    # (once per parent compile, like the precision/* counters above)
    elems = sum(p.size for p in flat_params.values())
    nbytes = sum(3 * p.size * p.dtype.itemsize      # read p,g + write p
                 for p in flat_params.values())
    for n in slot_names:
        nbytes += sum(2 * v.size * v.dtype.itemsize  # read + write slots
                      for v in state[n].values())
    from ..common import xprof

    xprof.note_subexec("pallas/update_bucket",
                       flops=float(_FLOPS_PER_ELEM.get(kind, 4) * elems),
                       bytes_accessed=float(nbytes),
                       kind=kind, mode=mode,
                       buckets=len(flat_params))
    return new_flat, ({} if not slot_names else new_state)


def apply_flat_updater(updater, flat_params, flat_grads, state, iteration,
                       key, mode: Optional[str] = None):
    """The flat-bucket dispatch the ZeRO-1 step and the single-device
    fused path share: the fused kernel when the updater has one, else the
    generic elementwise updater on the buckets (through
    ``learning.precision.apply_updater`` so ``state_dtype`` still works).
    Fallbacks are ledgered (``precision/fused_fallbacks``)."""
    if supports_fused(updater):
        return fused_apply(updater, flat_params, flat_grads, state,
                           iteration, key, mode=mode)
    from ..learning.precision import apply_updater

    OpProfiler.get().count("precision/fused_fallbacks")
    return apply_updater(updater, flat_grads, state, flat_params, iteration,
                         key)
