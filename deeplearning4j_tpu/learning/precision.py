"""Mixed-precision policy for the training hot path.

One module owns every dtype-boundary rule in the stack (the fp32-boundary
doc the serving tier and the trainer used to state separately):

- **Compute** may run in bfloat16 (``GlobalConf.compute_dtype``): params and
  activations cast down for the MXU, loss head and reductions in float32,
  gradients flow back to fp32 master params. (Implemented by the models;
  this module is the shared cast helper.)
- **Inference params** may be served in bfloat16
  (``ServingEngine.Builder.bf16``): one cast at startup, float32 at the API
  boundary. :func:`cast_floating` here is THE cast both sides use.
- **Updater state** may be *stored* in bfloat16
  (``updater.state_dtype = "bfloat16"``): moments live in bf16 (half the
  optimizer HBM; under ZeRO-1 half of the already-1/N per-replica
  footprint), the update math still runs in float32 (:func:`apply_updater`
  upcasts, applies the untouched fp32 updater, and writes the new moments
  back down with **stochastic rounding** driven by the step's existing RNG
  stream), so the parameter update itself never sees bf16 arithmetic.

Why stochastic rounding: deterministic round-to-nearest of a bf16
accumulator loses every increment smaller than ~2^-8 of the stored value —
an EMA like Adam's second moment simply stops moving once
``(1-beta2)*g^2`` drops below the rounding ulp. Rounding *stochastically*
(up with probability proportional to the dropped fraction) makes the
stored moment an unbiased estimator of the fp32 one: E[SR(x)] == x, so
the error is zero-mean noise instead of a systematic stall
(tests/test_precision.py pins the unbiasedness).

Where the bits come from: a rounding consumes 16 random bits, and a draw
is one ``threefry2x32`` block — 20 rounds on the vector unit for two
32-bit words, four halfwords — on the step's key
(``fold_in(step key, SR_STREAM_TAG)``, then ``fold_in(.., i)`` for
parameter leaf ``i``; the dropout stream is never touched). State whose
slots mirror the parameter tree (every built-in updater's) runs ONE block
per parameter element (:func:`threefry_words` says what its counter is)
and hands its halfwords to the slots in the order of their sorted names:
word 0 low, word 0 high, word 1 low, word 1 high (:func:`slot_bits`;
AdamW: ``m`` low, ``v`` high, word 1 unused). Any other state draws per
state leaf. The profiler's ``precision/sr_blocks`` (blocks baked into the
step), ``precision/sr_elements`` (stored elements rounded) and
``precision/sr_draws`` (uint32 words the generator returned) say what a
compiled step pays: ``16·sr_elements / (64·sr_blocks)`` is the share of
generated bits that are used (0.5 for two slots, 0.25 for one).

Documented numerics envelope (pinned by tests and the ``mfu-smoke``
bench): with ``state_dtype="bfloat16"`` the per-step training loss tracks
the fp32-state run within ``|Δ| <= 1e-3 + 0.05 * |loss|`` over the smoke
horizon. Parameters stay fp32; their trajectories accumulate the
zero-mean rounding noise and so wander apart chaotically rather than
tracking element-wise — measured ≲1e-2 absolute over the smoke horizon,
gated as gross-divergence-only (``0.01 + 0.1*|p|``). The fp32-state path
is bit-identical to the per-leaf reference — ``state_dtype=None``
changes NOTHING.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend.random import threefry2x32_p

from ..common.profiler import OpProfiler

Pytree = Any

# fold_in tags deriving the stochastic-rounding stream from the step key —
# distinct from the dropout splits (which use jax.random.split) and from
# each other, so no RNG draw is ever consumed twice
SR_STREAM_TAG = 0x5AD0


def _is_floating(leaf) -> bool:
    return hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                     jnp.floating)


def cast_floating(tree: Pytree, dtype) -> Pytree:
    """Cast every floating leaf of ``tree`` to ``dtype`` (round-to-nearest),
    leaving integer/bool leaves untouched. THE shared fp32-boundary cast:
    serving's bf16 inference params and the trainer's updater-state
    up/down casts all route through here."""
    dt = jnp.dtype(dtype)
    return jax.tree.map(
        lambda a: jnp.asarray(a, dt) if _is_floating(a) else a, tree)


def stochastic_round(x, rbits, dtype=jnp.bfloat16):
    """float32 ``x`` → ``dtype`` (bfloat16) with stochastic rounding.

    ``rbits``: uint32 random bits, same shape as ``x`` — only the LOW 16
    bits are consumed (callers holding one uint32 draw per element can
    spend the high halfword on a second tensor; see
    :func:`ops.pallas_update.fused_apply`).

    Mechanics: bf16 is the top 16 bits of the fp32 pattern, and for a
    fixed exponent the 2^16 droppable mantissa patterns are equidistant —
    adding a uniform 16-bit integer to the fp32 bits and truncating
    therefore rounds up with probability exactly (dropped bits)/2^16:
    E[SR(x)] == x. Carries propagate into the exponent correctly (IEEE
    ordering), overflow past the largest finite value rounds to ±inf (the
    round-up neighbor), and non-finite inputs pass through untouched.

    Pure jnp/lax elementwise — traces identically into XLA and into a
    Pallas kernel body, so the fused and unfused paths agree bit-for-bit
    given the same ``rbits``.
    """
    if jnp.dtype(dtype) != jnp.bfloat16:
        raise NotImplementedError(
            f"stochastic rounding targets bfloat16 (top half of the fp32 "
            f"pattern); got {dtype}")
    x32 = x.astype(jnp.float32)
    u = lax.bitcast_convert_type(x32, jnp.uint32)
    u = u + (rbits.astype(jnp.uint32) & jnp.uint32(0xFFFF))
    u = u & jnp.uint32(0xFFFF0000)
    rounded = lax.bitcast_convert_type(u, jnp.float32).astype(jnp.bfloat16)
    return jnp.where(jnp.isfinite(x32), rounded, x32.astype(jnp.bfloat16))


def _count_draws(blocks: int, words: int, elements: int) -> None:
    """The stochastic-rounding ledger. The counters bump at TRACE time (the
    Python body only runs while jax traces), so they record what is baked
    into one compiled step — the per-execution counts of every step that
    executable runs."""
    prof = OpProfiler.get()
    prof.count("precision/sr_blocks", blocks)
    prof.count("precision/sr_draws", words)
    prof.count("precision/sr_elements", elements)


def random_bits_for(key, shape, slots: int = 1) -> jnp.ndarray:
    """One uint32 of randomness per element (``jax.random.bits``: under
    ``jax_threefry_partitionable`` one threefry block an element, its two
    words xored into one), for a caller that rounds ``slots`` stored
    elements with each word's halfwords (:func:`halfword`) — the flat
    buckets of :func:`ops.pallas_update.fused_apply`. Counted in the
    ``precision/sr_*`` ledger: ``sr_draws`` uint32 words returned,
    ``sr_blocks`` threefry blocks run, ``sr_elements`` stored elements
    rounded."""
    n = math.prod(shape)
    blocks = n if jax.config.jax_threefry_partitionable else (n + 1) // 2
    _count_draws(blocks, n, n * slots)
    return jax.random.bits(key, shape, dtype=jnp.uint32)


def halfword(words, which: int):
    """The one definition of which bits go to which slot: halfword
    ``which`` of a uint32 array, moved to the LOW 16 bits where
    :func:`stochastic_round` reads it — 0 the low half, 1 the high half."""
    return words if which == 0 else words >> jnp.uint32(16)


def threefry_words(key, shape):
    """ONE ``threefry2x32`` block per element of ``shape`` on ``key`` (a
    raw threefry key pair or a typed threefry key), both output words
    kept: ``(word0, word1)``, uint32 arrays of ``shape``.

    An element's counter is its row-major index plus ``ndim`` times the
    key's first word, built from per-axis iotas: no flat array is reshaped
    between tiled layouts, and — the reason for the offset, which leaves
    the counters of one key distinct — no index vector is the same for two
    leaves of one shape. XLA hoists those vectors out of the update's loop
    fusion, and where two leaves shared them it merged the leaves' updates
    into one fusion that holds their gradients together (+0.64 GB of step
    scratch in ``phi4_mini_flash.train_s8k``: PERF.md §6, PR 33)."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    if key.shape[-1:] != (2,):
        raise ValueError(
            "stochastic rounding draws threefry2x32 blocks and needs a "
            f"threefry key (uint32[2]); got key data of shape {key.shape}")
    n = math.prod(shape)
    if n > 2 ** 32:
        raise NotImplementedError(
            f"a leaf of {n} elements needs a second counter word")
    k0, k1 = key[..., 0], key[..., 1]
    hi = lo = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for axis in reversed(range(len(shape))):
        lo = lo + (lax.broadcasted_iota(jnp.uint32, shape, axis)
                   * jnp.uint32(stride) + k0)
        stride *= int(shape[axis])
    _count_draws(n, 2 * n, 0)
    return threefry2x32_p.bind(k0, k1, hi, lo)


# a threefry block is four 16-bit halfwords, handed out in this order:
# word 0 low, word 0 high, word 1 low, word 1 high
_HALFWORDS = 4


def slot_bits(key, shape, slots: int):
    """Random bits for rounding ``slots`` stored arrays of ``shape`` (the
    moments of ONE parameter leaf): a list of ``slots`` uint32 arrays
    whose low 16 bits are independent uniform halfwords. One block an
    element serves the first four slots in the order above (AdamW: m the
    low half of word 0, v its high half); a fifth slot starts a second
    block on ``fold_in(key, 1)``."""
    _count_draws(0, 0, math.prod(shape) * slots)
    out = []
    for first in range(0, slots, _HALFWORDS):
        sub = jax.random.fold_in(key, first // _HALFWORDS) if first else key
        words = threefry_words(sub, shape)
        for j in range(min(_HALFWORDS, slots - first)):
            out.append(halfword(words[j // 2], j % 2))
    return out


def _mirrors(state, params) -> bool:
    """Whether ``state`` is slots that each mirror the parameter tree
    (``{"m": tree, "v": tree}``: every built-in stateful updater)."""
    if params is None or type(state) is not dict or not state:
        return False
    p_leaves, treedef = jax.tree.flatten(params)
    for slot in state.values():
        s_leaves, s_def = jax.tree.flatten(slot)
        if s_def != treedef or any(jnp.shape(a) != jnp.shape(b)
                                   for a, b in zip(s_leaves, p_leaves)):
            return False
    return True


def sr_cast_state(state: Pytree, dtype, key, params: Pytree = None) -> Pytree:
    """Stochastically round every floating leaf of an (fp32) updater-state
    tree down to ``dtype``.

    State whose slots mirror ``params``: the slots of parameter leaf ``i``
    share ONE :func:`slot_bits` draw on ``fold_in(key, i)`` — its floating
    slots, names sorted, take the block's halfwords in order. Any other
    state (a coupled updater's, a scalar): a draw per state leaf, leaf
    ``i`` on ``fold_in(key, i)``."""
    leaves, treedef = jax.tree.flatten(state)
    if _mirrors(state, params):
        # a dict flattens in the order of its sorted keys, slot by slot
        n = len(leaves) // len(state)
        groups = [range(i, len(leaves), n) for i in range(n)]
    else:
        groups = [(i,) for i in range(len(leaves))]
    for i, group in enumerate(groups):
        rounded = [j for j in group if _is_floating(leaves[j])]
        if not rounded:
            continue
        bits = slot_bits(jax.random.fold_in(key, i),
                         jnp.shape(leaves[rounded[0]]), len(rounded))
        for j, b in zip(rounded, bits):
            leaves[j] = stochastic_round(leaves[j], b, dtype)
    return jax.tree.unflatten(treedef, leaves)


def state_dtype_of(updater) -> Optional[str]:
    """The configured low-precision state dtype, or None for fp32."""
    sd = getattr(updater, "state_dtype", None)
    return str(jnp.dtype(sd)) if sd else None


def apply_updater(updater, grads, state, params, iteration, key=None):
    """THE updater dispatch every step core routes through.

    fp32 state (``state_dtype`` unset): exactly ``updater.apply`` —
    bit-identical to the historical path. Low-precision state: upcast the
    stored moments to float32, run the unmodified fp32 updater math, and
    stochastically round the NEW moments back down using ``key`` (the
    step's RNG stream, fold_in-tagged so dropout draws are untouched).
    Parameters stay fp32 throughout — only the stored state narrows.
    Scopes ``updater`` and ``sr``, inside the caller's ``update``.
    """
    sd = state_dtype_of(updater)
    if not sd:
        with jax.named_scope("updater"):
            return updater.apply(grads, state, params, iteration)
    if key is None:
        raise ValueError(
            f"{type(updater).__name__}(state_dtype={sd!r}) needs the step "
            "RNG key for stochastic rounding — this fit path does not "
            "thread one; unset state_dtype or use a pipeline fit")
    with jax.named_scope("updater"):
        wide = cast_floating(state, jnp.float32)
        new_params, new_state = updater.apply(grads, wide, params, iteration)
    with jax.named_scope("sr"):     # the rounding and its threefry draw
        sr_key = jax.random.fold_in(key, SR_STREAM_TAG)
        new_state = sr_cast_state(new_state, jnp.dtype(sd), sr_key, params)
    return new_params, new_state


def updater_state_bytes(state) -> Dict[str, int]:
    """Host-side footprint ledger: total bytes per leaf dtype (plus
    ``total``). Empty dict for stateless updaters."""
    out: Dict[str, int] = {}
    for leaf in jax.tree.leaves(state or {}):
        n = int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
        k = str(jnp.dtype(leaf.dtype))
        out[k] = out.get(k, 0) + n
    if out:
        out["total"] = sum(out.values())
    return out


def note_state_bytes(state, prefix: str = "precision") -> None:
    """Record the live updater-state footprint as profiler gauges
    (``precision/updater_state_bytes_<dtype>`` + ``..._total``) — the
    ``precision_stats()`` /api/health view of what the state actually
    costs. Level quantities: gauges, not counters."""
    prof = OpProfiler.get()
    fresh = updater_state_bytes(state)
    for k in list(prof.get_counters()):
        # zero out stale per-dtype gauges from a previous state layout
        # (the dtype SET changes when state_dtype flips)
        if k.startswith(f"{prefix}/updater_state_bytes_") \
                and k[len(prefix) + len("/updater_state_bytes_"):] \
                not in fresh:
            prof.gauge(k, 0)
    for k, v in fresh.items():
        prof.gauge(f"{prefix}/updater_state_bytes_{k}", v)
