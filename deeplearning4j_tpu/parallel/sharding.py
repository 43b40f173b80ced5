"""Tensor-parallel sharding rules + the ZeRO-1 flat param-bucketing scheme.

The reference has NO tensor parallelism (SURVEY.md §2.4 marks it absent);
on TPU it is a compiler annotation, so the rebuild provides it natively:
given a model's parameter pytree and a mesh with a ``model`` axis, produce a
matching tree of ``NamedSharding`` that splits the large matmul weights —
dense W=[in,out] on the output dim, conv W=[O,I,kh,kw] on the output-channel
dim — and lets GSPMD insert the ICI collectives (scaling-book recipe: pick a
mesh, annotate, let XLA do the rest).

The second half of this module is the flat layout behind cross-replica
weight-update sharding (ZeRO-1; arXiv:2004.13336): a parameter pytree is
raveled into one 1-D buffer per dtype ("bucket"), zero-padded to a multiple
of the data-axis size, and split EVENLY over the replicas — so uneven layer
sizes still balance (replica i owns elements [i*s, (i+1)*s) of every
bucket, not layer i). The layout is a pure permutation: it depends only on
the pytree structure and leaf shapes, NOT on the replica count (only the
zero padding does), which is what makes updater state saved from an N-way
run restorable into an M-way run exactly.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

FLAT_PREFIX = "flat::"   # bucket keys ("flat::float32") mark the flat layout


def tp_param_specs(params: Any, mesh: Mesh, axis: str = "model"):
    """PartitionSpec tree for tensor-parallel params; replicates anything that
    doesn't divide evenly (correct, just not sharded)."""
    size = mesh.shape[axis]

    def spec_for(leaf):
        shape = leaf.shape
        if len(shape) == 2 and shape[1] % size == 0 and shape[1] >= size:
            return P(None, axis)                    # dense [in, out]
        if len(shape) == 4 and shape[0] % size == 0 and shape[0] >= size:
            return P(axis, None, None, None)        # conv OIHW [out, ...]
        if len(shape) == 1 and shape[0] % size == 0 and shape[0] >= 2 * size:
            return P(axis)                          # bias / bn per-channel
        return P()

    return jax.tree.map(spec_for, params)


def tp_shardings(params: Any, mesh: Mesh, axis: str = "model"):
    specs = tp_param_specs(params, mesh, axis)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def apply_tp(params: Any, mesh: Mesh, axis: str = "model"):
    """Materialize params with tensor-parallel placement."""
    sh = tp_shardings(params, mesh, axis)
    return jax.tree.map(jax.device_put, params, sh)


# --------------------------------------------------------------------------
# ZeRO-1 flat param bucketing
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class _Bucket:
    key: str                 # "flat::<dtype>"
    dtype: Any               # numpy dtype
    leaf_idx: Tuple[int, ...]    # positions in the tree.flatten leaf order
    sizes: Tuple[int, ...]       # element count per leaf
    shapes: Tuple[Tuple[int, ...], ...]
    total: int               # true (unpadded) element count
    padded: int              # total rounded up to a multiple of n_shards
    shard: int               # padded // n_shards


def _leaf_layers(params) -> List[int]:
    """Layer/group index per leaf in ``jax.tree.flatten`` order — matches
    ``optimize.telemetry.groups`` (list order for MLN param lists, sorted
    node name for graph dicts), so flat-shard telemetry lands in the same
    per-layer slots as the dense path's."""
    from ..optimize.telemetry import groups

    out: List[int] = []
    for i, g in enumerate(groups(params)):
        out.extend([i] * len(jax.tree.leaves(g)))
    return out


class Zero1Plan:
    """The deterministic flat layout for one (params, n_shards) pair.

    ``flatten``/``unflatten`` are pure permutations (ravel + concat /
    split + reshape — no arithmetic), so running an ELEMENTWISE updater on
    the flat buffers is bit-identical to running it leaf-by-leaf; the
    in-graph versions trace into the compiled step, and ``xp=np`` gives
    the host-side versions checkpointing uses.

    No arithmetic is not no cost: on a TPU a rank-4 leaf lives tiled, and
    ravel/reshape to and from rank 1 is a physical relayout (a
    ``f32[512,512,3,3]`` leaf: 1.46 ms, ≈13 GB/s against 819 GB/s of
    HBM). A plan therefore belongs in a step only where a collective
    wants the contiguous buffer (``n_shards > 1``); with one shard the
    round trip was half of a ResNet-50 step (PERF.md, PR 27)."""

    def __init__(self, params, n_shards: int):
        from ..optimize.telemetry import groups

        leaves, treedef = jax.tree.flatten(params)
        self.treedef = treedef
        self.n_shards = int(n_shards)
        self.n_leaves = len(leaves)
        self.n_layers = len(groups(params))
        layer_of = _leaf_layers(params)
        by_dtype: Dict[str, List[int]] = {}
        for i, leaf in enumerate(leaves):
            by_dtype.setdefault(str(np.dtype(leaf.dtype)), []).append(i)
        self.buckets: List[_Bucket] = []
        self._bounds: Dict[str, np.ndarray] = {}
        self._interval_layers: Dict[str, np.ndarray] = {}
        for dt, idxs in sorted(by_dtype.items()):
            sizes = tuple(int(np.prod(leaves[i].shape)) for i in idxs)
            shapes = tuple(tuple(leaves[i].shape) for i in idxs)
            total = sum(sizes)
            padded = -(-total // self.n_shards) * self.n_shards
            b = _Bucket(key=FLAT_PREFIX + dt, dtype=np.dtype(dt),
                        leaf_idx=tuple(idxs), sizes=sizes, shapes=shapes,
                        total=total, padded=padded,
                        shard=padded // self.n_shards)
            self.buckets.append(b)
            # per-leaf flat-position boundaries (n_leaves+1 entries — the
            # TINY tables telemetry derives segment ids from in-graph;
            # the pad tail [total, padded) maps to the overflow interval,
            # layer id ``n_layers``, that segment-summed telemetry drops)
            self._bounds[b.key] = np.concatenate(
                [[0], np.cumsum(sizes)]).astype(np.int32)
            self._interval_layers[b.key] = np.asarray(
                [layer_of[i] for i in idxs] + [self.n_layers], np.int32)

    # -- layout transforms (xp=jnp traces into the step; xp=np is host) --
    def flatten(self, tree, xp=jnp) -> Dict[str, Any]:
        leaves = jax.tree.leaves(tree)
        if len(leaves) != self.n_leaves:
            raise ValueError(f"tree has {len(leaves)} leaves, plan expects "
                             f"{self.n_leaves}")
        out = {}
        for b in self.buckets:
            parts = [xp.ravel(leaves[i]) for i in b.leaf_idx]
            if b.padded > b.total:
                # pad in the LEAVES' dtype, not the bucket key's: a
                # low-precision updater-state tree (state_dtype=bfloat16)
                # flattens through its params' f32-keyed buckets, and an
                # f32 zero tail would silently promote the whole bucket
                parts.append(xp.zeros((b.padded - b.total,),
                                      parts[0].dtype))
            out[b.key] = xp.concatenate(parts) if len(parts) > 1 else parts[0]
        return out

    def unflatten(self, flats: Dict[str, Any], xp=jnp):
        leaves: List[Any] = [None] * self.n_leaves
        for b in self.buckets:
            flat = flats[b.key]
            pos = 0
            for i, sz, shape in zip(b.leaf_idx, b.sizes, b.shapes):
                leaves[i] = xp.reshape(flat[pos:pos + sz], shape)
                pos += sz
        return jax.tree.unflatten(self.treedef, leaves)

    def unflatten_diff(self, flats: Dict[str, Any]):
        """:meth:`unflatten` with its exact adjoint spelled out. The
        autodiff transpose of slice-and-reshape lowers as one
        full-bucket-size ``pad`` + ``add_any`` PER LEAF, so a flat-backward
        step through plain :meth:`unflatten` materializes O(n_leaves)
        bucket-sized temporaries. But unflatten is a pure permutation
        whose adjoint IS :meth:`flatten` — one concatenate per bucket —
        and the pad tail's cotangent is identically zero, which flatten's
        zero tail reproduces bitwise. Use this form wherever a step
        differentiates through the flat layout."""
        @jax.custom_vjp
        def _unflat(f):
            return self.unflatten(f)

        def _fwd(f):
            return self.unflatten(f), None

        def _bwd(_, ct):
            return (self.flatten(ct),)

        _unflat.defvjp(_fwd, _bwd)
        return _unflat(flats)

    def shard_slice(self, flats: Dict[str, Any], idx) -> Dict[str, Any]:
        """Replica ``idx``'s even slice of every bucket (in-graph)."""
        return {b.key: jax.lax.dynamic_slice(flats[b.key],
                                             (idx * b.shard,), (b.shard,))
                for b in self.buckets}

    def unpadded_views(self, flats: Dict[str, Any]) -> Dict[str, Any]:
        """Each bucket's live prefix (``[:total]``, a static slice) with
        the worker-count pad tail dropped. This is the integrity-fold
        contract (:func:`common.integrity.fingerprint_flats`): the pad
        tail's length changes with the replica count, so any digest that
        folded it in would break fingerprint stability across elastic
        resizes — only the live prefix is ever hashed."""
        return {b.key: flats[b.key][:b.total] for b in self.buckets}

    def shard_segment_ids(self, key: str, idx, shard: int):
        """Telemetry layer id for each flat position of replica ``idx``'s
        slice of bucket ``key``, derived IN-GRAPH from the bucket's tiny
        leaf-boundary tables — NOT a [padded] int32 constant baked into
        the executable (that would cost 4 bytes per model parameter per
        compiled step, against a feature whose point is cutting memory).
        Ascending (leaves follow layer order; pad bin ``n_layers`` last),
        so ``segment_sum(..., indices_are_sorted=True)`` stays valid."""
        pos = idx * shard + jnp.arange(shard, dtype=jnp.int32)
        k = jnp.searchsorted(jnp.asarray(self._bounds[key]), pos,
                             side="right") - 1
        return jnp.asarray(self._interval_layers[key])[k]

    def bucket_bytes(self) -> int:
        return sum(b.padded * b.dtype.itemsize for b in self.buckets)

    # -- updater-state layout conversion --------------------------------
    def flatten_state(self, state, xp=np):
        """Dense (params-mirroring) updater-state tree → flat buckets.
        Only subtrees shaped like the params flatten; anything else (none
        of the built-in updaters produce one) is passed through."""
        if not isinstance(state, dict):
            return state
        out = {}
        for k, v in state.items():
            if jax.tree.structure(v) == self.treedef:
                out[k] = self.flatten(v, xp=xp)
            else:
                out[k] = v
        return out

    def unflatten_state(self, state, xp=np):
        """Flat-bucketed updater state → dense tree (strips padding).
        Accepts buckets padded for a DIFFERENT shard count: the layout is
        replica-count-independent, so only the zero tail differs."""
        if not is_flat_state(state):
            return state
        out = {}
        for k, v in state.items():
            if isinstance(v, dict) and v and all(
                    str(kk).startswith(FLAT_PREFIX) for kk in v):
                out[k] = self.unflatten(
                    {b.key: self._repad(v[b.key], b, strip_only=True)
                     for b in self.buckets}, xp=xp)
            else:
                out[k] = v
        return out

    def _repad(self, arr, b: _Bucket, strip_only: bool = False):
        """Normalize one bucket array saved under any shard count to this
        plan's padding (exact: real elements are untouched, only the zero
        tail is cut/grown)."""
        arr = np.asarray(arr)
        if arr.size < b.total:
            raise ValueError(
                f"flat updater bucket {b.key} has {arr.size} elements; "
                f"params imply {b.total} — checkpoint does not match the "
                "model")
        arr = arr[:b.total]
        if strip_only:
            return arr
        if b.padded > b.total:
            arr = np.concatenate(
                [arr, np.zeros((b.padded - b.total,), arr.dtype)])
        return arr

    def reshard_state(self, state):
        """Flat state (any previous shard count) → flat host state padded
        for THIS plan. Dense trees are flattened first."""
        if is_flat_state(state):
            return {k: ({b.key: self._repad(v[b.key], b)
                         for b in self.buckets}
                        if isinstance(v, dict) and v and all(
                            str(kk).startswith(FLAT_PREFIX) for kk in v)
                        else v)
                    for k, v in state.items()}
        return self.flatten_state(
            jax.tree.map(np.asarray, state), xp=np)


def is_flat_state(state) -> bool:
    """True when ``state`` is in the ZeRO-1 flat-bucket layout (top-level
    values are dicts keyed ``flat::<dtype>``)."""
    if not isinstance(state, dict) or not state:
        return False
    return any(isinstance(v, dict) and v
               and all(str(k).startswith(FLAT_PREFIX) for k in v)
               for v in state.values())


def unflatten_updater_state(state, params, xp=np):
    """Host-side convenience: flat updater state → dense tree mirroring
    ``params`` (identity for dense state). Checkpoint writers call this so
    the on-disk updater layout is ALWAYS the dense one — a ZeRO-1 run's
    checkpoint restores into a single-device fit, a dense run, or a
    ZeRO-1 run with a different worker count without format negotiation."""
    if not is_flat_state(state):
        return state
    return Zero1Plan(params, 1).unflatten_state(state, xp=xp)
