"""ParallelWrapper — data-parallel training over a device mesh.

Reference: dl4j-scaleout ``org.deeplearning4j.parallelism.ParallelWrapper``
(+ ``trainer/{DefaultTrainer,SymmetricTrainer}``; SURVEY.md §2.4, §3.5).

The reference clones the model per GPU, pins trainer threads to devices, and
exchanges threshold-encoded gradients through host-RAM queues. On TPU this
whole topology is ONE SPMD program: the train step runs under ``shard_map``
over the mesh's ``data`` axis with the minibatch sharded and params
replicated; the accumulator's ``reduce_gradients`` (a ``pmean`` over ICI for
the default dense accumulator) is compiled into the step. Both reference
training modes collapse to the synchronous collective:

- SHARED_GRADIENTS → psum of gradients every step (exactly this program);
- AVERAGING (params averaged every N iters) → mathematically subsumed by
  per-step gradient averaging; accepted and treated as the same program
  (documented divergence: no stale-average window exists to configure).
"""

from __future__ import annotations

from functools import partial
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map

import logging

from ..common import faultinject, flightrec, xprof
from ..common import integrity as _integ
from ..common.profiler import OpProfiler
from ..data import pipeline as _pipe
from ..data.dataset import DataSet
from ..ndarray.rng import get_random
from ..nn.train_step import (_same_shapes, finish, group_listeners,
                             needs_tree_update, update)
from .accumulator import DenseAllReduceAccumulator, GradientsAccumulator
from .mesh import data_sharded, elastic_pool, make_mesh, probe_device
from .sharding import Zero1Plan, is_flat_state

logger = logging.getLogger("deeplearning4j_tpu")


class ParallelWrapper:
    class Builder:
        def __init__(self, model):
            self._model = model
            self._workers: Optional[int] = None
            self._mode = "shared_gradients"
            self._accumulator: Optional[GradientsAccumulator] = None
            self._prefetch = 2
            self._averaging_frequency = 1
            self._model_axis = 1

        def workers(self, n: int) -> "ParallelWrapper.Builder":
            self._workers = n
            return self

        def model_axis(self, m: int) -> "ParallelWrapper.Builder":
            """Devices along the mesh's ``model`` axis (workers must divide
            by it). Layers with a ``table_sharding`` config (EmbeddingLayer
            family) shard their tables over this axis — the product-API
            route into the sharded-embedding machinery (SURVEY §2.4 row 4)."""
            self._model_axis = int(m)
            return self

        def training_mode(self, mode: str) -> "ParallelWrapper.Builder":
            mode = mode.lower()
            if mode not in ("shared_gradients", "averaging"):
                raise ValueError(f"unknown training mode {mode!r}")
            self._mode = mode
            return self

        trainingMode = training_mode

        def gradients_accumulator(self, acc: GradientsAccumulator) -> "ParallelWrapper.Builder":
            self._accumulator = acc
            return self

        def averaging_frequency(self, n: int) -> "ParallelWrapper.Builder":
            self._averaging_frequency = n  # accepted for parity; see module doc
            return self

        def prefetch_buffer(self, n: int) -> "ParallelWrapper.Builder":
            self._prefetch = n
            return self

        def build(self) -> "ParallelWrapper":
            return ParallelWrapper(self._model, self._workers, self._mode,
                                   self._accumulator
                                   or DenseAllReduceAccumulator(),
                                   model_axis=self._model_axis,
                                   prefetch=self._prefetch)

    def __init__(self, model, workers: Optional[int], mode: str,
                 accumulator: GradientsAccumulator, model_axis: int = 1,
                 prefetch: int = 2):
        self.model = model
        n = workers or len(jax.devices())
        if n % model_axis:
            raise ValueError(
                f"workers={n} not divisible by model_axis={model_axis}")
        self.mesh = make_mesh(data=n // model_axis, model=model_axis,
                              devices=jax.devices()[:n])
        self.workers_count = n // model_axis   # data-parallel shards
        self.model_axis = model_axis
        self.mode = mode
        self.accumulator = accumulator
        self.prefetch = prefetch
        self._step = None
        self._chunk_step = None
        self._telemetry = None
        self._listeners: List[Any] = []
        self._zero1_plan = None
        # per-worker-count compiled artifacts (step, chunk step, plan,
        # mesh), stashed/restored by resize(): growing back to a count
        # already trained at must NOT recompile — the elastic contract is
        # one compile per worker count per fit config
        self._exec_cache: dict = {}
        self._lost_devices: set = set()   # once-lost, not yet probed healthy
        self._coll_bytes: dict = {}       # static bytes per collective kind
        self._drained_encoded = (0.0, 0.0, 0)   # nnz/elems/steps last drain

    def set_listeners(self, *ls) -> None:
        self._listeners = list(ls)
        cfg = group_listeners(self._listeners)
        if cfg != self._telemetry:
            # in-graph telemetry is a build-time property of the SPMD step
            # (see FitLoop.set_listeners); the aux statistics are
            # aggregated across shards with the same collectives as the
            # weight update
            self._telemetry = cfg
            self._step = None
            self._chunk_step = None
            self._exec_cache.clear()   # telemetry is baked into the steps

    # ------------------------------------------------------------------
    def _local_core(self):
        """The per-shard train step, shared by the per-step shard_map and
        the steps_per_dispatch scan (one definition, no drift).

        Three gradient-exchange/updater layouts, selected by the
        accumulator (see parallel/accumulator.py):

        - dense (default): pmean the grads, every replica applies the full
          update epilogue (``nn.train_step.update``) redundantly;
        - encoded (``stateful``): threshold-encode with residual carry,
          psum the encoded update, the same dense epilogue — the
          accumulator state pytree threads through the step (and scan
          chunks);
        - ZeRO-1 (``zero1``): reduce-scatter the flat grads, apply the
          updater to this replica's 1/N flat slice against SHARDED updater
          state, all-gather the updated params. Bit-identical to dense on
          the same replica count: the flat layout is a pure permutation,
          the built-in updaters are elementwise, and psum_scatter's
          accumulation order matches psum's. Flat shards cannot be
          clipped by a tree's norm, projected or kept per layer, so a
          configuration that asks for any of it is refused at build.

        The differentiation is the wrapper's own — collectives stand
        between the gradient and the update — over the model's
        ``_loss_of``; the telemetry tail is ``nn.train_step.finish``.
        """
        model = self.model
        updater = model.conf.global_conf.updater
        acc = self.accumulator
        axis = acc.axis_name
        zero1 = acc.zero1
        stateful = acc.stateful
        plan = self._zero1_plan if zero1 else None
        tele = self._telemetry
        from ..ops import pallas_update as _pupd
        from ..optimize import telemetry as _tel

        stats = tele is not None and tele.stats
        integ = tele.integrity_every if tele is not None else 0
        if integ and not zero1:
            pspec = self._param_specs()
            specs = ([] if pspec == P() else
                     jax.tree.leaves(pspec,
                                     is_leaf=lambda s: isinstance(s, P)))
            if self.model_axis != 1 or any(s != P() for s in specs):
                raise NotImplementedError(
                    "integrity fingerprints police the replicated-state "
                    "invariant — model-sharded params have no replica "
                    "copies to compare")

        # Backward-epilogue fusion, ZeRO-1's alone: its updater consumes
        # FLAT buckets (the unit of the reduce-scatter and the all-gather),
        # so differentiate w.r.t. the flat params — the forward unflattens
        # them and the cotangents accumulate directly into flat layout for
        # the exchange. The dense layouts keep params, grads and state as
        # trees: on a TPU a bucket round trip is a physical relayout of
        # every leaf (PERF.md, PR 27), which only a collective pays for.
        # Gated off when telemetry stats need the raw dense per-shard grads
        # (nonfinite_counts / layer_stats walk the layer tree) — a
        # stats-off aux (integrity fingerprints only) keeps it on.
        flat_bwd = (zero1 and not stats
                    and getattr(model.conf.global_conf, "flat_backward",
                                True))

        def local_step(params, states, upd_state, acc_state, batch, w, key,
                       it):
            idx = jax.lax.axis_index(axis)
            # the dense layouts apply the FULL update redundantly on every
            # replica: their stochastic-rounding draws must be the same
            # everywhere or the replicas' bf16 moments (and then their
            # params) drift apart. Dropout and the ZeRO-1 slices are
            # per-replica work and take the folded key.
            shared_key = key
            key = jax.random.fold_in(key, idx)
            # Per-shard weighted data loss with a GLOBAL divisor: each shard
            # divides its weighted sum by global_real/num_shards, so the
            # pmean of per-shard losses (and of their grads) is exactly the
            # mean over real examples across the whole batch — pad rows
            # (w=0) contribute nothing and, unlike a whole-loss rescale,
            # the regularization term is never inflated.
            n_shards = jax.lax.psum(1.0, axis)
            real = jax.lax.psum(jnp.sum(w), axis)
            denom = jnp.maximum(real, 1.0) / n_shards

            def loss_fn(p):
                return model._loss_of(p, states, batch, key, w=w,
                                      w_denom=denom)

            if flat_bwd:
                flat_params = plan.flatten(params)
                (loss, new_states), flat_grads = jax.value_and_grad(
                    lambda fp: loss_fn(plan.unflatten_diff(fp)),
                    has_aux=True)(flat_params)
                OpProfiler.get().gauge("precision/grads_flat_in_step", 1)
                grads = None
            else:
                (loss, new_states), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                OpProfiler.get().gauge("precision/grads_flat_in_step", 0)
            # non-finite counts are taken on the RAW per-shard grads
            # (reduction would smear one shard's NaN across all of them)
            # and aggregated with the same collective family as the
            # weight update
            raw_nf = (jax.lax.psum(_tel.nonfinite_counts(grads), axis)
                      if stats else None)
            density = None
            if stateful:
                grads, acc_state, density = acc.exchange(grads, acc_state,
                                                         axis)
            loss = jax.lax.pmean(loss, axis)
            # keep layer state consistent across shards: the mean, which is
            # right for BatchNorm's running statistics (a counter that has
            # to be summed, RoutedExpertsLayer's expert_load, is not served
            # by this path yet: ROADMAP.md M2)
            new_states = jax.tree.map(
                lambda s: jax.lax.pmean(s, axis)
                if jnp.issubdtype(s.dtype, jnp.floating) else s, new_states)
            if zero1:
                # ZeRO-1: mean-reduce-scatter the flat grads, update only
                # this replica's even slice of params+state, gather back.
                # The update itself runs through the fused flat-bucket
                # kernel (ops/pallas_update — one launch per dtype bucket;
                # fp32 bitwise-identical to the per-leaf path) with the
                # generic elementwise fallback for updaters it doesn't
                # cover; `key` (already folded per-replica) drives the
                # bf16-state stochastic rounding when state_dtype is set.
                flat_g = flat_grads if flat_bwd else plan.flatten(grads)
                g_sh = {k: jax.lax.psum_scatter(
                    v, axis, scatter_dimension=0, tiled=True)
                    / jnp.asarray(n_shards, v.dtype)
                    for k, v in flat_g.items()}
                flat_p = flat_params if flat_bwd else plan.flatten(params)
                p_sh = plan.shard_slice(flat_p, idx)
                new_p_sh, new_upd = _pupd.apply_flat_updater(
                    updater, p_sh, g_sh, upd_state, it, key)
                gathered = {k: jax.lax.all_gather(v, axis, tiled=True)
                            for k, v in new_p_sh.items()}
                new_params = plan.unflatten(gathered)
            else:
                if not stateful:
                    grads = acc.reduce_gradients(grads)
                grads, new_params, new_upd = update(
                    model, updater, grads, upd_state, params, it, shared_key)
            aux = None
            if tele is None:
                pass
            elif not stats:
                # integrity-only aux: the loss plus the consistency
                # verdict below — no per-layer stats, no dense grads
                aux = {"loss": loss}
            elif zero1:
                # per-layer norms from the flat shards: segment-summed
                # locally, psum'd across the data axis (the full gradient/
                # update tensors are never materialized for telemetry)
                parts = [(plan.shard_segment_ids(b.key, idx, b.shard),
                          g_sh[b.key], new_p_sh[b.key], p_sh[b.key])  # graftlint: disable=donated-grad-escape -- in-graph read: XLA keeps the traced g_sh shards alive for the stats; donation frees only jit-boundary buffers
                         for b in plan.buckets]
                aux = _tel.sharded_layer_stats(loss, parts, plan.n_layers,
                                               axis, nonfinite=raw_nf)
            # dense stats (in the tail) are norms on the REDUCED grads /
            # updated params: replicated values, identical on every shard.
            # The encoded-exchange density rides the aux into the metrics
            # bus alongside the profiler ledger.
            out = finish(
                tele, loss, (params, states, upd_state),
                (new_params, new_states, new_upd), grads, aux=aux,
                nonfinite=raw_nf,
                extra=None if density is None
                else {"exchange_density": density})
            if tele is None:
                return (*out[:3], acc_state, loss)
            new_params, new_states, new_upd, _, aux = out
            if integ:
                # Replica-consistency fingerprint (common.integrity): the
                # O(params) bitcast fold of the step's INPUT state — the
                # state every replica stored from the previous step, which
                # the data-parallel contract requires bitwise-identical —
                # runs under a lax.cond every `integrity_every` steps (the
                # alive-mask pattern: predicated fold, no retrace). Only
                # the 4-byte digest and the tile-transport bit travel:
                # their all_gather runs unconditionally so no collective
                # ever sits inside a cond arm.
                do_check = (it % integ) == 0
                zero_fp = jnp.zeros((), jnp.uint32)
                if zero1:
                    # digest the unpadded flat buckets (no dense
                    # materialization), and cross-check the tile this
                    # replica republished against what the all_gather
                    # round-tripped — a corrupt interconnect receive
                    # flags the observing replica
                    fp_p, fp_chk = jax.lax.cond(
                        do_check,
                        lambda: (lambda f: (f, f))(
                            _integ.fingerprint_flats(plan, flat_p)),
                        lambda: (zero_fp, zero_fp))
                    mism = jax.lax.cond(
                        do_check,
                        lambda: jnp.any(jnp.stack([
                            _integ.bitwise_neq(
                                plan.shard_slice(gathered, idx)[b.key],
                                new_p_sh[b.key])
                            for b in plan.buckets])).astype(jnp.int32),
                        lambda: jnp.zeros((), jnp.int32))
                else:
                    # dense: params AND the replicated updater state must
                    # match — a desynced Adam moment corrupts training
                    # just as surely as a desynced weight
                    fp_p, fp_chk = jax.lax.cond(
                        do_check,
                        lambda: (lambda f: (f, _integ.combine_fp(
                            f, _integ.fingerprint_tree(upd_state))))(
                            _integ.fingerprint_tree(params)),
                        lambda: (zero_fp, zero_fp))
                    mism = jnp.zeros((), jnp.int32)
                checked, diverged, replica = _integ.replica_verdict(
                    fp_chk, mism, axis, do_check)
                aux["integrity_checked"] = checked
                aux["integrity_diverged"] = diverged
                aux["integrity_replica"] = replica
                aux["integrity_fp"] = fp_p
                # freeze-on-divergence (the nan-guard pattern): survivors
                # carry their clean pre-step state to the quarantine
                # boundary; the corrupt replica's output stays its own
                # poisoned input, so the fault persists and re-detects
                ok = diverged == 0
                keep = lambda nw, od: jnp.where(ok, nw, od)
                new_params = jax.tree.map(keep, new_params, params)
                new_states = jax.tree.map(keep, new_states, states)
                new_upd = jax.tree.map(keep, new_upd, upd_state)
            return new_params, new_states, new_upd, acc_state, loss, aux

        return local_step

    def _build_step(self):
        local_step = self._local_core()
        pspec = self._param_specs()
        uspec = self._upd_specs(pspec)
        aspec = self.accumulator.state_specs(self.model._params)
        out_specs = (pspec, P(), uspec, aspec, P())
        if self._telemetry is not None:
            out_specs += (P(),)    # aux pytree: replicated device scalars
        sharded = shard_map(
            local_step, mesh=self.mesh,
            in_specs=(pspec, P(), uspec, aspec, P("data"), P("data"), P(),
                      P()),
            out_specs=out_specs,
            check_rep=False)

        def step(*args):
            OpProfiler.get().count("trace/pw_fit_step")
            return sharded(*args)

        return xprof.register_jit(
            "pw/fit_step", jax.jit(step, donate_argnums=(0, 1, 2, 3)),
            donate=(0, 1, 2, 3))

    def _build_chunk_step(self):
        """steps_per_dispatch=K: each shard scans its K local slices of the
        stacked chunk inside ONE SPMD program — the per-step collectives
        (gradient psum/reduce-scatter, loss/stats pmean) run inside the
        scan body, and Python dispatch + listener sync amortize over K
        steps. The updater-state and accumulator-state layouts (sharded
        flat buckets / residual carries) thread through the scan carry
        unchanged."""
        local_step = self._local_core()
        tele = self._telemetry

        def local_chunk(params, states, upd_state, acc_state, batches, ws,
                        keys, it0):
            def body(carry, inp):
                *state, it = carry
                out = local_step(*state, *inp, it)
                return (*out[:4], it + 1), out[4:]

            (*state, _), ys = jax.lax.scan(
                body, (params, states, upd_state, acc_state, it0),
                (batches, ws, keys))
            return (*state, *ys)

        pspec = self._param_specs()
        uspec = self._upd_specs(pspec)
        aspec = self.accumulator.state_specs(self.model._params)
        batch = P(None, "data")   # [K, B, ...]: stack axis whole, B sharded
        out_specs = (pspec, P(), uspec, aspec, P())
        if tele is not None:
            out_specs += (P(),)
        sharded = shard_map(
            local_chunk, mesh=self.mesh,
            in_specs=(pspec, P(), uspec, aspec, batch, batch, P(), P()),
            out_specs=out_specs,
            check_rep=False)

        def chunk(*args):
            OpProfiler.get().count("trace/pw_fit_chunk")
            return sharded(*args)

        return xprof.register_jit(
            "pw/fit_chunk", jax.jit(chunk, donate_argnums=(0, 1, 2, 3)),
            donate=(0, 1, 2, 3))

    def _param_specs(self):
        """Per-layer partition specs: replicated except row-sharded
        embedding tables (layers carrying ``table_sharding``)."""
        model = self.model
        if not hasattr(model.conf, "layers"):    # ComputationGraph
            for name, node in getattr(model.conf, "nodes", {}).items():
                lyr = getattr(node, "layer", None)
                if getattr(lyr, "table_sharding", None):
                    raise NotImplementedError(
                        "table_sharding through ParallelWrapper is wired "
                        "for MultiLayerNetwork; ComputationGraph tables "
                        "are not routed yet")
            return P()
        specs = []
        for layer in model.conf.layers:
            ax = getattr(layer, "table_sharding", None)
            if not ax:
                specs.append(P())
                continue
            if ax not in self.mesh.shape:
                raise ValueError(f"table_sharding={ax!r} is not a mesh "
                                 f"axis of {tuple(self.mesh.shape)}")
            n_sh = self.mesh.shape[ax]
            if layer.n_in is None or layer.n_in % n_sh:
                raise ValueError(
                    f"embedding vocab {layer.n_in} must be divisible by "
                    f"the {ax!r} axis size {n_sh} (pad the vocab)")
            specs.append({"W": P(ax, None)})
        return specs

    def _upd_specs(self, pspec):
        """Updater state mirrors params per top-level key (Adam m/v,
        Nesterov v, ...) — shard those subtrees like the params. Under
        ZeRO-1 the state is flat buckets, every leaf split evenly over the
        data axis (the whole point: 1/N of the state per replica)."""
        upd_state = self.model._updater_state
        if not isinstance(upd_state, dict) or not upd_state:
            return P()
        if self.accumulator.zero1:
            return jax.tree.map(lambda _: P("data"), upd_state)
        pstruct = jax.tree.structure(self.model._params)
        return {k: (pspec if jax.tree.structure(v) == pstruct else P())
                for k, v in upd_state.items()}

    # ------------------------------------------------------------------
    # training-state layout (ZeRO-1 sharded updater / accumulator state)
    # ------------------------------------------------------------------
    def _place(self, tree, specs):
        """Host/device tree → device arrays placed per spec. ``jnp.array``
        first: an owning copy, never a view of numpy-owned memory — the
        step DONATES these buffers (the PR-3 heap-corruption lesson)."""
        from jax.sharding import NamedSharding

        leaves, treedef = jax.tree.flatten(tree)
        spec_leaves = jax.tree.flatten(
            specs, is_leaf=lambda s: isinstance(s, P))[0]
        placed = [jax.device_put(jnp.array(l), NamedSharding(self.mesh, s))
                  for l, s in zip(leaves, spec_leaves)]
        return jax.tree.unflatten(treedef, placed)

    def _place_model_state(self) -> None:
        """Params, layer states and a dense updater state onto the mesh
        BEFORE the first dispatch. The step would re-place them itself,
        but in jax 0.9 an array's type carries its mesh: a first call on
        single-device arrays and a second on the step's own mesh-placed
        outputs are two cache keys — two traces and two compiles of the
        same step. (ZeRO-1 and accumulator state are placed where they
        are built.)"""
        from jax.sharding import NamedSharding

        model = self.model

        def placed(tree, prefix_specs):
            leaves = jax.tree.leaves(tree)
            if all(isinstance(l, jax.Array)
                   and isinstance(l.sharding, NamedSharding)
                   and l.sharding.mesh == self.mesh for l in leaves):
                return tree
            # broadcast the prefix spec tree (a bare P() stands for the
            # whole tree) down to one spec per leaf
            specs = jax.tree.map(
                lambda s, sub: jax.tree.map(lambda _: s, sub),
                prefix_specs, tree, is_leaf=lambda s: isinstance(s, P))
            return self._place(tree, specs)

        pspec = self._param_specs()
        model._params = placed(model._params, pspec)
        model._states = placed(model._states, P())
        if not self.accumulator.zero1 and model._updater_state:
            model._updater_state = placed(model._updater_state,
                                          self._upd_specs(pspec))

    def _ensure_parallel_state(self) -> None:
        """Bring the model's updater/accumulator state into THIS wrapper's
        layout before the step is (re)built — fresh init, dense↔ZeRO-1
        conversion, and resharding a flat state saved under a different
        worker count (the flat layout is replica-count-independent, so
        only the zero pad tail changes: exact resume across N)."""
        import numpy as np

        model = self.model
        updater = model.conf.global_conf.updater
        acc = self.accumulator
        prof = OpProfiler.get()
        if acc.zero1:
            if not getattr(updater, "elementwise", False):
                raise NotImplementedError(
                    f"{type(updater).__name__} does not declare "
                    "elementwise=True; ZeRO-1 weight-update sharding "
                    "(ReduceScatterAccumulator) requires an elementwise "
                    "updater — use the dense accumulator instead")
            asked = needs_tree_update(model)
            if asked:
                raise NotImplementedError(
                    f"the configuration asks for {asked}, which ZeRO-1 "
                    "(ReduceScatterAccumulator) cannot honour on its flat "
                    "parameter shards — use the dense accumulator instead")
            pspec = self._param_specs()
            spec_leaves = ([] if pspec == P() else jax.tree.leaves(
                pspec, is_leaf=lambda s: isinstance(s, P)))
            if self.model_axis != 1 or any(s != P() for s in spec_leaves):
                raise NotImplementedError(
                    "ZeRO-1 sharding assumes replicated params: it cannot "
                    "compose with model_axis/table_sharding yet")
            if self._zero1_plan is None \
                    or self._zero1_plan.n_shards != self.workers_count:
                self._zero1_plan = Zero1Plan(model._params,
                                             self.workers_count)
            plan = self._zero1_plan
            state = model._updater_state
            if self._flat_state_matches_plan(state, plan):
                # already this plan's device layout (a prior fit's step
                # outputs) — re-placing it would be a needless host
                # round-trip, and re-counting would inflate the gauges
                return self._finish_parallel_state(acc, model)
            if state is None:
                # init DIRECTLY in the flat layout (zeros flatten to
                # zeros, so this equals flatten(dense init) exactly).
                # np.array, not np.asarray: device_get views alias
                # donatable buffers (the PR-3 lesson; tools/graftlint
                # enforces the pattern)
                flat_p = plan.flatten(jax.tree.map(np.array,
                                                   jax.device_get(
                                                       model._params)),
                                      xp=np)
                state = updater.init(flat_p)
            elif is_flat_state(state) or isinstance(state, dict) and state:
                # dense tree or differently-padded flat state → this
                # plan's padding (host-side numpy; pure permutation)
                state = plan.reshard_state(jax.device_get(state))
            if isinstance(state, dict) and state:
                uspecs = jax.tree.map(lambda _: P("data"), state)
                state = self._place(state, uspecs)
                total = sum(l.size * l.dtype.itemsize
                            for l in jax.tree.leaves(state))
                prof.count("zero1/updater_state_bytes_total", int(total))
                prof.count("zero1/updater_state_bytes_per_replica",
                           int(total // self.workers_count))
                from ..learning.precision import note_state_bytes

                note_state_bytes(state)
            model._updater_state = state
        else:
            state = model._updater_state
            if is_flat_state(state):
                # ZeRO-1 → dense handoff (e.g. resumed under a dense
                # accumulator): unflatten on host, rematerialize owned
                from .sharding import unflatten_updater_state

                state = unflatten_updater_state(
                    jax.device_get(state),
                    jax.device_get(model._params), xp=np)
                state = jax.tree.map(lambda a: jnp.array(a), state)
                model._updater_state = state
            if model._updater_state is None:
                model._updater_state = updater.init(model._params)
            from ..learning.precision import note_state_bytes

            note_state_bytes(model._updater_state)
        self._finish_parallel_state(acc, model)

    def _flat_state_matches_plan(self, state, plan) -> bool:
        """True when ``state`` is already this plan's PLACED flat layout:
        every bucket leaf a device array of the plan's padded length. A
        flat state from a different worker count fails on shape; host
        (numpy) trees fail on the array type and go through placement."""
        if not is_flat_state(state):
            return False
        for v in state.values():
            if not (isinstance(v, dict) and v):
                continue
            for b in plan.buckets:
                arr = v.get(b.key)
                if not (isinstance(arr, jax.Array)
                        and arr.shape == (b.padded,)):
                    return False
        return True

    def _finish_parallel_state(self, acc, model) -> None:
        """Accumulator-state layout + the static collective byte ledger
        (the tail every `_ensure_parallel_state` path shares)."""
        # accumulator state (encoded exchange: residual carry + threshold)
        if acc.stateful:
            st = getattr(model, "_acc_state", None)
            if not self._acc_state_placed(st):
                aspecs = acc.state_specs(model._params)
                blob = getattr(model, "_acc_blob", None)
                if st is None and blob is not None:
                    st = self._load_acc_blob(blob, acc)
                    model._acc_blob = None
                if st is None:
                    st = acc.init_state(model._params,
                                        n_shards=self.workers_count)
                else:
                    st = self._reshape_acc_state(jax.device_get(st), acc)
                model._acc_state = self._place(st, aspecs)
        else:
            model._acc_state = {}

        # the live worker count rides checkpoints (resume.json) and the
        # elastic health gauge — an elastic run's resume metadata must
        # say how many replicas were actually training
        model._live_workers = self.workers_count
        OpProfiler.get().gauge("elastic/workers", self.workers_count)

        # static per-step collective byte ledger (gradient exchange only)
        param_bytes = int(sum(l.size * np.dtype(l.dtype).itemsize
                              for l in jax.tree.leaves(model._params)))
        if acc.zero1:
            flat = self._zero1_plan.bucket_bytes()
            self._coll_bytes = {"reduce_scatter_bytes": flat,
                                "all_gather_bytes": flat}
        else:
            self._coll_bytes = {"psum_bytes": param_bytes}
        self._coll_bytes["dense_grad_bytes"] = param_bytes

    def _acc_state_placed(self, st) -> bool:
        """True when the live accumulator state already carries this
        wrapper's layout (device arrays, residual leading axis == this
        worker count) — i.e. it came out of this wrapper's own step."""
        if not (isinstance(st, dict) and st and "residual" in st):
            return False
        leaves = jax.tree.leaves(st["residual"])
        return all(isinstance(l, jax.Array) and l.ndim >= 1
                   and l.shape[0] == self.workers_count for l in leaves)

    def _load_acc_blob(self, blob: bytes, acc):
        """Checkpointed accumulator state (raw npz bytes restored by
        util.checkpoint) → host tree against this accumulator's template."""
        from ..util.model_serializer import _load_into_tree

        template = acc.init_state(self.model._params,
                                  n_shards=self.workers_count)
        try:
            return _load_into_tree(blob, template, "accumulator state")
        except Exception:
            import logging

            logging.getLogger("deeplearning4j_tpu").warning(
                "checkpointed accumulator state does not match this "
                "accumulator; starting it fresh")
            return None

    def _reshape_acc_state(self, st, acc):
        """Validate a restored/live accumulator state against this worker
        count. Residuals are PER-REPLICA (leading replica axis): a changed
        worker count makes them meaningless — reset to zero (warned);
        replicated scalars (threshold, ledger counters) carry over."""
        import numpy as np

        res = st.get("residual") if isinstance(st, dict) else None
        if res is None:
            return st
        lead = {l.shape[0] for l in jax.tree.leaves(res)}
        if lead == {self.workers_count}:
            return st
        import logging

        logging.getLogger("deeplearning4j_tpu").warning(
            "encoded-accumulator residuals were saved for %s workers; "
            "resetting them for %d (threshold and ledger carry over)",
            sorted(lead), self.workers_count)
        st = dict(st)
        st["residual"] = jax.tree.map(
            lambda p: np.zeros((self.workers_count,) + tuple(p.shape),
                               np.dtype(p.dtype)), self.model._params)
        return st

    # ------------------------------------------------------------------
    # online elastic resize (shrink/grow the data axis, no restart)
    # ------------------------------------------------------------------
    def resize(self, workers: int, *, lost_replicas=None) -> List[Any]:
        """Online elastic resize of the data axis at a DISPATCH BOUNDARY:
        rebuild the mesh over ``workers`` devices and re-shard the
        training state in memory — no process restart, no disk.

        The state moves are exact by construction: params and layer
        states are replicated (a host-owning copy re-placed by the next
        dispatch), ZeRO-1 flat updater/param buckets reshard through
        ``Zero1Plan``'s replica-count-independent permutation layout (the
        same guarantee as checkpoint resharding — only the zero pad tail
        changes), and the encoded accumulator's per-replica residuals are
        carried through ``resize_state`` (shrink folds the lost replica's
        residual into a survivor so no gradient mass is dropped).
        Compiled steps are stashed per worker count, so a grow-back to a
        count already trained at reuses its executable — one compile per
        worker count, total.

        Consistency model: a resize can observe a partially-applied step
        NEVER. It must only run between dispatches (or after a fit
        unwound at a step boundary), where the holder's published state
        is the complete output of the last compiled step; an in-flight
        ``steps_per_dispatch`` chunk either completes or is abandoned
        wholesale, and the pipeline cursor (`epochs_done`,
        ``steps_in_epoch``) names the exact batch to continue from — pass
        it back through ``fit(resume_cursor=...)``.

        ``lost_replicas``: data-axis indices of replicas whose device is
        gone (from :class:`faultinject.DeviceLostError` or a probe);
        their devices are excluded from the new mesh and remembered
        ACROSS calls — a later resize (even to a cached worker count)
        re-probes every once-lost device and only lets it rejoin after it
        answers, so a stashed mesh can never silently reinstate a
        still-dead device. Returns the devices removed — the supervisor's
        grow-back probe targets.
        """
        n = int(workers)
        if n < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        if self.model_axis != 1:
            raise NotImplementedError(
                "online elastic resize is a data-axis operation; it does "
                "not compose with model_axis/table_sharding yet")
        old_n = self.workers_count
        lost = sorted({int(r) for r in (lost_replicas or ())})
        if any(r < 0 or r >= old_n for r in lost):
            raise ValueError(f"lost_replicas {lost} out of range for "
                             f"{old_n} workers")
        if n == old_n and not lost:
            return []
        prof = OpProfiler.get()
        model = self.model
        with flightrec.span("elastic/resize", severity="warn",
                            workers_from=old_n, workers_to=n, lost=lost), \
                prof.time_section("elastic/resize"):
            # 1) host-materialize the training state with OWNING copies —
            # the compiled steps donate their argument buffers, and on
            # the CPU backend device_get returns zero-copy views (the
            # PR-3 heap-corruption lesson). When replicas are being
            # quarantined, replicated leaves are read from a SURVIVOR's
            # shard: a plain device_get reads shard 0, which may be the
            # silently-corrupted copy the shrink exists to discard.
            live = (model._params, model._states, model._updater_state,
                    getattr(model, "_acc_state", None) or None)
            if lost:
                params, states, upd, acc = \
                    _integ.materialize_from_survivors(
                        live, list(self.mesh.devices.flat), lost)
            else:
                params, states, upd, acc = jax.tree.map(
                    np.array, jax.device_get(live))
            # 2) per-replica accumulator state rides the permutation too
            if acc is not None:
                acc = self.accumulator.resize_state(acc, old_n, n,
                                                    lost_replicas=lost)
            # 3) stash this count's compiled artifacts, then reuse or
            # rebuild the target count's mesh
            mesh_devs = list(self.mesh.devices.flat)
            lost_devs = [mesh_devs[r] for r in lost]
            if self._step is not None or self._chunk_step is not None:
                self._exec_cache[old_n] = {
                    "step": self._step, "chunk": self._chunk_step,
                    "plan": self._zero1_plan, "mesh": self.mesh}
            # once-lost devices are remembered ACROSS calls and re-probed
            # here: a later resize must not silently reinstate a
            # still-dead device from a stashed mesh; a device that
            # answers the probe again is healthy and may rejoin (keeping
            # grow-back on the zero-recompile cached path)
            self._lost_devices = {d for d in self._lost_devices
                                  if not probe_device(d)}
            self._lost_devices |= set(lost_devs)
            excl = set(lost_devs) | self._lost_devices
            cached = self._exec_cache.get(n)
            if cached is not None and not (
                    excl & set(cached["mesh"].devices.flat)):
                self.mesh = cached["mesh"]
                self._step = cached["step"]
                self._chunk_step = cached["chunk"]
                self._zero1_plan = cached["plan"]
            else:
                pool = elastic_pool(self.mesh, exclude=excl)
                if n > len(pool):
                    raise ValueError(
                        f"resize to {n} workers needs {n} devices; only "
                        f"{len(pool)} are available")
                self.mesh = make_mesh(data=n, model=1, devices=pool[:n])
                self._step = None
                self._chunk_step = None
                self._zero1_plan = None
                self._exec_cache.pop(n, None)
            # every old-mesh device NOT in the new mesh left the axis —
            # the named lost devices, plus the tail a shrink without an
            # explicit loss list drops (grow-back probes target them all)
            new_devs = set(self.mesh.devices.flat)
            removed = [d for d in mesh_devs if d not in new_devs]
            self.workers_count = n
            # 4) hand the host state back: replicated trees re-materialize
            # as owning device arrays (the next dispatch places them per
            # its in_specs); the FLAT zero1 updater state stays numpy so
            # _ensure_parallel_state reshards it through the new plan's
            # padding and places it explicitly
            model._params = jax.tree.map(jnp.array, params)
            model._states = jax.tree.map(jnp.array, states)
            if upd is not None and not is_flat_state(upd):
                upd = jax.tree.map(jnp.array, upd)
            model._updater_state = upd
            model._acc_state = acc
            # _finish_parallel_state sets _live_workers + the workers gauge
            self._ensure_parallel_state()
        prof.count("elastic/resizes")
        if n < old_n:
            prof.count("elastic/shrinks")
        elif n > old_n:
            prof.count("elastic/grows")
        logger.warning("elastic resize: data axis %d -> %d workers%s",
                       old_n, n,
                       f" (lost replicas {lost})" if lost else "")
        return removed

    def probe_replicas(self) -> List[int]:
        """Data-axis indices whose device fails a tiny round-trip — the
        ground-truth check behind shrink-and-continue when a failure did
        not name the lost replica itself."""
        return [i for i, d in enumerate(self.mesh.devices.flat)
                if not probe_device(d)]

    def _count_collectives(self, prof, k: int = 1) -> None:
        prof.count("collective/steps", k)
        for name, nbytes in self._coll_bytes.items():
            prof.count(f"collective/{name}", nbytes * k)

    def _drain_encoded_ledger(self, prof) -> None:
        """One tiny host readback per epoch: fold the in-graph encoded-
        exchange counters (elements sent / total / steps) into the
        profiler's collective ledger as deltas since the last drain."""
        st = getattr(self.model, "_acc_state", None)
        if not (self.accumulator.stateful and isinstance(st, dict)) \
                or "nnz_sum" not in st:
            return
        nnz, elems, steps = jax.device_get(
            (st["nnz_sum"], st["elems_sum"], st["steps"]))
        p_nnz, p_elems, p_steps = self._drained_encoded
        if int(steps) > p_steps:
            prof.count("collective/encoded_elems_sent",
                       int(float(nnz) - p_nnz))
            prof.count("collective/encoded_elems_total",
                       int(float(elems) - p_elems))
            prof.count("collective/encoded_steps", int(steps) - p_steps)
        self._drained_encoded = (float(nnz), float(elems), int(steps))

    def fit(self, data, epochs: int = 1, batch_size: Optional[int] = None,
            *, pad_partial: Optional[bool] = None,
            drop_remainder: bool = False, prefetch: Optional[int] = None,
            steps_per_dispatch: int = 1,
            resume_from: Optional[str] = None,
            resume_cursor: Optional[tuple] = None) -> None:
        """Data-parallel training on the shared input/dispatch pipeline
        (data/pipeline.py): batches are padded BOTH to the configured batch
        size (one compile per fit config) and to a multiple of the worker
        count (shardability) — padding wraps REAL rows (keeps BatchNorm
        batch stats sane; zero rows would pollute them) while the zeroed
        loss-mask and example-weight remove their loss/gradient
        contributions exactly (see ``_local_core``'s renormalization).
        Sharded device placement is issued ``prefetch`` batches ahead
        (default: the builder's ``prefetch_buffer``), and
        ``steps_per_dispatch=K`` scans K minibatches inside one SPMD
        dispatch. ``resume_from``: exact checkpoint resume — see
        MultiLayerNetwork.fit; the restored (host) params/updater are
        re-placed by the SPMD step's sharding on first dispatch.
        ``resume_cursor=(epochs_done, steps_in_epoch)``: IN-MEMORY
        continuation — fast-forward the pipeline to the exact dispatch
        boundary the holder's live state already sits at, touching no
        disk (the supervisor's elastic shrink-and-continue path; the
        cursor is the one the interrupted fit left on the holder)."""
        model = self.model
        model._check_init()
        if not self._listeners and getattr(model, "_listeners", None):
            # listeners attached to the MODEL must not silently stop
            # firing the moment training goes through the wrapper —
            # adopt them (set_listeners also wires bind_group/telemetry)
            self.set_listeners(*model._listeners)
        from ..util.checkpoint import begin_fit_cursor

        if resume_cursor is not None:
            if resume_from is not None:
                raise ValueError(
                    "resume_from and resume_cursor are mutually exclusive")
            # in-memory continuation: the holder IS the checkpoint — no
            # restore, no step invalidation (a resize already rebuilt or
            # cache-swapped the steps; live state matches their layout)
            skip = (int(resume_cursor[0]), int(resume_cursor[1]))
            model._fit_epoch0 = model._epoch - skip[0]
            model._steps_in_epoch = skip[1]
        else:
            skip = begin_fit_cursor(model, resume_from,
                                    listeners=self._listeners,
                                    keep_flat=self.accumulator.zero1)
            if skip is not None:
                # the wrapper's own compiled steps hold donated buffers of
                # the replaced params — rebuild them too (and drop the
                # per-worker-count cache, which holds the same objects)
                self._step = None
                self._chunk_step = None
                self._exec_cache.clear()
        prof = OpProfiler.get()
        model._fit_calls += 1
        with prof.time_section("fit/enter", call=model._fit_calls):
            self._ensure_parallel_state()
            self._place_model_state()
            if self._step is None:
                self._step = self._build_step()
            if steps_per_dispatch > 1 and self._chunk_step is None:
                self._chunk_step = self._build_chunk_step()

        def on_epoch():
            model._epoch += 1
            model._steps_in_epoch = 0
            self._drain_encoded_ledger(prof)
            for lst in self._listeners:
                if hasattr(lst, "epoch_done"):
                    lst.epoch_done(model, model._epoch)

        _pipe.run_epochs(
            data, epochs, batch_size,
            pad_partial=True if pad_partial is None else pad_partial,
            drop_remainder=drop_remainder,
            prefetch=self.prefetch if prefetch is None else prefetch,
            steps_per_dispatch=steps_per_dispatch,
            bind=self._bind_batch,
            place=lambda b: jax.device_put(b, data_sharded(self.mesh)),
            dispatch_one=lambda b: self._dispatch_one(b, prof),
            dispatch_chunk=lambda g: self._dispatch_chunk(g, prof),
            stackable=_same_shapes, on_epoch=on_epoch,
            round_to_multiple_of=self.workers_count, skip=skip,
            first_step=model._iteration)

    def _bind_batch(self, ds: DataSet, w):
        """DataSet → ``(batch, w)``, the model's own batch as HOST arrays.
        The loss's single ``_fold_weights`` application zeroes the pad
        rows, so w is never applied twice. Staying numpy here matters:
        the ONLY device placement is the sharded one (in the feed) — a
        device batch would sit whole on device 0 and then be resharded,
        doubling per-step H2D traffic."""
        # PerformanceListener derives samples/sec from this (the holder
        # the listener bus sees is the wrapped model)
        self.model._last_batch_size = ds.num_examples()
        return (jax.tree.map(np.asarray, self.model._bind(ds)),
                np.asarray(w, np.float32))

    def _inject_faults(self, model) -> None:
        """Pre-dispatch drill hook: the ``integrity/fingerprint`` site's
        ``bitflip`` kind corrupts ONE replica's stored param copy between
        dispatches (common.integrity.apply_bitflip) — pure data, zero
        retraces — so the in-graph consistency check has something real
        to catch. Indexed by the iteration the dispatch starts at; under
        steps_per_dispatch the flip lands at the chunk boundary."""
        for spec in faultinject.fault_point("integrity/fingerprint",
                                            int(model._iteration)):
            if spec.get("kind") == "bitflip":
                _integ.apply_bitflip(model, self.mesh, spec)

    def _dispatch_one(self, b, prof) -> None:
        model = self.model
        self._inject_faults(model)
        key = get_random().next_key()
        with prof.time_section("pipeline/dispatch", step=model._iteration):
            out = self._step(model._params, model._states,
                             model._updater_state, model._acc_state, *b,
                             key, jnp.asarray(model._iteration))
        # the accumulator state (residual carry / threshold / counters) is
        # the wrapper's own training state — peel it off before the shared
        # note_dispatch decodes the (params, states, upd, loss[, aux])
        # contract every fit path uses
        model._acc_state = out[3]
        self._count_collectives(prof)
        _pipe.note_dispatch(model, self._listeners, out[:3] + out[4:],
                            self._telemetry is not None)

    def _dispatch_chunk(self, group, prof) -> None:
        model = self.model
        # the group's arrays are already SHARDED by the feed's placement:
        # jnp.stack composes shardings device-side ([K, B, ...] with B
        # still split over the data axis), matching the chunk in_specs
        batches, ws = jax.tree.map(lambda *leaves: jnp.stack(leaves), *group)
        self._inject_faults(model)
        keys = jnp.stack([get_random().next_key() for _ in group])
        with prof.time_section("pipeline/dispatch", step=model._iteration,
                               steps=len(group)):
            out = self._chunk_step(model._params, model._states,
                                   model._updater_state, model._acc_state,
                                   batches, ws, keys,
                                   jnp.asarray(model._iteration))
        model._acc_state = out[3]
        self._count_collectives(prof, len(group))
        _pipe.note_dispatch(model, self._listeners, out[:3] + out[4:],
                            self._telemetry is not None, len(group))

    def shutdown(self) -> None:
        self._step = None
        self._chunk_step = None
        self._zero1_plan = None
        self._exec_cache.clear()
