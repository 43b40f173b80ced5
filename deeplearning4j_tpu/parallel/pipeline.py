"""Pipeline parallelism: microbatched stage execution over a mesh axis.

Reference status: the reference has NO pipeline parallelism (SURVEY §2.4
marks the row absent; "optional later via shard_map stages"). On TPU it is
a natural mesh dimension, so the rebuild provides the canonical GPipe-style
construction natively (same spirit as the ring-attention and tensor-parallel
additions):

- S stages live one-per-device along a mesh ``stage`` axis — HOMOGENEOUS
  repeated blocks as [S, ...]-stacked params (``pipeline_apply``), or
  HETEROGENEOUS per-stage programs/shapes via flattened-param rows and a
  ``lax.switch`` over padded activation payloads
  (:class:`HeterogeneousPipeline`, round 5);
- the global batch splits into M microbatches; a ``lax.scan`` runs
  M + S - 1 ticks in which every device applies its stage to the activation
  it holds and passes the result to the next stage with neighbor-only
  ``ppermute`` (rides ICI);
- stage 0 injects microbatch t at tick t; the last stage's outputs are
  collected tick-aligned and reassembled, then ``psum``-broadcast.

The whole pipeline is one jitted module and is DIFFERENTIABLE (scan +
ppermute both have transpose rules), so ``jax.grad`` through
``pipeline_apply`` yields per-stage parameter gradients — enough to train.
Bubble fraction is the textbook (S-1)/(M+S-1); pick M >> S.

Production tier (ISSUE 14): :class:`PipelineTrainer` generalizes the
construction to N-stage GPipe AND 1F1B schedules with explicit
forward/backward tick tables (:func:`schedule_meta`), composed with the
data axis on a ``(data × stage)`` mesh, behind the standard fit surface
(listeners, in-graph telemetry aux, checkpoint ``resume_from=`` and the
supervisor's in-memory ``resume_cursor=``). It is SELF-HEALING: a stage
lost mid-run re-cuts the layer partition over the surviving stage
devices (:meth:`PipelineTrainer.remap` — the supervisor's
``remap_and_continue`` policy) and continues from the exact dispatch
boundary, one compile per (stage-count, schedule) ever.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common import faultinject, flightrec, xprof
from ..common.profiler import OpProfiler

logger = logging.getLogger("deeplearning4j_tpu")

# jax < 0.5 has no varying-type system: pvary is the identity there (the
# rep checker it informs does not exist either)
_pvary = getattr(lax, "pvary", lambda x, axis_name: x)


def stack_stage_params(params_list):
    """[per-stage pytree, ...] → one pytree with leading stage axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def pipeline_apply(stage_fn: Callable, stacked_params, x: jnp.ndarray,
                   mesh: Mesh, n_micro: int, axis: str = "stage"):
    """Run ``stage_fn(params, x) -> y`` (same shape in/out) as an S-stage
    pipeline over ``axis``. x: [B, ...] with B divisible by ``n_micro``.
    Returns [B, ...] replicated."""
    from jax.experimental.shard_map import shard_map

    S = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_micro == 0, "batch must divide into microbatches"
    mb = B // n_micro

    def local(params_l, x_full):
        me = lax.axis_index(axis)
        p = jax.tree.map(lambda a: a[0], params_l)     # my stage's slice
        micro = x_full.reshape((n_micro, mb) + x_full.shape[1:])
        T = n_micro + S - 1
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            act = carry
            # stage 0 injects microbatch t (clipped; late ticks are
            # pipeline-drain bubbles masked out at collection)
            inj = micro[jnp.clip(t, 0, n_micro - 1)]
            inp = jnp.where(me == 0, inj, act)
            out = stage_fn(p, inp)
            nxt = lax.ppermute(out, axis, perm)
            return nxt, out

        act0 = _pvary(jnp.zeros((mb,) + x_full.shape[1:], x_full.dtype),
                      axis)
        _, outs = lax.scan(tick, act0, jnp.arange(T))   # [T, mb, ...]
        # microbatch m exits the LAST stage at tick m + S - 1
        final = lax.dynamic_slice_in_dim(outs, S - 1, n_micro, axis=0)
        final = final * (me == S - 1).astype(final.dtype)
        final = lax.psum(final, axis)                   # replicate
        return final.reshape((B,) + x_full.shape[1:])

    # P(axis) is a prefix spec: leading (stage) dim sharded, the rest
    # replicated, for every leaf of the params pytree
    fn = shard_map(local, mesh=mesh, in_specs=(P(axis), P()),
                   out_specs=P())
    return fn(stacked_params, x)


# --------------------------------------------------------------------------
# heterogeneous stages (round 5 — VERDICT r4 weak #2)


def _flatten_params(tree):
    """Pytree → (f32 vector, unflatten) — the per-stage param payload for
    the heterogeneous pipeline (stages have DIFFERENT param trees, so they
    ride a common [S, P_max] stacked-vector layout instead of a stacked
    pytree)."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [np.shape(l) for l in leaves]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    vec = (jnp.concatenate([jnp.ravel(jnp.asarray(l, jnp.float32))
                            for l in leaves])
           if leaves else jnp.zeros((0,), jnp.float32))

    def unflatten(v):
        out, off = [], 0
        for shp, sz in zip(shapes, sizes):
            out.append(v[off:off + sz].reshape(shp))
            off += sz
        return jax.tree.unflatten(treedef, out)

    return vec, unflatten


class HeterogeneousPipeline:
    """GPipe-style pipeline over stages with DIFFERENT programs, param
    trees, and activation shapes (the homogeneous construction above cannot
    express ResNet/BERT stage cuts — round-4 verdict weak #2).

    SPMD mechanics: every device runs the same jitted program; the
    per-stage computation is a ``lax.switch`` over the stage index, with
    activations packed into a fixed [PAD] f32 payload (PAD = the largest
    inter-stage activation) so every branch — and the neighbor ``ppermute``
    that moves activations down the pipe — has one static shape. Stage
    parameters are flattened to one f32 vector each and stacked [S, P_max],
    sharded over the ``stage`` mesh axis; each device unflattens only ITS
    row inside its switch branch. Differentiable end to end (switch, scan,
    ppermute all transpose), so ``train_step`` trains all stages.

    Parameters are held in FLOAT32 (the flattened payload's dtype).

    Checkpoint story (ISSUE 14 satellite): when built through
    :func:`pipeline_from_mln` the source model rides along (``model`` /
    ``_runs``), and :meth:`snapshot`/:meth:`restore` route the live stage
    params through the PR-3 ``snapshot_training_state`` /
    ``restore_training_state`` machinery — the on-disk layout is the
    model's ordinary per-layer tree, so a pipeline run kill+resumes
    bit-exactly and its checkpoints stay readable by every other path.
    """

    #: the source MultiLayerNetwork (+ its stage layer runs) when built
    #: via pipeline_from_mln — the checkpoint surface; None when the
    #: pipeline was assembled from raw stage_fns
    model = None
    _runs: Optional[List[tuple]] = None

    def __init__(self, stage_fns, params_list, in_shapes, out_shapes,
                 mesh: Mesh, n_micro: int, axis: str = "stage",
                 loss_fn: Callable = None):
        S = len(stage_fns)
        if mesh.shape[axis] != S:
            raise ValueError(f"{S} stages but mesh axis {axis!r} has "
                             f"{mesh.shape[axis]} devices")
        for s in range(S - 1):
            if tuple(out_shapes[s]) != tuple(in_shapes[s + 1]):
                raise ValueError(
                    f"stage {s} outputs {out_shapes[s]} but stage {s + 1} "
                    f"expects {in_shapes[s + 1]}")
        self.mesh, self.axis, self.n_micro = mesh, axis, n_micro
        self.in_shapes = [tuple(s) for s in in_shapes]
        self.out_shapes = [tuple(s) for s in out_shapes]
        self._loss_fn = loss_fn or (lambda out, y: jnp.mean((out - y) ** 2))
        self._stage_fns = list(stage_fns)
        self._place_param_rows(params_list)

    def _place_param_rows(self, params_list) -> None:
        """Flatten+pad per-stage trees into the [S, P_max] stage-sharded
        payload (shared by __init__ and sync_from_model)."""
        vecs, self._unflattens = zip(
            *[_flatten_params(p) for p in params_list])
        p_max = max(max(v.size for v in vecs), 1)
        stacked = jnp.stack([jnp.pad(v, (0, p_max - v.size)) for v in vecs])
        self.params = jax.device_put(
            stacked, NamedSharding(self.mesh, P(self.axis, None)))

    # --- checkpoint routing (state lives on the source model) -----------
    def sync_to_model(self) -> None:
        """Write the live stage rows back onto the source model as OWNING
        per-layer copies (``np.array`` of the device payload — device_get
        can return zero-copy views on the CPU backend, the PR-3 lesson)."""
        if self.model is None or self._runs is None:
            raise ValueError("this pipeline was not built from a model "
                             "(pipeline_from_mln); no checkpoint surface")
        host = np.array(jax.device_get(self.params))
        for s, (lo, hi) in enumerate(self._runs):
            tree = self._unflattens[s](host[s])
            for i in range(lo, hi):
                self.model._params[i] = jax.tree.map(
                    lambda a: jnp.array(a), tree[str(i)])

    def sync_from_model(self) -> None:
        """Re-stack the stage payload from the source model's per-layer
        params (after a checkpoint restore)."""
        if self.model is None or self._runs is None:
            raise ValueError("this pipeline was not built from a model "
                             "(pipeline_from_mln); no checkpoint surface")
        params_list = [{str(i): self.model._params[i]
                        for i in range(lo, hi)} for lo, hi in self._runs]
        self._place_param_rows(params_list)

    def snapshot(self, listeners=None):
        """Host snapshot through the standard checkpoint machinery —
        serialize/commit with ``util.checkpoint`` like any fit path."""
        from ..util.checkpoint import snapshot_training_state

        self.sync_to_model()
        return snapshot_training_state(self.model, listeners)

    def restore(self, path: str, listeners=None):
        """Restore a committed checkpoint into the source model AND the
        live stage payload; returns the pipeline cursor."""
        from ..util.checkpoint import restore_training_state

        cursor = restore_training_state(self.model, path,
                                        listeners=listeners)
        self.sync_from_model()
        return cursor

    def _build(self, mb: int):
        S = len(self._stage_fns)
        axis, n_micro = self.axis, self.n_micro
        in_sz = [mb * int(np.prod(s)) for s in self.in_shapes]
        out_sz = [mb * int(np.prod(s)) for s in self.out_shapes]
        pad = max(in_sz + out_sz)

        def branch(s):
            fn, unflat = self._stage_fns[s], self._unflattens[s]
            ishape, isz, osz = self.in_shapes[s], in_sz[s], out_sz[s]

            def b(pvec, act):
                x = act[:isz].reshape((mb,) + ishape)
                y = fn(unflat(pvec), x)
                return jnp.zeros((pad,), jnp.float32).at[:osz].set(
                    jnp.ravel(y).astype(jnp.float32))

            return b

        branches = [branch(s) for s in range(S)]
        perm = [(i, (i + 1) % S) for i in range(S)]
        o_last = out_sz[-1]
        oshape_last = self.out_shapes[-1]

        def local(pstacked, x_full):
            me = lax.axis_index(axis)
            pvec = pstacked[0]
            B = x_full.shape[0]
            micro = x_full.reshape((n_micro, mb) + x_full.shape[1:])
            T = n_micro + S - 1

            def tick(act, t):
                inj = jnp.zeros((pad,), jnp.float32).at[:in_sz[0]].set(
                    jnp.ravel(micro[jnp.clip(t, 0, n_micro - 1)]).astype(
                        jnp.float32))
                inp = jnp.where(me == 0, inj, act)
                out = lax.switch(me, branches, pvec, inp)
                nxt = lax.ppermute(out, axis, perm)
                return nxt, out

            act0 = _pvary(jnp.zeros((pad,), jnp.float32), axis)
            _, outs = lax.scan(tick, act0, jnp.arange(T))
            final = lax.dynamic_slice_in_dim(outs, S - 1, n_micro, axis=0)
            y = final[:, :o_last].reshape((B,) + oshape_last)
            y = y * (me == S - 1).astype(y.dtype)
            return lax.psum(y, axis)

        from jax.experimental.shard_map import shard_map

        # check_rep=False: the lax.switch over per-stage programs yields
        # branch outputs whose replication types the jax-0.4 checker cannot
        # unify (newer jax resolves this through pvary varying types); the
        # psum at the tail replicates the result regardless
        return shard_map(local, mesh=self.mesh,
                         in_specs=(P(axis, None), P()), out_specs=P(),
                         check_rep=False)

    def _fns(self, B: int):
        cache = getattr(self, "_jit_cache", None)
        if cache is None:
            cache = self._jit_cache = {}
        if B not in cache:
            assert B % self.n_micro == 0, \
                "batch must divide into microbatches"
            mb = B // self.n_micro
            pipe = self._build(mb)
            fwd = xprof.register_jit("pipeline/hetero_fwd", jax.jit(pipe))
            loss_fn = self._loss_fn

            @jax.jit
            def step(params, x, y, lr):
                def lf(p):
                    return loss_fn(pipe(p, x), y)

                loss, grads = jax.value_and_grad(lf)(params)
                return jax.tree.map(lambda p, g: p - lr * g, params,
                                    grads), loss

            step = xprof.register_jit("pipeline/hetero_step", step)
            cache[B] = (fwd, step)
        return cache[B]

    def forward(self, x) -> jnp.ndarray:
        x = jnp.asarray(x)
        return self._fns(x.shape[0])[0](self.params, x)

    def train_step(self, x, y, lr: float = 1e-2) -> float:
        x, y = jnp.asarray(x), jnp.asarray(y)
        self.params, loss = self._fns(x.shape[0])[1](
            self.params, x, y, jnp.float32(lr))
        return loss

    def stage_params(self, s: int):
        """Unflattened param tree of stage ``s`` (for parity checks /
        exporting back into a model). ``np.array``, not ``np.asarray``:
        the caller gets OWNING host copies, never views of the live
        device payload (the PR-3 owning-copy discipline)."""
        return self._unflattens[s](np.array(jax.device_get(self.params))[s])


def pipeline_from_mln(model, mesh: Mesh, n_micro: int, axis: str = "stage",
                      cuts=None, example_input=None):
    """Adapter from a ``MultiLayerNetwork`` to a pipeline.

    Without ``cuts`` (legacy form): the model must be S REPEATED same-shape
    blocks — the [S, ...]-stacked homogeneous construction (VERDICT r3
    item 3c).

    With ``cuts`` (round 5): ``cuts`` lists the first layer index of each
    stage after the first (e.g. ``cuts=[3]`` splits layers 0–2 | 3–end into
    2 stages), mapping ARBITRARY contiguous layer runs — conv front / dense
    head, transformer block splits — onto a :class:`HeterogeneousPipeline`.
    ``example_input`` (one batch-shaped array or shape tuple) is required
    to derive the inter-stage activation shapes. Stages run with
    ``training=False`` layer semantics (no dropout) and stateful layers
    (BatchNorm running stats) are refused, as in the legacy form.
    """
    if cuts is not None:
        return _pipeline_from_mln_het(model, mesh, n_micro, axis, cuts,
                                      example_input)
    return _pipeline_from_mln_homogeneous(model, mesh, n_micro, axis)


def _pipeline_from_mln_het(model, mesh, n_micro, axis, cuts, example_input):
    if example_input is None:
        raise ValueError("cuts=... needs example_input to derive "
                         "inter-stage activation shapes")
    layers = model.conf.layers
    cut_list = sorted(int(c) for c in cuts)
    if (len(set(cut_list)) != len(cut_list)
            or any(c <= 0 or c >= len(layers) for c in cut_list)):
        raise ValueError(
            f"bad cuts {cuts} for {len(layers)} layers: cut indices must "
            f"be unique and in (0, {len(layers)})")
    bounds = [0] + cut_list + [len(layers)]
    runs = list(zip(bounds[:-1], bounds[1:]))
    S = mesh.shape[axis]
    if len(runs) != S:
        raise ValueError(f"cuts give {len(runs)} stages but mesh axis "
                         f"{axis!r} has {S} devices")
    for i in range(len(layers)):
        if model._states[i]:
            raise ValueError(
                f"layer {i} carries state ({list(model._states[i])}) — "
                "stateful layers (BatchNorm) cannot ride this pipeline")

    key = jax.random.PRNGKey(0)

    def make_stage(lo, hi):
        def fn(params, x):
            for i in range(lo, hi):
                pre = model.conf.preprocessors.get(i)
                if pre is not None:
                    x = pre(x)
                x, _ = layers[i].apply(params[str(i)], x, {}, False, key)
            return x

        return fn

    stage_fns = [make_stage(lo, hi) for lo, hi in runs]
    params_list = [{str(i): model._params[i] for i in range(lo, hi)}
                   for lo, hi in runs]

    x = (jnp.zeros(example_input, jnp.float32)
         if isinstance(example_input, (tuple, list))
         else jnp.asarray(example_input))
    in_shapes, out_shapes = [], []
    cur = jax.eval_shape(lambda a: a, x)
    for s, fn in enumerate(stage_fns):
        in_shapes.append(tuple(cur.shape[1:]))
        cur = jax.eval_shape(fn, params_list[s],
                             jax.ShapeDtypeStruct(cur.shape, jnp.float32))
        out_shapes.append(tuple(cur.shape[1:]))
    pp = HeterogeneousPipeline(stage_fns, params_list, in_shapes,
                               out_shapes, mesh, n_micro, axis)
    pp.model = model
    pp._runs = runs
    return pp


def _pipeline_from_mln_homogeneous(model, mesh: Mesh, n_micro: int,
                                   axis: str = "stage") -> "PipelineParallel":
    """S REPEATED same-shape blocks → [S, ...]-stacked pipeline.

    Constraint (documented, inherent to the [S, ...]-stacked construction):
    every layer must be the same class with identical param tree shapes and
    same input/output shape, and be stateless (no BatchNorm running state) —
    e.g. a stack of Dense(n→n) blocks or identical transformer/attention
    blocks. Heterogeneous models (ResNet/BERT stage cuts) go through
    ``cuts=...`` → :class:`HeterogeneousPipeline`.
    """
    layers = model.conf.layers
    S = mesh.shape[axis]
    if len(layers) != S:
        raise ValueError(f"model has {len(layers)} layers but the "
                         f"{axis!r} mesh axis has {S} stages")
    # the shared identical-blocks contract (also PipelineTrainer's):
    # full config equality, stateless, no preprocessors — stage_fn runs
    # every stage with layer 0's program, so any divergence would
    # silently change the math
    _check_identical_blocks(model)
    l0 = layers[0]
    key = jax.random.PRNGKey(0)

    def stage_fn(p, x):
        out, _ = l0.apply(p, x, {}, False, key)
        return out

    pp = PipelineParallel(stage_fn,
                          [model._params[i] for i in range(S)],
                          mesh, n_micro, axis)
    pp.model = model
    return pp


class PipelineParallel:
    """Convenience wrapper: holds stacked stage params sharded over the
    mesh axis and exposes jitted forward / train_step.

    Checkpoint story (ISSUE 14 satellite): when built through
    :func:`pipeline_from_mln` (homogeneous form) the source model rides
    along and :meth:`snapshot`/:meth:`restore` route the stage params
    through ``snapshot_training_state``/``restore_training_state`` —
    on-disk layout is the ordinary per-layer tree, so a pipeline run
    kill+resumes bit-exactly and stays readable by every other path."""

    #: the source MultiLayerNetwork when built via pipeline_from_mln
    model = None

    def __init__(self, stage_fn: Callable, params_list, mesh: Mesh,
                 n_micro: int, axis: str = "stage"):
        self.stage_fn = stage_fn
        self.mesh = mesh
        self.axis = axis
        self.n_micro = n_micro
        stacked = stack_stage_params(params_list)
        self.params = jax.tree.map(
            lambda a: jax.device_put(a, NamedSharding(
                mesh, P(*(axis,) + (None,) * (a.ndim - 1)))), stacked)

        @jax.jit
        def fwd(params, x):
            return pipeline_apply(self.stage_fn, params, x, self.mesh,
                                  self.n_micro, self.axis)

        self._fwd = xprof.register_jit("pipeline/legacy_fwd", fwd)

        @jax.jit
        def step(params, x, y, lr):
            def loss_fn(p):
                out = pipeline_apply(self.stage_fn, p, x, self.mesh,
                                     self.n_micro, self.axis)
                return jnp.mean((out - y) ** 2)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            return new, loss

        self._step = xprof.register_jit("pipeline/legacy_step", step)

    def forward(self, x) -> jnp.ndarray:
        return self._fwd(self.params, jnp.asarray(x))

    def train_step(self, x, y, lr: float = 1e-2) -> float:
        self.params, loss = self._step(self.params, jnp.asarray(x),
                                       jnp.asarray(y), jnp.float32(lr))
        return loss

    # --- checkpoint routing (state lives on the source model) -----------
    def sync_to_model(self) -> None:
        """Write the live [S, ...]-stacked stage params back onto the
        source model as OWNING per-layer copies (``np.array`` first —
        device_get can return zero-copy views on the CPU backend)."""
        if self.model is None:
            raise ValueError("this pipeline was not built from a model "
                             "(pipeline_from_mln); no checkpoint surface")
        host = jax.tree.map(np.array, jax.device_get(self.params))
        n = len(self.model.conf.layers)
        for i in range(n):
            self.model._params[i] = jax.tree.map(
                lambda a, _i=i: jnp.array(a[_i]), host)

    def sync_from_model(self) -> None:
        """Re-stack + re-place the stage params from the source model's
        per-layer trees (after a checkpoint restore)."""
        if self.model is None:
            raise ValueError("this pipeline was not built from a model "
                             "(pipeline_from_mln); no checkpoint surface")
        n = len(self.model.conf.layers)
        stacked = stack_stage_params(
            [self.model._params[i] for i in range(n)])
        self.params = jax.tree.map(
            lambda a: jax.device_put(a, NamedSharding(
                self.mesh, P(*(self.axis,) + (None,) * (a.ndim - 1)))),
            stacked)

    def snapshot(self, listeners=None):
        """Host snapshot through the standard checkpoint machinery."""
        from ..util.checkpoint import snapshot_training_state

        self.sync_to_model()
        return snapshot_training_state(self.model, listeners)

    def restore(self, path: str, listeners=None):
        """Restore a committed checkpoint into the source model AND the
        live stacked params; returns the pipeline cursor."""
        from ..util.checkpoint import restore_training_state

        cursor = restore_training_state(self.model, path,
                                        listeners=listeners)
        self.sync_from_model()
        return cursor


# --------------------------------------------------------------------------
# N-stage GPipe / 1F1B schedules + the self-healing production trainer
# (ISSUE 14; ROADMAP item 2)
# --------------------------------------------------------------------------

SCHEDULES = ("1f1b", "gpipe")


def stage_partition(n_layers: int, stages: int) -> List[tuple]:
    """Contiguous, RE-CUTTABLE layer partition: stage ``s`` owns layers
    ``[runs[s][0], runs[s][1])``, earlier stages absorbing the remainder.
    A remap from S to S' stages is a pure re-cut of the same layer order,
    so the math (and the checkpoint layout, which is per-layer) is
    stage-count-independent."""
    if stages < 1 or n_layers < stages:
        raise ValueError(
            f"cannot cut {n_layers} layers into {stages} stages "
            "(every stage needs at least one layer)")
    base, rem = divmod(n_layers, stages)
    runs, lo = [], 0
    for s in range(stages):
        hi = lo + base + (1 if s < rem else 0)
        runs.append((lo, hi))
        lo = hi
    return runs


def schedule_meta(schedule: str, stages: int, n_micro: int) -> dict:
    """The microbatch tick schedule as DATA: boolean/index tables over the
    (tick, stage) grid, baked as constants into the compiled step AND fed
    to the profiler ledger and the flight-recorder stage lanes — one
    source of truth, so the bubble accounting can never drift from what
    executes.

    Both schedules run T = 2(M+S-1) ticks with one forward OR one
    backward op per stage per busy tick (2M busy of T → the textbook
    bubble fraction (S-1)/(M+S-1) for both). They differ in the
    INTERLEAVE, which is what bounds the stash (saved stage inputs):

    - ``gpipe``: all M forwards (stage s fwd of microbatch m at tick
      s+m), then all M backwards — M microbatches in flight per stage;
    - ``1f1b``: stage s fwd(m) at tick s+2m, bwd(m) at tick 2S-1-s+2m —
      fwd and bwd tick parities differ per stage so they alternate
      without collision, and at most S-s microbatches are in flight at
      stage s (stash depth S, independent of M).

    Backward ops re-run the stage forward under ``jax.vjp`` against the
    stashed INPUT (activation recompute), which is what makes the 1F1B
    stash bound real rather than cosmetic.
    """
    S, M = int(stages), int(n_micro)
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r}; pick one of "
                         f"{SCHEDULES}")
    T = 2 * (M + S - 1)
    t = np.arange(T)[:, None]
    s = np.arange(S)[None, :]
    if schedule == "1f1b":
        df = t - s
        fwd = (df >= 0) & (df % 2 == 0) & (df < 2 * M)
        m_f = np.clip(df // 2, 0, M - 1)
        db = t - (2 * S - 1 - s)
        bwd = (db >= 0) & (db % 2 == 0) & (db < 2 * M)
        m_b = np.clip(db // 2, 0, M - 1)
        stash = min(S, M)
    else:
        df = t - s
        fwd = (df >= 0) & (df < M)
        m_f = np.clip(df, 0, M - 1)
        db = t - (M + 2 * S - 2 - s)
        bwd = (db >= 0) & (db < M)
        m_b = np.clip(db, 0, M - 1)
        stash = M
    assert not (fwd & bwd).any(), "schedule bug: fwd/bwd tick collision"
    assert fwd.sum() == bwd.sum() == M * S, "schedule bug: dropped op"
    lanes = []
    for k in range(S):
        ft = np.where(fwd[:, k])[0]
        bt = np.where(bwd[:, k])[0]
        lanes.append({"fwd": (int(ft[0]), int(ft[-1]) + 1),
                      "bwd": (int(bt[0]), int(bt[-1]) + 1)})
    busy = int(fwd.sum() + bwd.sum())
    return {"schedule": schedule, "T": T, "stash": stash,
            "fwd": fwd, "m_f": m_f, "bwd": bwd, "m_b": m_b,
            "busy_ticks": busy, "tick_slots": T * S,
            "bubble_fraction": 1.0 - busy / float(T * S),
            "lanes": lanes}


def _check_identical_blocks(model) -> int:
    """The homogeneous-pipeline model contract: every layer the same
    class/config/param shapes (so one block program serves every row of
    the re-cuttable stacked layout), stateless, no preprocessors.
    Returns the layer count."""
    import dataclasses

    layers = model.conf.layers

    def conf_sig(layer):
        d = dataclasses.asdict(layer)
        d.pop("name", None)
        return d

    sig0 = jax.tree.map(lambda a: (a.shape, str(a.dtype)), model._params[0])
    conf0 = conf_sig(layers[0])
    for i in range(len(layers)):
        if i and (jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                               model._params[i]) != sig0
                  or type(layers[i]) is not type(layers[0])
                  or conf_sig(layers[i]) != conf0):
            raise ValueError(
                f"layer {i} ({type(layers[i]).__name__}) does not match "
                f"layer 0 ({type(layers[0]).__name__}) — pipeline stages "
                "must be identical same-shape, same-config blocks")
        if model._states[i]:
            raise ValueError(
                f"layer {i} carries state ({list(model._states[i])}) — "
                "stateful layers (BatchNorm) cannot ride this pipeline")
        if model.conf.preprocessors.get(i) is not None:
            raise ValueError(
                f"layer {i} has an input preprocessor — preprocessors "
                "break the identical-blocks contract")
    return len(layers)


def _weighted_mse(out: jnp.ndarray, y: jnp.ndarray,
                  w: jnp.ndarray) -> jnp.ndarray:
    """Default pipeline loss: per-example MSE weighted by the pipeline's
    pad mask, SUMMED (the trainer divides by the global real-row count
    in-graph, so padded rows contribute exactly nothing)."""
    per = jnp.mean(jnp.square(out - y), axis=tuple(range(1, out.ndim)))
    return jnp.sum(per * w)


class PipelineTrainer:
    """N-stage pipeline-parallel training with GPipe or 1F1B schedules,
    composed with the data axis on a ``(data × stage)`` mesh, behind the
    repo's standard fit surface — and self-healing by ELASTIC REMAP.

    Model contract: a ``MultiLayerNetwork`` of L >= ``stages`` IDENTICAL
    stateless blocks (:func:`_check_identical_blocks`); the loss is
    ``loss_fn(out, y, w)`` — a per-microbatch WEIGHTED SUM (default
    :func:`_weighted_mse`) divided in-graph by the global real-row count,
    so the shared input pipeline's shape-stable pad rows are inert.

    Mechanics: the L layers are cut into contiguous runs
    (:func:`stage_partition`) and stacked into ``[stages * rows, ...]``
    arrays sharded over the ``stage`` mesh axis (pad rows masked, with
    exactly-zero gradients). One ``lax.scan`` over the tick tables of
    :func:`schedule_meta` runs the whole M-microbatch forward+backward
    AND the updater as ONE compiled dispatch per optimizer step: each
    busy tick a stage applies its run to the activation it holds
    (forward, input stashed) or re-runs it under ``jax.vjp`` against the
    stashed input (backward — activation recompute, the 1F1B memory
    bound); neighbor ``ppermute`` moves activations down and cotangents
    up the pipe. Per-layer gradients accumulate in ascending microbatch
    order and cross-replica sums ride a fixed-width data axis, so the
    loss/gradient sequence is BITWISE-identical across schedules and
    stage counts (and to a single-device microbatched reference) — the
    property the kill-a-stage drill's parity gate rests on. Forward and
    backward tick bodies sit behind ``lax.cond``, so a tick pays only
    for the op its schedule slot actually runs (idle bubble ticks cost
    branch overhead, not stage FLOPs); the bubble is accounted in tick
    slots of the executed mask tables (the ``pipeline`` profiler ledger
    + the smoke bench gate, which polices the TABLES against the
    analytic bound — it is schedule accounting, not a wall-clock
    measurement) and rendered as per-stage Chrome-trace lanes
    (``pipeline/stage_fwd``/``_bwd`` flight-recorder events).

    Self-healing: a stage classified as lost triggers the supervisor's
    ``remap_and_continue`` policy → :meth:`remap` re-cuts the layer
    partition over the surviving stage devices (``mesh.elastic_pool``)
    at a dispatch boundary, re-shards the host-materialized OWNING state
    in memory (the PR-3 donation lesson: ``np.array``, never device_get
    views), and training continues from the exact cursor via
    ``fit(resume_cursor=...)`` — no process restart, no disk. Compiled
    steps, meshes and partitions are cached per (stage-count, schedule):
    one compile per (stage-count, schedule) EVER, so a remap or a
    grow-back to a count already trained at swaps executables. A remap
    can never observe a partially-applied microbatch step: the whole
    schedule plus the update is one XLA dispatch and remap only runs
    between dispatches. Checkpoint-restart stays the fallback whenever
    the remap gate refuses (surviving stages < 2, unidentifiable stage,
    state not boundary-consistent).

    Checkpoints ride the standard machinery unchanged: after every
    dispatch the stacked state is republished onto the model as lazy
    per-layer views (nothing is donated, so the views stay valid through
    the listener window), and ``snapshot_training_state`` sees the
    ordinary per-layer tree — a pipeline checkpoint restores into a
    single-device fit or a different stage count with no format
    negotiation, keyed by stage position only through the partition.
    """

    def __init__(self, model, stages: int, n_micro: int,
                 schedule: str = "1f1b", data: int = 1,
                 loss_fn: Optional[Callable] = None,
                 devices: Optional[List[Any]] = None):
        from .mesh import make_pipeline_mesh

        if schedule not in SCHEDULES:
            raise ValueError(f"unknown schedule {schedule!r}; pick one of "
                             f"{SCHEDULES}")
        if stages < 2:
            raise ValueError("a pipeline needs >= 2 stages; a 1-stage "
                             "'pipeline' is a plain fit")
        model._check_init()
        n_layers = _check_identical_blocks(model)
        if n_layers < stages:
            raise ValueError(f"model has {n_layers} layers but the "
                             f"pipeline wants {stages} stages")
        if int(n_micro) < 1:
            raise ValueError("n_micro must be >= 1")
        self.model = model
        self.schedule = schedule
        self.n_micro = int(n_micro)
        self.data_axis = int(data)
        self.stages_count = int(stages)
        self.mesh = make_pipeline_mesh(self.data_axis, self.stages_count,
                                       devices=devices)
        l0 = model.conf.layers[0]
        key0 = jax.random.PRNGKey(0)

        def block(p, x):
            out, _ = l0.apply(p, x, {}, False, key0)
            return out

        self._block = block
        self._loss_fn = loss_fn or _weighted_mse
        self._listeners: List[Any] = []
        self._telemetry = None
        #: per-(stage-count, schedule) compiled artifacts — step, meta,
        #: mesh, partition, active mask. The elastic contract: one
        #: compile per (stage-count, schedule), total.
        self._exec_cache: dict = {}
        self._lost_devices: set = set()
        self._step = None
        self._meta: Optional[dict] = None
        self._stk_params = None
        self._stk_upd = None
        self._active = None
        self._upd_stacked_keys: set = set()
        self._pub_params = None
        self._set_partition(self.stages_count)

    # --- partition / state layout ---------------------------------------
    def _set_partition(self, stages: int) -> None:
        L = len(self.model.conf.layers)
        self._runs = stage_partition(L, stages)
        self._rows = max(hi - lo for lo, hi in self._runs)
        row_of = []
        for s, (lo, hi) in enumerate(self._runs):
            for l in range(lo, hi):
                row_of.append(s * self._rows + (l - lo))
        self._row_of_layer = row_of
        active = np.zeros((stages * self._rows,), np.float32)
        for r in row_of:
            active[r] = 1.0
        self._active_host = active

    def _stack_host(self, per_layer) -> Any:
        """List of L same-structure HOST layer trees → one host tree with
        leading [stages * rows] axis (pad rows zero)."""
        leaves0, treedef = jax.tree.flatten(per_layer[0])
        flat = [jax.tree.flatten(p)[0] for p in per_layer]
        rows: List[Optional[int]] = []
        for lo, hi in self._runs:
            for r in range(self._rows):
                rows.append(lo + r if lo + r < hi else None)
        out = []
        for i in range(len(leaves0)):
            zero = np.zeros_like(np.asarray(leaves0[i]))
            out.append(np.stack([np.asarray(flat[l][i])
                                 if l is not None else zero for l in rows]))
        return jax.tree.unflatten(treedef, out)

    def _place_stacked(self, host_tree):
        sh = NamedSharding(self.mesh, P("stage"))
        return jax.tree.map(lambda a: jax.device_put(a, sh), host_tree)

    def _restack_from_host(self, host_p, host_u) -> None:
        """Host per-layer state → placed stacked device state + published
        per-layer views. The single restack path (first fit, checkpoint
        restore, remap)."""
        self._stk_params = self._place_stacked(self._stack_host(host_p))
        self._active = jax.device_put(
            self._active_host, NamedSharding(self.mesh, P("stage")))
        pstruct = jax.tree.structure(host_p)
        self._upd_stacked_keys = set()
        if isinstance(host_u, dict) and host_u:
            stk = {}
            for k, v in host_u.items():
                if jax.tree.structure(v) == pstruct:
                    self._upd_stacked_keys.add(k)
                    stk[k] = self._place_stacked(self._stack_host(v))
                else:
                    stk[k] = jax.tree.map(jnp.array, v)
            self._stk_upd = stk
        else:
            self._stk_upd = {}
        self._publish()

    def _layer_views(self, stacked):
        return [jax.tree.map(lambda a, _r=r: a[_r], stacked)
                for r in self._row_of_layer]

    def _publish(self) -> None:
        """Republish the live stacked state onto the model as per-layer
        views — lazy device slices, no host sync. MUST precede the
        listener callbacks (a checkpoint listener snapshots
        ``model._params`` at iteration boundaries); valid until the next
        dispatch because the step donates nothing."""
        model = self.model
        model._params = self._layer_views(self._stk_params)
        if isinstance(self._stk_upd, dict) and self._stk_upd:
            model._updater_state = {
                k: (self._layer_views(v) if k in self._upd_stacked_keys
                    else v)
                for k, v in self._stk_upd.items()}
        else:
            model._updater_state = self._stk_upd
        self._pub_params = model._params
        model._live_stages = self.stages_count

    def _ensure_state(self) -> None:
        """Bring the model's per-layer state into this trainer's stacked
        placed layout — first fit, after a checkpoint restore replaced
        the params under us (detected by identity vs the last published
        views), or after an external mutation."""
        model = self.model
        if self._stk_params is not None \
                and model._params is self._pub_params:
            return
        if model._updater_state is None:
            model._updater_state = \
                model.conf.global_conf.updater.init(model._params)
        host_p, host_u = jax.tree.map(np.array, jax.device_get(
            (model._params, model._updater_state)))
        self._restack_from_host(host_p, host_u)
        OpProfiler.get().gauge("pipeline/stages", self.stages_count)

    # --- compiled step ---------------------------------------------------
    def _upd_spec(self):
        if isinstance(self._stk_upd, dict) and self._stk_upd:
            return {k: (P("stage") if k in self._upd_stacked_keys else P())
                    for k in self._stk_upd}
        return P()

    def _ensure_step(self) -> None:
        key = (self.stages_count, self.schedule)
        ent = self._exec_cache.setdefault(key, {})
        ent.update(mesh=self.mesh, runs=self._runs, rows=self._rows,
                   row_of=self._row_of_layer, active=self._active_host)
        if ent.get("step") is None:
            ent["meta"] = schedule_meta(self.schedule, self.stages_count,
                                        self.n_micro)
            ent["step"] = self._build_step(
                self.mesh, self.stages_count, self._rows,
                self._row_of_layer, ent["meta"])
        self._step = ent["step"]
        self._meta = ent["meta"]

    def _build_step(self, mesh: Mesh, S: int, R: int, row_of, meta: dict):
        from jax.experimental.shard_map import shard_map

        M = self.n_micro
        T, K = meta["T"], meta["stash"]
        fwd_c = jnp.asarray(meta["fwd"])
        bwd_c = jnp.asarray(meta["bwd"])
        mf_c = jnp.asarray(meta["m_f"])
        mb_c = jnp.asarray(meta["m_b"])
        row_sel = jnp.asarray(np.asarray(row_of, np.int32))
        block = self._block
        loss_fn = self._loss_fn
        updater = self.model.conf.global_conf.updater
        tele = self._telemetry
        perm_f = [(i, (i + 1) % S) for i in range(S)]
        perm_b = [((i + 1) % S, i) for i in range(S)]

        def run_stage(p_rows, active, x):
            # the stage's (padded) run of layers, applied in order; a pad
            # row selects the input unchanged, so its params get EXACTLY
            # zero gradient through the where
            for r in range(R):
                p_r = jax.tree.map(lambda a, _r=r: a[_r], p_rows)
                x = jnp.where(active[r] > 0, block(p_r, x), x)
            return x

        def local(params, active, upd_state, x, y, w, it):
            me = lax.axis_index("stage")
            is_last = me == S - 1
            mb = x.shape[0] // M
            micro_x = x.reshape((M, mb) + x.shape[1:])
            micro_y = y.reshape((M, mb) + y.shape[1:])
            micro_w = w.reshape((M, mb))
            # global real-row divisor, fixed before the schedule runs —
            # every per-microbatch loss/cotangent divides by it, so the
            # accumulated gradient equals the global weighted mean
            denom = jnp.maximum(lax.psum(jnp.sum(w), "data"), 1.0)

            def tick(carry, t):
                fwd_act, bwd_cot, stash, dp, loss_sum = carry
                fwd_on = fwd_c[t, me]
                bwd_on = bwd_c[t, me]
                m_f = mf_c[t, me]
                m_b = mb_c[t, me]
                # forward: stage 0 injects microbatch m_f, later stages
                # consume the neighbor activation that arrived last
                # tick. lax.cond so an idle/backward tick pays no
                # forward FLOPs (bubbles cost branch overhead, not
                # compute); the schedule is per-device, and no
                # collective sits inside a branch
                x_in = jnp.where(me == 0, micro_x[m_f], fwd_act)
                y_out = lax.cond(fwd_on,
                                 lambda xx: run_stage(params, active, xx),
                                 lambda xx: xx, x_in)
                slot = m_f % K
                stash = stash.at[slot].set(
                    jnp.where(fwd_on, x_in, stash[slot]))

                # backward: re-run the stage under vjp against the
                # stashed input (activation recompute); the last stage
                # seeds the cotangent from the loss, everyone else from
                # the neighbor cotangent that arrived last tick. Also
                # behind a cond — a fwd/idle tick pays no vjp.
                def bwd(ops):
                    x_sv, y_mb, w_mb, cot = ops
                    y_sv, vjp_fn = jax.vjp(
                        lambda p, xx: run_stage(p, active, xx),
                        params, x_sv)
                    l_m = loss_fn(y_sv, y_mb, w_mb) / denom
                    g_seed = jax.grad(
                        lambda yy: loss_fn(yy, y_mb, w_mb) / denom)(y_sv)
                    return vjp_fn(jnp.where(is_last, g_seed, cot)) + (l_m,)

                def bwd_skip(ops):
                    return (jax.tree.map(jnp.zeros_like, params),
                            jnp.zeros_like(ops[0]), jnp.float32(0.0))

                dp_m, dx, l_m = lax.cond(
                    bwd_on, bwd, bwd_skip,
                    (stash[m_b % K], micro_y[m_b], micro_w[m_b], bwd_cot))
                # ascending-m accumulation; adding the skip branch's
                # exact zeros is bitwise-neutral, which is what makes the
                # two schedules (and any stage count) produce identical
                # gradients
                dp = jax.tree.map(lambda a, d: a + d, dp, dp_m)
                loss_sum = loss_sum + jnp.where(bwd_on & is_last,
                                                l_m, 0.0)
                return (lax.ppermute(y_out, "stage", perm_f),
                        lax.ppermute(dx, "stage", perm_b),
                        stash, dp, loss_sum), None

            zero_act = jnp.zeros((mb,) + x.shape[1:], x.dtype)
            carry0 = (_pvary(zero_act, "stage"),
                      _pvary(zero_act, "stage"),
                      _pvary(jnp.zeros((K, mb) + x.shape[1:], x.dtype),
                             "stage"),
                      jax.tree.map(jnp.zeros_like, params),
                      jnp.float32(0.0))
            (_, _, _, dp, loss_sum), _ = lax.scan(tick, carry0,
                                                  jnp.arange(T))
            dp = jax.tree.map(lambda a: lax.psum(a, "data"), dp)
            # only the last stage accumulated loss; the stage psum
            # broadcasts it (summing exact zeros elsewhere)
            loss = lax.psum(lax.psum(loss_sum, "data"), "stage")
            new_params, new_upd = updater.apply(dp, upd_state, params, it)
            if tele is None:
                return new_params, new_upd, loss

            def rows_sumsq(tree):
                tot = jnp.zeros((R,), jnp.float32)
                for leaf in jax.tree.leaves(tree):
                    tot = tot + jnp.sum(
                        jnp.square(leaf.astype(jnp.float32)).reshape(R, -1),
                        axis=1)
                return tot

            nf = jnp.zeros((R,), jnp.int32)
            for leaf in jax.tree.leaves(dp):
                nf = nf + jnp.sum(
                    (~jnp.isfinite(leaf)).astype(jnp.int32).reshape(R, -1),
                    axis=1)

            def per_layer(v):
                # local [R] rows → [S*R] over the stage axis → [L] slots
                return lax.all_gather(v, "stage", tiled=True)[row_sel]

            grad_norm = jnp.sqrt(per_layer(rows_sumsq(dp)))
            update_norm = jnp.sqrt(per_layer(rows_sumsq(
                jax.tree.map(lambda n, o: n - o, new_params, params))))
            param_norm = jnp.sqrt(per_layer(rows_sumsq(new_params)))
            nf_l = per_layer(nf)
            aux = {
                "loss": loss,
                "grad_norm": grad_norm,
                "update_norm": update_norm,
                "param_norm": param_norm,
                "update_ratio": update_norm / jnp.maximum(param_norm,
                                                          1e-12),
                "nonfinite": nf_l,
                "nonfinite_total": (jnp.sum(nf_l).astype(jnp.int32)
                                    + (~jnp.isfinite(loss)).astype(
                                        jnp.int32)),
            }
            return new_params, new_upd, loss, aux

        pspec = P("stage")
        uspec = self._upd_spec()
        out_specs = (pspec, uspec, P())
        if tele is not None:
            out_specs += (P(),)
        sharded = shard_map(
            local, mesh=mesh,
            in_specs=(pspec, P("stage"), uspec, P("data"), P("data"),
                      P("data"), P()),
            out_specs=out_specs, check_rep=False)

        def step(*args):
            OpProfiler.get().count("trace/pipeline_fit_step")
            return sharded(*args)

        return xprof.register_jit("pipeline/fit_step", jax.jit(step))

    # --- fit surface -----------------------------------------------------
    def set_listeners(self, *ls) -> None:
        from ..nn.train_step import group_listeners

        self._listeners = list(ls)
        cfg = group_listeners(self._listeners)
        if cfg != self._telemetry:
            # in-graph telemetry is a build-time property of the step —
            # drop every cached executable (meta/mesh/partition stay)
            self._telemetry = cfg
            for ent in self._exec_cache.values():
                ent.pop("step", None)
            self._step = None

    def _bind_batch(self, ds, w):
        x = ds.features.to_numpy()
        y = ds.labels.to_numpy()
        if ds.labels_mask is not None:
            raise ValueError(
                "labels masks do not ride the pipeline trainer; the "
                "example-weight vector carries the pad discipline")
        self.model._last_batch_size = int(x.shape[0])
        return x, y, np.asarray(w, np.float32)

    def _pre_dispatch(self, ordinal: int) -> None:
        # the pipeline-specific drill site, sharing the fit call's
        # dispatch ordinal: device_loss names a STAGE (→ remap drill),
        # slow is a straggler stage, wedge a hung schedule
        faultinject.fault_point("pipeline/stage", ordinal)

    def _emit_stage_lanes(self, meta: dict, t0: float, t1: float) -> None:
        """Derived per-stage Chrome-trace lanes: the dispatch wall time
        split over the tick grid, one fwd WINDOW slice and one bwd
        WINDOW slice per stage on separate sub-lanes (fwd and bwd
        interleave under 1F1B, and partially-overlapping slices on ONE
        Perfetto track render wrong). Each slice spans first..last op of
        its direction — under 1F1B's steady state every other tick in
        the window belongs to the opposite direction, recorded as
        ``tick_stride`` — so the warmup/cooldown bubbles are the leading/
        trailing gaps on each lane."""
        tick = max(t1 - t0, 1e-9) / meta["T"]
        stride = 2 if meta["schedule"] == "1f1b" else 1
        for s, lane in enumerate(meta["lanes"]):
            flo, fhi = lane["fwd"]
            blo, bhi = lane["bwd"]
            flightrec.event("pipeline/stage_fwd", stage=s,
                            micro=self.n_micro, tick_stride=stride,
                            lane=f"pipeline/stage{s}/fwd",
                            dur_s=(fhi - flo) * tick,
                            ts_mono=t0 + fhi * tick)
            flightrec.event("pipeline/stage_bwd", stage=s,
                            micro=self.n_micro, tick_stride=stride,
                            lane=f"pipeline/stage{s}/bwd",
                            dur_s=(bhi - blo) * tick,
                            ts_mono=t0 + bhi * tick)

    def _dispatch_one(self, b, prof) -> None:
        from ..data import pipeline as _pipe

        model = self.model
        xs, ys, ws = b
        meta = self._meta
        t0 = time.monotonic()
        with prof.time_section("pipeline/dispatch"):
            out = self._step(self._stk_params, self._active, self._stk_upd,
                             xs, ys, ws, jnp.asarray(model._iteration))
        self._stk_params, self._stk_upd = out[0], out[1]
        loss = out[2]
        aux = out[3] if self._telemetry is not None else None
        self._publish()
        prof.count("pipeline/microbatches", self.n_micro)
        prof.count("pipeline/busy_ticks", meta["busy_ticks"])
        prof.count("pipeline/tick_slots", meta["tick_slots"])
        if flightrec.enabled():
            self._emit_stage_lanes(meta, t0, time.monotonic())
        _pipe.note_steps(model, self._listeners, [loss],
                         [aux] if aux is not None else None)

    def fit(self, data, epochs: int = 1, batch_size: Optional[int] = None,
            *, pad_partial: Optional[bool] = None,
            drop_remainder: bool = False, prefetch: int = 2,
            resume_from: Optional[str] = None,
            resume_cursor: Optional[tuple] = None) -> None:
        """Pipeline-parallel training on the shared input/dispatch
        pipeline: batches pad to a multiple of data_axis × n_micro
        (shape-stable microbatches), placement is sharded over the data
        axis, and each dispatch runs the whole microbatch schedule plus
        the update as one compiled step. ``resume_from``: exact
        checkpoint resume through the PR-3 machinery (per-layer on-disk
        layout — stage-count-independent). ``resume_cursor=(epochs_done,
        steps_in_epoch)``: in-memory continuation from the holder's live
        state at a dispatch boundary (the supervisor's remap-and-continue
        path)."""
        from ..nn.train_step import _same_shapes
        from ..util.checkpoint import begin_fit_cursor
        from ..data import pipeline as _pipe
        from .mesh import shard_batch

        model = self.model
        model._check_init()
        if not self._listeners and getattr(model, "_listeners", None):
            self.set_listeners(*model._listeners)
        if resume_cursor is not None:
            if resume_from is not None:
                raise ValueError(
                    "resume_from and resume_cursor are mutually exclusive")
            skip = (int(resume_cursor[0]), int(resume_cursor[1]))
            model._fit_epoch0 = model._epoch - skip[0]
            model._steps_in_epoch = skip[1]
        else:
            # a restore replaces the per-layer params under us; nothing
            # is donated, so cached executables stay valid — only the
            # stacked placement rebuilds (_ensure_state detects the
            # identity change)
            skip = begin_fit_cursor(model, resume_from,
                                    listeners=self._listeners)
        self._ensure_state()
        self._ensure_step()
        # re-stamp liveness after the begin_fit_cursor anchor cleared it
        # (per-fit metadata: only pipeline fits record a stage count)
        model._live_stages = self.stages_count
        prof = OpProfiler.get()
        prof.gauge("pipeline/stages", self.stages_count)

        def on_epoch():
            model._epoch += 1
            model._steps_in_epoch = 0
            for lst in self._listeners:
                if hasattr(lst, "epoch_done"):
                    lst.epoch_done(model, model._epoch)

        _pipe.run_epochs(
            data, epochs, batch_size,
            pad_partial=True if pad_partial is None else pad_partial,
            drop_remainder=drop_remainder, prefetch=prefetch,
            steps_per_dispatch=1,
            bind=self._bind_batch,
            place=lambda b: shard_batch(self.mesh, *b),
            dispatch_one=lambda b: self._dispatch_one(b, prof),
            dispatch_chunk=lambda g: None,
            stackable=_same_shapes, on_epoch=on_epoch,
            round_to_multiple_of=self.data_axis * self.n_micro, skip=skip,
            pre_dispatch=self._pre_dispatch)

    # --- elastic remap (shrink/grow the stage axis, no restart) ----------
    def remap(self, stages: int, *, lost_stages=None) -> List[Any]:
        """Online elastic REMAP of the pipeline at a DISPATCH BOUNDARY:
        re-cut the layer partition over ``stages`` stage columns of
        surviving devices, re-shard the training state in memory — no
        process restart, no disk.

        Exact by construction: the per-layer state is host-materialized
        with OWNING copies and re-stacked under the new partition (a pure
        permutation — the same guarantee as checkpoint resharding), and
        the schedule math is stage-count-independent, so the post-remap
        loss sequence is bitwise-equal to a fresh run at the surviving
        count handed the same state/cursor/RNG. Compiled steps are cached
        per (stage-count, schedule); a remap (or grow-back) to a count
        already trained at reuses its executable and mesh.

        ``lost_stages``: stage indices whose device column is gone; their
        devices are excluded from the new mesh and remembered ACROSS
        calls — a later remap re-probes every once-lost device and only
        lets it rejoin after it answers. Returns the devices removed —
        the supervisor's grow-back probe targets.

        Consistency rule (documented for the README): a remap can never
        observe a partially-applied microbatch step — the whole schedule
        plus update is one XLA dispatch, and remap only runs between
        dispatches (or after a fit unwound at a step boundary)."""
        from .mesh import elastic_pool, make_pipeline_mesh, probe_device

        S_new = int(stages)
        old = self.stages_count
        if S_new < 2:
            raise ValueError(
                "a pipeline needs >= 2 stages; shrinking below that is "
                "the remap gate's refusal case (checkpoint-restart owns "
                "it)")
        if S_new > len(self.model.conf.layers):
            raise ValueError(
                f"model has {len(self.model.conf.layers)} layers; cannot "
                f"cut into {S_new} stages")
        lost = sorted({int(s) for s in (lost_stages or ())})
        if any(s < 0 or s >= old for s in lost):
            raise ValueError(f"lost_stages {lost} out of range for "
                             f"{old} stages")
        if S_new == old and not lost:
            return []
        prof = OpProfiler.get()
        with flightrec.span("pipeline/remap", severity="warn",
                            stages_from=old, stages_to=S_new, lost=lost), \
                prof.time_section("pipeline/remap"):
            # 1) host-materialize the per-layer training state with
            # OWNING copies (np.array — never device_get views)
            model = self.model
            self._ensure_state()
            host_p, host_u = jax.tree.map(np.array, jax.device_get(
                (model._params, model._updater_state)))
            # 2) stash this count's artifacts, then reuse or rebuild the
            # target count's mesh+partition. Once-lost devices are
            # remembered across calls and re-probed: a cached mesh can
            # never silently reinstate a still-dead device.
            ent = self._exec_cache.setdefault((old, self.schedule), {})
            ent.update(mesh=self.mesh, runs=self._runs, rows=self._rows,
                       row_of=self._row_of_layer, active=self._active_host)
            old_devs = list(self.mesh.devices.flat)
            lost_devs = [d for s in lost
                         for d in self.mesh.devices[:, s].tolist()]
            self._lost_devices = {d for d in self._lost_devices
                                  if not probe_device(d)}
            self._lost_devices |= set(lost_devs)
            cached = self._exec_cache.get((S_new, self.schedule))
            if cached is not None and cached.get("mesh") is not None \
                    and not (self._lost_devices
                             & set(cached["mesh"].devices.flat)):
                self.mesh = cached["mesh"]
                self._runs = cached["runs"]
                self._rows = cached["rows"]
                self._row_of_layer = cached["row_of"]
                self._active_host = cached["active"]
            else:
                pool = elastic_pool(self.mesh,
                                    exclude=self._lost_devices)
                need = self.data_axis * S_new
                if need > len(pool):
                    raise ValueError(
                        f"remap to {S_new} stages needs {need} devices; "
                        f"only {len(pool)} are available")
                self.mesh = make_pipeline_mesh(self.data_axis, S_new,
                                               devices=pool[:need])
                self._set_partition(S_new)
                if cached is not None:
                    cached.pop("step", None)
            self.stages_count = S_new
            new_devs = set(self.mesh.devices.flat)
            removed = [d for d in old_devs if d not in new_devs]
            # 3) re-stack + place under the new partition, republish
            self._restack_from_host(host_p, host_u)
            self._step = None
            self._meta = None
            prof.gauge("pipeline/stages", S_new)
        prof.count("pipeline/remaps")
        logger.warning("pipeline remap: %d -> %d stages%s", old, S_new,
                       f" (lost stages {lost})" if lost else "")
        return removed

    def resize(self, stages: int, *, lost_replicas=None) -> List[Any]:
        """Supervisor-facing alias: the grow-back machinery drives every
        elastic target through ``resize`` — for a pipeline that means a
        stage-count remap."""
        return self.remap(stages, lost_stages=lost_replicas)

    def probe_stages(self) -> List[int]:
        """Stage indices with any device failing the tiny round-trip
        probe — the ground-truth check behind remap-and-continue when a
        failure did not name the lost stage itself."""
        from .mesh import probe_device

        cols = self.mesh.devices
        return [s for s in range(self.stages_count)
                if any(not probe_device(d) for d in cols[:, s].tolist())]

    def shutdown(self) -> None:
        self._step = None
        self._exec_cache.clear()
