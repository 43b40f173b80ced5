"""One training step and one fit loop for a network whose state is a tree
on the device.

``MultiLayerNetwork`` and ``ComputationGraph`` differ in the shape of a
batch and in how a loss is computed from it; everything after the loss is
the same. A network gives a trainer

- ``_bind(ds) -> batch``: a DataSet as the pytree its loss reads
  (``(x, y, mask, fmask)`` / ``(inputs, labels, masks)``);
- ``_loss_of(params, states, batch, key, *, training=True, w=None,
  w_denom=None, ...) -> (loss, new_states)``: the loss, under the scope
  ``forward`` (so every trainer that differentiates it inherits the name);
- ``_keyed_layers()``: ``(key, layer)`` for every entry of its params
  container, which says what to keep after an update (``FrozenLayer``) and
  what to project (``constraints``).

and this module writes the rest once, under the step's fixed scopes
(``jax.named_scope``: :data:`FORWARD` on the networks' ``_loss_of``,
:data:`UPDATE` with ``grad_norm``, ``updater``, ``sr`` and ``constraints``
inside, ``telemetry``, each vertex under :func:`vertex_scope`;
``common.xprof.scope_times`` reads them back from a trace): the update
epilogue (:func:`update`),
the telemetry tail (:func:`finish`), the step body (:func:`make_core`), the
per-step and ``lax.scan`` chunk programs (:func:`step_program`,
:func:`chunk_program`; the network jits them under its census names, which
the census lint wants as literals) and the fit loop with the state it carries
(:class:`FitLoop`). ``FleetTrainer`` vmaps the same core;
``ParallelWrapper`` keeps its own differentiation, because its collectives
stand between the gradient and the update, and calls :func:`update` and
:func:`finish` after them.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..common.profiler import OpProfiler
from ..data import pipeline as _pipe
from ..learning.precision import apply_updater, note_state_bytes
from ..ndarray.ndarray import NDArray
from ..ndarray.rng import get_random
from ..optimize import telemetry as _tel
from .conf import layers as L

# leaves that constraints leave alone (reference BaseConstraint: weights
# only, biases and norm params excluded)
_UNCONSTRAINED = ("b", "beta", "gamma", "mean", "var", "centers")
#: the step's phase scopes (``common.xprof.classify_scope`` reads them back)
FORWARD, UPDATE = "forward", "update"


# --- the step -----------------------------------------------------------------

def _normalize_gradients(grads, mode: str, threshold: float):
    mode = mode.lower()
    if mode == "clipelementwiseabsolutevalue":
        return jax.tree.map(lambda g: jnp.clip(g, -threshold, threshold), grads)
    if mode == "clipl2pergradient":
        def clip(g):
            n = jnp.sqrt(jnp.sum(jnp.square(g)))
            return jnp.where(n > threshold, g * (threshold / n), g)

        return jax.tree.map(clip, grads)
    if mode == "clipl2perparamtype" or mode == "renormalizel2perlayer":
        leaves = jax.tree.leaves(grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
        scale = jnp.minimum(1.0, threshold / jnp.maximum(gnorm, 1e-12))
        return jax.tree.map(lambda g: g * scale, grads)
    raise ValueError(f"unknown gradient normalization {mode!r}")


def _fold_weights(mask, w):
    """Fold per-example weights ``w`` [B] into an (optional) loss mask —
    the padded-batch contract: pad rows carry w=0, so their per-element
    loss terms multiply to exactly 0.0."""
    if mask is None:
        return w
    wb = w
    while wb.ndim < mask.ndim:
        wb = wb[..., None]
    return mask * wb


def vertex_scope(key) -> str:
    """The ``jax.named_scope`` of one entry of a network's params container:
    a graph vertex's name (a ``/`` would read as a further scope), a
    ``MultiLayerNetwork`` layer's index as ``layer<i>``."""
    return f"layer{key}" if isinstance(key, int) else str(key).replace("/", ".")


@jax.named_scope(UPDATE)
def update(model, updater, grads, upd_state, params, iteration, key):
    """The update epilogue: gradient normalisation
    (``GlobalConf.grad_normalization``) → ``apply_updater`` → frozen layers
    restored → constraints projected. Returns ``(grads, new_params,
    new_upd)``, the gradients as the updater got them. Scope ``update``;
    ``apply_updater`` names ``updater`` and ``sr`` inside it."""
    gc = model.conf.global_conf
    if gc.grad_normalization:
        with jax.named_scope("grad_norm"):
            grads = _normalize_gradients(grads, gc.grad_normalization,
                                         gc.grad_norm_threshold)
    new_params, new_upd = apply_updater(updater, grads, upd_state, params,
                                        iteration, key)
    for k, layer in model._keyed_layers():
        if isinstance(layer, L.FrozenLayer):
            # stop_gradient already zeroes their grads; restoring the
            # original tensors also shields them from stateful-updater
            # side effects (weight decay, momentum drift)
            new_params[k] = params[k]
        if getattr(layer, "constraints", None):
            with jax.named_scope("constraints"):
                new_params[k] = {
                    name: (leaf if name in _UNCONSTRAINED
                           else _project(layer.constraints, leaf))
                    for name, leaf in new_params[k].items()}
    return grads, new_params, new_upd


def _project(constraints, leaf):
    for c in constraints:
        leaf = c.apply(leaf)
    return leaf


def needs_tree_update(model) -> Optional[str]:
    """What of :func:`update` a configuration asks for beyond the updater
    itself, or None — for a path that updates flat shards (ZeRO-1) and so
    has to refuse it."""
    if model.conf.global_conf.grad_normalization:
        return "gradient_normalization"
    for _, layer in model._keyed_layers():
        if isinstance(layer, L.FrozenLayer):
            return "a FrozenLayer"
        if getattr(layer, "constraints", None):
            return "layer constraints"
    return None


def finish(tele, loss, old, new, grads=None, *, aux=None, nonfinite=None,
           extra=None):
    """The telemetry tail. ``old`` and ``new`` are ``(params, states,
    upd_state)`` before and after the update. Without telemetry the step
    returns ``(*new, loss)``; with it the in-graph aux pytree rides along
    (per-layer grad/update/param norms, update:param ratio, non-finite
    counts — optimize.telemetry) and, under ``nan_guard``, a step with a
    non-finite gradient carries ``old`` forward. These 4- and 5-tuples
    are what ``data.pipeline.note_dispatch`` unpacks. ``aux``: statistics
    the caller already has (ZeRO-1's sharded ones); ``nonfinite``: counts
    taken on raw per-shard gradients; ``extra``: further aux entries."""
    if tele is None:
        return (*new, loss)
    with jax.named_scope("telemetry"):
        if aux is None:
            aux = _tel.layer_stats(old[0], new[0], grads, loss,
                                   nonfinite=nonfinite)
        if extra:
            aux.update(extra)
        if tele.nan_guard:
            aux, *new = _tel.apply_nan_guard(aux, new[0], old[0], new[1],
                                             old[1], new[2], old[2])
    return (*new, loss, aux)


def make_core(model, tele):
    """``core(params, states, upd_state, batch, key, iteration, w,
    hyper=None)``: the one train-step computation, shared verbatim by the
    per-step jit, the multi-step ``lax.scan`` dispatch and the vmapped
    fleet, so the paths cannot drift numerically.

    ``hyper`` (default None — the solo paths never pass it): a dict of
    TRACED per-call scalar hyperparameter overrides, the vmapped-fleet
    sweep hook (parallel.fleet). Recognized keys: ``lr`` replaces the
    updater's learning rate, ``l2`` replaces every layer's effective l2
    (handed to the network's ``_loss_of``), and ``dropout`` replaces the
    rate of every layer whose input dropout is configured on. Scalars must
    be float64 (weak-Python-float matching under x64) so an override equal
    to the baked value is bitwise identical to the solo step."""
    updater = model.conf.global_conf.updater

    def core(params, states, upd_state, batch, key, iteration, w,
             hyper=None):
        hp = {k: _weak_scalar(v) for k, v in (hyper or {}).items()}
        up = (dataclasses.replace(updater, learning_rate=hp["lr"])
              if "lr" in hp else updater)
        l2 = {"l2": hp["l2"]} if "l2" in hp else {}

        def loss_fn(p):
            with (L.dropout_rate_override(hp["dropout"]) if "dropout" in hp
                  else contextlib.nullcontext()):
                return model._loss_of(p, states, batch, key, w=w, **l2)

        (loss, new_states), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        OpProfiler.get().gauge("precision/grads_flat_in_step", 0)
        grads, new_params, new_upd = update(model, up, grads, upd_state,
                                            params, iteration, key)
        return finish(tele, loss, (params, states, upd_state),
                      (new_params, new_states, new_upd), grads)

    return core


def _weak_scalar(v):
    """Re-weak-type a traced f64 hyperparameter scalar so it promotes
    EXACTLY like the Python float it overrides (a strong f64 tracer
    would widen f32 updater math to f64 — a different computation, not
    just different bits). Uses jax's internal weak-type convert — the
    same mechanism jnp uses for Python scalars; if the private API moves,
    the override still works strong-typed with ulp-level (documented)
    deviation from the baked-constant run."""
    try:
        from jax._src.lax.lax import _convert_element_type

        return _convert_element_type(v, jnp.dtype(jnp.float64), weak_type=True)
    except (ImportError, TypeError):    # pragma: no cover - jax internals
        return v


def step_program(core, counter: str, batch_len: int):
    """The per-step program of ``core``, for the network to jit (donating
    arguments 0-2) under its census name. Its positional layout is the
    one callers outside the loop (``bench.py``, tests) and the benchmark's
    compiled module already have: the batch's first three members,
    ``key``, ``iteration``, then the batch's further members and ``w``,
    the trailing ones optional. The function is named ``step``: the
    benchmark finds the executable and its trace events by ``jit_step``."""
    n_tail = batch_len - 3 + 1

    def step(params, states, upd_state, a, b, c, key, iteration, *tail):
        OpProfiler.get().count(counter)
        if len(tail) > n_tail:
            raise TypeError(f"step takes at most {n_tail} arguments after "
                            f"iteration, got {len(tail)}")
        tail += (None,) * (n_tail - len(tail))
        return core(params, states, upd_state, (a, b, c) + tail[:-1], key,
                    iteration, tail[-1])

    return step


def chunk_program(core, counter: str):
    """Multi-step dispatch (``steps_per_dispatch=K``), for the network to
    jit as :func:`step_program`'s: one module runs K minibatches through
    a ``lax.scan`` device loop over the stacked chunk — Python dispatch,
    listener sync, and H2D fencing amortize over K steps."""

    def chunk(params, states, upd_state, batches, keys, iteration0, ws):
        OpProfiler.get().count(counter)

        def body(carry, inp):
            *state, it = carry
            out = core(*state, inp[0], inp[1], it, inp[2])
            # with telemetry, aux rides the scan's stacked outputs:
            # [K, ...] per leaf
            return (*out[:3], it + 1), out[3:]

        (params, states, upd_state, _), ys = jax.lax.scan(
            body, (params, states, upd_state, iteration0),
            (batches, keys, ws))
        return (params, states, upd_state, *ys)

    return chunk


# --- the loop -----------------------------------------------------------------

def _same_shapes(group) -> bool:
    """True when every bound batch of the chunk is the same pytree with
    the same array shapes — the stacking precondition."""
    def sig(b):
        leaves, treedef = jax.tree.flatten(b)
        return treedef, [tuple(a.shape) for a in leaves]

    first = sig(group[0])
    return all(sig(b) == first for b in group[1:])


def group_listeners(listeners):
    """Introduce a trainer's listeners to each other — checkpoint-style
    listeners snapshot their peers' state (state_dict protocol) for exact
    resume — and return the telemetry configuration they imply."""
    for lst in listeners:
        bind = getattr(lst, "bind_group", None)
        if callable(bind):
            bind(listeners)
    return _tel.config_for(listeners)


class FitLoop:
    """The training state of a tree-state network and the loop that
    advances it. A subclass declares ``_params`` and ``_states``, the
    seam of the module docstring, ``_build_fit_step`` and
    ``_build_chunk_step`` (the two programs, jitted and registered), and:

    - ``_one_batch``: the types of ``data`` that are one batch of one
      stable shape when no ``batch_size`` is given;
    - ``_allow_multi``: whether a ``MultiDataSet`` may be fed.
    """

    _one_batch: tuple
    _allow_multi = False

    def __init__(self, conf):
        self.conf = conf
        self._updater_state = None
        self._initialized = False
        self._iteration = 0
        self._epoch = 0
        self._fit_calls = 0
        self._listeners: List[Any] = []
        self._telemetry = None
        self._fit_step = None
        self._chunk_step = None
        self._infer_fn = None
        self._score_dev = None

    @property
    def score_value(self) -> float:
        return float(self._score_dev) if self._score_dev is not None else float("nan")

    @score_value.setter
    def score_value(self, v) -> None:
        self._score_dev = v

    def _check_init(self) -> None:
        if not self._initialized:
            raise ValueError("call init() first")

    def params(self) -> NDArray:
        """Every parameter, flattened (reference ``params()`` contract)."""
        leaves = jax.tree.leaves(self._params)
        if not leaves:
            return NDArray(jnp.zeros((0,)))
        return NDArray(jnp.concatenate([l.ravel() for l in leaves]))

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(self._params))

    def scope_kinds(self) -> dict:
        """``{vertex scope: layer class name}``: the kind of each vertex
        that the step names (:func:`vertex_scope`), for a reader of
        ``common.xprof.scope_times`` to group vertices without parsing
        their names."""
        return {vertex_scope(k): type(layer).__name__
                for k, layer in self._keyed_layers()}

    def score(self, ds, training: bool = False) -> float:
        """The loss of one DataSet at the current parameters."""
        self._check_init()
        loss, _ = self._loss_of(self._params, self._states, self._bind(ds),
                                get_random().next_key(), training=training)
        return float(loss)

    def compute_gradient_and_score(self, ds):
        """(gradients, score) — the GradientCheckUtil entry point."""
        self._check_init()
        batch, key = self._bind(ds), jax.random.PRNGKey(0)

        def loss_fn(params):
            return self._loss_of(params, self._states, batch, key,
                                 training=False)[0]

        loss, grads = jax.value_and_grad(loss_fn)(self._params)
        self.score_value = float(loss)
        return grads, self.score_value

    def save(self, path: str, save_updater: bool = False) -> None:
        from ..util.model_serializer import write_model

        write_model(self, path, save_updater)

    def _drop_steps(self) -> None:
        """Forget the compiled training programs: a build-time property
        changed, or the buffers they donated were replaced."""
        self._fit_step = None
        self._chunk_step = None

    def set_listeners(self, *listeners) -> None:
        self._listeners = list(listeners)
        cfg = group_listeners(self._listeners)
        if cfg != self._telemetry:
            # telemetry is a build-time property of the jitted step: the
            # aux pytree is computed IN-GRAPH, so flipping it rebuilds the
            # step exactly once (trace/<step> stays 1 per fit config) and
            # adds zero per-iteration host syncs
            self._telemetry = cfg
            self._drop_steps()

    def set_remat_policy(self, policy) -> None:
        """Switch the rematerialization policy in place. Like telemetry,
        the policy is a build-time property of the jitted step: flipping
        it rebuilds the step exactly ONCE on the next fit (one trace/
        compile), after which the loop is steady again — asserted by
        tests/test_remat_policies.py under tracecheck."""
        if policy == self.conf.global_conf.remat_policy:
            return
        self.conf.global_conf.remat_policy = policy
        self._drop_steps()

    def _iter_data(self, data, batch_size=None):
        # one data protocol for serial and pipelined paths alike
        yield from _pipe.iter_datasets(data, batch_size,
                                       allow_multi=self._allow_multi)

    def fit(self, data, epochs: int = 1, batch_size: Optional[int] = None,
            *, pad_partial: Optional[bool] = None,
            drop_remainder: bool = False, prefetch: int = 2,
            steps_per_dispatch: int = 1,
            resume_from: Optional[str] = None) -> None:
        """The north-star loop (SURVEY.md §3.1): per minibatch, ONE compiled
        train-step executes forward+backward+updater on device. The host
        side runs the shared input/dispatch pipeline (data/pipeline.py):

        - ``pad_partial`` (default on when a target batch size is known):
          the final partial batch is padded to the configured batch size
          with a zero example-weight mask threaded into the loss, so the
          step compiles exactly ONCE per fit config instead of retracing
          on the remainder shape; ``drop_remainder=True`` skips it instead.
        - ``prefetch``: device placement of upcoming batches is issued this
          many batches ahead of compute (double-buffered H2D overlap;
          0 = serial feed).
        - ``steps_per_dispatch=K``: run K minibatches per Python dispatch
          through a ``lax.scan`` device loop, syncing loss/listeners once
          per chunk.

        NOTE on padding numerics: the padded run computes the loss the
        unpadded masked-loss run computes for per-example models — to
        reduction order, since a batch of another size is another program
        (tests pin it to a relative 1e-6, and bit-for-bit where the
        shapes are equal). Layers with CROSS-example statistics
        (BatchNormalization) see the wrapped pad rows in their batch
        mean/variance on the final partial batch — the same deliberate
        policy ParallelWrapper has always used (in-distribution wrapped
        rows beat zero rows); pass ``drop_remainder=True`` or
        ``pad_partial=False`` if exact BN parity with the unpadded loop
        matters more than trace stability.

        ``resume_from`` (preemption recovery, SURVEY §5.3): path of a
        checkpoint written by CheckpointListener. Restores params, layer
        states, updater state, iteration/epoch counters, the RNG stream
        key, and listener state, then fast-forwards the input pipeline to
        the checkpoint's cursor — the resumed call must be given the SAME
        data/epochs/batch arguments as the killed one, and its loss
        sequence continues bit-identically (CPU, per-example models)
        where the uninterrupted run would have gone.
        """
        self._check_init()
        prof = OpProfiler.get()
        self._fit_calls += 1
        with prof.time_section("fit/enter", call=self._fit_calls):
            skip = self._begin_fit(resume_from)
            if self._updater_state is None:
                self._updater_state = self.conf.global_conf.updater.init(
                    self._params)
            note_state_bytes(self._updater_state)
            if self._fit_step is None:
                self._fit_step = self._build_fit_step()
        # A single batch with no batch size has one stable shape by
        # construction (the bench hot loops), and a subclass may have a
        # loop of its own per batch (TBPTT) — both stay on the serial path.
        if self._serial_only() or (isinstance(data, self._one_batch)
                                   and batch_size is None):
            self._fit_serial(data, epochs, batch_size, skip=skip)
            return
        if steps_per_dispatch > 1 and self._chunk_step is None:
            self._chunk_step = self._build_chunk_step()
        _pipe.run_epochs(
            data, epochs, batch_size,
            pad_partial=True if pad_partial is None else pad_partial,
            drop_remainder=drop_remainder, prefetch=prefetch,
            steps_per_dispatch=steps_per_dispatch,
            bind=self._bind_fit_batch, place=jax.device_put,
            dispatch_one=lambda b: self._dispatch_one(b, prof),
            dispatch_chunk=lambda g: self._dispatch_chunk(g, prof),
            stackable=_same_shapes, on_epoch=self._on_epoch,
            allow_multi=self._allow_multi, skip=skip,
            first_step=self._iteration)

    def _serial_only(self) -> bool:
        return False

    def _begin_fit(self, resume_from: Optional[str]):
        from ..util.checkpoint import begin_fit_cursor

        return begin_fit_cursor(self, resume_from,
                                listeners=self._listeners)

    def _on_epoch(self) -> None:
        self._epoch += 1
        self._steps_in_epoch = 0
        for lst in self._listeners:
            if hasattr(lst, "epoch_done"):
                lst.epoch_done(self, self._epoch)

    def _bind_fit_batch(self, ds, w):
        """The fit-loop bind: ``(batch, w)`` plus the bookkeeping only
        fit needs (PerformanceListener derives samples/sec from the bound
        batch size)."""
        self._last_batch_size = ds.num_examples()
        return self._bind(ds), w

    def _launch(self, batch, w, key):
        # step_program's positional layout
        return self._fit_step(
            self._params, self._states, self._updater_state, *batch[:3],
            key, jnp.asarray(self._iteration), *batch[3:], w)

    def _dispatch_one(self, b, prof) -> None:
        key = get_random().next_key()
        with prof.time_section("pipeline/dispatch", step=self._iteration):
            out = self._launch(*b, key)
        _pipe.note_dispatch(self, self._listeners, out,
                            self._telemetry is not None)

    def _dispatch_chunk(self, group, prof) -> None:
        batches, ws = jax.tree.map(lambda *leaves: jnp.stack(leaves), *group)
        # keys drawn in batch order — the chunked loop consumes the SAME
        # rng stream the per-step loop would
        keys = jnp.stack([get_random().next_key() for _ in group])
        with prof.time_section("pipeline/dispatch", step=self._iteration,
                               steps=len(group)):
            out = self._chunk_step(self._params, self._states,
                                   self._updater_state, batches, keys,
                                   jnp.asarray(self._iteration), ws)
        _pipe.note_dispatch(self, self._listeners, out,
                            self._telemetry is not None, len(group))

    def _fit_serial(self, data, epochs: int = 1,
                    batch_size: Optional[int] = None, skip=None) -> None:
        skip_epochs, skip_steps = skip if skip is not None else (0, 0)
        for e in range(max(1, epochs)):
            if e < skip_epochs:
                # resume fast-forward: consume (advances iterator state),
                # dispatch nothing; on_epoch effects are already in the
                # restored checkpoint
                for _ in self._iter_data(data, batch_size):
                    pass
                continue
            to_skip = skip_steps if e == skip_epochs else 0
            for ds in self._iter_data(data, batch_size):
                if to_skip:
                    to_skip -= 1
                    continue
                # device scalars throughout; float() only on access (avoids
                # per-step sync). Listeners get the device values too and
                # sync only at their own print/collect/drain boundaries.
                self._serial_step(self._bind(ds), get_random().next_key())
            self._on_epoch()

    def _serial_step(self, batch, key) -> None:
        _pipe.note_dispatch(self, self._listeners,
                            self._launch(batch, None, key),
                            self._telemetry is not None)
