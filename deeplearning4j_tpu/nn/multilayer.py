"""MultiLayerNetwork — the north-star entry point.

Reference: dl4j-nn ``org.deeplearning4j.nn.multilayer.MultiLayerNetwork``
(~4k LoC; SURVEY.md §2.3, §3.1). API surface kept: ``init/fit/output/
feed_forward/score/evaluate/params/save``; the execution model inverted for
TPU: where the reference's fit loop makes ~100+ JNI crossings per iteration
(per-op dispatch through NativeOpExecutioner), here the WHOLE training
iteration — forward, loss, backward, updater — is one jit-compiled XLA module
with donated buffers, executed once per minibatch (SURVEY.md §7.1.1).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..common import xprof
from ..common.profiler import OpProfiler
from ..data import pipeline as _pipe
from ..data.dataset import DataSet
from ..ndarray.ndarray import NDArray
from ..ndarray.rng import get_random
from .conf.builder import MultiLayerConfiguration, remat_wrap
from .conf import layers as L


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = conf.layers
        self._params: List[Dict[str, jnp.ndarray]] = []
        self._states: List[Dict[str, jnp.ndarray]] = []
        self._updater_state = None
        self._initialized = False
        self._iteration = 0
        self._epoch = 0
        self._fit_calls = 0
        self._listeners: List[Any] = []
        self._telemetry = None
        self._fit_step = None
        self._chunk_step = None
        self._tbptt_step = None
        self._infer_fn = None
        self._score_dev = None
        self._rnn_state_map = None

    @property
    def score_value(self) -> float:
        return float(self._score_dev) if self._score_dev is not None else float("nan")

    @score_value.setter
    def score_value(self, v) -> None:
        self._score_dev = v

    # ------------------------------------------------------------------
    def init(self, seed: Optional[int] = None) -> "MultiLayerNetwork":
        if self.conf.input_type is None:
            raise ValueError("configuration needs set_input_type(...) before init()")
        with OpProfiler.get().time_section("build/init"):
            key = jax.random.PRNGKey(
                seed if seed is not None else self.conf.global_conf.seed)
            dtype = jnp.dtype(self.conf.global_conf.dtype)
            self._params = []
            self._states = []
            for layer in self.layers:
                key, sub = jax.random.split(key)
                self._params.append(layer.init_params(sub, dtype)
                                    if layer.has_params else {})
                self._states.append(layer.init_state())
        self._initialized = True
        return self

    def set_listeners(self, *listeners) -> None:
        self._listeners = list(listeners)
        for lst in self._listeners:
            # checkpoint-style listeners snapshot their peers' state
            # (state_dict protocol) for exact resume
            bind = getattr(lst, "bind_group", None)
            if callable(bind):
                bind(self._listeners)
        from ..optimize.telemetry import config_for

        cfg = config_for(self._listeners)
        if cfg != self._telemetry:
            # telemetry is a build-time property of the jitted step: the
            # aux pytree is computed IN-GRAPH, so flipping it rebuilds the
            # step exactly once (trace/<step> stays 1 per fit config) and
            # adds zero per-iteration host syncs
            self._telemetry = cfg
            self._fit_step = None
            self._chunk_step = None
            self._tbptt_step = None

    setListeners = set_listeners

    def set_remat_policy(self, policy) -> None:
        """Switch the rematerialization policy in place. Like telemetry,
        the policy is a build-time property of the jitted step: flipping
        it rebuilds the step exactly ONCE on the next fit (one trace/
        compile), after which the loop is steady again — asserted by
        tests/test_remat_policies.py under tracecheck."""
        if policy == self.conf.global_conf.remat_policy:
            return
        self.conf.global_conf.remat_policy = policy
        self._fit_step = None
        self._chunk_step = None
        self._tbptt_step = None

    # --- parameter access (flattened, reference params() contract) ------
    def params(self) -> NDArray:
        leaves = jax.tree.leaves(self._params)
        if not leaves:
            return NDArray(jnp.zeros((0,)))
        return NDArray(jnp.concatenate([l.ravel() for l in leaves]))

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(self._params))

    def set_params(self, flat: Union[NDArray, np.ndarray]) -> None:
        vec = jnp.asarray(flat.value if isinstance(flat, NDArray) else flat)
        leaves, treedef = jax.tree.flatten(self._params)
        out, off = [], 0
        for l in leaves:
            n = int(np.prod(l.shape))
            out.append(vec[off:off + n].reshape(l.shape).astype(l.dtype))
            off += n
        if off != vec.size:
            raise ValueError(f"param vector length {vec.size} != model params {off}")
        self._params = jax.tree.unflatten(treedef, out)
        self._fit_step = None  # donated buffers were replaced
        self._chunk_step = None

    def param_table(self, layer_idx: int) -> Dict[str, NDArray]:
        return {k: NDArray(v) for k, v in self._params[layer_idx].items()}

    def _cast_compute(self, params, x):
        """Mixed precision: cast activations+params to the compute dtype (bf16
        on TPU); grads flow back through the cast to fp32 master params."""
        cd = self.conf.global_conf.compute_dtype
        if not cd:
            return params, x
        ct = jnp.dtype(cd)
        cast = lambda a: a.astype(ct) if jnp.issubdtype(a.dtype, jnp.floating) else a
        return jax.tree.map(cast, params), cast(x)

    # --- forward ---------------------------------------------------------
    def _apply_layer(self, layer, lp, x, st, training, rng, fmask,
                     idx=None):
        """One layer forward, routing through apply_masked when a
        per-timestep feature mask is present (SURVEY §5.7). Under the
        configured remat policy (GlobalConf.remat_policy, or the legacy
        gradient_checkpointing bool) the layer apply is wrapped in
        jax.checkpoint: backward rematerializes (some of) this layer's
        activations instead of keeping them live across the step. The
        selective-list form matches on the layer INDEX here."""

        def run(lp, x, st, rng, fmask):
            if layer.weight_noise is not None:
                rng, sub = jax.random.split(rng)
                lp = layer.weight_noise.apply(lp, sub, training)
            if fmask is not None:
                return layer.apply_masked(lp, x, st, training, rng, fmask)
            return layer.apply(lp, x, st, training, rng)

        if training:
            run = remat_wrap(self.conf.global_conf, run, block=idx)
        return run(lp, x, st, rng, fmask)

    def _forward(self, params, states, x, training: bool, rng, fmask=None):
        """Single traced forward pass through preprocessors + layers."""
        params, x = self._cast_compute(params, x)
        new_states = []
        for i, layer in enumerate(self.layers):
            pre = self.conf.preprocessors.get(i)
            if pre is not None:
                x = pre(x)
            if isinstance(layer, L.MaskingLayer) and fmask is None:
                # Keras Masking semantics: the mask is DERIVED in-graph and
                # threaded to downstream mask-aware layers (round-5)
                fmask = layer.derive_mask(x)
            rng, sub = jax.random.split(rng)
            x, st = self._apply_layer(layer, params[i], x, states[i],
                                      training, sub, fmask, idx=i)
            new_states.append(st)
        return x, new_states

    def _forward_to_preout(self, params, states, x, training: bool, rng,
                           fmask=None, rnn_states=None):
        """Forward stopping BEFORE the output head's activation (for loss).

        ``rnn_states`` (TBPTT): explicit recurrent carries per layer; when
        given, recurrent layers start from them and the new carries are
        returned as a third element."""
        params, x = self._cast_compute(params, x)
        new_states = []
        new_rnn = [] if rnn_states is not None else None
        for i, layer in enumerate(self.layers[:-1]):
            pre = self.conf.preprocessors.get(i)
            if pre is not None:
                x = pre(x)
            if isinstance(layer, L.MaskingLayer) and fmask is None:
                fmask = layer.derive_mask(x)   # see _forward
            rng, sub = jax.random.split(rng)
            if rnn_states is not None and layer.is_rnn():
                def run_rnn(lp, xx, rs, st, k, _l=layer):
                    return _l.apply_rnn(lp, xx, rs, st, training, k)

                if training:
                    # TBPTT recurrent segments are exactly where
                    # activation memory bites — same policy applies
                    run_rnn = remat_wrap(self.conf.global_conf, run_rnn,
                                         block=i)
                x, r, st = run_rnn(params[i], x, rnn_states[i],
                                   states[i], sub)
                if fmask is not None:
                    x = x * fmask[:, :, None].astype(x.dtype)
                new_rnn.append(r)
            else:
                x, st = self._apply_layer(layer, params[i], x, states[i],
                                          training, sub, fmask, idx=i)
                if rnn_states is not None:
                    new_rnn.append(rnn_states[i])
            new_states.append(st)
        i = len(self.layers) - 1
        pre = self.conf.preprocessors.get(i)
        if pre is not None:
            x = pre(x)
        # the output head's configured input dropout applies on this path too
        rng, sub = jax.random.split(rng)
        x = self.layers[i]._maybe_dropout(x, training, sub)
        new_states.append(states[i])  # output head is stateless; keep list aligned
        if rnn_states is not None:
            new_rnn.append(None)
            return x, new_states, new_rnn
        return x, new_states

    def output(self, x, training: bool = False, fmask=None) -> NDArray:
        """Inference forward (reference output()): one compiled module.
        ``fmask`` [B, T]: per-timestep feature mask for sequence inputs."""
        self._check_init()
        xv = jnp.asarray(x.value if isinstance(x, NDArray) else x)
        if fmask is not None:
            fmask = jnp.asarray(fmask.value if isinstance(fmask, NDArray)
                                else fmask)
        if self._infer_fn is None:
            def infer(params, states, xin, key, fm=None):
                out, _ = self._forward(params, states, xin, False, key, fm)
                return out

            self._infer_fn = xprof.register_jit("mln/infer",
                                                jax.jit(infer))
        out = self._infer_fn(self._params, self._states, xv,
                             get_random().next_key(), fmask)
        return NDArray(out)

    def feed_forward(self, x, training: bool = False) -> List[NDArray]:
        """All layer activations (reference feedForward)."""
        self._check_init()
        xv = jnp.asarray(x.value if isinstance(x, NDArray) else x)
        acts = [NDArray(xv)]
        rng = get_random().next_key()
        cur = xv
        for i, layer in enumerate(self.layers):
            pre = self.conf.preprocessors.get(i)
            if pre is not None:
                cur = pre(cur)
            rng, sub = jax.random.split(rng)
            cur, _ = layer.apply(self._params[i], cur, self._states[i], training, sub)
            acts.append(NDArray(cur))
        return acts

    # --- loss ------------------------------------------------------------
    def _loss(self, params, states, x, labels, mask, training: bool, rng,
              fmask=None, rnn_states=None, w=None, w_denom=None):
        out_layer = self.layers[-1]
        if not hasattr(out_layer, "compute_score"):
            raise ValueError("last layer must be a loss head (OutputLayer/"
                             "LossLayer/Yolo2OutputLayer/...) to train")
        # Keras Masking semantics end at the LOSS too: with a leading
        # MaskingLayer and no explicit masks, the derived mask masks the
        # per-timestep loss of a recurrent head (round-5; the reference
        # propagates feature masks into label masks the same way). Derived
        # here (not just inside the forward) so compute_score sees it.
        if fmask is None and self.layers \
                and isinstance(self.layers[0], L.MaskingLayer):
            x0 = x
            pre0 = self.conf.preprocessors.get(0)
            if pre0 is not None:
                x0 = pre0(x0)
            fmask = self.layers[0].derive_mask(jnp.asarray(x0))
        if mask is None and fmask is not None \
                and isinstance(out_layer, L.RnnOutputLayer):
            mask = fmask
        if rnn_states is not None:
            pre, new_states, new_rnn = self._forward_to_preout(
                params, states, x, training, rng, fmask, rnn_states)
        else:
            pre, new_states = self._forward_to_preout(params, states, x,
                                                      training, rng, fmask)
            new_rnn = None
        # under reduced-precision compute, run the head + loss reduction in
        # fp32; leave fp64 runs (gradient checks) untouched
        if self.conf.global_conf.compute_dtype:
            head_params = jax.tree.map(
                lambda a: (a.astype(jnp.float32)
                           if jnp.issubdtype(a.dtype, jnp.floating) else a),
                params[-1])
            if jnp.issubdtype(pre.dtype, jnp.floating):
                pre = pre.astype(jnp.float32)
        else:
            head_params = params[-1]
        if w is None:
            data_loss = out_layer.compute_score(head_params, pre, labels,
                                                mask, average=True)
        else:
            # example-weighted mean (shape-stable batching): pad rows carry
            # w=0, so the weighted sum excludes them exactly and the divisor
            # is the REAL example count — numerically the same loss the
            # unpadded batch would produce (sum over reals / n_real).
            # ``w_denom`` overrides the divisor for SPMD shards, where the
            # correct denominator is global_real/num_shards so the pmean of
            # per-shard losses equals the global mean over real examples
            # (the regularization term stays unscaled either way).
            total = out_layer.compute_score(head_params, pre, labels,
                                            _fold_weights(mask, w),
                                            average=False)
            data_loss = total / (w_denom if w_denom is not None
                                 else jnp.maximum(jnp.sum(w), 1.0))
        reg = 0.0
        gc = self.conf.global_conf
        for lp, layer in zip(params, self.layers):
            if isinstance(layer, L.FrozenLayer):
                continue  # frozen params take no updates, incl. weight decay
            l1 = layer.l1 if layer.l1 is not None else gc.l1
            l2 = layer.l2 if layer.l2 is not None else gc.l2
            for name, w in lp.items():
                if name in ("b", "beta", "mean", "var"):
                    continue  # biases/norm params excluded (reference default)
                if l2:
                    reg = reg + 0.5 * l2 * jnp.sum(jnp.square(w))
                if l1:
                    reg = reg + l1 * jnp.sum(jnp.abs(w))
        if new_rnn is not None:
            return data_loss + reg, (new_states, new_rnn)
        return data_loss + reg, new_states

    def score(self, dataset: DataSet, training: bool = False) -> float:
        self._check_init()
        x = jnp.asarray(dataset.features.value)
        y = jnp.asarray(dataset.labels.value)
        mask = jnp.asarray(dataset.labels_mask.value) if dataset.labels_mask is not None else None
        fmask = (jnp.asarray(dataset.features_mask.value)
                 if dataset.features_mask is not None else None)
        loss, _ = self._loss(self._params, self._states, x, y, mask, training,
                             get_random().next_key(), fmask)
        return float(loss)

    def compute_gradient_and_score(self, dataset: DataSet):
        """(gradients, score) — the GradientCheckUtil entry point."""
        self._check_init()
        x = jnp.asarray(dataset.features.value)
        y = jnp.asarray(dataset.labels.value)
        mask = jnp.asarray(dataset.labels_mask.value) if dataset.labels_mask is not None else None
        fmask = (jnp.asarray(dataset.features_mask.value)
                 if dataset.features_mask is not None else None)
        key = jax.random.PRNGKey(0)

        def loss_fn(params):
            loss, _ = self._loss(params, self._states, x, y, mask, False, key,
                                 fmask)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(self._params)
        self.score_value = float(loss)
        return grads, self.score_value

    # --- training --------------------------------------------------------
    def _frozen_indices(self):
        return [i for i, l in enumerate(self.layers)
                if isinstance(l, L.FrozenLayer)]

    def _step_core(self):
        """The single train-step computation, shared verbatim by the
        per-step jit and the multi-step ``lax.scan`` dispatch so the two
        paths cannot drift numerically. When telemetry is enabled the core
        additionally returns the in-graph aux pytree (per-layer grad/
        update/param norms, update:param ratio, non-finite counts — see
        optimize.telemetry) computed inside the same compiled module.

        ``hyper`` (keyword-only, default None — the solo paths never pass
        it): a dict of TRACED per-call scalar hyperparameter overrides,
        the vmapped-fleet sweep hook (parallel.fleet). Recognized keys:
        ``lr`` replaces the updater's learning rate, ``l2`` replaces
        every layer's effective l2 (an additive delta on the loss under
        the same exclusions the base regularization applies), and
        ``dropout`` replaces the rate of every layer whose input dropout
        is configured on. Scalars must be float64 (weak-Python-float
        matching under x64) so an override equal to the baked value is
        bitwise identical to the solo step."""
        gc = self.conf.global_conf
        updater = gc.updater
        frozen = self._frozen_indices()
        tele = self._telemetry
        from ..learning import precision as _prec
        from ..optimize import telemetry as _tel

        def core(params, states, upd_state, x, y, mask, key, iteration,
                 fmask, w, hyper=None):
            hp = {k: _weak_scalar(v) for k, v in (hyper or {}).items()}
            up = (dataclasses.replace(updater, learning_rate=hp["lr"])
                  if "lr" in hp else updater)

            def loss_fn(p):
                if "dropout" in hp:
                    with L.dropout_rate_override(hp["dropout"]):
                        loss, new_states = self._loss(p, states, x, y,
                                                      mask, True, key,
                                                      fmask, w=w)
                else:
                    loss, new_states = self._loss(p, states, x, y, mask,
                                                  True, key, fmask, w=w)
                if "l2" in hp:
                    loss = loss + _l2_delta(self.conf, self.layers, p,
                                            hp["l2"])
                return loss, new_states

            (loss, new_states), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if gc.grad_normalization:
                grads = _normalize_gradients(
                    grads, gc.grad_normalization, gc.grad_norm_threshold)
            OpProfiler.get().gauge("precision/grads_flat_in_step", 0)
            new_params, new_upd = _prec.apply_updater(
                up, grads, upd_state, params, iteration, key)
            for i in frozen:
                # stop_gradient already zeroes their grads; restoring the
                # original tensors also shields them from stateful-updater
                # side effects (weight decay, momentum drift)
                new_params[i] = params[i]
            new_params = self._apply_constraints(new_params)
            if tele is None:
                return new_params, new_states, new_upd, loss
            aux = _tel.layer_stats(params, new_params, grads, loss)
            if tele.nan_guard:
                aux, new_params, new_states, new_upd = _tel.apply_nan_guard(
                    aux, new_params, params, new_states, states, new_upd,
                    upd_state)
            return new_params, new_states, new_upd, loss, aux

        return core

    def _build_fit_step(self):
        core = self._step_core()

        def step(params, states, upd_state, x, y, mask, key, iteration,
                 fmask=None, w=None):
            OpProfiler.get().count("trace/mln_fit_step")
            return core(params, states, upd_state, x, y, mask, key,
                        iteration, fmask, w)

        return xprof.register_jit(
            "mln/fit_step", jax.jit(step, donate_argnums=(0, 1, 2)),
            donate=(0, 1, 2))

    def _build_chunk_step(self):
        """Multi-step dispatch (``steps_per_dispatch=K``): one jitted
        module runs K minibatches through a ``lax.scan`` device loop over
        the stacked chunk — Python dispatch, listener sync, and H2D fencing
        amortize over K steps."""
        core = self._step_core()
        tele = self._telemetry

        def chunk(params, states, upd_state, xs, ys, masks, keys,
                  iteration0, fmasks=None, ws=None):
            OpProfiler.get().count("trace/mln_fit_chunk")

            def body(carry, inp):
                params, states, upd_state, it = carry
                x, y, m, k, fm, w = inp
                out = core(params, states, upd_state, x, y, m, k, it, fm, w)
                if tele is None:
                    params, states, upd_state, loss = out
                    return (params, states, upd_state, it + 1), loss
                params, states, upd_state, loss, aux = out
                # aux rides the scan's stacked outputs: [K, ...] per leaf
                return (params, states, upd_state, it + 1), (loss, aux)

            (params, states, upd_state, _), ys_out = jax.lax.scan(
                body, (params, states, upd_state, iteration0),
                (xs, ys, masks, keys, fmasks, ws))
            if tele is None:
                return params, states, upd_state, ys_out
            losses, auxes = ys_out
            return params, states, upd_state, losses, auxes

        return xprof.register_jit(
            "mln/fit_chunk", jax.jit(chunk, donate_argnums=(0, 1, 2)),
            donate=(0, 1, 2))

    def _apply_constraints(self, params):
        """Project weights after each update (reference BaseConstraint —
        applied to weight params, biases/norm params excluded)."""
        out = params
        for i, layer in enumerate(self.layers):
            cs = getattr(layer, "constraints", None)
            if not cs:
                continue
            lp = dict(out[i])
            for name, w in lp.items():
                if name in ("b", "beta", "gamma", "mean", "var", "centers"):
                    continue
                for c in cs:
                    w = c.apply(w)
                lp[name] = w
            out[i] = lp
        return out

    def _build_tbptt_step(self):
        """TBPTT segment step (reference: MultiLayerNetwork
        truncatedBPTTGradient / rnnActivateUsingStoredState): gradients flow
        within the segment only — the incoming recurrent carries are jit
        inputs, so backprop truncates at the segment boundary by
        construction."""
        gc = self.conf.global_conf
        updater = gc.updater
        frozen = self._frozen_indices()
        tele = self._telemetry
        from ..learning import precision as _prec
        from ..optimize import telemetry as _tel

        def step(params, states, upd_state, rnn_states, x, y, mask, key,
                 iteration, fmask=None):
            def loss_fn(p):
                loss, aux = self._loss(p, states, x, y, mask, True, key,
                                       fmask, rnn_states)
                return loss, aux

            (loss, (new_states, new_rnn)), grads =                 jax.value_and_grad(loss_fn, has_aux=True)(params)
            if gc.grad_normalization:
                grads = _normalize_gradients(grads, gc.grad_normalization,
                                             gc.grad_norm_threshold)
            new_params, new_upd = _prec.apply_updater(
                updater, grads, upd_state, params, iteration, key)
            for i in frozen:
                new_params[i] = params[i]
            new_params = self._apply_constraints(new_params)
            if tele is None:
                return new_params, new_states, new_upd, new_rnn, loss
            aux = _tel.layer_stats(params, new_params, grads, loss)
            if tele.nan_guard:
                aux, new_params, new_states, new_upd = _tel.apply_nan_guard(
                    aux, new_params, params, new_states, states, new_upd,
                    upd_state)
                # the recurrent carries of a skipped segment are poisoned
                # too — restore them alongside the params
                ok = aux["skipped"] == 0
                new_rnn = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                       new_rnn, rnn_states)
            return new_params, new_states, new_upd, new_rnn, loss, aux

        return xprof.register_jit(
            "mln/tbptt_step", jax.jit(step, donate_argnums=(0, 1, 2)),
            donate=(0, 1, 2))

    def fit(self, data, epochs: int = 1, batch_size: Optional[int] = None,
            *, pad_partial: Optional[bool] = None,
            drop_remainder: bool = False, prefetch: int = 2,
            steps_per_dispatch: int = 1, host_prefetch: int = 0,
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
        - ``host_prefetch=N`` (opt-in): run batch assembly (slicing,
          padding, array conversion) on a worker thread through an
          N-deep queue. The default of 0 was chosen on a set-up that
          is gone (worker-thread jax array creation serialized there);
          not measured on this chip.

        NOTE on padding numerics: the padded run is numerically identical
        to the unpadded masked-loss run for per-example models (pinned
        bit-for-bit in tests). Layers with CROSS-example statistics
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
        from ..learning.precision import note_state_bytes

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

        tbptt = self.conf.backprop_type == "TruncatedBPTT"
        # Single-DataSet/tuple calls with no batch size have one stable
        # shape by construction (the bench hot loops); TBPTT has its own
        # segment loop — both stay on the serial path.
        if tbptt or (isinstance(data, (DataSet, tuple))
                     and batch_size is None):
            self._fit_serial(data, epochs, batch_size, skip=skip)
            return
        if steps_per_dispatch > 1 and self._chunk_step is None:
            self._chunk_step = self._build_chunk_step()

        def on_epoch():
            self._epoch += 1
            self._steps_in_epoch = 0
            for lst in self._listeners:
                if hasattr(lst, "epoch_done"):
                    lst.epoch_done(self, self._epoch)

        _pipe.run_epochs(
            data, epochs, batch_size,
            pad_partial=True if pad_partial is None else pad_partial,
            drop_remainder=drop_remainder, prefetch=prefetch,
            steps_per_dispatch=steps_per_dispatch,
            bind=self._bind_batch, place=jax.device_put,
            dispatch_one=lambda b: self._dispatch_one(b, prof),
            dispatch_chunk=lambda g: self._dispatch_chunk(g, prof),
            stackable=_same_shapes, on_epoch=on_epoch,
            host_prefetch=host_prefetch, skip=skip,
            first_step=self._iteration)

    def _begin_fit(self, resume_from: Optional[str]):
        from ..util.checkpoint import begin_fit_cursor

        return begin_fit_cursor(self, resume_from,
                                listeners=self._listeners)

    def _bind_batch(self, ds: DataSet, w):
        """DataSet → the jit argument tuple (x, y, mask, fmask, w)."""
        # PerformanceListener derives samples/sec from this
        self._last_batch_size = ds.num_examples()
        return (jnp.asarray(ds.features.value),
                jnp.asarray(ds.labels.value),
                jnp.asarray(ds.labels_mask.value)
                if ds.labels_mask is not None else None,
                jnp.asarray(ds.features_mask.value)
                if ds.features_mask is not None else None,
                w)

    def _dispatch_one(self, b, prof) -> None:
        x, y, mask, fmask, w = b
        key = get_random().next_key()
        with prof.time_section("pipeline/dispatch", step=self._iteration):
            out = self._fit_step(self._params, self._states,
                                 self._updater_state, x, y, mask, key,
                                 jnp.asarray(self._iteration), fmask, w)
        _pipe.note_dispatch(self, self._listeners, out,
                            self._telemetry is not None)

    def _dispatch_chunk(self, group, prof) -> None:
        xs, ys, masks, fmasks, ws = _stack_batches(group)
        # keys drawn in batch order — the chunked loop consumes the SAME
        # rng stream the per-step loop would
        keys = jnp.stack([get_random().next_key() for _ in group])
        with prof.time_section("pipeline/dispatch", step=self._iteration,
                               steps=len(group)):
            out = self._chunk_step(self._params, self._states,
                                   self._updater_state, xs, ys, masks,
                                   keys, jnp.asarray(self._iteration),
                                   fmasks, ws)
        _pipe.note_dispatch(self, self._listeners, out,
                            self._telemetry is not None, len(group))

    def _fit_serial(self, data, epochs: int = 1,
                    batch_size: Optional[int] = None, skip=None) -> None:
        tbptt = self.conf.backprop_type == "TruncatedBPTT"
        skip_epochs, skip_steps = skip if skip is not None else (0, 0)
        for e in range(max(1, epochs)):
            if e < skip_epochs:
                # resume fast-forward: consume (advances iterator state),
                # dispatch nothing; on_epoch effects are already in the
                # restored checkpoint
                for _ in _iter_data(data, batch_size):
                    pass
                continue
            to_skip = skip_steps if e == skip_epochs else 0
            for ds in _iter_data(data, batch_size):
                if to_skip:
                    to_skip -= 1
                    continue
                x = jnp.asarray(ds.features.value)
                y = jnp.asarray(ds.labels.value)
                mask = (jnp.asarray(ds.labels_mask.value)
                        if ds.labels_mask is not None else None)
                fmask = (jnp.asarray(ds.features_mask.value)
                         if ds.features_mask is not None else None)
                key = get_random().next_key()
                # device scalars throughout; float() only on access (avoids
                # per-step sync). Listeners get the device values too and
                # sync only at their own print/collect/drain boundaries.
                if tbptt and x.ndim == 3:
                    loss, aux = self._fit_tbptt(x, y, mask, fmask, key)
                    _pipe.note_steps(self, self._listeners, [loss],
                                     [aux] if aux is not None else None)
                else:
                    out = self._fit_step(self._params, self._states,
                                         self._updater_state, x, y, mask,
                                         key, jnp.asarray(self._iteration),
                                         fmask)
                    _pipe.note_dispatch(self, self._listeners, out,
                                        self._telemetry is not None)
            self._epoch += 1
            self._steps_in_epoch = 0
            for lst in self._listeners:
                if hasattr(lst, "epoch_done"):
                    lst.epoch_done(self, self._epoch)

    def pretrain(self, data, epochs: int = 1) -> None:
        """Layerwise unsupervised pretraining (reference:
        MultiLayerNetwork.pretrain(DataSetIterator) over pretrainable
        layers — here the VariationalAutoencoder's negative ELBO). Each
        pretrainable layer is optimized on the inference-mode activations
        of the layers below it, with a fresh instance of the configured
        updater."""
        self._check_init()
        updater = self.conf.global_conf.updater
        for idx, layer in enumerate(self.layers):
            if not getattr(layer, "is_pretrain_layer", lambda: False)():
                continue

            def below(params, x, key, idx=idx):
                for i, ll in enumerate(self.layers[:idx]):
                    pre = self.conf.preprocessors.get(i)
                    if pre is not None:
                        x = pre(x)
                    key, sub = jax.random.split(key)
                    x, _ = ll.apply(params[i], x, self._states[i], False, sub)
                pre = self.conf.preprocessors.get(idx)
                return pre(x) if pre is not None else x

            def step(lp, upd_state, params, x, key, it, idx=idx,
                     layer=layer):
                feats = below(params, x, key)

                def loss_fn(p):
                    return layer.pretrain_loss(p, feats, key)

                loss, grads = jax.value_and_grad(loss_fn)(lp)
                from ..learning.precision import apply_updater

                new_lp, new_upd = apply_updater(updater, grads, upd_state,
                                                lp, it, key)
                return new_lp, new_upd, loss

            step = xprof.register_jit(
                "mln/pretrain_step",
                jax.jit(step, donate_argnums=(0, 1)), donate=(0, 1))
            lp = self._params[idx]
            upd_state = updater.init(lp)
            it = 0
            for _ in range(max(1, epochs)):
                for ds in _iter_data(data, None):
                    x = jnp.asarray(ds.features.value)
                    lp, upd_state, loss = step(
                        lp, upd_state, self._params, x,
                        get_random().next_key(), jnp.asarray(it))
                    it += 1
                    self._score_dev = loss
            self._params[idx] = lp
            self._fit_step = None
            self._chunk_step = None
            self._infer_fn = None

    def _fit_tbptt(self, x, y, mask, fmask, key):
        """Split [B, T, F] into tbptt_fwd_length segments, carrying recurrent
        state across segments (gradient truncates at each boundary)."""
        if self._tbptt_step is None:
            self._tbptt_step = self._build_tbptt_step()
        k = self.conf.tbptt_fwd_length
        T = x.shape[1]
        dtype = jnp.dtype(self.conf.global_conf.compute_dtype
                          or self.conf.global_conf.dtype)
        rnn = [l.init_rnn_state(x.shape[0], dtype) if l.is_rnn() else None
               for l in self.layers]
        loss, aux, seg_aux = None, None, None
        for s0 in range(0, T, k):
            seg = slice(s0, min(s0 + k, T))
            key, sub = jax.random.split(key)
            out = self._tbptt_step(
                self._params, self._states, self._updater_state, rnn,
                x[:, seg], y[:, seg] if y.ndim == 3 else y,
                mask[:, seg] if mask is not None and mask.ndim >= 2 else mask,
                sub, jnp.asarray(self._iteration),
                fmask[:, seg] if fmask is not None else None)
            if self._telemetry is not None:
                (self._params, self._states, self._updater_state, rnn,
                 loss, seg_aux) = out
                if aux is None:
                    aux = dict(seg_aux)
                else:
                    # norms report the FINAL segment (the one the carried
                    # params came from), but the NaN evidence accumulates
                    # across segments — a poisoned middle segment must not
                    # vanish from the iteration's aux or the NanSentinel
                    # would miss it
                    prev = aux
                    aux = dict(seg_aux)
                    for k_ in ("nonfinite", "nonfinite_total", "skipped"):
                        if k_ in seg_aux:
                            aux[k_] = prev[k_] + seg_aux[k_]
            else:
                (self._params, self._states, self._updater_state, rnn,
                 loss) = out
        return loss, aux

    # --- streaming inference (reference: MultiLayerNetwork.rnnTimeStep
    # with its per-layer stateMap) ----------------------------------------
    def rnn_time_step(self, x) -> NDArray:
        """Forward [B, T, F] (or [B, F] for one step) continuing from the
        stored recurrent state; updates the stored state."""
        self._check_init()
        xv = jnp.asarray(x.value if isinstance(x, NDArray) else x)
        if xv.ndim == 2:
            xv = xv[:, None, :]
        dtype = jnp.dtype(self.conf.global_conf.dtype)
        if self._rnn_state_map is None:
            self._rnn_state_map = [
                l.init_rnn_state(xv.shape[0], dtype) if l.is_rnn() else None
                for l in self.layers]
        cur = xv
        rng = get_random().next_key()
        for i, layer in enumerate(self.layers):
            pre = self.conf.preprocessors.get(i)
            if pre is not None:
                cur = pre(cur)
            rng, sub = jax.random.split(rng)
            if layer.is_rnn():
                cur, r, _ = layer.apply_rnn(self._params[i], cur,
                                            self._rnn_state_map[i],
                                            self._states[i], False, sub)
                self._rnn_state_map[i] = r
            else:
                cur, _ = layer.apply(self._params[i], cur, self._states[i],
                                     False, sub)
        return NDArray(cur)

    rnnTimeStep = rnn_time_step

    def rnn_clear_previous_state(self) -> None:
        self._rnn_state_map = None

    rnnClearPreviousState = rnn_clear_previous_state

    # --- evaluation -------------------------------------------------------
    def evaluate(self, data, batch_size: Optional[int] = None):
        from ..eval.evaluation import Evaluation

        ev = Evaluation()
        for ds in _iter_data(data, batch_size):
            out = self.output(ds.features, fmask=ds.features_mask)
            ev.eval(ds.labels.to_numpy(), out.to_numpy(),
                    ds.labels_mask.to_numpy() if ds.labels_mask is not None else None)
        return ev

    def evaluate_regression(self, data, batch_size: Optional[int] = None):
        from ..eval.evaluation import RegressionEvaluation

        ev = RegressionEvaluation()
        for ds in _iter_data(data, batch_size):
            out = self.output(ds.features)
            ev.eval(ds.labels.to_numpy(), out.to_numpy())
        return ev

    # --- persistence ------------------------------------------------------
    def save(self, path: str, save_updater: bool = False) -> None:
        from ..util.model_serializer import write_model

        write_model(self, path, save_updater)

    @staticmethod
    def load(path: str, load_updater: bool = False) -> "MultiLayerNetwork":
        from ..util.model_serializer import restore_multi_layer_network

        return restore_multi_layer_network(path, load_updater)

    # --- misc -------------------------------------------------------------
    def summary(self) -> str:
        lines = [f"{'idx':<4}{'layer':<28}{'out type':<28}{'params':<10}"]
        total = 0
        for i, layer in enumerate(self.layers):
            n = (sum(int(np.prod(p.shape)) for p in jax.tree.leaves(self._params[i]))
                 if self._initialized else 0)
            total += n
            ot = (self.conf.layer_output_types[i]
                  if i < len(self.conf.layer_output_types) else "?")
            lines.append(f"{i:<4}{type(layer).__name__:<28}{str(ot):<28}{n:<10}")
        lines.append(f"Total params: {total}")
        return "\n".join(lines)

    def get_layer(self, idx: int) -> L.Layer:
        return self.layers[idx]

    def n_layers(self) -> int:
        return len(self.layers)

    def _check_init(self) -> None:
        if not self._initialized:
            raise ValueError("call init() first")

    def clone(self) -> "MultiLayerNetwork":
        import copy

        net = MultiLayerNetwork(copy.deepcopy(self.conf))
        net.init()
        # REAL buffer copies (jnp.array), not aliases: the source's fit
        # step donates its param buffers, which would invalidate an
        # aliasing clone the next time the source trains
        net._params = jax.tree.map(jnp.array, self._params)
        net._states = jax.tree.map(jnp.array, self._states)
        return net


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


def _l2_delta(conf, layers, params, l2_m):
    """A traced per-member l2 override as an ADDITIVE delta on the solo
    loss: replacing every layer's effective l2 with ``l2_m`` equals
    adding ``0.5*(l2_m - base_l2)*sum(w^2)`` per layer under the same
    exclusions ``_loss`` applies (biases/norm params out, FrozenLayers
    take no decay). With a zero base l2 this is bitwise identical to a
    solo model configured with ``l2=l2_m`` (0.5*x and x-0 are exact);
    over a nonzero base it is mathematically equal but may differ in the
    last ulp from the directly-configured run."""
    gc = conf.global_conf
    delta = 0.0
    for lp, layer in zip(params, layers):
        if isinstance(layer, L.FrozenLayer):
            continue
        base = layer.l2 if layer.l2 is not None else gc.l2
        for name, wt in lp.items():
            if name in ("b", "beta", "mean", "var"):
                continue
            delta = delta + (0.5 * (l2_m - base)) * jnp.sum(jnp.square(wt))
    return delta


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


def _same_shapes(group) -> bool:
    """True when every batch tuple in the chunk has identical array shapes
    (None members must agree too) — the stacking precondition."""
    def sig(b):
        return tuple(None if a is None else tuple(a.shape) for a in b)

    first = sig(group[0])
    return all(sig(b) == first for b in group[1:])


def _stack_batches(group):
    """Stack K batch tuples [(x, y, mask, fmask, w), ...] along a new
    leading axis for the scan device loop; None columns stay None."""
    def col(i):
        if group[0][i] is None:
            return None
        return jnp.stack([b[i] for b in group])

    return col(0), col(1), col(2), col(3), col(4)


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


def _iter_data(data, batch_size):
    # one data protocol for serial and pipelined paths alike
    yield from _pipe.iter_datasets(data, batch_size)
