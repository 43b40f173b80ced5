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

import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

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
from .train_step import (FORWARD, FitLoop, _fold_weights, chunk_program,
                         finish, make_core, step_program, update, vertex_scope)


class MultiLayerNetwork(FitLoop):
    _one_batch = (DataSet, tuple)

    def __init__(self, conf: MultiLayerConfiguration):
        super().__init__(conf)
        self.layers = conf.layers
        self._params: List[Dict[str, jnp.ndarray]] = []
        self._states: List[Dict[str, jnp.ndarray]] = []
        self._tbptt_step = None
        self._rnn_state_map = None

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

    def _drop_steps(self) -> None:
        super()._drop_steps()
        self._tbptt_step = None

    setListeners = FitLoop.set_listeners

    # --- parameter access (flattened, reference params() contract) ------
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
        self._drop_steps()  # donated buffers were replaced

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
        cast_params = []
        for i, lp in enumerate(params):     # each layer's casts are its own
            with jax.named_scope(vertex_scope(i)):
                cast_params.append(jax.tree.map(cast, lp))
        return cast_params, cast(x)

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

        # the layer's scope lies INSIDE what remat wraps, so the recomputed
        # forward carries it too
        @jax.named_scope(vertex_scope(idx))
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
                @jax.named_scope(vertex_scope(i))
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
        new_states.append(states[i])  # the head's state passes through; keep list aligned
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
        with jax.named_scope(vertex_scope(len(self.layers) - 1)):
            # under reduced-precision compute, run the head + loss reduction
            # in fp32; leave fp64 runs (gradient checks) untouched
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
                # example-weighted mean (shape-stable batching): pad rows
                # carry w=0, so the weighted sum excludes them exactly and
                # the divisor is the REAL example count — numerically the
                # same loss the unpadded batch would produce (sum over reals
                # / n_real). ``w_denom`` overrides the divisor for SPMD
                # shards, where the correct denominator is
                # global_real/num_shards so the pmean of per-shard losses
                # equals the global mean over real examples (the
                # regularization term stays unscaled either way).
                total = out_layer.compute_score(head_params, pre, labels,
                                                _fold_weights(mask, w),
                                                average=False)
                data_loss = total / (w_denom if w_denom is not None
                                     else jnp.maximum(jnp.sum(w), 1.0))
        reg = 0.0
        gc = self.conf.global_conf
        with jax.named_scope("loss"):
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

    # --- training (the step and the loop are nn.train_step's) -------------
    def _keyed_layers(self):
        return enumerate(self.layers)

    def _bind(self, ds: DataSet):
        """DataSet → the batch ``(x, y, mask, fmask)``."""
        return (jnp.asarray(ds.features.value),
                jnp.asarray(ds.labels.value),
                jnp.asarray(ds.labels_mask.value)
                if ds.labels_mask is not None else None,
                jnp.asarray(ds.features_mask.value)
                if ds.features_mask is not None else None)

    @jax.named_scope(FORWARD)
    def _loss_of(self, params, states, batch, key, *, training=True, w=None,
                 w_denom=None, rnn_states=None, l2=None):
        """The loss of one batch. ``rnn_states`` (TBPTT) makes the aux
        ``(new_states, new_rnn)``; ``l2`` is the fleet's traced override
        of every layer's effective l2."""
        x, y, mask, fmask = batch
        loss, aux = self._loss(params, states, x, y, mask, training, key,
                               fmask, rnn_states, w, w_denom)
        if l2 is not None:
            loss = loss + _l2_delta(self.conf, self.layers, params, l2)
        return loss, aux

    def _build_fit_step(self):
        step = step_program(make_core(self, self._telemetry),
                            "trace/mln_fit_step", batch_len=4)
        return xprof.register_jit(
            "mln/fit_step", jax.jit(step, donate_argnums=(0, 1, 2)),
            donate=(0, 1, 2))

    def _build_chunk_step(self):
        chunk = chunk_program(make_core(self, self._telemetry),
                              "trace/mln_fit_chunk")
        return xprof.register_jit(
            "mln/fit_chunk", jax.jit(chunk, donate_argnums=(0, 1, 2)),
            donate=(0, 1, 2))

    def _serial_only(self) -> bool:
        # TBPTT has its own segment loop per batch
        return self.conf.backprop_type == "TruncatedBPTT"

    def _serial_step(self, batch, key) -> None:
        if self._serial_only() and batch[0].ndim == 3:
            loss, aux = self._fit_tbptt(*batch, key)
            _pipe.note_steps(self, self._listeners, [loss],
                             [aux] if aux is not None else None)
        else:
            super()._serial_step(batch, key)

    def _build_tbptt_step(self):
        """TBPTT segment step (reference: MultiLayerNetwork
        truncatedBPTTGradient / rnnActivateUsingStoredState): gradients flow
        within the segment only — the incoming recurrent carries are jit
        inputs, so backprop truncates at the segment boundary by
        construction. Its own differentiation (the carry rides the aux),
        then the shared update epilogue and telemetry tail."""
        updater = self.conf.global_conf.updater
        tele = self._telemetry

        def step(params, states, upd_state, rnn_states, x, y, mask, key,
                 iteration, fmask=None):
            def loss_fn(p):
                return self._loss_of(p, states, (x, y, mask, fmask), key,
                                     rnn_states=rnn_states)

            (loss, (new_states, new_rnn)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads, new_params, new_upd = update(
                self, updater, grads, upd_state, params, iteration, key)
            out = finish(tele, loss, (params, states, upd_state),
                         (new_params, new_states, new_upd), grads)
            if tele is not None and tele.nan_guard:
                # the recurrent carries of a skipped segment are poisoned
                # too — restore them alongside the params
                ok = out[4]["skipped"] == 0
                new_rnn = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                       new_rnn, rnn_states)
            return (*out[:3], new_rnn, *out[3:])

        return xprof.register_jit(
            "mln/tbptt_step", jax.jit(step, donate_argnums=(0, 1, 2)),
            donate=(0, 1, 2))

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
                for ds in self._iter_data(data):
                    x = jnp.asarray(ds.features.value)
                    lp, upd_state, loss = step(
                        lp, upd_state, self._params, x,
                        get_random().next_key(), jnp.asarray(it))
                    it += 1
                    self._score_dev = loss
            self._params[idx] = lp
            self._drop_steps()
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
        for ds in self._iter_data(data, batch_size):
            out = self.output(ds.features, fmask=ds.features_mask)
            ev.eval(ds.labels.to_numpy(), out.to_numpy(),
                    ds.labels_mask.to_numpy() if ds.labels_mask is not None else None)
        return ev

    def evaluate_regression(self, data, batch_size: Optional[int] = None):
        from ..eval.evaluation import RegressionEvaluation

        ev = RegressionEvaluation()
        for ds in self._iter_data(data, batch_size):
            out = self.output(ds.features)
            ev.eval(ds.labels.to_numpy(), out.to_numpy())
        return ev

    # --- persistence ------------------------------------------------------
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
