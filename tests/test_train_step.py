"""The one training step (``nn/train_step.py``).

What a training step is — gradient normalisation, the updater, frozen
layers kept, constraints projected — is written once, so every way of
training the same stack on the same data must land where
``MultiLayerNetwork`` lands: its TBPTT segment step, ``ComputationGraph``
and dense ``ParallelWrapper`` (which ignored some of it until the step was
shared). ZeRO-1 updates flat shards and cannot honour any of it, so it
refuses; ``SameDiff``'s step goes through ``apply_updater`` and so keeps
low-precision moments.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.data import DataSet, NDArrayDataSetIterator
from deeplearning4j_tpu.learning import Adam, AdamW, Sgd
from deeplearning4j_tpu.nn import (ComputationGraph,
                                   ComputationGraphConfiguration, InputType,
                                   MultiLayerNetwork, NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.layers_ext import MaxNormConstraint

CLIP_MODES = ("ClipElementWiseAbsoluteValue", "ClipL2PerGradient",
              "ClipL2PerParamType", "RenormalizeL2PerLayer")
FEATURES = CLIP_MODES + ("max_norm", "frozen")
TARGETS = ("mln", "tbptt", "graph", "wrapper")
CLIP, MAX_NORM, T = 0.02, 0.4, 5
# another program of the same arithmetic (a dict of leaves for a list, a
# pmean of two half batches for one mean): float32 reduction order
RTOL = 2e-5


def _base(feature):
    """The global configuration of a feature: clipping is global, and a
    frozen layer shows under an updater that moves a weight whose gradient
    is zero (AdamW's decay)."""
    b = NeuralNetConfiguration.builder().seed(11).weight_init("xavier") \
        .activation("tanh")
    if feature == "frozen":
        return b.updater(AdamW(learning_rate=0.05, weight_decay=0.1))
    b = b.updater(Sgd(learning_rate=0.5))
    if feature in CLIP_MODES:
        b = b.gradient_normalization(feature, CLIP)
    return b


def _layers(feature, recurrent):
    first = (L.LSTM(n_out=6) if recurrent else L.DenseLayer(n_out=6))
    second = (L.DenseLayer(n_out=6) if not recurrent else None)
    if feature == "max_norm":
        first.constraints = [MaxNormConstraint(MAX_NORM)]
    if feature == "frozen":
        first = L.FrozenLayer(layer=first)
    head = (L.RnnOutputLayer if recurrent else L.OutputLayer)(
        n_out=3, loss="mcxent", activation="softmax")
    return [l for l in (first, second, head) if l is not None]


def _input_type(recurrent):
    return (InputType.recurrent(4, T) if recurrent
            else InputType.feed_forward(4))


def _mln(feature, recurrent=False, tbptt=False):
    lb = _base(feature).list()
    for layer in _layers(feature, recurrent):
        lb = lb.layer(layer)
    if tbptt:
        lb = lb.backprop_type("TruncatedBPTT").tbptt_length(T)
    return MultiLayerNetwork(
        lb.set_input_type(_input_type(recurrent)).build()).init()


def _data(recurrent):
    rng = np.random.RandomState(0)
    if recurrent:
        x = 3.0 * rng.randn(16, T, 4).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, (16, T))]
    else:
        x = 3.0 * rng.randn(16, 4).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 16)]
    return x, y


def _fit(model, recurrent, fit=None):
    x, y = _data(recurrent)
    (fit or model.fit)(NDArrayDataSetIterator(x, y, batch_size=8), epochs=2)
    return [jax.tree.map(np.asarray, lp) for lp in _per_layer(model)]


def _per_layer(model):
    if isinstance(model._params, dict):
        return [model._params[n] for n in model.conf.order
                if n in model._params]
    return model._params


@functools.lru_cache(maxsize=None)
def _reference(feature, recurrent):
    """What ``MultiLayerNetwork`` (standard backprop) makes of the stack,
    with the feature and without it."""
    return (_fit(_mln(feature, recurrent), recurrent),
            _fit(_mln(None, recurrent), recurrent))


def _train(target, feature):
    if target == "mln":
        return _fit(_mln(feature), False)
    if target == "tbptt":
        # one segment spans the sequence: the TBPTT step computes what the
        # plain step computes, through its own differentiation
        return _fit(_mln(feature, True, tbptt=True), True)
    if target == "graph":
        gb = ComputationGraphConfiguration.graph_builder(_base(feature)) \
            .add_inputs("in")
        prev = "in"
        for i, layer in enumerate(_layers(feature, False)):
            gb = gb.add_layer(f"l{i}", layer, prev)
            prev = f"l{i}"
        g = ComputationGraph(gb.set_outputs(prev).set_input_types(
            _input_type(False)).build()).init()
        # the graph draws its initial weights as the stack does
        for a, b in zip(jax.tree.leaves(_per_layer(g)),
                        jax.tree.leaves(_mln(feature)._params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return _fit(g, False)
    from deeplearning4j_tpu.parallel import ParallelWrapper

    model = _mln(feature)
    pw = ParallelWrapper.Builder(model).workers(2).build()
    return _fit(model, False, fit=pw.fit)


def _acts(feature, trained, recurrent):
    """The feature shows in the trained parameters themselves."""
    first = trained[0]
    if feature == "frozen":
        fresh = _mln(feature, recurrent)._params[0]
        for k in first:
            np.testing.assert_array_equal(first[k], np.asarray(fresh[k]))
    if feature == "max_norm":
        for k, leaf in first.items():
            if k != "b":
                norms = np.sqrt((leaf ** 2).sum(axis=0))
                assert norms.max() <= MAX_NORM * (1 + 1e-6), (k, norms.max())


@pytest.mark.parametrize("feature", FEATURES)
@pytest.mark.parametrize("target", TARGETS)
def test_every_trainer_lands_where_multilayer_lands(target, feature):
    if target == "wrapper" and len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    recurrent = target == "tbptt"
    want, without = _reference(feature, recurrent)
    got = _train(target, feature)
    _acts(feature, got, recurrent)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=RTOL,
                                   atol=RTOL * np.abs(w).max())
    # ... and not where the stack lands without the feature, so the
    # agreement above is the feature's
    gap = max(np.abs(g - w).max() for g, w in
              zip(jax.tree.leaves(got), jax.tree.leaves(without)))
    assert gap > 1e-3, gap


@pytest.mark.parametrize("feature",
                         ["ClipL2PerGradient", "max_norm", "frozen"])
def test_zero1_refuses_what_flat_shards_cannot_honour(feature):
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    from deeplearning4j_tpu.parallel import ParallelWrapper
    from deeplearning4j_tpu.parallel.accumulator import \
        ReduceScatterAccumulator

    model = _mln(feature)
    pw = (ParallelWrapper.Builder(model).workers(2)
          .gradients_accumulator(ReduceScatterAccumulator()).build())
    x, y = _data(False)
    with pytest.raises(NotImplementedError, match="ZeRO-1"):
        pw.fit(DataSet(x, y), epochs=1, batch_size=8)


def test_samediff_fit_keeps_low_precision_moments():
    from deeplearning4j_tpu.autodiff.samediff import SameDiff, TrainingConfig

    rng = np.random.RandomState(0)
    sd = SameDiff.create()
    x = sd.placeholder("x", shape=(None, 5))
    y = sd.placeholder("y", shape=(None, 3))
    w = sd.var("w", init=rng.randn(5, 3).astype(np.float32) * 0.3)
    sd.loss_ops.softmax_cross_entropy(x @ w, y).rename("loss")
    sd.set_loss_variables("loss")
    updater = Adam(1e-2)
    updater.state_dtype = "bfloat16"
    sd.set_training_config(TrainingConfig(updater=updater, loss_name="loss"))
    batches = [{"x": rng.randn(4, 5).astype(np.float32),
                "y": np.eye(3, dtype=np.float32)[rng.randint(0, 3, 4)]}
               for _ in range(3)]
    sd.fit(batches, epochs=2)
    for name in ("m", "v"):
        leaf = sd._updater_state[name]["w"]
        assert leaf.dtype == jnp.bfloat16, (name, leaf.dtype)
        assert float(jnp.abs(leaf.astype(jnp.float32)).sum()) > 0
    assert sd._vars["w"].value.dtype == jnp.float32
