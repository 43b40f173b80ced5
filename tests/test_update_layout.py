"""The update runs in the layout the state lives in (ISSUE 27).

Where params and updater state are whole on the device as trees
(ComputationGraph.fit, MultiLayerNetwork.fit, ParallelWrapper's dense
all-reduce) the compiled step differentiates with respect to the tree and
updates it leaf by leaf: no flat bucket is built, whatever ``fused_update``
says. On a TPU a rank-4 -> rank-1 reshape is a physical relayout, and the
bucket round trip cost 46 ms of a 93 ms ResNet-50 step (PERF.md, PR 27).
Buckets are for sharded state: under ZeRO-1 the same models still take the
fused bucket kernel with gradients born flat.
"""

import functools
import re

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.common import xprof
from deeplearning4j_tpu.common.profiler import OpProfiler
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.learning.updaters import Adam, Nesterovs, Sgd
from deeplearning4j_tpu.ndarray.rng import set_default_seed
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                         ComputationGraphConfiguration)
from deeplearning4j_tpu.parallel import (ParallelWrapper,
                                         ReduceScatterAccumulator)


def _nesterovs_bf16():
    u = Nesterovs(learning_rate=0.05, momentum=0.9)
    u.state_dtype = "bfloat16"
    return u


UPDATERS = {"sgd": lambda: Sgd(0.05), "nesterovs_bf16": _nesterovs_bf16,
            "adam": lambda: Adam(1e-3)}
# kind -> (model family, the census name of its compiled step)
KINDS = {"graph": ("graph", "graph/fit_step"),
         "multilayer": ("multilayer", "mln/fit_step"),
         "dense_wrapper": ("multilayer", "pw/fit_step")}
STEPS, BATCH = 3, 8


def _model(family, updater, fused):
    b = NeuralNetConfiguration.builder().seed(5).updater(updater)
    if fused:
        b = b.fused_update()
    conv = lambda: L.ConvolutionLayer(  # noqa: E731
        n_out=6, kernel_size=(3, 3), padding=(1, 1), activation="relu")
    out = lambda: L.OutputLayer(n_out=5, activation="softmax",  # noqa: E731
                                loss="mcxent")
    if family == "graph":
        gb = ComputationGraphConfiguration.graph_builder(b).add_inputs("in")
        gb.add_layer("c1", conv(), "in")
        gb.add_layer("bn", L.BatchNormalization(), "c1")
        gb.add_layer("c2", conv(), "bn")
        gb.add_layer("out", out(), "c2")
        gb.set_outputs("out")
        gb.set_input_types(InputType.convolutional(8, 8, 4))
        return ComputationGraph(gb.build()).init()
    conf = (b.list().layer(conv()).layer(L.BatchNormalization())
            .layer(conv()).layer(out())
            .set_input_type(InputType.convolutional(8, 8, 4)).build())
    return MultiLayerNetwork(conf).init()


def _data():
    rng = np.random.default_rng(1)
    n = STEPS * BATCH
    return DataSet(rng.normal(size=(n, 4, 8, 8)).astype(np.float32),
                   np.eye(5, dtype=np.float32)[rng.integers(0, 5, n)])


def _fit(kind, updater, fused, zero1=False):
    """Three steps through the entry point of ``kind``: the trained model
    and whatever owns its compiled step (the census holds steps weakly)."""
    family, _ = KINDS[kind]
    set_default_seed(99)
    model = owner = _model(family, UPDATERS[updater](), fused)
    if kind == "dense_wrapper" or zero1:
        b = ParallelWrapper.Builder(model).workers(2)
        if zero1:
            b = b.gradients_accumulator(ReduceScatterAccumulator())
        owner = b.build()
    owner.fit(_data(), epochs=1, batch_size=BATCH)
    return model, owner


@functools.lru_cache(maxsize=None)
def _unsharded(kind, updater, fused):
    """(host params, host updater state, leaf shapes, the step's lowered
    text as the census saw it called)."""
    xprof.reset()
    xprof.configure(enabled=True)
    model, _owner = _fit(kind, updater, fused)
    entry = xprof._CENSUS._entries[KINDS[kind][1]]
    args, kwargs = entry.avals
    text = entry.fn_ref().lower(*args, **kwargs).as_text()
    shapes = [tuple(l.shape) for l in jax.tree.leaves(model._params)]
    return (jax.device_get(model._params),
            jax.device_get(model._updater_state), shapes, text)


def _dims(t):
    """'tensor<6x4x3x3xf32>' body -> (6, 4, 3, 3)."""
    return tuple(int(d) for d in t.split("x")[:-1])


CASES = [(k, u) for k in KINDS for u in UPDATERS]


@pytest.mark.parametrize("kind,updater", CASES)
def test_step_builds_no_bucket(kind, updater):
    _, _, shapes, text = _unsharded(kind, updater, True)
    weights = {s for s in shapes if len(s) >= 2}
    assert any(len(s) == 4 for s in weights)
    n_params = sum(int(np.prod(s)) for s in shapes)
    for src, dst in re.findall(
            r"stablehlo\.reshape.*:\s*\(tensor<([^>]+)>\)\s*->\s*"
            r"tensor<([^>]+)>", text):
        assert not (_dims(src) in weights and len(_dims(dst)) == 1), \
            f"a weight-shaped operand is flattened: {src} -> {dst}"
    for dst in re.findall(r"stablehlo\.concatenate.*->\s*tensor<([^>]+)>",
                          text):
        d = _dims(dst)
        assert not (len(d) == 1 and d[0] >= n_params), \
            f"a parameter-sized bucket is concatenated: {dst}"
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("kind,updater", CASES)
def test_fused_update_selects_nothing(kind, updater):
    pa, sa, _, _ = _unsharded(kind, updater, True)
    pb, sb, _, _ = _unsharded(kind, updater, False)
    for a, b in ((pa, pb), (sa, sb)):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        assert len(la) == len(lb)
        assert all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(la, lb))
    if updater == "nesterovs_bf16":
        assert {str(np.asarray(l).dtype) for l in jax.tree.leaves(sa)} \
            == {"bfloat16"}


@pytest.mark.parametrize("updater", list(UPDATERS))
@pytest.mark.parametrize("kind", ["graph", "multilayer"])
def test_zero1_keeps_the_bucket_kernel(kind, updater):
    prof = OpProfiler.get()
    prof.reset()
    _fit(kind, updater, False, zero1=True)
    stats = prof.precision_stats()
    assert stats["fused_hits"] > 0
    assert stats["grads_flat_in_step"] == 1


def test_unsharded_fit_reports_tree_path():
    prof = OpProfiler.get()
    prof.reset()
    _fit("graph", "nesterovs_bf16", True)
    stats = prof.precision_stats()
    assert stats.get("grads_flat_in_step") == 0
    assert not stats.get("fused_hits") and not stats.get(
        "fused_buckets_pallas")
    assert stats["sr_draws"] > 0
