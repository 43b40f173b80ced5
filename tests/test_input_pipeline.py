"""Shared input/dispatch pipeline (data/pipeline.py).

The acceptance contract of the trace-stable, overlapped training loop:

1. shape-stable batching — an epoch whose final batch is PARTIAL still
   compiles the train step exactly ONCE (retrace counter proof), and the
   padded, weight-masked training run produces the params of the unpadded
   masked-loss loop (to reduction order: ``_assert_padding_invisible``);
2. multi-step dispatch — ``steps_per_dispatch=K``'s lax.scan device loop
   matches the per-step loop's final params exactly (same rng stream,
   same core step function);
3. drop_remainder, the device-feed ordering, and the ParallelWrapper /
   ComputationGraph integrations.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.common.background import staged_iter
from deeplearning4j_tpu.common.profiler import OpProfiler
from deeplearning4j_tpu.data import DataSet, NDArrayDataSetIterator
from deeplearning4j_tpu.data import pipeline as pipe
from deeplearning4j_tpu.learning import Adam, Sgd
from deeplearning4j_tpu.ndarray.rng import get_random
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.optimize.listeners import PipelineMetricsListener


def _mlp(seed: int = 7, updater=None):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(updater or Sgd(learning_rate=0.05))
            .activation("tanh").weight_init("xavier").list()
            .layer(L.DenseLayer(n_out=16))
            .layer(L.OutputLayer(n_out=3, loss="mcxent",
                                 activation="softmax"))
            .set_input_type(InputType.feed_forward(5)).build())
    return MultiLayerNetwork(conf).init()


def _data(n: int = 22, seed: int = 0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 5).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, n)]
    return x, y


def _leaves(model):
    return [np.asarray(l) for l in jax.tree.leaves(model._params)]


# A padded last batch and an unpadded one are two batch shapes, so two
# compiled programs, and XLA promises no reduction order across programs:
# the pad rows' terms are exactly 0.0, but the sums they sit in may be
# associated differently. Measured on this tree (jax 0.9, CPU): after the
# first padded step 1 float32 ulp on 2 of 3 (LSTM case) or 2 of 16 (MLP)
# elements, max relative 8.7e-8 to 2.1e-7; the later steps then train from
# parameters 1 ulp apart. Held to 1e-6 of the leaf's largest magnitude,
# not of each element: a bias that is the remainder of a few updates of
# opposite sign (1.6e-5 in a leaf of 1.0, 3e-3 in one of 2.8e-2) carries
# the rounding of its terms, 9-16 ulp of itself after six steps and
# 1.3e-7 of its leaf. A gap beyond that would be a fault, not an order.
PAD_RTOL = 1e-6


def _assert_padding_invisible(run):
    """``run(pad_partial)`` trains a fresh model and returns its params'
    leaves. Padded against padded (equal shapes, one program) stays
    bit-for-bit; padded against unpadded is held to ``PAD_RTOL``."""
    padded, again, unpadded = run(True), run(True), run(False)
    for a, b in zip(padded, again):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(padded, unpadded):
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=PAD_RTOL * np.abs(b).max())


class TestShapeStableBatching:
    def test_padded_training_matches_masked_unpadded_bitforbit(self):
        """22 examples at batch 8 → 8, 8, 6: the padded run (6→8 with
        zero example weights) must land on the params of the unpadded
        weight-masked run — padding is numerically invisible."""
        x, y = _data()

        def run(pad):
            model = _mlp()
            get_random().set_seed(1)
            model.fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=3,
                      pad_partial=pad)
            return _leaves(model)

        _assert_padding_invisible(run)

    def test_one_compile_across_epoch_with_partial_final_batch(self):
        x, y = _data()
        prof = OpProfiler.get()
        prof.reset()
        model = _mlp()
        listener = PipelineMetricsListener()
        model.set_listeners(listener)
        model.fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=2)
        assert prof.counter_value("trace/mln_fit_step") == 1, \
            prof.trace_counts()
        # 22 @ 8 → one padded remainder per epoch
        assert prof.counter_value("pipeline/padded_batches") == 2
        # and the listener bus surfaces the same ledger
        assert listener.trace_count("mln_fit_step") == 1
        assert listener.snapshots[-1]["traces"]["trace/mln_fit_step"] == 1

    def test_unpadded_run_retraces_on_remainder(self):
        """Control for the counter itself: with padding OFF the partial
        batch costs a second trace."""
        x, y = _data()
        prof = OpProfiler.get()
        prof.reset()
        model = _mlp()
        model.fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=2,
                  pad_partial=False)
        assert prof.counter_value("trace/mln_fit_step") == 2

    def test_drop_remainder_skips_partial_batch(self):
        x, y = _data()
        model = _mlp()
        model.fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=1,
                  drop_remainder=True)
        assert model._iteration == 2     # 22 @ 8 → 2 full batches only

        seen = [ds.num_examples()
                for ds in DataSet(x, y).batch_by(8, drop_remainder=True)]
        assert seen == [8, 8]
        # the source-level knob on the iterator drops it before the
        # pipeline ever sees it
        seen = [ds.num_examples() for ds in
                NDArrayDataSetIterator(x, y, 8, drop_remainder=True)]
        assert seen == [8, 8]

    def test_pad_dataset_wraps_rows_and_zero_weights(self):
        x, y = _data(6)
        ds, w = pipe.pad_dataset(DataSet(x, y), 8)
        np.testing.assert_array_equal(np.asarray(w),
                                      [1, 1, 1, 1, 1, 1, 0, 0])
        got = ds.features.to_numpy()
        np.testing.assert_array_equal(got[:6], x)
        np.testing.assert_array_equal(got[6:], x[:2])   # wrapped, not zeros

    def test_masked_sequence_loss_survives_padding(self):
        """Padding must compose with an existing per-timestep labels mask
        (the weight folds INTO the mask, it doesn't replace it)."""
        rng = np.random.RandomState(3)
        n, t = 11, 6
        x = rng.randn(n, t, 4).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, (n, t))]
        mask = (rng.rand(n, t) > 0.3).astype(np.float32)
        mask[:, 0] = 1.0
        conf = (NeuralNetConfiguration.builder().seed(5)
                .updater(Sgd(learning_rate=0.05)).activation("tanh")
                .weight_init("xavier").list()
                .layer(L.LSTM(n_out=8))
                .layer(L.RnnOutputLayer(n_out=3, loss="mcxent",
                                        activation="softmax"))
                .set_input_type(InputType.recurrent(4, t)).build())

        def run(pad):
            m = MultiLayerNetwork(conf).init(seed=5)
            get_random().set_seed(2)
            data = [DataSet(x[i:i + 4], y[i:i + 4],
                            labels_mask=mask[i:i + 4])
                    for i in range(0, n, 4)]
            from deeplearning4j_tpu.data import ExistingDataSetIterator

            it = ExistingDataSetIterator(data)
            m.fit(it, epochs=2, batch_size=4, pad_partial=pad)
            return _leaves(m)

        _assert_padding_invisible(run)


class TestMultiStepDispatch:
    def test_chunked_loop_matches_per_step_params(self):
        x, y = _data(32)     # 4 full batches @ 8 → clean chunks of 2
        per_step = _mlp(updater=Adam(0.01))
        get_random().set_seed(9)
        per_step.fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=3)
        chunked = _mlp(updater=Adam(0.01))
        get_random().set_seed(9)
        chunked.fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=3,
                    steps_per_dispatch=2)
        for a, b in zip(_leaves(per_step), _leaves(chunked)):
            np.testing.assert_array_equal(a, b)

    def test_chunk_tail_runs_through_per_step_path(self):
        """22 @ 8 → 3 padded batches; K=2 leaves a 1-batch tail that must
        train through the per-step jit — total params equal the K=1 run."""
        x, y = _data()
        a = _mlp()
        get_random().set_seed(4)
        a.fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=2)
        b = _mlp()
        get_random().set_seed(4)
        b.fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=2,
              steps_per_dispatch=2)
        assert b._iteration == a._iteration == 6
        for pa, pb in zip(_leaves(a), _leaves(b)):
            np.testing.assert_array_equal(pa, pb)

    def test_chunk_compiles_once_and_syncs_per_chunk_losses(self):
        x, y = _data(48)
        prof = OpProfiler.get()
        prof.reset()
        model = _mlp()
        from deeplearning4j_tpu.optimize.listeners import \
            CollectScoresIterationListener

        scores = CollectScoresIterationListener()
        model.set_listeners(scores)
        model.fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=2,
                  steps_per_dispatch=3)
        assert prof.counter_value("trace/mln_fit_chunk") == 1
        assert prof.counter_value("trace/mln_fit_step") == 0
        assert len(scores.scores) == 12      # every step still reported
        assert all(np.isfinite(s) for _, s in scores.scores)


class TestDeviceFeed:
    def test_staged_iter_preserves_order_and_stages_ahead(self):
        staged = []
        out = []
        it = staged_iter(range(6), stage=lambda i: staged.append(i) or i,
                         depth=2)
        for v in it:
            out.append(v)
            if v == 0:
                # by the time item 0 is handed over, items 1 and 2 must
                # already be staged (double buffering)
                assert staged == [0, 1, 2]
        assert out == list(range(6))
        assert staged == list(range(6))

    def test_staged_iter_host_prefetch_thread(self):
        out = list(staged_iter(iter(range(20)), depth=2, host_prefetch=4))
        assert out == list(range(20))

    def test_overlap_stats_recorded(self):
        x, y = _data(32)
        prof = OpProfiler.get()
        prof.reset()
        model = _mlp()
        model.fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=1)
        stats = prof.overlap_stats()
        assert stats["host_wait_count"] >= 4
        assert stats["dispatch_count"] == 4
        assert 0.0 <= stats["host_wait_frac"] <= 1.0


class TestGraphPipeline:
    def _graph(self):
        from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                                 ComputationGraphConfiguration)

        return ComputationGraph(
            ComputationGraphConfiguration
            .graph_builder(NeuralNetConfiguration.builder().seed(7)
                           .updater(Sgd(0.05)).activation("tanh")
                           .weight_init("xavier"))
            .add_inputs("in")
            .add_layer("d", L.DenseLayer(n_out=16), "in")
            .add_layer("out", L.OutputLayer(n_out=3, loss="mcxent",
                                            activation="softmax"), "d")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(5))
            .build()).init()

    def test_graph_one_compile_and_padded_equivalence(self):
        x, y = _data()
        prof = OpProfiler.get()
        prof.reset()

        def run(pad):
            g = self._graph()
            get_random().set_seed(1)
            g.fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=2,
                  pad_partial=pad)
            return _leaves(g)

        run(True)
        assert prof.counter_value("trace/graph_fit_step") == 1
        _assert_padding_invisible(run)

    def test_graph_chunked_matches_per_step(self):
        x, y = _data(32)
        a = self._graph()
        get_random().set_seed(2)
        a.fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=2)
        b = self._graph()
        get_random().set_seed(2)
        b.fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=2,
              steps_per_dispatch=2)
        for pa, pb in zip([np.asarray(l) for l in jax.tree.leaves(a._params)],
                          [np.asarray(l) for l in jax.tree.leaves(b._params)]):
            np.testing.assert_array_equal(pa, pb)


class TestParallelWrapperPipeline:
    @pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >= 4 devices")
    def test_wrapper_one_compile_with_partial_batches(self):
        from deeplearning4j_tpu.parallel import ParallelWrapper

        x, y = _data()
        prof = OpProfiler.get()
        prof.reset()
        model = _mlp()
        get_random().set_seed(1)
        pw = ParallelWrapper.Builder(model).workers(4).build()
        pw.fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=2)
        assert prof.counter_value("trace/pw_fit_step") == 1
        assert model._iteration == 6
        assert np.isfinite(float(model._score_dev))

    @pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >= 4 devices")
    def test_wrapper_regularized_padded_matches_single_device(self):
        """The padded remainder must not inflate the weight-decay term:
        per-shard losses divide the weighted data sum by global_real/S
        while reg stays unscaled, so a wrapper run over a partial final
        batch tracks the single-device pipeline run on an L2 model."""
        from deeplearning4j_tpu.parallel import ParallelWrapper

        x, y = _data()        # 22 @ 8 → final batch 6, padded
        def build():
            conf = (NeuralNetConfiguration.builder().seed(7)
                    .updater(Sgd(learning_rate=0.05)).activation("tanh")
                    .weight_init("xavier").l2(1e-2).list()
                    .layer(L.DenseLayer(n_out=16))
                    .layer(L.OutputLayer(n_out=3, loss="mcxent",
                                         activation="softmax"))
                    .set_input_type(InputType.feed_forward(5)).build())
            return MultiLayerNetwork(conf).init()

        a = build()
        get_random().set_seed(5)
        ParallelWrapper.Builder(a).workers(2).build() \
            .fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=3)
        b = build()
        get_random().set_seed(5)
        b.fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=3)
        for pa, pb in zip(_leaves(a), _leaves(b)):
            np.testing.assert_allclose(pa, pb, rtol=0, atol=1e-5)

    @pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >= 4 devices")
    def test_wrapper_chunked_matches_per_step(self):
        from deeplearning4j_tpu.parallel import ParallelWrapper

        x, y = _data(32)
        a = _mlp()
        get_random().set_seed(3)
        ParallelWrapper.Builder(a).workers(4).build() \
            .fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=2)
        b = _mlp()
        get_random().set_seed(3)
        ParallelWrapper.Builder(b).workers(4).build() \
            .fit(NDArrayDataSetIterator(x, y, batch_size=8), epochs=2,
                 steps_per_dispatch=2)
        for pa, pb in zip(_leaves(a), _leaves(b)):
            np.testing.assert_array_equal(pa, pb)


class TestPipelinePrimitives:
    def test_stable_batches_uniform_shapes(self):
        x, y = _data(22)
        sizes = [(ds.num_examples(), int(np.asarray(w).sum()), n) for ds, w, n
                 in pipe.stable_batches(NDArrayDataSetIterator(x, y, 8))]
        assert sizes == [(8, 8, 8), (8, 8, 8), (8, 6, 6)]

    def test_stable_batches_round_to_multiple(self):
        x, y = _data(22)
        sizes = [(ds.num_examples(), n) for ds, _w, n in
                 pipe.stable_batches(DataSet(x, y),
                                     round_to_multiple_of=8)]
        assert sizes == [(24, 22)]

    def test_drop_remainder_with_worker_rounding_keeps_full_batches(self):
        """Regression: batch_size=6 with 4 workers rounds the target to 8;
        drop_remainder must drop only the REAL remainder (n < 6), not the
        full 6-row batches that merely need worker-padding to 8."""
        x, y = _data(15)     # 6, 6, 3 @ batch 6
        out = [(ds.num_examples(), n) for ds, _w, n in
               pipe.stable_batches(NDArrayDataSetIterator(x, y, 6),
                                   drop_remainder=True,
                                   round_to_multiple_of=4)]
        assert out == [(8, 6), (8, 6)]      # padded to 8, remainder dropped

    def test_chunked_groups(self):
        assert list(pipe.chunked(iter(range(7)), 3)) == \
            [[0, 1, 2], [3, 4, 5], [6]]
        with pytest.raises(ValueError):
            list(pipe.chunked(iter(range(3)), 0))

    def test_resolve_batch_size(self):
        x, y = _data(8)
        assert pipe.resolve_batch_size(NDArrayDataSetIterator(x, y, 4),
                                       None) == 4
        # an iterator's NATIVE batch size wins: the pipeline cannot
        # re-batch a self-batching source, and padding every batch up to
        # a larger explicit figure would silently multiply per-step FLOPs
        assert pipe.resolve_batch_size(NDArrayDataSetIterator(x, y, 4),
                                       16) == 4
        assert pipe.resolve_batch_size(DataSet(x, y), 16) == 16
        assert pipe.resolve_batch_size(DataSet(x, y), None) is None
