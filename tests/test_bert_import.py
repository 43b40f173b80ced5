"""BERT import e2e (north-star config 3, SURVEY.md §3.4).

Tiny-config BERT (same graph topology as base — the layer count/width are the
only differences) built with local TF, frozen, imported, checked for forward
parity against TF, then fine-tuned: constants promoted to variables, a
classifier head + loss grafted on, sd.fit() with dict batches, loss falls.
The full-size BERT-base step runs on the TPU in ``chip_smoke.py``; its
samples/sec comes from ``bench.py --config bert`` there (not measured on
this chip).
"""

from __future__ import annotations

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")

from deeplearning4j_tpu.autodiff.samediff import TrainingConfig  # noqa: E402
from deeplearning4j_tpu.imports import import_frozen_tf  # noqa: E402
from deeplearning4j_tpu.imports.tf_fixtures import (  # noqa: E402
    build_bert_frozen_graph, make_bert_batch)
from deeplearning4j_tpu.learning import Adam  # noqa: E402

CFG = dict(batch=2, seq=16, hidden=32, layers=2, heads=4, intermediate=64,
           vocab=97, type_vocab=2, max_pos=32)


@pytest.fixture(scope="module")
def bert_graph():
    gd, in_names, n_params = build_bert_frozen_graph(**CFG)
    return gd, in_names, n_params


class TestBertImport:
    def test_forward_parity_vs_tf(self, bert_graph):
        gd, in_names, _ = bert_graph
        ids, types, mask, _ = make_bert_batch(CFG["batch"], CFG["seq"],
                                              CFG["vocab"], 3)
        # TF golden
        g = tf.Graph()
        with g.as_default():
            tf.graph_util.import_graph_def(gd, name="")
        with tf.compat.v1.Session(graph=g) as sess:
            out_name = [n.name for n in gd.node][-1] + ":0"
            expected = sess.run(out_name, {f"{n}:0": v for n, v in
                                           zip(in_names, (ids, types, mask))})
        sd = import_frozen_tf(gd)
        assert len(sd.tf_outputs) == 1
        got = sd.output(dict(zip(in_names, (ids, types, mask))),
                        sd.tf_outputs)[sd.tf_outputs[0]].to_numpy()
        np.testing.assert_allclose(got, expected, atol=2e-4, rtol=1e-3)

    def test_fine_tune_loss_falls(self, bert_graph):
        gd, in_names, _ = bert_graph
        sd = import_frozen_tf(gd)
        pooled = sd.get_variable(sd.tf_outputs[0])

        promoted = sd.convert_to_variables()
        assert len(promoted) > 10  # encoder weights are trainable now

        n_classes = 3
        w = sd.var("cls_w", shape=(CFG["hidden"], n_classes), init="xavier")
        b = sd.var("cls_b", shape=(n_classes,), init="zeros")
        logits = pooled.mmul(w).add(b).rename("logits")
        labels = sd.placeholder("labels", shape=(CFG["batch"], n_classes))
        loss = sd.ops.softmax_cross_entropy(logits, labels, name="loss")
        sd.set_loss_variables("loss")
        sd.set_training_config(TrainingConfig(updater=Adam(1e-3),
                                              loss_name="loss"))

        ids, types, mask, y = make_bert_batch(CFG["batch"], CFG["seq"],
                                              CFG["vocab"], n_classes)
        batch = dict(zip(in_names, (ids, types, mask)))
        batch["labels"] = y

        loss_before = float(sd.output(batch, ["loss"])["loss"].to_numpy())
        hist = sd.fit([batch] * 10, epochs=1)
        loss_after = float(sd.output(batch, ["loss"])["loss"].to_numpy())
        assert np.isfinite(loss_after)
        assert loss_after < loss_before * 0.8, (loss_before, loss_after)
        assert hist.final_loss() is not None and np.isfinite(hist.final_loss())
