"""The program's sections as host spans on the profiler's clock.

``OpProfiler.time_section`` is the one span primitive: besides its
aggregate and its flight-recorder event, the body runs inside a
``jax.profiler.TraceAnnotation``, so under a profiler session every
section is a span in the same ``.xplane.pb`` as the device's ops. Both
``fit`` paths (``ComputationGraph.fit`` over ``data/pipeline.run_epochs``,
``SameDiff.fit``) emit their sections under shared names; ``PERF.md``
section 3 has the table. These tests read the trace the way the benchmark's
reducer does (``jax.profiler.ProfileData``) and hold each path to it.
"""

from __future__ import annotations

import glob
import os
import sys

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.autodiff.samediff import SameDiff, TrainingConfig
from deeplearning4j_tpu.common import flightrec
from deeplearning4j_tpu.common.profiler import OpProfiler
from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.learning import Sgd
from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                         ComputationGraphConfiguration)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
import trace_reduce  # noqa: E402  (benchmarks/trace_reduce.py: stdlib only)

EPOCHS, BATCHES = 2, 3
STEPS = EPOCHS * BATCHES
CALL = "test/fit_call"      # the test's own frame, as bench/fit_call is

#: the sections each path emits inside one traced ``fit`` call
SECTIONS = {
    "graph": ("fit/enter", "pipeline/next_batch", "pipeline/dispatch",
              "fit/epoch_end"),
    "samediff": ("fit/enter", "pipeline/next_batch", "pipeline/dispatch",
                 "fit/epoch_end", "fit/exit"),
}
#: and the ones it must not (nothing there to time: neither fit blocks on a
#: device value on its default path)
ABSENT = {"graph": ("fit/sync", "fit/exit"), "samediff": ("fit/sync",)}


def _graph():
    return ComputationGraph(
        ComputationGraphConfiguration
        .graph_builder(NeuralNetConfiguration.builder().seed(7)
                       .updater(Sgd(0.05)).activation("tanh")
                       .weight_init("xavier"))
        .add_inputs("in")
        .add_layer("d", L.DenseLayer(n_out=8), "in")
        .add_layer("out", L.OutputLayer(n_out=3, loss="mcxent",
                                        activation="softmax"), "d")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(5))
        .build()).init()


def _graph_fit():
    rng = np.random.RandomState(0)
    x = rng.randn(BATCHES * 4, 5).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, BATCHES * 4)]
    model = _graph()
    return model, lambda: model.fit(DataSet(x, y), epochs=EPOCHS,
                                    batch_size=4)


def _samediff_fit():
    rng = np.random.RandomState(0)
    sd = SameDiff.create()
    x = sd.placeholder("x", shape=(None, 5))
    y = sd.placeholder("y", shape=(None, 3))
    w = sd.var("w", init=rng.randn(5, 3).astype(np.float32) * 0.3)
    b = sd.var("b", shape=(3,), init="zeros")
    sd.loss_ops.softmax_cross_entropy((x @ w) + b, y).rename("loss")
    sd.set_loss_variables("loss")
    sd.set_training_config(TrainingConfig(updater=Sgd(learning_rate=0.05),
                                          loss_name="loss"))
    batches = [{"x": rng.randn(4, 5).astype(np.float32),
                "y": np.eye(3, dtype=np.float32)[rng.randint(0, 3, 4)]}
               for _ in range(BATCHES)]
    return sd, lambda: sd.fit(batches, epochs=EPOCHS)


def _host_spans(logdir: str) -> list:
    """Every span of a host plane: name, start, end, stats, thread line."""
    (xplane,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
    out = []
    for plane in trace_reduce.load(xplane).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.append({"name": e.name, "start": e.start_ns,
                            "end": e.start_ns + e.duration_ns,
                            "stats": dict(e.stats), "line": line.name})
    return out


@pytest.fixture(scope="module", params=["graph", "samediff"])
def traced(request, tmp_path_factory):
    """One ``fit`` call of the path under ``OpProfiler.trace``, after one
    untraced call that compiles the step: the path's name, the model's
    iteration as the traced call began, the program's spans inside the
    call by section, the call's own span, and the section counts of the
    traced call alone."""
    model, fit = (_graph_fit if request.param == "graph"
                  else _samediff_fit)()
    prof = OpProfiler.get()
    fit()
    first_step, call = model._iteration, model._fit_calls + 1
    before = prof.get_statistics()
    logdir = str(tmp_path_factory.mktemp(f"trace_{request.param}"))
    with prof.trace(logdir):
        with jax.profiler.TraceAnnotation(CALL):
            fit()
    after = prof.get_statistics()
    counts = {k: v["count"] - before.get(k, {"count": 0})["count"]
              for k, v in after.items()}
    spans = _host_spans(logdir)
    (frame,) = [s for s in spans if s["name"] == CALL]
    sections: dict = {}
    for s in spans:
        if (s["name"] in after and s["line"] == frame["line"]
                and frame["start"] <= s["start"] and s["end"] <= frame["end"]):
            sections.setdefault(s["name"], []).append(s)
    for rows in sections.values():
        rows.sort(key=lambda s: s["start"])
    return {"path": request.param, "first_step": first_step, "call": call,
            "sections": sections, "frame": frame, "counts": counts}


# --- (a) each section of the table is a host span, in its place -------------

def test_every_section_of_the_path_is_a_host_span(traced):
    missing = [n for n in SECTIONS[traced["path"]]
               if n not in traced["sections"]]
    assert not missing, (missing, sorted(traced["sections"]))


def test_sections_with_nothing_to_time_are_absent(traced):
    present = [n for n in ABSENT[traced["path"]] if n in traced["sections"]]
    assert not present


@pytest.mark.parametrize("section", ["pipeline/dispatch",
                                     "pipeline/next_batch"])
def test_step_sections_carry_their_step(traced, section):
    """One span a step, each with the model's iteration it belongs to; the
    feed's last ``next()`` of an epoch finds it exhausted and is a span
    too, under the step that did not come."""
    steps = [s["stats"].get("step") for s in traced["sections"][section]]
    first = traced["first_step"]
    if section == "pipeline/dispatch":
        assert steps == list(range(first, first + STEPS))
    else:
        per_epoch = [list(range(first + e * BATCHES,
                                first + (e + 1) * BATCHES + 1))
                     for e in range(EPOCHS)]
        assert steps == sum(per_epoch, [])


def test_the_spans_of_one_step_share_its_step_and_its_order(traced):
    """``pipeline/next_batch`` of step n ends before ``pipeline/dispatch``
    of step n begins."""
    nxt = {s["stats"]["step"]: s
           for s in traced["sections"]["pipeline/next_batch"]}
    for d in traced["sections"]["pipeline/dispatch"]:
        step = d["stats"]["step"]
        # two spans share the first step of a later epoch: the exhausted
        # next() that ended the epoch before, and the real one, which is
        # the later of the two and so the one the dict kept
        assert nxt[step]["end"] <= d["start"], step


def test_epoch_sections_carry_their_epoch(traced):
    ends = traced["sections"]["fit/epoch_end"]
    assert [s["stats"].get("epoch") for s in ends] == list(range(EPOCHS))


def test_enter_ends_before_the_first_dispatch(traced):
    (enter,) = traced["sections"]["fit/enter"]
    assert enter["stats"].get("call") == traced["call"]
    assert enter["end"] <= traced["sections"]["pipeline/dispatch"][0]["start"]
    assert enter["end"] <= traced["sections"]["pipeline/next_batch"][0]["start"]


def test_exit_starts_after_the_last_dispatch(traced):
    if traced["path"] == "graph":   # nothing to do on the way out: no span
        assert "fit/exit" not in traced["sections"]
        return
    (leave,) = traced["sections"]["fit/exit"]
    assert leave["stats"].get("call") == traced["call"]
    assert leave["start"] >= traced["sections"]["pipeline/dispatch"][-1]["end"]
    assert leave["start"] >= traced["sections"]["fit/epoch_end"][-1]["end"]


def test_no_program_span_contains_a_whole_call(traced):
    """``trace_reduce._name_gap`` names a gap after the non-``bench/`` span
    that covers most of it: a program span around a whole call, or around
    an epoch's loop, would swallow every name beneath it."""
    dispatches = traced["sections"]["pipeline/dispatch"]
    loops = [(dispatches[0], dispatches[-1])] + [
        (dispatches[e * BATCHES], dispatches[(e + 1) * BATCHES - 1])
        for e in range(EPOCHS)]
    for name, rows in traced["sections"].items():
        for s in rows:
            for first, last in loops:
                assert not (s["start"] <= first["start"]
                            and s["end"] >= last["end"]), name


# --- (b) the counts repeat exactly ------------------------------------------

@pytest.mark.parametrize("section,graph,samediff", [
    ("pipeline/dispatch", STEPS, STEPS),
    ("pipeline/next_batch", STEPS + EPOCHS, STEPS + EPOCHS),
    ("fit/sync", 0, 0),
    ("fit/epoch_end", EPOCHS, EPOCHS),
    ("fit/enter", 1, 1),
    ("fit/exit", 0, 1),
])
def test_section_counts_repeat_exactly(traced, section, graph, samediff):
    want = graph if traced["path"] == "graph" else samediff
    assert traced["counts"].get(section, 0) == want
    assert len(traced["sections"].get(section, ())) == want


# --- (c) the reducer's gap naming prefers the program's spans ---------------

_MS = 1_000_000
_GAP = (100 * _MS, 110 * _MS)


@pytest.mark.parametrize("spans,want", [
    # the benchmark's frame and the program's section both cover the gap
    ([(0, 500 * _MS, "bench/fit_call"),
      (99 * _MS, 111 * _MS, "fit/exit")], "fit/exit"),
    # only a runtime span covers it
    ([(0, 500 * _MS, "bench/fit_call"),
      (100 * _MS, 110 * _MS, "np.asarray(jax.Array)")],
     "np.asarray(jax.Array)"),
    # section and runtime span inside it: the innermost names the gap
    ([(0, 500 * _MS, "bench/fit_call"),
      (99 * _MS, 112 * _MS, "fit/epoch_end"),
      (99.5 * _MS, 111 * _MS, "fit/sync"),
      (100 * _MS, 110 * _MS, "np.asarray(jax.Array)")],
     "np.asarray(jax.Array)"),
    # a section that covers most of the gap beats one that covers a little
    ([(0, 500 * _MS, "bench/fit_call"),
      (92 * _MS, 107 * _MS, "fit/epoch_end"),
      (107 * _MS, 120 * _MS, "pipeline/next_batch")], "fit/epoch_end"),
    # nothing of the program there: the fallback names the frame
    ([(0, 500 * _MS, "bench/fit_call"),
      (100 * _MS, 102 * _MS, "fit/sync")],
     "bench/fit_call: Python between runtime calls"),
    # no span at all
    ([], "outside the benchmark's calls: Python between runtime calls"),
], ids=["section_over_frame", "runtime_span_alone", "innermost_wins",
        "most_coverage_wins", "under_half_falls_back", "no_span"])
def test_name_gap(spans, want):
    assert trace_reduce._name_gap(spans, *_GAP) == want


# --- (d) with no profiler session: aggregate and event as before ------------

def test_no_session_aggregate_and_event_carry_the_attrs():
    prof = OpProfiler.get()
    name = "test_trace_spans/no_session"
    seq0 = flightrec.get().stats()["events_total"]
    for step in (3, 4):
        with prof.time_section(name, step=step):
            pass
    with prof.time_section(name):
        pass
    row = prof.get_statistics()[name]
    assert row["count"] == 3
    assert 0.0 <= row["max_s"] <= row["total_s"]
    assert set(row) == {"count", "total_s", "max_s"}
    mine = [e for e in flightrec.events("profiler/section")
            if e["seq"] >= seq0 and e["attrs"].get("section") == name]
    assert [e["attrs"].get("step") for e in mine] == [3, 4, None]
    assert all(e["attrs"]["dur_s"] >= 0.0 for e in mine)


def test_section_records_when_the_body_raises():
    prof = OpProfiler.get()
    name = "test_trace_spans/raises"
    with pytest.raises(KeyError):
        with prof.time_section(name, epoch=1):
            raise KeyError("x")
    assert prof.get_statistics()[name]["count"] == 1


def test_graph_init_section():
    prof = OpProfiler.get()
    n0 = prof.get_statistics().get("build/init", {"count": 0})["count"]
    _graph()
    assert prof.get_statistics()["build/init"]["count"] == n0 + 1


def test_multilayer_init_and_enter_sections():
    from deeplearning4j_tpu.data import NDArrayDataSetIterator
    from deeplearning4j_tpu.nn import MultiLayerNetwork

    prof = OpProfiler.get()

    def count(section):
        return prof.get_statistics().get(section, {"count": 0})["count"]

    init0, enter0 = count("build/init"), count("fit/enter")
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.05))
            .activation("tanh").weight_init("xavier").list()
            .layer(L.DenseLayer(n_out=8))
            .layer(L.OutputLayer(n_out=3, loss="mcxent",
                                 activation="softmax"))
            .set_input_type(InputType.feed_forward(5)).build())
    model = MultiLayerNetwork(conf).init()
    assert count("build/init") == init0 + 1
    rng = np.random.RandomState(0)
    x = rng.randn(8, 5).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 8)]
    model.fit(NDArrayDataSetIterator(x, y, batch_size=4), epochs=1)
    assert count("fit/enter") == enter0 + 1
    assert model._fit_calls == 1


def test_tf_import_section():
    tf = pytest.importorskip("tensorflow")
    from deeplearning4j_tpu.imports import import_frozen_tf

    prof = OpProfiler.get()
    n0 = prof.get_statistics().get("build/import_graph",
                                   {"count": 0})["count"]
    g = tf.Graph()
    with g.as_default():
        x = tf.compat.v1.placeholder(tf.float32, (2, 3), name="x")
        tf.identity(tf.nn.relu(x), name="out")
    import_frozen_tf(g.as_graph_def())
    assert prof.get_statistics()["build/import_graph"]["count"] == n0 + 1
