"""Device time by model scope (ISSUE 36).

The step names its phases (``forward`` / ``update`` and what JAX derives from
them), the networks name each vertex inside what remat wraps, and
``common.xprof.scope_times`` reads the names back from a profiler session's
raw ``XSpace``. Four parts: (a) the lowered step of every entry point
carries the scopes; (b) ``scope_kinds()`` covers the vertices; (c) the
reader on a trace recorded on the chip (``tests/resources/scope_trace``) and
on synthetic planes; (d) the benchmark's six ``scope_*`` metric files
against a synthetic table.
"""

from __future__ import annotations

import gzip
import importlib.util
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.autodiff.samediff import (SameDiff, TrainingConfig,
                                                  _op_scope)
from deeplearning4j_tpu.common import xprof
from deeplearning4j_tpu.common.profiler import OpProfiler
from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.learning.updaters import Adam
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                         ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.train_step import vertex_scope

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESOURCES = os.path.join(ROOT, "tests", "resources", "scope_trace")
FIXTURE = os.path.join(RESOURCES, "tiny_decoder_tpu.xplane.pb.gz")
METRICS = os.path.join(ROOT, "benchmarks", "metrics")


# --- (a) the lowered step carries the scopes ----------------------------------

def _adam_bf16():
    u = Adam(1e-3)
    u.state_dtype = "bfloat16"      # so that the step rounds: scope ``sr``
    return u


def _builder():
    return (NeuralNetConfiguration.builder().seed(7).updater(_adam_bf16())
            .activation("tanh").weight_init("xavier").remat_policy("full"))


def _xy():
    rng = np.random.RandomState(0)
    return (rng.randn(8, 5).astype(np.float32),
            np.eye(3, dtype=np.float32)[rng.randint(0, 3, 8)])


def _graph():
    g = ComputationGraph(
        ComputationGraphConfiguration.graph_builder(_builder())
        .add_inputs("in")
        .add_layer("enc/d1", L.DenseLayer(n_out=8), "in")
        .add_layer("out", L.OutputLayer(n_out=3, loss="mcxent",
                                        activation="softmax"), "enc/d1")
        .set_outputs("out").set_input_types(InputType.feed_forward(5))
        .build()).init()
    g.fit(DataSet(*_xy()), epochs=1, batch_size=4)
    return "graph/fit_step", "enc.d1"


def _multilayer():
    m = MultiLayerNetwork(
        _builder().list().layer(L.DenseLayer(n_out=8))
        .layer(L.OutputLayer(n_out=3, loss="mcxent", activation="softmax"))
        .set_input_type(InputType.feed_forward(5)).build()).init()
    m.fit(DataSet(*_xy()), epochs=1, batch_size=4)
    return "mln/fit_step", "layer0"


def _samediff():
    rng = np.random.RandomState(0)
    sd = SameDiff.create()
    x = sd.placeholder("x", shape=(None, 5))
    y = sd.placeholder("y", shape=(None, 3))
    w = sd.var("enc/dense/w", init=rng.randn(5, 3).astype(np.float32) * 0.3)
    (x @ w).rename("enc/dense/MatMul")
    sd.loss_ops.softmax_cross_entropy(sd.get_variable("enc/dense/MatMul"),
                                      y).rename("loss")
    sd.set_loss_variables("loss")
    sd.set_training_config(TrainingConfig(updater=_adam_bf16(),
                                          loss_name="loss"))
    xs, ys = _xy()
    sd.fit([{"x": xs[:4], "y": ys[:4]}], epochs=1)
    return "samediff/fit_step", "enc/dense"


ENTRY_POINTS = {"graph": _graph, "multilayer": _multilayer,
                "samediff": _samediff}


@pytest.fixture(scope="module", params=sorted(ENTRY_POINTS))
def lowered(request):
    """(entry point, the vertex that holds its hidden layer, the name stacks
    of its step as it was called)."""
    xprof.reset()
    xprof.configure(enabled=True)
    census, vertex = ENTRY_POINTS[request.param]()
    entry = xprof._CENSUS._entries[census]
    args, kwargs = entry.avals
    text = entry.fn_ref().lower(*args, **kwargs).as_text(debug_info=True)
    return request.param, vertex, set(re.findall(r'loc\("([^"]*)"', text))


def _has(names, pattern):
    return any(re.search(pattern, n) for n in names)


def test_forward_names_the_vertex(lowered):
    _, vertex, names = lowered
    assert _has(names, rf"^jit\(step\)/jvp\(forward\)/{vertex}/")


def test_backward_is_the_transpose_of_forward(lowered):
    _, vertex, names = lowered
    assert _has(names, rf"^jit\(step\)/transpose\(jvp\(forward\)\)/.*{vertex}/")


def test_recomputed_forward_carries_the_vertex(lowered):
    kind, vertex, names = lowered
    if kind == "samediff":      # its step does not rematerialise
        assert not _has(names, "rematted_computation")
    else:
        assert _has(names, rf"/rematted_computation/{vertex}/")


def test_update_names_updater_and_rounding(lowered):
    _, _, names = lowered
    assert _has(names, r"^jit\(step\)/update/updater/")
    assert _has(names, r"^jit\(step\)/update/sr/")
    assert not _has(names, r"/update/.*forward")


def test_every_name_stack_has_a_phase(lowered):
    """No op of the step, but for a handful of scalars (the loss's final
    add), lies outside the phases — and the reader's rules class every stack
    the step really has."""
    _, vertex, names = lowered
    stacks = [n for n in names if n.startswith("jit(step)/")]
    phases = {xprof.classify_scope(n)[0] for n in stacks}
    assert {"forward", "backward", "update"} <= phases
    lost = [n for n in stacks if xprof.classify_scope(n)[0] == "other"]
    assert len(lost) <= 0.05 * len(stacks), lost
    # (a SameDiff scope is a directory: its first component is the vertex)
    assert (("backward", vertex.split("/")[0]) in
            {xprof.classify_scope(n)[:2] for n in stacks})


@pytest.mark.parametrize("stack,expected", [
    ("jit(step)/jvp(forward)/attn_1/mla_q/dot_general:",
     ("forward", "attn_1", "mla_q")),
    ("jit(step)/transpose(jvp(forward))/jvp(forward)/checkpoint/"
     "rematted_computation/l2_ffn/moe_router/jit(_where)/select_n",
     ("recompute", "l2_ffn", "moe_router")),
    ("jit(step)/transpose(jvp(forward))/jvp(forward)/checkpoint/l2_ffn/"
     "moe_experts/mul", ("backward", "l2_ffn", "moe_experts")),
    ("jit(step)/transpose(jvp(forward))/head/while/body/closed_call/"
     "dot_general", ("backward", "head", "")),
    ("jit(step)/jvp(forward)/while", ("forward", "", "")),
    ("jit(step)/update/sr/threefry2x32", ("update", "", "sr")),
    ("jit(step)/update/updater/mul", ("update", "", "updater")),
    ("jit(step)/telemetry/sqrt", ("other", "telemetry", "")),
    ("jit(step)/forward/bert/encoder/layer_3/attention/MatMul",
     ("forward", "bert", "encoder/layer_3/attention")),
    # an imported graph's directories keep their names: ``while`` and
    # ``cond`` are JAX's only with JAX's next word behind them, a phase's
    # name only as the step's first scope
    ("jit(step)/jvp(forward)/cond/while/Less", ("forward", "cond", "while")),
    ("jit(step)/jvp(forward)/body/cond/branch_1_fun/while/cond/lt",
     ("forward", "body", "")),
    ("jit(step)/jvp(forward)/forward/update/mul",
     ("forward", "forward", "update")),
    ("jit(shmap_body)/shard_map/vmap(update)/updater/mul",
     ("update", "", "updater")),
    ("", ("other", "", "")),
])
def test_classify_scope(stack, expected):
    assert xprof.classify_scope(stack) == expected


def test_op_scope_is_the_variables_directory():
    assert _op_scope("bert/encoder/layer_3/attention/self/MatMul") == \
        "bert/encoder/layer_3/attention"
    assert _op_scope("enc/dense/MatMul") == "enc/dense"
    assert _op_scope("add_3") == "add_3"


def test_vertex_scope():
    assert vertex_scope("enc/d1") == "enc.d1"
    assert vertex_scope(3) == "layer3"


# --- (b) scope_kinds ----------------------------------------------------------

def test_scope_kinds_cover_every_vertex_with_parameters():
    from deeplearning4j_tpu.models import Lfm2Moe

    m = Lfm2Moe(layers=[2], vocab_rows=96, experts_held=(0, 4),
                hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_attention_heads=4,
                num_key_value_heads=2, num_experts=8, num_experts_per_tok=2,
                seq_len=16).init()
    kinds = m.scope_kinds()
    assert {vertex_scope(n) for n in m._params} <= set(kinds)
    assert {"RotaryAttentionLayer", "RoutedExpertsLayer",
            "TiedOutputLayer"} <= set(kinds.values())
    mln = MultiLayerNetwork(
        _builder().list().layer(L.DenseLayer(n_out=8))
        .layer(L.OutputLayer(n_out=3, loss="mcxent", activation="softmax"))
        .set_input_type(InputType.feed_forward(5)).build()).init()
    assert mln.scope_kinds() == {"layer0": "DenseLayer",
                                 "layer1": "OutputLayer"}


# --- (c) the reader -----------------------------------------------------------

def _synthetic(tmp_path, ops, modules=((0, 1000),), host=()):
    """An ``XSpace`` with one device plane: ``ops`` are ``(start_ns, end_ns,
    name, tf_op)`` on the ``XLA Ops`` line, ``modules`` the executions of
    ``jit_step``; ``host`` are ``(start_ns, end_ns, name)`` of one thread."""
    space = xprof._xplane_classes()()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].name = "tf_op"
    plane.stat_metadata[2].name = "hlo_category"
    plane.stat_metadata[3].name = "flops"
    line = plane.lines.add(name="XLA Ops", timestamp_ns=10)
    ids = {}
    for start, end, name, tf_op in ops:
        if name not in ids:
            ids[name] = mid = len(ids) + 1
            meta = plane.event_metadata[mid]
            meta.name = name
            meta.stats.add(metadata_id=1, str_value=tf_op)
            meta.stats.add(metadata_id=2, str_value="loop fusion")
            meta.stats.add(metadata_id=3, uint64_value=100)
        line.events.add(metadata_id=ids[name], offset_ps=start * 1000,
                        duration_ps=(end - start) * 1000)
    plane.event_metadata[99].name = "jit_step(123)"
    mods = plane.lines.add(name="XLA Modules", timestamp_ns=10)
    for start, end in modules:
        mods.events.add(metadata_id=99, offset_ps=start * 1000,
                        duration_ps=(end - start) * 1000)
    hp = space.planes.add(name="/host:CPU")
    thread = hp.lines.add(name="main", timestamp_ns=10)
    for k, (start, end, name) in enumerate(host):
        hp.event_metadata[k + 1].name = name
        thread.events.add(metadata_id=k + 1, offset_ps=start * 1000,
                          duration_ps=(end - start) * 1000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    return str(path)


def test_nested_while_is_counted_once(tmp_path):
    """A ``while`` of 600 ns that holds two body ops of 200 ns and a nested
    ``while`` of 100 ns with one 60 ns op: self times are 100 / 400 / 40 /
    60, and they sum to the union."""
    f = "jit(step)/jvp(forward)/head/"
    path = _synthetic(tmp_path, [
        (0, 600, "%while.1 = while()", f + "while"),
        (0, 200, "%fusion.1 = fusion(), kind=kLoop", f + "while/body/mul"),
        (250, 350, "%while.2 = while()", f + "while/body/while"),
        (260, 320, "%fusion.2 = fusion(), kind=kOutput",
         f + "while/body/while/body/dot_general"),
        (400, 600, "%fusion.1 = fusion(), kind=kLoop", f + "while/body/mul"),
        (700, 800, "%copy.3 = copy()", ""),
        (2000, 2100, "%copy.3 = copy()", ""),      # outside the step
    ])
    t = xprof.scope_times(path, "jit_step")
    by_op = {r["op"]: r for r in t["rows"]}
    assert t["steps"] == 1
    assert by_op["while"]["ms"] == pytest.approx((100 + 40) * 1e-6)
    assert by_op["while"]["calls"] == 2
    assert by_op["fusion[kLoop]"]["ms"] == pytest.approx(400e-6)
    assert by_op["fusion[kOutput]"]["ms"] == pytest.approx(60e-6)
    assert by_op["fusion[kLoop]"]["flops"] == 200
    assert sum(r["ms"] for r in t["rows"]) == pytest.approx(t["step_ms"])
    assert t["step_ms"] == pytest.approx(700e-6)      # the union, in a step
    assert t["unattributed_ms"] == pytest.approx(100e-6)      # the copy
    assert {r["vertex"] for r in t["rows"]} == {"head", ""}
    # a program that never ran is an error, not an empty table
    with pytest.raises(ValueError, match="jit_step"):
        xprof.scope_times(path, "jit_chunk")


def test_rows_are_per_step_and_sections_are_the_profilers(tmp_path):
    ops = []
    for k in (0, 1000):
        ops += [(k, k + 100, "%fusion.1 = fusion(), kind=kOutput",
                 "jit(step)/jvp(forward)/attn/q/dot_general"),
                (k + 100, k + 300, "%moe_gmm.2 = custom-call()",
                 "jit(step)/transpose(jvp(forward))/ffn/moe_experts/"
                 "pallas_call"),
                (k + 300, k + 350, "%fusion.3 = fusion(), kind=kLoop",
                 "jit(step)/update/sr/add")]
    path = _synthetic(tmp_path, ops, modules=((0, 400), (1000, 1400)),
                      host=((0, 900, "pipeline/dispatch#step=1#"),
                            (100, 300, "fit/enter"),
                            (150, 160, "$profiler.py:91 trace"),
                            (400, 500, "tpu/not_a_section")))
    prof = OpProfiler.get()
    for section in ("pipeline/dispatch", "fit/enter"):  # the program's own
        with prof.time_section(section):
            pass
    t = xprof.scope_times(path)
    assert t["steps"] == 2 and t["step_ms"] == pytest.approx(350e-6)
    got = {(r["phase"], r["vertex"], r["inner"]): r["ms"] for r in t["rows"]}
    assert got == {
        ("forward", "attn", "q"): pytest.approx(100e-6),
        ("backward", "ffn", "moe_experts"): pytest.approx(200e-6),
        ("update", "", "sr"): pytest.approx(50e-6)}
    assert t["unattributed_ms"] == 0
    assert t["host"] == {
        "pipeline/dispatch": {"count": 1, "total_ms": pytest.approx(900e-6),
                              "self_ms": pytest.approx(700e-6)},
        "fit/enter": {"count": 1, "total_ms": pytest.approx(200e-6),
                      "self_ms": pytest.approx(200e-6)}}   # not the tracer's
    text = xprof.scope_table(t, "phase")
    assert "backward" in text and "2 steps" in text
    assert "ffn" in xprof.scope_table(t)


def test_profiler_remembers_its_last_logdir(tmp_path):
    """``OpProfiler.trace`` + ``scope_times()``: on the CPU the trace holds
    no device plane, so the table is empty and the sections are there."""
    prof = OpProfiler.get()
    logdir = str(tmp_path / "trace")
    f = jax.jit(lambda a: a * 2.0)
    f(np.ones(4, np.float32))
    with prof.trace(logdir):
        with prof.time_section("pipeline/dispatch", step=1):
            jax.block_until_ready(f(np.ones(4, np.float32)))
    t = prof.scope_times()
    assert t["rows"] == [] and t["step_ms"] == 0
    assert t["host"]["pipeline/dispatch"]["count"] == 1
    assert prof.scope_times(logdir)["host"].keys() == t["host"].keys()


def test_cached_executable_keeps_its_scopes(tmp_path):
    """JAX's persistent cache would hand a scoped step the executable of
    the same step without scopes (its key leaves metadata out by default),
    and a trace of it would name nothing: the package keys the cache on the
    metadata too."""
    code = (
        "import sys, jax, jax.numpy as jnp, deeplearning4j_tpu\n"
        f"jax.config.update('jax_compilation_cache_dir', {str(tmp_path)!r})\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)\n"
        "import contextlib\n"
        "scope = (jax.named_scope('forward') if sys.argv[1] == 'scoped'\n"
        "         else contextlib.nullcontext())\n"
        "def step(x):\n"
        "    with scope:\n"
        "        return jnp.tanh(x @ x).sum()\n"
        "text = jax.jit(step).lower(jnp.ones((8, 8))).compile().as_text()\n"
        "assert ('forward' in text) == (sys.argv[1] == 'scoped'), text\n")
    for which in ("plain", "scoped"):
        subprocess.run([sys.executable, "-c", code, which], check=True,
                       cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert os.listdir(tmp_path)     # the cache was in use


def test_reader_imports_no_tensorflow():
    code = ("import sys; from deeplearning4j_tpu.common import xprof; "
            f"xprof.scope_times({FIXTURE!r}, 'jit_step'); "
            "assert not any(m.split('.')[0] in ('tensorflow', 'tsl') "
            "for m in sys.modules), 'tensorflow was imported'")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(RESOURCES, "expected.json")) as f:
        expected = json.load(f)
    t = xprof.scope_times(FIXTURE)
    for r in t["rows"]:
        r["kind"] = expected["kinds"].get(r["vertex"], "")
    return t, expected


def test_recorded_rows_sum_to_the_step(recorded):
    t, expected = recorded
    assert t["steps"] == expected["steps"]
    assert sum(r["ms"] for r in t["rows"]) == pytest.approx(t["step_ms"],
                                                            rel=0.01)
    assert t["step_ms"] == pytest.approx(expected["step_ms"], rel=1e-6)
    # the union of the op intervals inside the step's executions, reckoned
    # the benchmark reducer's way from the same file
    assert t["step_ms"] == pytest.approx(expected["reducer_step_ms"],
                                         rel=0.01)


def test_recorded_phases_and_kinds(recorded):
    t, expected = recorded
    by_phase = {}
    for r in t["rows"]:
        by_phase[r["phase"]] = by_phase.get(r["phase"], 0.0) + r["ms"]
    assert {"forward", "recompute", "backward", "update"} <= set(by_phase)
    for phase, ms in expected["by_phase_ms"].items():
        assert by_phase[phase] == pytest.approx(ms, rel=1e-6), phase
    kinds = {r["kind"] for r in t["rows"]}
    assert {"RotaryAttentionLayer", "RoutedExpertsLayer",
            "TiedOutputLayer"} <= kinds
    assert t["unattributed_ms"] == pytest.approx(
        expected["unattributed_ms"], rel=1e-6)
    # (a 0.3 ms step: the fixed copies weigh more than in any cell)
    assert t["unattributed_ms"] < 0.25 * t["step_ms"]


def test_cli_lists_one_layer_scope_by_phase_and_op(recorded, capsys):
    """``--inner moe_experts`` (PR 37's step 0): after the by-phase table,
    the rows of that layer scope by phase and op, summed over the vertices,
    adding up to the scope's own time."""
    t, _ = recorded
    assert xprof._main([FIXTURE, "--by", "phase", "--inner",
                        "moe_experts"]) == 0
    first, second = capsys.readouterr().out.split(" steps, ")[1:]
    listed = [line.split() for line in second.splitlines()[1:]]
    want = sum(r["ms"] for r in t["rows"] if r["inner"] == "moe_experts")
    assert sum(float(l[0]) for l in listed) == pytest.approx(want, abs=1e-3 *
                                                             len(listed))
    assert {l[3] for l in listed} <= {"forward", "recompute", "backward"}
    # (the recorded decoder is 64 wide: its experts ran ``ragged_dot`` and
    # the masks PR 37 took off the kernel path)
    assert any(l[4] == "broadcast_select_fusion[kLoop]" for l in listed)
    assert "  update" in first and "fusion" not in first


def test_recorded_while_is_self_time(recorded):
    """The head's token-block loops: the ``while`` rows hold the loops' own
    time, not their bodies' again (the reducer's ``short_name`` sum counts a
    nested event under every ``while`` around it)."""
    t, expected = recorded
    own = sum(r["ms"] for r in t["rows"] if r["op"] == "while")
    assert own == pytest.approx(expected["while_self_ms"], rel=1e-6)
    assert own < expected["while_total_ms"]
    assert all(r["category"] for r in t["rows"] if r["op"] == "while")


def test_recorded_against_the_profilers_own_classes(recorded):
    """The oracle: TensorFlow's generated ``xplane_pb2`` reads the same
    planes, lines, events and ``tf_op`` stats."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    with gzip.open(FIXTURE, "rb") as f:
        blob = f.read()
    theirs = xplane_pb2.XSpace.FromString(blob)
    ours = xprof._xplane_classes().FromString(blob)
    assert [p.name for p in theirs.planes] == [p.name for p in ours.planes]
    for a, b in zip(theirs.planes, ours.planes):
        assert ([(l.name, len(l.events)) for l in a.lines]
                == [(l.name, len(l.events)) for l in b.lines])
        assert sorted(a.event_metadata) == sorted(b.event_metadata)
        names = {k: v.name for k, v in a.stat_metadata.items()}
        for mid, meta in a.event_metadata.items():
            want = [s.str_value for s in meta.stats
                    if names.get(s.metadata_id) == "tf_op"]
            got = [s.str_value for s in b.event_metadata[mid].stats
                   if names.get(s.metadata_id) == "tf_op"]
            assert want == got


# --- (d) the benchmark's metric files -------------------------------------------

def _metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _row(phase, vertex, kind, op, ms):
    return {"phase": phase, "vertex": vertex, "inner": "", "op": op,
            "category": "", "ms": ms, "calls": 1, "flops": 0, "bytes": 0,
            "kind": kind}


TABLE = {"steps": 8, "step_ms": 100.0, "unattributed_ms": 2.0, "host": {},
         "rows": [
             _row("forward", "attn", "LatentAttentionLayer", "fusion", 10.0),
             _row("recompute", "attn", "LatentAttentionLayer",
                  "flash_attention_fwd", 8.0),
             _row("backward", "attn2", "RotaryAttentionLayer", "copy", 4.0),
             _row("backward", "ffn", "RoutedExpertsLayer", "moe_gmm", 20.0),
             _row("recompute", "ffn", "RoutedExpertsLayer",
                  "fusion[kCustom]", 16.0),
             _row("forward", "head", "TiedOutputLayer", "while", 5.0),
             _row("backward", "mtp_head", "LMHeadLayer", "while", 6.0),
             _row("update", "", "", "is-finite_select_fusion[kLoop]", 9.0),
             _row("other", "", "", "copy-done", 2.0),
             _row("forward", "mlp", "GatedMLPLayer", "fusion", 20.0)]}

EXPECTED = {"scope_ms.update": 9.0, "scope_ms.recompute": 24.0,
            "scope_ms.attention": 22.0, "scope_ms.moe_around_kernels": 16.0,
            "scope_ms.head": 11.0, "scope_unattributed_share": 2.0}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_reads_the_table(name):
    mod = _metric(name)
    assert mod.read({"scope_table": TABLE}) == pytest.approx(EXPECTED[name])
    assert mod.read({"scope_table": None}) is None
    assert mod.read({}) is None
    assert callable(mod.stop) and not hasattr(mod, "start")


@pytest.mark.parametrize("name", ["scope_ms.recompute", "scope_ms.attention",
                                  "scope_ms.moe_around_kernels",
                                  "scope_ms.head"])
def test_metric_reads_nothing_where_no_row_matches(name):
    table = {**TABLE, "rows": [r for r in TABLE["rows"]
                               if r["phase"] == "update"]}
    assert _metric(name).read({"scope_table": table}) is None


def test_stop_profiles_once_and_survives_a_program_without_the_reader(
        monkeypatch):
    """``stop`` of any of the six files makes the one profile; on the CPU
    the table has no rows, so every metric reads nothing; a program from
    before this PR (no ``OpProfiler.scope_times``) leaves the table None and
    does not raise. The profiled call's rows are not the traced call's:
    ``moe_gmm_roofline_share``'s reading before that call is taken again."""
    calls = []

    class Job:
        model = None

        def fit(self, data, epochs):
            calls.append(epochs)

        def fence(self):
            pass

        def buffers(self):      # the rows routed so far: 5 a call
            return {"ffn": {"expert_load": np.full(4, 5.0 * len(calls))}}

    class Conf:
        STEP_PROGRAM = "jit_step"

    lines = []
    ctx = {"job": Job(), "data": None, "conf": Conf, "sizes":
           {"experts_held": (0, 2)},
           "phase": lambda name, **kw: lines.append((name, kw))}
    _metric("moe_gmm_roofline_share").stop(ctx)     # as the window closes
    assert ctx["moe_rows_before_trace"] == 0
    for name in sorted(EXPECTED):
        _metric(name).stop(ctx)
    assert calls == [1]
    assert ctx["moe_rows_before_trace"] == 10       # after the profiled call
    assert ctx["scope_table"]["rows"] == []
    assert [n for n, _ in lines] == ["scope_table"]
    assert all(_metric(n).read(ctx) is None for n in EXPECTED)
    monkeypatch.delattr(OpProfiler, "scope_times")
    old = {"job": Job(), "data": None, "conf": Conf, "phase": None}
    _metric("scope_ms.head").stop(old)
    assert old["scope_table"] is None and calls == [1]


@pytest.mark.parametrize("name", ["moe_kernel_fallbacks",
                                  "mla_moe_kernel_fallbacks"])
def test_kernel_fallback_readers_still_count_the_gated_op(name):
    """The routed layer calls one gated op where it called two grouped
    products (PR 37); the op counts its two products under the names the
    benchmark's readers (files this PR does not touch) add up: a kernel-path
    trace moves ``moe/gmm_kernel`` and leaves the reading alone, a fallback
    trace adds its two products to it, and the reading is a number, not
    "nothing to read"."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import moe

    metric = _metric(name)

    def reading():
        ctx = {}
        metric.stop(ctx)
        return metric.read(ctx)

    f32 = jnp.float32
    args = (jnp.ones((32, 128), f32), jnp.ones((2, 128, 256), f32),
            jnp.ones((2, 128, 128), f32), jnp.asarray([20, 5]))
    prof = OpProfiler.get()
    kernels = prof.counter_value("moe/gmm_kernel")
    moe.grouped_gated_mlp(*args, interpret=True)
    assert prof.counter_value("moe/gmm_kernel") == kernels + 2
    before = reading()
    assert isinstance(before, (int, float))
    moe.grouped_gated_mlp(*args, interpret=True)
    assert reading() == before
    moe.grouped_gated_mlp(*args)            # the CPU's own path
    assert reading() == before + 2


def test_compile_step_lists_a_scope_outside_its_kernels(capsys):
    """``tools/compile_step.py --scope``: the compiled entry's instructions
    under a scope, Pallas calls left out, largest result first — how PR 37
    saw, with no chip, that nothing buffer-sized was left between the routed
    layer's kernels."""
    spec = importlib.util.spec_from_file_location(
        "compile_step_tool", os.path.join(ROOT, "tools", "compile_step.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    name = 'metadata={op_name="jit(step)/jvp(forward)/l2_ffn/%s"}'
    tool.print_scope("\n".join([
        "ENTRY %main {",
        "  %moe_gmm.1 = bf16[65536,3072]{1,0} custom-call(%a), "
        'custom_call_target="tpu_custom_call", ' + name % "moe_experts/moe_gmm",
        "  %broadcast_select_fusion.7 = bf16[65536,3072]{1,0:T(8,128)(2,1)} "
        "fusion(%b), kind=kLoop, " + name % "moe_experts/select_n",
        "  %fusion.3 = s32[263]{0} fusion(%c), kind=kLoop, "
        + name % "moe_experts/cumsum",
        "  %fusion.9 = bf16[65536,2048]{1,0} fusion(%d), kind=kCustom, "
        + name % "moe_combine/gather",
        "}"]), "moe_experts")
    out = capsys.readouterr().out.splitlines()
    assert "'instructions': 2" in out[0]
    assert f"'elements_of_results': {65536 * 3072 + 263}" in out[0]
    assert "broadcast_select_fusion.7" in out[1] and "fusion.3" in out[2]
    assert len(out) == 3


def test_manifest_lists_the_six_metrics():
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    import manifest as manifest_mod

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest_mod.check(manifest)
    # the six came last when PR 36 appended them; later PRs append after them
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index("scope_ms.update")
    tail = manifest["per_layer"][first:first + 6]
    assert [m["name"] for m in tail] == [
        "scope_ms.update", "scope_ms.recompute", "scope_ms.attention",
        "scope_ms.moe_around_kernels", "scope_ms.head",
        "scope_unattributed_share"]
    # their lists name the five cells of PR 36, closed to the later ones
    cells = [w["name"] for w in manifest["workloads"]][:5]
    # ``update`` where ``sr`` is its content: the fused part of an update is
    # booked to ``backward`` (PERF.md section 3)
    assert tail[0]["workloads"] == tail[1]["workloads"] == cells[2:]
    assert tail[5]["workloads"] == cells
    assert all(m["source"] == "device_trace" and m["better"] == "lower"
               and m["moves"] == "examples_per_s"
               and os.path.isfile(os.path.join(METRICS, m["name"] + ".py"))
               for m in tail)
    assert OpProfiler.LEDGERS[-1] == ("integrity", "integrity_stats")
    assert len(OpProfiler.LEDGERS) == 18
