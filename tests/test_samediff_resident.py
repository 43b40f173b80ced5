"""``SameDiff.fit`` brings nothing to the host that nobody asked for.

A variable's value lives on the device once it has been there
(``SameDiff._params`` uploads a host array once and keeps the device array
in ``.value``), ``fit`` leaves the trained values and the updater state
where the step put them, and the epoch losses stay device scalars until the
``History`` is read. The step donates its inputs, so everything that hands
a value out, or takes one back after a failure, has to answer for the
buffers the next ``fit`` will delete. These tests hold each of those
answers.
"""

from __future__ import annotations

import functools
import operator

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.autodiff.history import History
from deeplearning4j_tpu.autodiff.samediff import SameDiff, TrainingConfig
from deeplearning4j_tpu.common.profiler import OpProfiler
from deeplearning4j_tpu.learning import Adam, Sgd

BATCHES = 3
RESIDENT, UPLOADED = "samediff/vars_resident", "samediff/vars_uploaded"


def _model(updater=None, seed=0):
    rng = np.random.RandomState(seed)
    sd = SameDiff.create()
    x = sd.placeholder("x", shape=(None, 5))
    y = sd.placeholder("y", shape=(None, 3))
    w1 = sd.var("w1", init=rng.randn(5, 8).astype(np.float32) * 0.3)
    b1 = sd.var("b1", shape=(8,), init="zeros")
    w2 = sd.var("w2", init=rng.randn(8, 3).astype(np.float32) * 0.3)
    b2 = sd.var("b2", shape=(3,), init="zeros")
    h = sd.math.tanh((x @ w1) + b1)
    ((h @ w2) + b2).rename("logits")
    sd.loss_ops.softmax_cross_entropy(
        sd.get_variable("logits"), y).rename("loss")
    sd.set_loss_variables("loss")
    sd.set_training_config(TrainingConfig(
        updater=updater or Adam(1e-2), loss_name="loss"))
    return sd


def _batches(seed=1, n=BATCHES):
    rng = np.random.RandomState(seed)
    return [{"x": rng.randn(4, 5).astype(np.float32),
             "y": np.eye(3, dtype=np.float32)[rng.randint(0, 3, 4)]}
            for _ in range(n)]


def _counters():
    c = OpProfiler.get().get_counters()
    return c.get(RESIDENT, 0), c.get(UPLOADED, 0)


def _delta(before):
    after = _counters()
    return after[0] - before[0], after[1] - before[1]


def _values(sd):
    return {n: np.array(sd._vars[n].value) for n in sd.variables()}


# --- (a) where the values are after fit -------------------------------------

def test_fit_leaves_every_variable_on_the_device():
    sd = _model()
    assert all(isinstance(sd._vars[n].value, np.ndarray)
               for n in sd.variables())
    sd.fit(_batches(), epochs=2)
    for n in sd.variables():
        v = sd._vars[n].value
        assert isinstance(v, jax.Array) and not v.is_deleted(), n
        assert v.shape == sd._vars[n].shape


def test_fit_keeps_the_steps_updater_state():
    sd = _model()
    sd.fit(_batches(), epochs=2)
    leaves = jax.tree.leaves(sd._updater_state)
    assert leaves and all(isinstance(l, jax.Array) and not l.is_deleted()
                          for l in leaves)
    # Adam's first moment moved: it is the step's output, not init's zeros
    assert set(sd._updater_state["m"]) == set(sd.variables())
    assert all(float(jnp.abs(m).sum()) > 0
               for m in sd._updater_state["m"].values())


# --- (b) calls compose, and the second uploads nothing ----------------------

@pytest.mark.parametrize("n", [1, 3])
def test_two_calls_equal_one_call_bit_for_bit(n):
    data = _batches()
    one, two = _model(), _model()
    one.fit(data, epochs=2 * n)
    two.fit(data, epochs=n)
    two.fit(data, epochs=n)
    assert one._iteration == two._iteration == 2 * n * BATCHES
    assert one._epoch == two._epoch == 2 * n
    for name in one.variables():
        np.testing.assert_array_equal(np.asarray(one._vars[name].value),
                                      np.asarray(two._vars[name].value))
    a, b = (jax.tree.leaves(jax.device_get(m._updater_state))
            for m in (one, two))
    assert len(a) == len(b)
    for p, q in zip(a, b):
        np.testing.assert_array_equal(p, q)


def test_counters_say_what_each_call_found_on_the_device():
    sd, data = _model(), _batches()
    n_vars = len(sd.variables())
    before = _counters()
    sd.fit(data, epochs=1)
    assert _delta(before) == (0, n_vars)        # first call: all uploaded
    before = _counters()
    sd.fit(data, epochs=1)
    assert _delta(before) == (n_vars, 0)        # second: all found there
    before = _counters()
    sd.output({"x": data[0]["x"]}, ["logits"])
    sd.calculate_gradients(data[0], "loss")
    assert _delta(before) == (2 * n_vars, 0)    # readers after fit: no upload


def test_output_before_any_fit_uploads_once():
    sd, data = _model(), _batches()
    n_vars = len(sd.variables())
    before = _counters()
    first = sd.output({"x": data[0]["x"]}, ["logits"])["logits"].to_numpy()
    again = sd.output({"x": data[0]["x"]}, ["logits"])["logits"].to_numpy()
    assert _delta(before) == (n_vars, n_vars)
    np.testing.assert_array_equal(first, again)


# --- (c) what was handed out survives the next fit's donation ---------------

def test_arr_and_output_survive_the_next_fit():
    sd, data = _model(), _batches()
    sd.fit(data, epochs=1)
    held = sd._vars["w1"].value                  # the variable's own buffer
    want = np.array(held)
    arr = sd.get_variable("w1").arr()            # a reader's handle
    out = sd.output({"x": data[0]["x"]}, ["logits", "w1"])
    logits = out["logits"].to_numpy().copy()
    sd.fit(data, epochs=1)
    assert held.is_deleted()                     # the step did donate it
    np.testing.assert_array_equal(arr.to_numpy(), want)
    np.testing.assert_array_equal(out["w1"].to_numpy(), want)
    np.testing.assert_array_equal(out["logits"].to_numpy(), logits)
    assert not np.array_equal(np.asarray(sd._vars["w1"].value), want)


def test_arr_of_a_host_value_and_of_nothing():
    sd = _model()
    w = sd.get_variable("w1").arr()
    np.testing.assert_array_equal(w.to_numpy(), sd._vars["w1"].value)
    assert sd.get_variable("x").arr() is None    # a placeholder holds none


def test_gradients_survive_the_next_fit():
    sd, data = _model(), _batches()
    sd.fit(data, epochs=1)
    grads = sd.calculate_gradients(data[0], "loss")
    want = {n: g.to_numpy().copy() for n, g in grads.items()}
    sd.fit(data, epochs=1)
    for n, g in grads.items():
        np.testing.assert_array_equal(g.to_numpy(), want[n])


# --- (d) a value assigned between calls -------------------------------------

def test_assigned_numpy_value_is_trained_from_and_uploaded_once():
    data = _batches()
    new_w2 = np.random.RandomState(9).randn(8, 3).astype(np.float32)
    a = _model(Sgd(0.1))
    a.fit(data, epochs=1)
    a._vars["w2"].value = new_w2
    a._updater_state = None
    a._iteration = 0
    # b never saw the first fit's w2: same other values, same assignment
    b = _model(Sgd(0.1))
    for n, v in _values(a).items():
        b._vars[n].value = v
    n_vars = len(a.variables())
    before = _counters()
    a.fit(data, epochs=1)
    assert _delta(before) == (n_vars - 1, 1)
    before = _counters()
    a.fit(data, epochs=1)
    assert _delta(before) == (n_vars, 0)
    b.fit(data, epochs=2)
    for n in a.variables():
        np.testing.assert_array_equal(np.asarray(a._vars[n].value),
                                      np.asarray(b._vars[n].value))
    np.testing.assert_array_equal(                 # the caller's array is
        new_w2, np.random.RandomState(9).randn(8, 3).astype(np.float32))


def test_assigned_device_value_and_reset_state_are_picked_up():
    """What the benchmark's ``Job.reset`` does between calls."""
    data = _batches()
    fresh = _values(_model())
    a = _model()
    a.fit(data, epochs=2)
    for n, v in fresh.items():
        a._vars[n].value = jnp.asarray(v)
    a._updater_state = None
    a._iteration = 0
    a.fit(data, epochs=2)
    b = _model()
    b.fit(data, epochs=2)
    for n in a.variables():
        np.testing.assert_array_equal(np.asarray(a._vars[n].value),
                                      np.asarray(b._vars[n].value))
    assert (float(a._updater_state["m"]["w1"].sum())
            == float(b._updater_state["m"]["w1"].sum()))


# --- (e) a failure mid-fit --------------------------------------------------

class _Boom(RuntimeError):
    pass


class _RaiseAt:
    def __init__(self, iteration):
        self.iteration = iteration

    def iteration_done(self, model, iteration, loss):
        if iteration == self.iteration:
            raise _Boom(iteration)


def _assert_usable(sd, data):
    for n in sd.variables():
        assert np.isfinite(np.asarray(sd._vars[n].value)).all(), n
    sd.output({"x": data[0]["x"]}, ["logits"])["logits"].to_numpy()
    hist = sd.fit(data, epochs=1)
    assert np.isfinite(hist.final_loss())


def test_a_listener_that_raises_leaves_the_last_finished_step():
    data = _batches()
    sd = _model()
    sd.fit(data, epochs=1)
    with pytest.raises(_Boom):
        sd.fit(data, epochs=2, listeners=[_RaiseAt(sd._iteration + 2)])
    ref = _model()
    ref.fit(data, epochs=1)
    ref.fit(data[:2], epochs=1)
    assert sd._iteration == ref._iteration == BATCHES + 2
    for n in sd.variables():
        np.testing.assert_array_equal(np.asarray(sd._vars[n].value),
                                      np.asarray(ref._vars[n].value))
    _assert_usable(sd, data)


def test_data_that_raises_mid_epoch_leaves_the_model_usable():
    data = _batches()

    def feed():
        yield from data[:2]
        raise _Boom("the reader died")

    class Feed:
        def reset(self):
            pass

        def __iter__(self):
            return feed()

    sd = _model()
    sd.fit(data, epochs=1)
    with pytest.raises(_Boom):
        sd.fit(Feed(), epochs=1)          # no listener: .value was stale
    assert sd._iteration == BATCHES + 2
    assert not any(sd._vars[n].value.is_deleted() for n in sd.variables())
    assert not any(l.is_deleted()
                   for l in jax.tree.leaves(sd._updater_state))
    _assert_usable(sd, data)


def test_a_step_that_cannot_be_traced_leaves_the_model_usable():
    data = _batches()
    bad = dict(data[1], x=np.zeros((4, 7), np.float32))   # 7 != 5 columns
    sd = _model()
    with pytest.raises(Exception):
        sd.fit([data[0], bad, data[2]], epochs=1)
    assert sd._iteration == 1
    _assert_usable(sd, data)


def test_a_step_that_consumed_its_inputs_fails_loudly_not_silently():
    """A run-time failure after the launch: the inputs are donated, nothing
    came back. There is no value to restore; the model says so when read,
    and trains again once it has been given values."""
    data = _batches()
    sd = _model()
    sd.fit(data, epochs=1)
    good = _values(sd)
    step = sd._train_step_fn("loss", tuple(sd.placeholders()))
    key = next(k for k, v in sd._fn_cache.items() if v is step)

    def consume_then_fail(params, state, *rest):
        step(params, state, *rest)
        raise _Boom("device out of memory")

    sd._fn_cache[key] = consume_then_fail
    with pytest.raises(_Boom):
        sd.fit(data, epochs=1)
    sd._fn_cache[key] = step
    assert sd._updater_state is None             # momenta restart
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(sd._vars["w1"].value)
    for n, v in good.items():
        sd._vars[n].value = v
    _assert_usable(sd, data)


# --- (f) save / load --------------------------------------------------------

def test_save_load_round_trips_device_values(tmp_path):
    sd, data = _model(), _batches()
    sd.fit(data, epochs=2)
    path = str(tmp_path / "model.zip")
    sd.save(path, save_updater=True)
    loaded = SameDiff.load(path)
    assert loaded._iteration == sd._iteration
    for n in sd.variables():
        np.testing.assert_array_equal(np.asarray(loaded._vars[n].value),
                                      np.asarray(sd._vars[n].value))
    for p, q in zip(jax.tree.leaves(jax.device_get(loaded._updater_state)),
                    jax.tree.leaves(jax.device_get(sd._updater_state))):
        np.testing.assert_array_equal(p, q)
    # saving read the values, it did not move them; both go on alike
    assert all(isinstance(sd._vars[n].value, jax.Array)
               for n in sd.variables())
    sd.fit(data, epochs=1)
    loaded.fit(data, epochs=1)
    for n in sd.variables():
        np.testing.assert_array_equal(np.asarray(loaded._vars[n].value),
                                      np.asarray(sd._vars[n].value))


# --- (g) the history --------------------------------------------------------

class _Losses:
    def __init__(self):
        self.by_epoch = [[]]

    def iteration_done(self, model, iteration, loss):
        self.by_epoch[-1].append(loss)

    def epoch_done(self, model, epoch):
        self.by_epoch.append([])


def test_history_reads_python_floats_equal_to_the_eager_means():
    sd, data = _model(), _batches()
    seen = _Losses()
    hist = sd.fit(data, epochs=3, listeners=[seen])
    want = [float(functools.reduce(operator.add, losses) / len(losses))
            for losses in seen.by_epoch[:3]]
    curve = hist.loss_curve()
    assert curve == want
    assert all(type(l) is float for l in curve)
    assert type(hist.final_loss()) is float and hist.final_loss() == want[-1]
    assert all(type(l) is float for l in hist._epoch_losses)   # kept
    assert repr(hist) == f"History(epochs=3, final_loss={want[-1]})"


def test_history_holds_device_scalars_until_read_and_fit_never_syncs():
    sd, data = _model(), _batches()
    sd.fit(data, epochs=1)
    before = OpProfiler.get().get_statistics()
    hist = sd.fit(data, epochs=2)
    after = OpProfiler.get().get_statistics()
    assert (after.get("fit/sync", {"count": 0})["count"]
            == before.get("fit/sync", {"count": 0})["count"])
    assert after["fit/epoch_end"]["count"] \
        == before["fit/epoch_end"]["count"] + 2
    assert after["fit/exit"]["count"] == before["fit/exit"]["count"] + 1
    assert all(isinstance(l, jax.Array) for l in hist._epoch_losses)
    assert hist.final_loss() < hist.loss_curve()[0] + 1.0


def test_empty_history_and_empty_epoch():
    assert History().final_loss() is None
    assert History().loss_curve() == []
    assert repr(History()) == "History(epochs=0, final_loss=None)"

    class Empty:
        def reset(self):
            pass

        def __iter__(self):
            return iter(())

    sd = _model()
    with pytest.raises(ValueError, match="no batches"):
        sd.fit(Empty(), epochs=1)
    sd.output({"x": np.zeros((4, 5), np.float32)}, ["logits"])  # still usable
