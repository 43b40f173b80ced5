"""Online elastic data-parallel training (ISSUE 6; ROADMAP item 4(b);
arXiv:2004.13336): shrink/grow the worker set at a dispatch boundary with no
process restart — ``ParallelWrapper.resize`` bitwise parity against a fresh
run from the same state, encoded-residual carry through the permutation
layout, the ``device/loss`` fault kind, and the supervisor's
``shrink_and_continue`` policy with grow-back probes."""

import json
import zipfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.common import faultinject, flightrec
from deeplearning4j_tpu.common.profiler import OpProfiler
from deeplearning4j_tpu.data import NDArrayDataSetIterator
from deeplearning4j_tpu.learning import Adam, Sgd
from deeplearning4j_tpu.ndarray.rng import get_random, set_default_seed
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.optimize.listeners import (
    CheckpointListener, CollectScoresIterationListener)
from deeplearning4j_tpu.parallel import (EncodedGradientsAccumulator,
                                         ParallelWrapper,
                                         ReduceScatterAccumulator,
                                         TrainingSupervisor, elastic_pool,
                                         make_mesh)
from deeplearning4j_tpu.parallel.distributed import (CLASS_DEVICE,
                                                     DEFAULT_POLICIES,
                                                     classify_failure)


@pytest.fixture(autouse=True)
def _clean():
    faultinject.clear_plan()
    OpProfiler.get().reset()
    yield
    faultinject.clear_plan()


def small_model(updater=None, seed=5):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(updater or Adam(learning_rate=0.05))
            .activation("tanh").list()
            .layer(L.DenseLayer(n_out=9))      # odd widths: uneven leaves
            .layer(L.OutputLayer(n_out=3, loss="mcxent",
                                 activation="softmax"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def make_iter(n=96, batch=24):
    rng = np.random.RandomState(7)
    x = rng.randn(n, 4).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, n)]
    return NDArrayDataSetIterator(x, y, batch_size=batch, shuffle=True,
                                  seed=3)


def build_wrapper(model, workers=4, acc="zero1"):
    b = ParallelWrapper.Builder(model).workers(workers)
    if acc == "zero1":
        b.gradients_accumulator(ReduceScatterAccumulator())
    elif acc is not None:
        b.gradients_accumulator(acc)
    return b.build()


def host_state(model):
    """Owning host snapshot of the full training state (the same moves
    resize() makes before re-placing)."""
    return jax.tree.map(np.array, jax.device_get(
        (model._params, model._states, model._updater_state,
         getattr(model, "_acc_state", None) or None)))


def install_state(model, state):
    """Fresh-run-from-state: hand a host snapshot to a model a NEW wrapper
    will own (params/states re-materialized; updater/accumulator state
    left host-side so `_ensure_parallel_state` does its own resharding)."""
    params, states, upd, acc = state
    model._params = jax.tree.map(jnp.array, params)
    model._states = jax.tree.map(jnp.array, states)
    model._updater_state = upd
    model._acc_state = acc


def leaves_equal(a, b):
    la = jax.tree.leaves(jax.device_get(a))
    lb = jax.tree.leaves(jax.device_get(b))
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def run_to_device_loss(pw, step, replica, epochs=3, **fit_kwargs):
    """Fit until the injected device loss fires; return the live cursor
    and the rng state at the boundary the fit unwound at."""
    faultinject.set_plan(faultinject.FaultPlan(
        [{"site": "device/loss", "index": step, "kind": "device_loss",
          "replica": replica}]))
    with pytest.raises(faultinject.DeviceLostError) as ei:
        pw.fit(make_iter(), epochs=epochs, **fit_kwargs)
    faultinject.clear_plan()
    m = pw.model
    assert ei.value.replica == replica
    return ((m._epoch - m._fit_epoch0, m._steps_in_epoch),
            get_random().get_state())


# ---------------------------------------------------------------------------
# device pool + fault kind + classification plumbing
# ---------------------------------------------------------------------------

class TestElasticPlumbing:
    def test_elastic_pool_orders_survivors_first(self):
        devs = jax.devices()
        mesh = make_mesh(data=3, model=1, devices=devs[:3])
        pool = elastic_pool(mesh)
        assert pool[:3] == list(mesh.devices.flat)
        assert set(pool) == set(devs)

    def test_elastic_pool_excludes_lost(self):
        devs = jax.devices()
        mesh = make_mesh(data=4, model=1, devices=devs[:4])
        pool = elastic_pool(mesh, exclude=[devs[1]])
        assert devs[1] not in pool
        assert pool[:3] == [devs[0], devs[2], devs[3]]

    def test_device_loss_fault_raises_and_counts(self):
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "device/loss", "index": 2, "kind": "device_loss",
              "replica": 3}]))
        assert faultinject.fault_point("device/loss", 0) == []
        with pytest.raises(faultinject.DeviceLostError) as ei:
            faultinject.fault_point("device/loss", 2)
        assert ei.value.replica == 3
        assert OpProfiler.get().fault_stats()[
            "faults/device/loss/device_loss"] == 1

    def test_device_loss_classifies_as_device_failure(self):
        exc = faultinject.DeviceLostError("gone", replica=1)
        assert classify_failure(exc) == CLASS_DEVICE
        assert DEFAULT_POLICIES[CLASS_DEVICE] == "shrink_and_continue"


# ---------------------------------------------------------------------------
# encoded-accumulator residual carry (pure numpy; satellite 1)
# ---------------------------------------------------------------------------

class TestResidualResize:
    def _state(self, n, shapes=((5,), (3, 2))):
        rng = np.random.RandomState(0)
        return {
            "residual": [rng.randn(n, *s).astype(np.float32)
                         for s in shapes],
            "threshold": np.float32(1e-3),
            "steps": np.int32(7),
        }

    def test_shrink_folds_lost_residual_mass(self):
        acc = EncodedGradientsAccumulator()
        st = self._state(4)
        out = acc.resize_state(st, 4, 3, lost_replicas=[1])
        for old, new in zip(st["residual"], out["residual"]):
            assert new.shape == (3,) + old.shape[1:]
            # survivors 0/2/3 compact to rows 0/1/2; row 1's mass folds
            # into survivor 0 — total pending mass is preserved exactly
            np.testing.assert_array_equal(new[0], old[0] + old[1])
            np.testing.assert_array_equal(new[1], old[2])
            np.testing.assert_array_equal(new[2], old[3])
            np.testing.assert_allclose(new.sum(axis=0), old.sum(axis=0),
                                       rtol=1e-6)
        assert out["threshold"] == st["threshold"]
        assert out["steps"] == st["steps"]

    def test_grow_adds_zero_rows(self):
        acc = EncodedGradientsAccumulator()
        st = self._state(3)
        out = acc.resize_state(st, 3, 4)
        for old, new in zip(st["residual"], out["residual"]):
            np.testing.assert_array_equal(new[:3], old)
            assert not new[3].any()

    def test_shrink_without_loss_list_folds_tail(self):
        acc = EncodedGradientsAccumulator()
        st = self._state(4)
        out = acc.resize_state(st, 4, 3)
        for old, new in zip(st["residual"], out["residual"]):
            np.testing.assert_array_equal(new[0], old[0] + old[3])
            np.testing.assert_allclose(new.sum(axis=0), old.sum(axis=0),
                                       rtol=1e-6)

    def test_non_residual_state_passthrough(self):
        acc = EncodedGradientsAccumulator()
        assert acc.resize_state({"foo": 1}, 4, 3) == {"foo": 1}
        assert acc.resize_state(None, 4, 3) is None

    def test_stateless_accumulator_passthrough(self):
        acc = ReduceScatterAccumulator()
        st = {"anything": np.zeros(3)}
        assert acc.resize_state(st, 4, 2) is st


# ---------------------------------------------------------------------------
# resize mechanics + bitwise parity (the tentpole contract)
# ---------------------------------------------------------------------------

class TestResizeParity:
    def test_resize_same_count_is_noop(self):
        set_default_seed(99)
        pw = build_wrapper(small_model(), workers=3)
        pw.fit(make_iter(), epochs=1)
        assert pw.resize(3) == []
        assert OpProfiler.get().counter_value("elastic/resizes") == 0

    def test_resize_validations(self):
        set_default_seed(99)
        pw = build_wrapper(small_model(), workers=2)
        with pytest.raises(ValueError):
            pw.resize(0)
        with pytest.raises(ValueError):
            pw.resize(1, lost_replicas=[5])
        with pytest.raises(ValueError):
            pw.resize(len(jax.devices()) + 1)

    def test_shrink_midepoch_bitwise_parity_zero1(self):
        # elastic: 4 workers, device loss mid epoch 2, resize to 3,
        # continue — must equal a FRESH 3-worker run from the same state
        set_default_seed(99)
        m1 = small_model()
        pw = build_wrapper(m1, workers=4)
        cursor, rng = run_to_device_loss(pw, step=5, replica=1)
        assert cursor == (1, 1)          # mid-epoch: 4 steps/epoch
        snap = host_state(m1)
        it, ep = m1._iteration, m1._epoch
        removed = pw.resize(3, lost_replicas=[1])
        assert len(removed) == 1
        # the resize is a span on the flight-recorder timeline, with the
        # from/to counts a postmortem needs
        ev = [e for e in flightrec.events("elastic/resize")
              if e["ph"] == "B"][-1]
        assert ev["attrs"]["workers_from"] == 4
        assert ev["attrs"]["workers_to"] == 3
        assert ev["attrs"]["lost"] == [1]
        pw.fit(make_iter(), epochs=3, resume_cursor=cursor)

        set_default_seed(99)
        m2 = small_model()
        install_state(m2, snap)
        m2._iteration, m2._epoch = it, ep
        get_random().set_state(rng)
        pw2 = build_wrapper(m2, workers=3)
        pw2.fit(make_iter(), epochs=3, resume_cursor=cursor)
        assert leaves_equal(m1._params, m2._params)
        assert leaves_equal(m1._updater_state, m2._updater_state)

    def test_shrink_parity_dense_accumulator(self):
        set_default_seed(99)
        m1 = small_model(updater=Sgd(learning_rate=0.1))
        pw = build_wrapper(m1, workers=4, acc=None)
        cursor, rng = run_to_device_loss(pw, step=6, replica=0)
        snap = host_state(m1)
        it, ep = m1._iteration, m1._epoch
        pw.resize(3, lost_replicas=[0])
        pw.fit(make_iter(), epochs=3, resume_cursor=cursor)

        set_default_seed(99)
        m2 = small_model(updater=Sgd(learning_rate=0.1))
        install_state(m2, snap)
        m2._iteration, m2._epoch = it, ep
        get_random().set_state(rng)
        pw2 = build_wrapper(m2, workers=3, acc=None)
        pw2.fit(make_iter(), epochs=3, resume_cursor=cursor)
        assert leaves_equal(m1._params, m2._params)

    def test_growback_parity(self):
        # 3 -> 4 at an epoch boundary must equal a fresh 4-worker run
        # from the same state
        set_default_seed(99)
        m1 = small_model()
        pw = build_wrapper(m1, workers=3)
        pw.fit(make_iter(), epochs=1)
        snap = host_state(m1)
        it, ep = m1._iteration, m1._epoch
        rng = get_random().get_state()
        pw.resize(4)
        assert pw.workers_count == 4
        pw.fit(make_iter(), epochs=2, resume_cursor=(1, 0))

        set_default_seed(99)
        m2 = small_model()
        install_state(m2, snap)
        m2._iteration, m2._epoch = it, ep
        get_random().set_state(rng)
        pw2 = build_wrapper(m2, workers=4)
        pw2.fit(make_iter(), epochs=2, resume_cursor=(1, 0))
        assert leaves_equal(m1._params, m2._params)

    def test_one_compile_per_worker_count(self):
        # shrink then grow back: the per-worker-count executable cache
        # must hold the elastic contract at exactly one compile per count
        set_default_seed(99)
        prof = OpProfiler.get()
        m = small_model()
        pw = build_wrapper(m, workers=4)
        pw.fit(make_iter(), epochs=1)
        pw.resize(3)
        pw.fit(make_iter(), epochs=2, resume_cursor=(1, 0))
        pw.resize(4)
        pw.fit(make_iter(), epochs=3, resume_cursor=(2, 0))
        assert prof.trace_counts().get("trace/pw_fit_step") == 2
        stats = prof.elastic_stats()
        assert stats["resizes"] == 2
        assert stats["shrinks"] == 1 and stats["grows"] == 1
        assert stats["workers"] == 4

    def test_shrink_encoded_chunks_parity(self):
        # encoded accumulator + steps_per_dispatch chunks: the residual
        # carry rides the resize (no reset warning) and the continuation
        # equals a fresh 3-worker run handed the SAME folded residuals
        set_default_seed(99)
        m1 = small_model()
        acc1 = EncodedGradientsAccumulator()
        pw = build_wrapper(m1, workers=4, acc=acc1)
        cursor, rng = run_to_device_loss(pw, step=4, replica=2,
                                         steps_per_dispatch=2)
        snap = host_state(m1)
        it, ep = m1._iteration, m1._epoch
        pw.resize(3, lost_replicas=[2])
        res = jax.device_get(m1._acc_state["residual"])
        assert all(l.shape[0] == 3 for l in jax.tree.leaves(res))
        pw.fit(make_iter(), epochs=3, resume_cursor=cursor,
               steps_per_dispatch=2)

        set_default_seed(99)
        m2 = small_model()
        acc2 = EncodedGradientsAccumulator()
        params, states, upd, acc_st = snap
        acc_st = acc2.resize_state(acc_st, 4, 3, lost_replicas=[2])
        install_state(m2, (params, states, upd, acc_st))
        m2._iteration, m2._epoch = it, ep
        get_random().set_state(rng)
        pw2 = build_wrapper(m2, workers=3, acc=acc2)
        pw2.fit(make_iter(), epochs=3, resume_cursor=cursor,
                steps_per_dispatch=2)
        assert leaves_equal(m1._params, m2._params)
        assert leaves_equal(m1._acc_state["residual"],
                            m2._acc_state["residual"])

    def test_checkpoint_records_live_workers_and_resumes(self, tmp_path):
        # shrink composed with checkpoint resume: a snapshot taken AFTER
        # the shrink records workers=3 in resume.json and restores into
        # a fresh 3-worker wrapper bit-exactly
        set_default_seed(99)
        m1 = small_model()
        pw = build_wrapper(m1, workers=4)
        cursor, rng = run_to_device_loss(pw, step=5, replica=1)
        pw.resize(3, lost_replicas=[1])
        cl = CheckpointListener(str(tmp_path))
        path = cl.save_now(m1, "post_shrink", rng_state=rng)
        cl.close()
        with zipfile.ZipFile(path) as zf:
            resume = json.loads(zf.read("resume.json"))
        assert resume["cursor"]["workers"] == 3
        assert resume["cursor"] == {"epochs_done": cursor[0],
                                    "steps_in_epoch": cursor[1],
                                    "workers": 3}
        pw.fit(make_iter(), epochs=3, resume_cursor=cursor)

        set_default_seed(99)
        m2 = small_model()
        pw2 = build_wrapper(m2, workers=3)
        pw2.fit(make_iter(), epochs=3, resume_from=path)
        assert m2._ckpt_workers == 3
        assert leaves_equal(m1._params, m2._params)


# ---------------------------------------------------------------------------
# supervisor-driven elastic drills (satellites 3 + the end-to-end criterion)
# ---------------------------------------------------------------------------

class TestSupervisorElastic:
    def test_supervised_shrink_drill_bitwise_parity(self, tmp_path):
        # THE acceptance drill: device/loss kills 1 of 4 workers
        # mid-epoch; the supervised run completes without a restart and
        # its final params equal a manually-resized reference
        set_default_seed(99)
        m1 = small_model()
        pw = build_wrapper(m1, workers=4)
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "device/loss", "index": 5, "kind": "device_loss",
              "replica": 1}]))
        sup = TrainingSupervisor(pw, checkpoint_dir=str(tmp_path),
                                 elastic_grow=False)
        res = sup.fit(make_iter, epochs=3)
        faultinject.clear_plan()
        assert res.status == "completed"
        assert res.restarts == 0          # progress accounting: no budget
        assert [h["policy"] for h in res.history] == ["shrink_and_continue"]
        assert pw.workers_count == 3
        stats = OpProfiler.get().elastic_stats()
        assert stats["shrinks"] == 1 and stats["workers"] == 3
        assert OpProfiler.get().counter_value("supervisor/shrinks") == 1

        # manual reference: same fault, caught by hand, manual resize
        set_default_seed(99)
        m2 = small_model()
        pw2 = build_wrapper(m2, workers=4)
        cursor, _rng = run_to_device_loss(pw2, step=5, replica=1)
        pw2.resize(3, lost_replicas=[1])
        pw2.fit(make_iter(), epochs=3, resume_cursor=cursor)
        assert leaves_equal(m1._params, m2._params)

    def test_shrink_counts_as_progress_never_storms(self, tmp_path):
        # a device loss must complete with max_restarts=0 and
        # storm_threshold=1: shrink-and-continue consumes neither
        set_default_seed(99)
        pw = build_wrapper(small_model(), workers=4)
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "device/loss", "index": 3, "kind": "device_loss",
              "replica": 3}]))
        sup = TrainingSupervisor(pw, checkpoint_dir=str(tmp_path),
                                 max_restarts=0, storm_threshold=1,
                                 elastic_grow=False)
        res = sup.fit(make_iter, epochs=2)
        faultinject.clear_plan()
        assert res.status == "completed"
        assert res.restarts == 0
        assert pw.workers_count == 3

    def test_fallback_to_restart_without_resize_target(self, tmp_path):
        # a target with no resize() (plain MLN) must take the documented
        # checkpoint-restart fallback and still heal
        set_default_seed(99)
        model = small_model()
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "device/loss", "index": 3, "kind": "device_loss"}]))
        sup = TrainingSupervisor(model, checkpoint_dir=str(tmp_path),
                                 backoff_base_s=0.01)
        res = sup.fit(make_iter, epochs=2)
        faultinject.clear_plan()
        assert res.status == "completed"
        assert res.restarts == 1
        assert [h["policy"] for h in res.history] == ["restart"]

    def test_resize_never_reinstates_dead_device_from_cache(
            self, monkeypatch):
        # a later resize to a cached worker count must re-probe once-lost
        # devices: a still-dead one is excluded (cache rejected, mesh
        # rebuilt), never silently reinstated from the stashed mesh
        set_default_seed(99)
        pw = build_wrapper(small_model(), workers=4)
        pw.fit(make_iter(), epochs=1)
        dead = list(pw.mesh.devices.flat)[1]
        pw.resize(3, lost_replicas=[1])
        from deeplearning4j_tpu.parallel import wrapper as wmod
        monkeypatch.setattr(wmod, "probe_device", lambda d: d is not dead)
        pw.resize(4)
        assert pw.workers_count == 4
        assert dead not in set(pw.mesh.devices.flat)   # a spare took over

    def test_grow_failure_limit_gives_up_and_stays_shrunk(
            self, tmp_path, monkeypatch):
        # the lost device answers probes but the grow RESIZE keeps
        # failing: after grow_failure_limit consecutive failures the
        # supervisor abandons grow-back instead of unwinding training
        # every backoff period forever
        set_default_seed(99)
        pw = build_wrapper(small_model(), workers=4)
        orig = pw.resize

        def flaky(n, **kw):
            if n > pw.workers_count and not kw.get("lost_replicas"):
                raise RuntimeError("placement OOM on returning device")
            return orig(n, **kw)

        monkeypatch.setattr(pw, "resize", flaky)
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "device/loss", "index": 2, "kind": "device_loss",
              "replica": 1}]))
        # the probe rides the monitor's poll: poll well inside the time the
        # shrunk run has left (its step is compiled once, so that is short)
        sup = TrainingSupervisor(pw, checkpoint_dir=str(tmp_path),
                                 grow_probe_base_s=0.0,
                                 grow_probe_max_s=0.01,
                                 grow_failure_limit=2, poll_s=0.002)
        res = sup.fit(make_iter, epochs=30)
        faultinject.clear_plan()
        assert res.status == "completed"
        assert res.restarts == 0
        assert pw.workers_count == 3                   # stayed shrunk
        policies = [h["policy"] for h in res.history]
        assert policies.count("grow_failed") == 2
        assert "grow_and_continue" not in policies
        assert OpProfiler.get().counter_value("elastic/grow_abandoned") == 1

    def test_second_loss_disarms_pending_grow_and_merges(self, tmp_path):
        # a grow-back armed before a SECOND device loss must not fire
        # (it would reinstate a cached mesh containing the new dead
        # device): the shrink disarms it and the probe list merges both
        # losses, with the ORIGINAL full count kept as the grow target
        set_default_seed(99)
        pw = build_wrapper(small_model(), workers=4)
        pw.fit(make_iter(), epochs=1)
        sup = TrainingSupervisor(pw, checkpoint_dir=str(tmp_path))
        removed_a = sup._apply_shrink([2])
        assert sup._grow["target"] == 4
        sup._resize_request = 4            # probe found device A healthy
        removed_b = sup._apply_shrink([0])
        assert sup._resize_request is None
        assert sup._grow["target"] == 4
        assert set(sup._grow["devices"]) == set(removed_a + removed_b)
        assert pw.workers_count == 2

    @pytest.mark.slow
    def test_supervised_growback_drill(self, tmp_path):
        # shrink on device loss, then the grow-back probe returns the
        # device at the next dispatch boundary; every step still lands
        set_default_seed(99)
        m = small_model()
        scores = CollectScoresIterationListener()
        pw = build_wrapper(m, workers=4)
        pw.set_listeners(scores)
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "device/loss", "index": 3, "kind": "device_loss",
              "replica": 2}]))
        sup = TrainingSupervisor(pw, checkpoint_dir=str(tmp_path),
                                 elastic_grow=True, grow_probe_base_s=0.0)
        res = sup.fit(make_iter, epochs=6)
        faultinject.clear_plan()
        assert res.status == "completed"
        assert res.restarts == 0
        assert pw.workers_count == 4
        classes = [h["class"] for h in res.history]
        assert classes[0] == "device_failure"
        assert "elastic_grow" in classes
        assert len(scores.scores) == 6 * 4      # no step lost or doubled
        stats = OpProfiler.get().elastic_stats()
        assert stats["shrinks"] == 1 and stats["grows"] >= 1
        assert stats["workers"] == 4

    @pytest.mark.slow
    def test_grow_probe_failure_backoff(self, tmp_path):
        # a still-dead device (elastic/probe fault) keeps the axis shrunk
        # through the failed probes, then grows back when probes succeed
        set_default_seed(99)
        pw = build_wrapper(small_model(), workers=4)
        faultinject.set_plan(faultinject.FaultPlan([
            {"site": "device/loss", "index": 2, "kind": "device_loss",
             "replica": 0},
            {"site": "elastic/probe", "kind": "dead_replica", "times": 2},
        ]))
        sup = TrainingSupervisor(pw, checkpoint_dir=str(tmp_path),
                                 elastic_grow=True,
                                 grow_probe_base_s=0.05,
                                 grow_probe_max_s=0.1)
        res = sup.fit(make_iter, epochs=8)
        faultinject.clear_plan()
        assert res.status == "completed"
        assert pw.workers_count == 4
        prof = OpProfiler.get()
        assert prof.counter_value("elastic/probe_failures") == 2
        assert prof.counter_value("elastic/probes") >= 3
