"""Serving-tier tests (ISSUE 7): bucket routing, AOT warmup, padded-bucket
bitwise parity, the oversize admission rule, deadline expiry under a wedged
replica, retirement transparent to in-flight load, shutdown draining, the
HTTP endpoint, and the serving ledger. The Poisson SLO load test itself is
``bench.py --config serving-smoke``; a mini version runs here marked slow.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.common import faultinject
from deeplearning4j_tpu.common.profiler import OpProfiler
from deeplearning4j_tpu.data.pipeline import pad_rows
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.ndarray.ndarray import NDArray
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.parallel import (BucketLadder, OversizeRequest,
                                         ServingEngine, serving_devices,
                                         serving_health)


def mlp(seed=1, n_in=4, n_out=3):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(0.05))
            .activation("tanh").list()
            .layer(L.DenseLayer(n_out=16))
            .layer(L.OutputLayer(n_out=n_out))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def build_engine(model=None, buckets=(1, 2, 4, 8), workers=1, **kw):
    b = (ServingEngine.Builder(model or mlp())
         .buckets(buckets, seq_lens=kw.pop("seq_lens", None),
                  oversize=kw.pop("oversize", "split"))
         .input_shape(kw.pop("input_shape", (4,)))
         .workers(workers).max_wait_ms(kw.pop("max_wait_ms", 2.0))
         .request_timeout_ms(kw.pop("request_timeout_ms", 15000)))
    if kw.pop("bf16", False):
        b.bf16(True)
    if kw.pop("pin", False):
        b.pin_devices(True)
    assert not kw, kw
    return b.build()


@pytest.fixture(autouse=True)
def _clean():
    faultinject.clear_plan()
    yield
    faultinject.clear_plan()


class TestBucketLadder:
    def test_bucket_routing(self):
        lad = BucketLadder([8, 1, 4, 2])          # sorted + deduped
        assert lad.batch_sizes == (1, 2, 4, 8)
        assert lad.bucket_batch(1) == 1
        assert lad.bucket_batch(3) == 4
        assert lad.bucket_batch(8) == 8
        assert lad.bucket_batch(9) is None

    def test_admit_split_rule(self):
        lad = BucketLadder([1, 2, 4], oversize="split")
        assert lad.admit(3) == [3]
        assert lad.admit(4) == [4]
        assert lad.admit(9) == [4, 4, 1]          # documented chunking

    def test_admit_reject_rule(self):
        lad = BucketLadder([1, 2, 4], oversize="reject")
        with pytest.raises(OversizeRequest, match="oversize='reject'"):
            lad.admit(5)
        with pytest.raises(ValueError, match="at least one row"):
            lad.admit(0)

    def test_seq_ladder_oversize_always_rejects(self):
        lad = BucketLadder([2], seq_lens=[4, 8])
        assert lad.bucket_seq(3) == 4
        with pytest.raises(OversizeRequest, match="sequence length"):
            lad.bucket_seq(9)

    def test_warmup_shape_set(self):
        assert BucketLadder([1, 2]).shapes((4,)) == [(1, 4), (2, 4)]
        assert BucketLadder([2], seq_lens=[3, 5]).shapes((9, 7)) == \
            [(2, 3, 7), (2, 5, 7)]

    def test_validation(self):
        with pytest.raises(ValueError, match="positive"):
            BucketLadder([0, 2])
        with pytest.raises(ValueError, match="split.*reject"):
            BucketLadder([2], oversize="explode")


class TestPadRows:
    def test_wraps_real_rows_and_masks(self):
        a = np.arange(6, dtype=np.float32).reshape(3, 2)
        padded, w = pad_rows(a, 5)
        assert padded.shape == (5, 2)
        np.testing.assert_array_equal(padded[3], a[0])   # row[i % n]
        np.testing.assert_array_equal(padded[4], a[1])
        np.testing.assert_array_equal(w, [1, 1, 1, 0, 0])

    def test_exact_fit_and_axis1(self):
        a = np.arange(12, dtype=np.float32).reshape(2, 3, 2)
        same, w = pad_rows(a, 2)
        assert same is a and w.sum() == 2
        padded, _ = pad_rows(a, 4, axis=1)
        assert padded.shape == (2, 4, 2)
        np.testing.assert_array_equal(padded[:, 3], a[:, 0])

    def test_oversize_raises(self):
        with pytest.raises(ValueError, match="exceed"):
            pad_rows(np.zeros((5, 2)), 4)


class TestServingEngine:
    def test_padded_bucket_bitwise_equal_to_direct_output(self):
        """The inertness proof: a request served through a LARGER padded
        bucket is BITWISE-identical to the model run directly on the
        unpadded rows (fp32 path)."""
        model = mlp()
        eng = build_engine(model, buckets=(8,))
        try:
            for n in (1, 3, 5, 8):
                x = np.random.randn(n, 4).astype(np.float32)
                served = eng.output(x).to_numpy()
                direct = model.output(x).to_numpy()
                assert np.array_equal(served, direct), \
                    f"{n}-row request differs through the 8-bucket"
        finally:
            eng.shutdown()

    def test_one_compile_per_bucket_flat_after_warmup(self):
        prof = OpProfiler.get()
        before = prof.counter_value("trace/serving_infer")
        eng = build_engine(buckets=(1, 2, 4, 8))
        try:
            assert prof.counter_value("trace/serving_infer") - before == 4
            futs = [eng.output_async(
                np.random.randn((i % 4) + 1, 4).astype(np.float32))
                for i in range(24)]
            for f in futs:
                f.result(timeout=15)
            # steady state: the counter is FLAT, nothing traced again
            assert prof.counter_value("trace/serving_infer") - before == 4
            assert prof.counter_value("serving/traces_after_warmup") == 0
        finally:
            eng.shutdown()

    def test_oversize_split_concatenates_in_order(self):
        model = mlp()
        eng = build_engine(model, buckets=(1, 2, 4))
        try:
            x = np.linspace(-1, 1, 11 * 4, dtype=np.float32).reshape(11, 4)
            out = eng.output(x).to_numpy()          # 11 -> chunks 4+4+3
            assert out.shape == (11, 3)
            assert np.array_equal(out, model.output(x).to_numpy())
            assert OpProfiler.get().counter_value("serving/oversize_split") \
                >= 1
        finally:
            eng.shutdown()

    def test_serves_a_computation_graph(self):
        """A ComputationGraph's forward takes and returns {node: array}:
        the engine binds the one input / one output itself (the zoo's
        ResNet-50 is a graph), and refuses a graph with several."""
        from deeplearning4j_tpu.nn import (ComputationGraph,
                                           ComputationGraphConfiguration)

        def graph(outputs):
            gb = (ComputationGraphConfiguration
                  .graph_builder(NeuralNetConfiguration.builder().seed(3)
                                 .updater(Adam(0.05)).activation("tanh"))
                  .add_inputs("in"))
            gb.add_layer("h", L.DenseLayer(n_out=16), "in")
            for o in outputs:
                gb.add_layer(o, L.OutputLayer(n_out=3), "h")
            return ComputationGraph(
                gb.set_outputs(*outputs)
                .set_input_types(InputType.feed_forward(4)).build()).init()

        model = graph(["out"])
        eng = build_engine(model, buckets=(1, 4))
        try:
            x = np.linspace(-1, 1, 6 * 4, dtype=np.float32).reshape(6, 4)
            out = eng.output(x).to_numpy()          # 6 -> chunks 4+2
            assert np.array_equal(out, model.output(x)[0].to_numpy())
        finally:
            eng.shutdown()
        with pytest.raises(ValueError, match="exactly one of each"):
            build_engine(graph(["out", "out2"]), buckets=(1,))

    def test_oversize_reject_raises_synchronously(self):
        eng = build_engine(buckets=(1, 2, 4), oversize="reject")
        try:
            with pytest.raises(OversizeRequest):
                eng.output_async(np.zeros((5, 4), np.float32))
        finally:
            eng.shutdown()

    def test_shape_validation(self):
        eng = build_engine()
        try:
            with pytest.raises(ValueError, match="rank"):
                eng.output_async(np.zeros((3,), np.float32))
            with pytest.raises(ValueError, match="feature shape"):
                eng.output_async(np.zeros((2, 5), np.float32))
            with pytest.raises(ValueError, match="at least one row"):
                eng.output_async(np.zeros((0, 4), np.float32))
        finally:
            eng.shutdown()

    def test_bf16_serving_close_to_fp32_api_stays_float32(self):
        model = mlp()
        eng = build_engine(model, buckets=(4,), bf16=True)
        try:
            x = np.random.randn(3, 4).astype(np.float32)
            out = eng.output(x).to_numpy()
            assert out.dtype == np.float32          # API boundary
            np.testing.assert_allclose(out, model.output(x).to_numpy(),
                                       atol=5e-2)
        finally:
            eng.shutdown()

    def test_generic_model_fallback(self):
        """A model without a jittable ``_forward`` still serves (its own
        jit cache is warmed per bucket instead of AOT executables), and
        the per-bucket warm run happens ONCE — not again per dispatch."""

        class Doubler:
            calls = 0

            def output(self, batch):
                Doubler.calls += 1
                return NDArray(np.asarray(batch) * 2.0)

        eng = build_engine(Doubler(), buckets=(4,))
        try:
            assert Doubler.calls == 1        # ONE priming run at warmup
            x = np.random.randn(3, 4).astype(np.float32)
            for _ in range(3):
                np.testing.assert_array_equal(eng.output(x).to_numpy(),
                                              x * 2)
            assert Doubler.calls == 4
        finally:
            eng.shutdown()

    def test_builder_rejects_non_batched_mode(self):
        with pytest.raises(ValueError, match="batched"):
            ServingEngine.Builder(mlp()).inference_mode("sequential")

    def test_seq_bucket_routing_pads_and_slices(self):
        """Sequence-length ladder: a [n, t, f] request pads to the seq
        bucket by wrapping time steps and the per-timestep output slices
        back to the true length."""

        class PerStep:
            def output(self, batch):
                return NDArray(np.asarray(batch).sum(-1, keepdims=True))

        eng = build_engine(PerStep(), buckets=(2,), seq_lens=(4, 8),
                           input_shape=(8, 3))
        try:
            x = np.random.randn(1, 3, 3).astype(np.float32)   # t=3 -> 4
            out = eng.output(x).to_numpy()
            assert out.shape == (1, 3, 1)
            np.testing.assert_allclose(out, x.sum(-1, keepdims=True),
                                       rtol=1e-6)
            assert OpProfiler.get().counter_value("serving/seq_padded") >= 1
            with pytest.raises(OversizeRequest):
                eng.output_async(np.zeros((1, 9, 3), np.float32))
        finally:
            eng.shutdown()

    def test_pooled_seq_output_matching_a_rung_is_not_sliced(self):
        """A pooled output whose width happens to equal a sequence rung
        must NOT be mistaken for per-timestep and sliced: warmup probes
        the ladder (width constant across rungs => pooled)."""

        class Pooled:
            def output(self, batch):      # [n, t, 8] -> [n, 8]
                return NDArray(np.asarray(batch).sum(axis=1))

        eng = build_engine(Pooled(), buckets=(2,), seq_lens=(4, 8),
                           input_shape=(8, 8))
        try:
            # t=5 pads to rung 8 == output width: the old shape heuristic
            # would wrongly slice the 8 pooled features down to 5
            out = eng.output(np.zeros((1, 5, 8), np.float32)).to_numpy()
            assert out.shape == (1, 8)
        finally:
            eng.shutdown()

    def test_enqueue_fault_index_is_request_ordinal(self):
        """The ``serving/enqueue`` drill index counts output_async calls
        — a split oversize request consumes ONE ordinal, not one per
        chunk."""
        eng = build_engine(buckets=(1, 2))
        try:
            faultinject.set_plan(faultinject.FaultPlan(
                [{"site": "serving/enqueue", "kind": "transient",
                  "index": 1}]))
            eng.output(np.zeros((3, 4), np.float32))     # ordinal 0, split
            with pytest.raises(faultinject.TransientFault):
                eng.output_async(np.zeros((1, 4), np.float32))  # ordinal 1
        finally:
            faultinject.clear_plan()
            eng.shutdown()

    def test_warmup_on_second_engine_does_not_trip_first_engines_alarm(self):
        """traces-after-warmup is PER-ENGINE: another engine's warmup
        bumping the shared trace ledger must not read as a retrace
        here."""
        prof = OpProfiler.get()
        base = prof.counter_value("serving/traces_after_warmup")
        eng_a = build_engine(buckets=(2,))
        try:
            eng_a.output(np.zeros((2, 4), np.float32))
            eng_b = build_engine(buckets=(1, 2, 4))      # traces 3 buckets
            try:
                eng_a.output(np.zeros((2, 4), np.float32))
                assert prof.counter_value("serving/traces_after_warmup") \
                    == base
            finally:
                eng_b.shutdown()
        finally:
            eng_a.shutdown()

    def test_shutdown_fails_stashed_requests_too(self):
        """A request stashed for the next batch (bucket overflow / shape
        mismatch) is still queue state: shutdown must fail it, not leave
        its waiter hanging."""
        from deeplearning4j_tpu.parallel.inference import _Request
        from concurrent.futures import Future

        eng = build_engine(buckets=(2,))
        eng.shutdown()               # workers gone; nobody drains now
        fut = Future()
        fut.enqueued_at = time.monotonic()
        eng._stash(_Request(np.zeros((1, 4), np.float32), fut, 0,
                            fut.enqueued_at))
        assert eng._fail_queued(RuntimeError(
            "ServingEngine shut down with this request still queued")) == 1
        with pytest.raises(RuntimeError, match="still queued"):
            fut.result(timeout=0)

    def test_deadline_expiry_under_wedged_replica_reports_queue_time(self):
        """The satellite contract: a deadline error names TRUE
        time-in-queue from the request's queue-entry timestamp."""
        eng = build_engine(workers=1, request_timeout_ms=300)
        try:
            # wedge the single replica's next dispatch for far longer
            # than the request deadline
            faultinject.set_plan(faultinject.FaultPlan(
                [{"site": "serving/dispatch", "kind": "slow",
                  "seconds": 2.0}]))
            t0 = time.monotonic()
            with pytest.raises(TimeoutError) as ei:
                eng.output(np.zeros((1, 4), np.float32))
            waited = time.monotonic() - t0
            msg = str(ei.value)
            assert "in queue" in msg and "replicas alive" in msg
            assert waited < 1.5          # deadline, not the wedge length
        finally:
            faultinject.clear_plan()
            eng.shutdown()

    def test_mid_load_retirement_zero_failed_requests(self):
        """Kill a replica mid-load: its in-flight batch requeues
        (bounded), survivors serve it, nothing fails."""
        prof = OpProfiler.get()
        retired0 = prof.counter_value("inference/replica_retired")
        model = mlp()
        eng = build_engine(model, buckets=(1, 2, 4, 8), workers=2)
        try:
            faultinject.set_plan(faultinject.FaultPlan(
                [{"site": "serving/dispatch", "kind": "dead_replica",
                  "index": 2}]))
            x = np.random.randn(2, 4).astype(np.float32)
            futs = [eng.output_async(x) for _ in range(40)]
            outs = [f.result(timeout=20) for f in futs]   # nothing raises
            assert len(outs) == 40
            direct = model.output(x).to_numpy()
            for o in outs:
                assert np.array_equal(o.to_numpy(), direct)
            assert prof.counter_value("inference/replica_retired") \
                == retired0 + 1
            assert prof.counter_value("serving/requeued") >= 1
        finally:
            faultinject.clear_plan()
            eng.shutdown()

    def test_transient_dispatch_fault_requeues_and_recovers(self):
        model = mlp()
        eng = build_engine(model, buckets=(2,))
        try:
            faultinject.set_plan(faultinject.FaultPlan(
                [{"site": "serving/dispatch", "kind": "transient",
                  "index": 0}]))
            x = np.random.randn(2, 4).astype(np.float32)
            out = eng.output(x)
            assert np.array_equal(out.to_numpy(),
                                  model.output(x).to_numpy())
        finally:
            faultinject.clear_plan()
            eng.shutdown()

    def test_shutdown_drains_in_flight_then_fails_queued(self):
        """Satellite contract: a request a replica already picked up
        resolves with its RESULT through shutdown; still-queued requests
        fail immediately."""

        class Slow:
            def output(self, batch):
                time.sleep(0.4)
                return NDArray(np.asarray(batch) + 1.0)

        eng = build_engine(Slow(), buckets=(1,), workers=1,
                           max_wait_ms=1.0)
        try:
            in_flight = eng.output_async(np.zeros((1, 4), np.float32))
            time.sleep(0.15)             # worker picked it up (0.1s poll)
            queued = [eng.output_async(np.zeros((1, 4), np.float32))
                      for _ in range(3)]
        finally:
            eng.shutdown(drain_timeout_s=3.0)
        np.testing.assert_array_equal(
            in_flight.result(timeout=0).to_numpy(), np.ones((1, 4)))
        for f in queued:
            with pytest.raises(RuntimeError, match="still queued"):
                f.result(timeout=0)

    def test_refresh_params_swaps_without_recompile(self):
        prof = OpProfiler.get()
        model = mlp()
        eng = build_engine(model, buckets=(4,))
        try:
            traces = prof.counter_value("trace/serving_infer")
            x = np.random.randn(2, 4).astype(np.float32)
            before = eng.output(x).to_numpy()
            flat = model.params().to_numpy()
            model.set_params(flat + 0.25)
            eng.refresh_params()
            after = eng.output(x).to_numpy()
            assert not np.array_equal(before, after)
            assert np.array_equal(after, model.output(x).to_numpy())
            assert prof.counter_value("trace/serving_infer") == traces
        finally:
            eng.shutdown()

    def test_future_carries_enqueue_timestamp(self):
        eng = build_engine()
        try:
            t0 = time.monotonic()
            fut = eng.output_async(np.zeros((1, 4), np.float32))
            assert abs(getattr(fut, "enqueued_at") - t0) < 1.0
            fut.result(timeout=15)
        finally:
            eng.shutdown()

    def test_serving_ledger_and_health(self):
        prof = OpProfiler.get()
        prof.reset()
        eng = build_engine(buckets=(1, 2, 4))
        try:
            for _ in range(5):
                eng.output(np.zeros((3, 4), np.float32))
            stats = prof.serving_stats()
            assert stats["requests"] == 5 and stats["batches"] >= 1
            assert 0 < stats["fill_ratio"] <= 1
            assert stats["pad_waste"] == pytest.approx(
                1 - stats["fill_ratio"])
            assert stats["warmup_count"] == 1
            health = serving_health()
            assert health["engines"] >= 1
            assert health["latency_p99_ms"] > 0
            mine = [e for e in health["engine_stats"]
                    if e["buckets_compiled"] == 3]
            assert mine and mine[0]["warm"] and mine[0]["window"] == 5
        finally:
            eng.shutdown()

    def test_shutdown_removes_engine_from_health_census(self):
        eng = build_engine(buckets=(1,))
        n0 = serving_health()["engines"]
        assert n0 >= 1
        eng.shutdown()
        assert serving_health()["engines"] == n0 - 1

    def test_queue_depth_hwm_is_windowed_and_peak_is_lifetime(self):
        """ISSUE 11 satellite: the queue-depth high-water mark is a
        DECAYING windowed signal (usable for scale-down — the old
        only-rising fleet max could never fall), while the lifetime
        maximum survives separately as ``queue_depth_peak``."""
        prof = OpProfiler.get()
        eng = build_engine(buckets=(1,))
        try:
            eng._qwin_s = 0.05          # tiny windows so decay is fast
            eng._qwin_update(50)        # a backlog spike
            assert eng.queue_depth_hwm() == 50
            assert eng.queue_depth_peak == 50
            stats = eng.serving_stats()
            assert stats["queue_depth_hwm"] == 50
            assert stats["queue_depth_peak"] == 50
            # the fleet gauges reflect it (windowed gauge = fleet max of
            # windowed values; peak gauge only ever rises)
            assert prof.counter_value("serving/queue_depth_hwm") == 50
            assert prof.counter_value("serving/queue_depth_peak") >= 50
            time.sleep(0.12)            # > 2 windows: the spike ages out
            assert eng.queue_depth_hwm() == 0
            assert eng.queue_depth_peak == 50      # lifetime max persists
            stats = eng.serving_stats()
            assert stats["queue_depth_hwm"] == 0
            assert stats["queue_depth_peak"] == 50
            # the shared windowed gauge FELL with the backlog...
            assert prof.counter_value("serving/queue_depth_hwm") < 50
            # ...and the lifetime peak gauge did not
            assert prof.counter_value("serving/queue_depth_peak") >= 50
        finally:
            eng.shutdown()

    def test_resurrected_replica_reclaims_freed_device_slot(self):
        """With device pinning, a resurrected replica takes over the DEAD
        replica's device slot (worker ids grow monotonically; a plain
        ``worker_id % ndev`` would pile every generation onto chip 0)."""
        prof = OpProfiler.get()
        res0 = prof.counter_value("inference/replica_resurrected")
        eng = build_engine(mlp(), buckets=(2,), workers=2, pin=True)
        try:
            for _ in range(100):
                if len(eng._dev_of) == 2:
                    break
                time.sleep(0.01)
            assert sorted(eng._dev_of.values()) == [0, 1]
            faultinject.set_plan(faultinject.FaultPlan(
                [{"site": "serving/dispatch", "kind": "dead_replica",
                  "index": 0}]))
            eng.output(np.zeros((2, 4), np.float32))  # requeued, served
            faultinject.clear_plan()
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                if (prof.counter_value("inference/replica_resurrected")
                        > res0 and len(eng._dev_of) == 2):
                    break
                time.sleep(0.05)
            assert sorted(eng._dev_of.values()) == [0, 1], \
                "replacement did not reclaim the freed device slot"
        finally:
            faultinject.clear_plan()
            eng.shutdown()

    def test_serving_devices_round_robin(self):
        import jax

        devs = serving_devices(3)
        assert len(devs) == 3
        assert devs[0] is jax.devices()[0]

    @pytest.mark.slow
    def test_pinned_devices_serve_correctly(self):
        """Device-pinned replicas (one param copy + executable set per
        device) still serve bitwise-correct results. Warmup-heavy:
        compiles buckets × devices."""
        model = mlp()
        eng = build_engine(model, buckets=(2, 4), workers=2, pin=True)
        try:
            x = np.random.randn(3, 4).astype(np.float32)
            direct = model.output(x).to_numpy()
            futs = [eng.output_async(x) for _ in range(12)]
            for f in futs:
                assert np.array_equal(f.result(timeout=20).to_numpy(),
                                      direct)
        finally:
            eng.shutdown()


class TestHTTPServing:
    def test_infer_roundtrip_and_error_codes(self):
        from deeplearning4j_tpu.ui.server import UIServer

        model = mlp()
        eng = build_engine(model, buckets=(1, 2, 4), oversize="reject")
        ui = UIServer().attach_serving(eng)
        port = ui.enable(0)
        base = f"http://127.0.0.1:{port}"

        def post(payload, raw=None):
            req = urllib.request.Request(
                base + "/api/infer",
                data=raw if raw is not None else json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            return urllib.request.urlopen(req, timeout=15)

        try:
            x = np.random.randn(3, 4).astype(np.float32)
            with post({"inputs": x.tolist()}) as r:
                body = json.loads(r.read())
            assert body["shape"] == [3, 3]
            assert body["latency_ms"] > 0
            np.testing.assert_allclose(
                np.asarray(body["outputs"], np.float32),
                model.output(x).to_numpy(), atol=1e-6)
            # health carries the serving section
            with urllib.request.urlopen(base + "/api/health",
                                        timeout=15) as r:
                h = json.loads(r.read())
            assert h["serving"]["engines"] >= 1
            assert h["serving"]["requests"] >= 1
            # oversize (reject ladder) -> 413; malformed -> 400
            with pytest.raises(urllib.error.HTTPError) as ei:
                post({"inputs": np.zeros((9, 4)).tolist()})
            assert ei.value.code == 413
            with pytest.raises(urllib.error.HTTPError) as ei:
                post(None, raw=b"{not json")
            assert ei.value.code == 400
        finally:
            ui.stop()
            ui.detach_all()
            eng.shutdown()

    def test_infer_without_engine_is_503(self):
        from deeplearning4j_tpu.ui.server import UIServer

        ui = UIServer()
        port = ui.enable(0)
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/infer",
                data=b'{"inputs": [[0]]}',
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=15)
            assert ei.value.code == 503
        finally:
            ui.stop()


@pytest.mark.slow
class TestPoissonLoad:
    def test_open_loop_poisson_meets_slo_and_never_retraces(self):
        """Mini serving-smoke: open-loop Poisson arrivals, zero failures,
        p99 under a generous CPU bound, trace counter flat. The full
        SLO-gated run (incl. the kill drill) is
        ``bench.py --config serving-smoke``."""
        prof = OpProfiler.get()
        eng = build_engine(mlp(), buckets=(1, 2, 4, 8), workers=2)
        traces0 = prof.counter_value("trace/serving_infer")
        r = np.random.RandomState(3)
        lat, failures = [], []
        lock = threading.Lock()
        try:
            gaps = r.exponential(1 / 120.0, 240)
            t_next = time.monotonic()
            futs = []
            for i in range(240):
                t_next += gaps[i]
                d = t_next - time.monotonic()
                if d > 0:
                    time.sleep(d)
                fut = eng.output_async(
                    np.random.randn(r.randint(1, 5), 4).astype(np.float32))

                def on_done(f, t_sub=t_next):
                    with lock:
                        if f.exception() is not None:
                            failures.append(str(f.exception()))
                        else:
                            lat.append(time.monotonic() - t_sub)

                fut.add_done_callback(on_done)
                futs.append(fut)
            for f in futs:
                f.exception(timeout=20)      # resolve without raising
            assert not failures, failures[:3]
            p99 = float(np.percentile(np.asarray(lat) * 1e3, 99))
            assert p99 < 500.0, f"p99 {p99:.1f}ms"
            assert prof.counter_value("trace/serving_infer") == traces0
        finally:
            eng.shutdown()
