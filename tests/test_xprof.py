"""XLA performance observatory (ISSUE 15): executable census, roofline
attribution, HBM watermarks, and the benchtrack regression gates.

Doubles as the DRILL CORPUS for graftlint's executable-census rule and
the xprof/exec + xprof/hbm flight-recorder events: the EXPECTED_SITES
table below carries every registered census name literally, and the
live tests exercise the core trainer families (mln fit/infer, fleet,
serving AOT, fused-Pallas counted sub-executable)."""

import gc
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.common import flightrec, xprof
from deeplearning4j_tpu.common.profiler import OpProfiler
from deeplearning4j_tpu.data import NDArrayDataSetIterator
from deeplearning4j_tpu.learning import Adam, Nesterovs
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf import layers as L

# the census registry, literally — the executable-census lint rule
# requires every registered name referenced from the test corpus, and
# this table IS that reference (asserted complete below)
EXPECTED_SITES = [
    "data/feature_transform",
    "embeddings/lookup", "embeddings/update",
    "fleet/infer", "fleet/step",
    "graph/fit_chunk", "graph/fit_step", "graph/infer",
    "mln/fit_chunk", "mln/fit_step", "mln/infer", "mln/pretrain_step",
    "mln/tbptt_step",
    "nlp/fasttext_block", "nlp/glove_block",
    "nlp/pv_dbow_block", "nlp/pv_dm_block",
    "nlp/pv_pos_map", "nlp/pv_subsample",
    "nlp/w2v_cbow_block", "nlp/w2v_sg_block", "nlp/w2v_subsample",
    "nlp/w2v_table_block",
    "pallas/update_bucket",
    "pipeline/fit_step", "pipeline/hetero_fwd", "pipeline/hetero_step",
    "pipeline/legacy_fwd", "pipeline/legacy_step",
    "pw/fit_chunk", "pw/fit_step",
    "samediff/exec", "samediff/fit_step", "samediff/grad",
    "serving/bucket",
    "transfer/featurize",
]


def _mlp(n_in=16, hidden=24, n_out=4, updater=None, seed=3):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(updater or Nesterovs(learning_rate=0.01,
                                          momentum=0.9))
            .activation("relu").weight_init("xavier").list()
            .layer(L.DenseLayer(n_out=hidden))
            .layer(L.OutputLayer(n_out=n_out, loss="mcxent",
                                 activation="softmax"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _batches(n=96, n_in=16, n_out=4, batch=32, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, n_in).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rng.randint(0, n_out, n)]
    return x, y, NDArrayDataSetIterator(x, y, batch_size=batch)


@pytest.fixture
def fresh_census():
    xprof.reset()
    xprof.configure(enabled=True)
    yield
    xprof.reset()
    xprof.configure(enabled=True)


class TestCensusCore:
    def test_unknown_site_refused(self):
        with pytest.raises(ValueError, match="unknown executable-census"):
            xprof.register_jit("bogus/site", jax.jit(lambda x: x))

    def test_wrapper_counts_calls_and_generations(self, fresh_census):
        f = xprof.register_jit("mln/infer", jax.jit(lambda x: x * 2))
        f(jnp.ones((4,)))
        f(jnp.ones((4,)))
        e = xprof.census()["mln/infer"]
        assert e["calls"] == 2 and e["generations"] == 1
        f(jnp.ones((8,)))           # new signature = new executable
        e = xprof.census()["mln/infer"]
        assert e["calls"] == 3 and e["generations"] == 2
        assert e["compile_s"] > 0

    def test_wrapper_is_call_transparent(self, fresh_census):
        jitted = jax.jit(lambda x: x + 1)
        f = xprof.register_jit("mln/infer", jitted)
        # attribute fall-through: AOT introspection sees the jit
        lowered = f.lower(jnp.ones((3,)))
        assert lowered.cost_analysis() is not None
        assert f.wrapped is jitted

    def test_disabled_census_counts_nothing(self, fresh_census):
        f = xprof.register_jit("mln/infer", jax.jit(lambda x: x))
        xprof.configure(enabled=False)
        try:
            assert float(f(jnp.ones((2,)))[0]) == 1.0
            assert xprof.census()["mln/infer"]["calls"] == 0
        finally:
            xprof.configure(enabled=True)

    def test_reregistration_accumulates(self, fresh_census):
        # a rebuilt step (set_params, telemetry flip) re-registers the
        # same name — that IS the retrace-generation ledger
        f1 = xprof.register_jit("mln/fit_step", jax.jit(lambda x: x))
        f1(jnp.ones((2,)))
        f2 = xprof.register_jit("mln/fit_step", jax.jit(lambda x: -x))
        f2(jnp.ones((2,)))
        e = xprof.census()["mln/fit_step"]
        assert e["calls"] == 2 and e["generations"] == 2

    def test_register_aot_extracts_immediately(self, fresh_census):
        jitted = jax.jit(lambda a, b: a @ b)
        aval = jax.ShapeDtypeStruct((8, 16), jnp.float32)
        bval = jax.ShapeDtypeStruct((16, 4), jnp.float32)
        exe = jitted.lower(aval, bval).compile()
        xprof.register_aot("serving/bucket", exe, variant="(8, 16)",
                           compile_s=0.25)
        e = xprof.census()["serving/bucket"]
        assert e["variants"] == 1 and e["compile_s"] == 0.25
        assert e["cost"]["flops"] == pytest.approx(2 * 8 * 16 * 4)
        assert e["memory"]["argument_bytes"] > 0
        # a second bucket accumulates onto the same entry
        xprof.register_aot("serving/bucket", exe, variant="again")
        e = xprof.census()["serving/bucket"]
        assert e["variants"] == 2
        assert e["cost"]["flops"] == pytest.approx(2 * 2 * 8 * 16 * 4)

    def test_register_aot_none_is_noop(self, fresh_census):
        xprof.register_aot("serving/bucket", None)
        assert "serving/bucket" not in xprof.census()

    def test_reset_opens_a_clean_window_without_orphaning(
            self, fresh_census):
        # a live wrapper must re-enter the census after reset() — the
        # entry is resolved by name per dispatch, never captured
        f = xprof.register_jit("mln/fit_step", jax.jit(lambda x: x * 2),
                               donate=(0,))
        f(jnp.ones((4,)))
        xprof.reset()
        assert xprof.census() == {}
        f(jnp.ones((4,)))            # warm cache, fresh window
        e = xprof.census()["mln/fit_step"]
        assert e["calls"] == 1
        # the warm executable counts as this window's first generation
        # and its avals are re-captured so analyze() still works
        assert e["generations"] == 1
        assert e["fingerprint"]["donate_argnums"] == (0,)
        assert list(xprof.analyze()) == ["mln/fit_step"]

    def test_note_subexec_counted_last_trace_wins(self, fresh_census):
        xprof.note_subexec("pallas/update_bucket", flops=100.0,
                           bytes_accessed=400.0, kind="adam")
        # a re-trace (rebuild / analysis lowering) must not inflate the
        # row — the cost always describes ONE parent execution
        xprof.note_subexec("pallas/update_bucket", flops=100.0,
                           bytes_accessed=400.0, kind="adam")
        e = xprof.census()["pallas/update_bucket"]
        assert e["subexec"] is True and e["cost_source"] == "counted"
        assert e["generations"] == 2
        assert e["cost"]["flops"] == 100.0
        assert e["cost"]["bytes_accessed"] == 400.0


class TestAnalysis:
    def test_xla_cost_matches_hand_computed_flops(self, fresh_census):
        # roofline join against hand-computed matmul flops: XLA counts
        # x@w on (B,K)x(K,N) as 2*B*K*N
        B, K, N = 8, 32, 6
        f = xprof.register_jit("mln/infer",
                               jax.jit(lambda x, w: x @ w))
        f(jnp.ones((B, K), jnp.float32), jnp.ones((K, N), jnp.float32))
        res = xprof.analyze()
        assert "mln/infer" in res
        e = xprof.census()["mln/infer"]
        assert e["cost_source"] == "xla"
        assert e["cost"]["flops"] == pytest.approx(2 * B * K * N)
        # bytes accessed: inputs + output, f32
        assert e["cost"]["bytes_accessed"] == pytest.approx(
            4 * (B * K + K * N + B * N))
        assert e["memory"]["argument_bytes"] == 4 * (B * K + K * N)
        assert e["memory"]["output_bytes"] == 4 * B * N

    def test_analyze_is_idempotent_per_generation(self, fresh_census):
        f = xprof.register_jit("mln/infer", jax.jit(lambda x: x * 3))
        f(jnp.ones((4,)))
        assert list(xprof.analyze()) == ["mln/infer"]
        assert xprof.analyze() == {}      # nothing new to analyze
        f(jnp.ones((6,)))                 # new generation -> re-analyzed
        assert list(xprof.analyze()) == ["mln/infer"]

    def test_counted_fallback_when_backend_analysis_fails(
            self, fresh_census, monkeypatch):
        f = xprof.register_jit("mln/infer", jax.jit(lambda x: x + 1))
        f(jnp.ones((10,), jnp.float32))
        # backend returns nothing: both analysis surfaces unavailable
        monkeypatch.setattr(xprof, "_cost_dict", lambda obj: None)
        monkeypatch.setattr(xprof, "_memory_dict", lambda obj: None)
        res = xprof.analyze()
        e = res["mln/infer"]
        assert e["cost_source"] == "counted"
        # counted bytes = input avals (+ output when the lowering's
        # out_info is available)
        assert e["cost"]["bytes_accessed"] >= 40
        ledger = xprof.ledger()
        assert ledger["mln/infer/counted"] == 1.0

    def test_collected_executable_degrades_gracefully(self, fresh_census):
        f = xprof.register_jit("mln/infer", jax.jit(lambda x: x + 2))
        f(jnp.ones((4,)))
        del f
        gc.collect()
        res = xprof.analyze()
        e = res["mln/infer"]
        assert e["cost_source"] == "counted"
        assert "collected" in e["error"]


class TestRoofline:
    def test_join_math_and_bound_verdict(self, fresh_census):
        # hand-checkable join: roof 1 TFLOP/s + 100 GB/s -> ridge 10
        # flops/byte. 5e8 flops / 1e9 bytes -> AI 0.5 -> HBM-bound;
        # measured 1 ms -> 5e11 flops/s -> MFU 0.5.
        xprof.set_roof(1e12, 1e11)
        xprof.note_subexec("pallas/update_bucket", flops=5e8,
                           bytes_accessed=1e9)
        xprof.note_measured("pallas/update_bucket", 1e-3)
        row = xprof.roofline()["pallas/update_bucket"]
        assert row["arithmetic_intensity"] == pytest.approx(0.5)
        assert row["bound"] == "hbm"
        assert row["mfu"] == pytest.approx(0.5)
        assert row["effective_flops_per_s"] == pytest.approx(5e11)
        # flip to compute-bound (last trace wins): AI 20 >= ridge 10
        xprof.note_subexec("pallas/update_bucket", flops=2e10,
                           bytes_accessed=1e9)
        row = xprof.roofline()["pallas/update_bucket"]
        assert row["arithmetic_intensity"] == pytest.approx(20.0)
        assert row["bound"] == "compute"

    def test_ledger_is_flat_and_on_the_profiler(self, fresh_census):
        xprof.set_roof(1e12, 1e11)
        xprof.note_subexec("pallas/update_bucket", flops=1e6,
                           bytes_accessed=1e7)
        led = OpProfiler.get().xla_stats()
        assert led["executables"] == 1
        assert led["pallas/update_bucket/flops"] == 1e6
        assert led["pallas/update_bucket/compute_bound"] == 0.0
        assert all(isinstance(v, (int, float)) for v in led.values())
        assert ("xla", "xla_stats") in OpProfiler.LEDGERS

    def test_measured_step_beats_dispatch_mean(self, fresh_census):
        f = xprof.register_jit("mln/infer", jax.jit(lambda x: x))
        f(jnp.ones((4,)))
        xprof.note_measured("mln/infer", 42.0)
        assert xprof.roofline()["mln/infer"]["step_s"] == 42.0


class TestTrainerFamilies:
    def test_mln_fit_and_infer_register(self, fresh_census):
        model = _mlp()
        x, y, it = _batches()
        model.fit(it, epochs=1)
        model.output(x[:8])
        census = xprof.census()
        assert census["mln/fit_step"]["calls"] >= 3
        assert census["mln/fit_step"]["generations"] >= 1
        assert census["mln/infer"]["calls"] == 1
        # fingerprint records the donation signature
        assert census["mln/fit_step"]["fingerprint"][
            "donate_argnums"] == (0, 1, 2)

    def test_mln_chunk_step_registers(self, fresh_census):
        model = _mlp()
        _, _, it = _batches(n=128)
        model.fit(it, epochs=1, steps_per_dispatch=2)
        assert xprof.census()["mln/fit_chunk"]["calls"] >= 1

    def test_fleet_step_registers(self, fresh_census):
        from deeplearning4j_tpu.parallel.fleet import FleetTrainer

        fleet = FleetTrainer(_mlp(n_in=8, hidden=8, n_out=2,
                                  updater=Adam(1e-3)), 3, seed=7)
        rng = np.random.RandomState(0)
        x = rng.randn(16, 8).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng.randint(0, 2, 16)]
        fleet.step(x, y)
        assert xprof.census()["fleet/step"]["calls"] == 1

    def test_serving_bucket_aot_registers(self, fresh_census):
        from deeplearning4j_tpu.parallel import ServingEngine

        model = _mlp(n_in=12, hidden=8, n_out=3, updater=Adam(1e-3))
        eng = (ServingEngine.Builder(model)
               .buckets([1, 4]).input_shape((12,))
               .workers(1).max_wait_ms(1.0).build())
        try:
            e = xprof.census()["serving/bucket"]
            assert e["variants"] == 2
            assert e["cost_source"] == "xla"
            assert e["cost"]["flops"] > 0
            assert e["compile_s"] > 0
            # serving warmup took an HBM watermark sample
            assert xprof.watermarks()["serving_warmup"]["samples"] >= 1
        finally:
            eng.shutdown()

    def test_fused_pallas_counted_subexec(self, fresh_census):
        """The bucket kernel runs where the state is sharded as flat
        buckets (ZeRO-1), over this replica's slice."""
        from deeplearning4j_tpu.parallel import (ParallelWrapper,
                                                 ReduceScatterAccumulator)
        from deeplearning4j_tpu.parallel.sharding import Zero1Plan

        model = _mlp(updater=Adam(1e-3))
        _, _, it = _batches()
        pw = (ParallelWrapper.Builder(model).workers(2)
              .gradients_accumulator(ReduceScatterAccumulator()).build())
        pw.fit(it, epochs=1)
        e = xprof.census()["pallas/update_bucket"]
        assert e["subexec"] is True and e["cost_source"] == "counted"
        shard = sum(b.shard for b in Zero1Plan(model._params, 2).buckets)
        assert shard * 2 >= model.num_params()
        # adam: 12 flops/elem analytic; one trace -> one bump
        assert e["cost"]["flops"] == pytest.approx(12 * shard)
        assert e["cost"]["bytes_accessed"] > 0

    def test_exec_events_emitted(self, fresh_census):
        rec = flightrec.get()
        rec.configure(enabled=True)
        before = len(rec.events(prefix="xprof/exec"))
        model = _mlp()
        _, _, it = _batches()
        model.fit(it, epochs=1)
        evs = rec.events(prefix="xprof/exec")[before:]
        assert any(e["attrs"].get("executable") == "mln/fit_step"
                   for e in evs)


class TestWatermarks:
    def test_rise_and_fall_across_fit(self, fresh_census):
        model = _mlp()
        _, _, it = _batches()
        model.fit(it, epochs=3)
        wm = xprof.watermarks()["fit"]
        assert wm["samples"] == 3
        assert wm["peak_live_bytes"] >= wm["last_live_bytes"] > 0
        counters = OpProfiler.get().get_counters()
        assert counters.get("xprof/live_buffer_bytes", 0) > 0
        assert "xprof/peak_live_bytes/fit" in counters
        # a big allocation raises the peak; releasing it lowers LAST but
        # never the peak (rise-and-fall)
        ballast = jnp.ones((256, 1024), jnp.float32) + 0
        xprof.memory_watermark("fit")
        peak_with_ballast = xprof.watermarks()["fit"]["peak_live_bytes"]
        assert peak_with_ballast >= 2**20    # the 1 MiB ballast is live
        del ballast
        gc.collect()
        xprof.memory_watermark("fit")
        wm2 = xprof.watermarks()["fit"]
        assert wm2["peak_live_bytes"] == peak_with_ballast
        assert wm2["last_live_bytes"] < peak_with_ballast

    def test_watermark_shares_the_health_census(self, fresh_census):
        # one census function: the watermark returns exactly the
        # memory_summary() shape /api/health serves
        census = xprof.memory_watermark("global")
        assert "host" in census and "devices" in census
        assert "live_buffers" in census
        evs = flightrec.events(prefix="xprof/hbm")
        assert any(e["attrs"].get("phase") == "global" for e in evs)

    def test_dump_memory_census(self, fresh_census, tmp_path):
        xprof.memory_watermark("fit")
        path = str(tmp_path / "memcensus.json")
        assert xprof.dump_memory_census(path) == path
        blob = json.load(open(path))
        assert blob["watermarks"]["fit"]["samples"] == 1
        assert "census" in blob and "ledger" in blob

    def test_blackbox_dumps_memcensus_alongside(self, fresh_census,
                                                tmp_path):
        from deeplearning4j_tpu.parallel import TrainingSupervisor

        model = _mlp()
        sup = TrainingSupervisor(model, str(tmp_path))
        xprof.memory_watermark("fit")
        assert sup._dump_blackbox() is not None
        assert os.path.exists(sup.blackbox_path())
        assert os.path.exists(sup.memcensus_path())
        blob = json.load(open(sup.memcensus_path()))
        assert "watermarks" in blob and "census" in blob

    def test_health_and_metrics_carry_the_xla_ledger(self, fresh_census):
        from deeplearning4j_tpu.ui.server import UIServer, prometheus_text

        xprof.set_roof(1e12, 1e11)
        xprof.note_subexec("pallas/update_bucket", flops=1e6,
                          bytes_accessed=1e7)
        health = UIServer().health()
        assert health["xla"]["pallas/update_bucket/flops"] == 1e6
        text = prometheus_text()
        assert 'ledger="xla"' in text


class TestBenchtrack:
    def _round_file(self, tmp_path, n, records):
        tail = "\n".join(json.dumps(r) for r in records)
        path = tmp_path / f"BENCH_r{n:02d}.json"
        path.write_text(json.dumps(
            {"n": n, "cmd": "python bench.py", "rc": 0, "tail": tail,
             "parsed": records[-1]}))
        return str(path)

    def _rec(self, **over):
        rec = {"metric": "resnet50_imagenet_train", "value": 2500.0,
               "unit": "images/sec", "batch": 128, "platform": "tpu",
               "step_ms_median": 50.0, "step_ms_p10": 49.5,
               "mfu_vs_bf16_peak": 0.29,
               "traces": {"trace/graph_fit_step": 1},
               "updater_state_bytes": {"total": 1000}}
        rec.update(over)
        return rec

    def test_parse_driver_round_shape(self, tmp_path):
        from tools import benchtrack

        path = self._round_file(tmp_path, 6, [self._rec()])
        rnd = benchtrack.parse_round(path)
        assert rnd["round"] == 6 and rnd["rc"] == 0
        assert "resnet50_imagenet_train" in rnd["records"]

    def test_trajectory_and_markdown(self, tmp_path):
        from tools import benchtrack

        self._round_file(tmp_path, 1, [self._rec(value=2000.0)])
        self._round_file(tmp_path, 2, [self._rec(value=2500.0)])
        rounds = benchtrack.load_rounds(str(tmp_path))
        traj = benchtrack.trajectory(rounds)
        assert [n for n, _ in traj["resnet50_imagenet_train"]] == [1, 2]
        md = benchtrack.render_markdown(rounds)
        assert "resnet50_imagenet_train" in md and "| r01 |" in md

    def test_regressed_record_fails(self):
        from tools import benchtrack

        base = {"m": self._rec()}
        cur = {"m": self._rec(step_ms_median=60.0, step_ms_p10=59.5,
                              value=2083.0)}
        res = benchtrack.compare_records(base, cur)
        assert any("step time regressed" in v for v in res["violations"])
        assert any("throughput regressed" in v
                   for v in res["violations"])

    def test_noisy_but_flat_passes(self):
        from tools import benchtrack

        # median 8% up (host noise) but p10 at baseline: the min-over-
        # rounds bound says the hardware still hits the old time
        base = {"m": self._rec()}
        cur = {"m": self._rec(step_ms_median=54.0, step_ms_p10=49.8,
                              value=2320.0)}
        res = benchtrack.compare_records(base, cur)
        assert res["violations"] == []
        assert res["compared"] == ["m"]

    def test_platform_change_skips_never_fails(self):
        from tools import benchtrack

        base = {"m": self._rec()}
        cur = {"m": self._rec(platform="cpu", step_ms_median=5000.0,
                              step_ms_p10=4900.0, value=25.0)}
        res = benchtrack.compare_records(base, cur)
        assert res["violations"] == [] and res["compared"] == []
        assert any("platform changed" in s for s in res["skipped"])

    def test_compile_count_and_state_bytes_gates(self):
        from tools import benchtrack

        base = {"m": self._rec()}
        cur = {"m": self._rec(
            traces={"trace/graph_fit_step": 3},
            updater_state_bytes={"total": 2000})}
        res = benchtrack.compare_records(base, cur)
        assert any("compile count grew" in v for v in res["violations"])
        assert any("state bytes grew" in v for v in res["violations"])

    def test_mfu_gate(self):
        from tools import benchtrack

        base = {"m": self._rec()}
        res = benchtrack.compare_records(
            base, {"m": self._rec(mfu_vs_bf16_peak=0.20)})
        assert any("MFU regressed" in v for v in res["violations"])

    def test_missing_fields_skip_gates(self):
        from tools import benchtrack

        base = {"m": {"metric": "m", "value": 1.0, "unit": "x",
                      "platform": "cpu"}}
        cur = {"m": {"metric": "m", "value": 1.0, "unit": "x",
                     "platform": "cpu"}}
        assert benchtrack.compare_records(base, cur)["violations"] == []

    def test_empty_baseline_skips_with_message(self, tmp_path):
        """An empty baseline round (smoke config that emitted nothing,
        truncated file) gates nothing, says so, and exits 0 — never a
        crash, never a silent vacuous pass."""
        import json as _json

        from tools import benchtrack

        res = benchtrack.compare_records({}, {"m": self._rec()})
        assert res["violations"] == [] and res["compared"] == []
        assert any("no records" in s for s in res["skipped"])
        # end-to-end through the CLI: exit 0 on the empty baseline
        empty = tmp_path / "BENCH_r00.json"
        empty.write_text(_json.dumps({"n": 0, "rc": 0, "tail": "",
                                      "parsed": []}))
        cur = tmp_path / "BENCH_r01.json"
        cur.write_text(_json.dumps(self._rec() | {"metric": "m"}))
        assert benchtrack.main(["--compare", str(empty), str(cur)]) == 0


class TestRegistryTable:
    """The 4-way agreement's test-corpus leg (mirrors the fault-site
    and event-name registries)."""

    def test_expected_sites_match_registry(self):
        assert EXPECTED_SITES == sorted(xprof.EXEC_SITES)

    def test_registry_covers_every_docstring_site(self):
        for site in xprof.EXEC_SITES:
            assert site in (xprof.__doc__ or ""), site

    def test_registry_entries_carry_desc_and_drill(self):
        assert len(xprof.EXEC_SITES) >= 30
        for site, meta in xprof.EXEC_SITES.items():
            assert meta["desc"], site
            assert meta["drill"], site

    def test_xprof_events_registered(self):
        assert "xprof/exec" in flightrec.EVENT_SITES
        assert "xprof/hbm" in flightrec.EVENT_SITES
