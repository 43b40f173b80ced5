"""Policy-driven rematerialization + fused backward epilogue (ISSUE 16):
named remat policies are numerically free (bitwise loss/param parity vs
"none" on CPU), a policy flip costs exactly one recompile, the remat
primitive really lands in the jaxpr, dots_only's memory win is asserted
on hardware (TPU-gated like test_l6_features — the CPU scheduler shows
the inverse), and the flat-backward fused epilogue — ZeRO-1's alone since
the unsharded step updates trees (PR 27) — is ledgered and bitwise
against the dense-grads-then-flatten step on a 2-device ZeRO-1 wrapper."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.common import tracecheck
from deeplearning4j_tpu.common.profiler import OpProfiler
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.learning.updaters import Adam, Sgd
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.builder import (REMAT_POLICIES,
                                                effective_remat_policy,
                                                remat_wrap)
from deeplearning4j_tpu.parallel import (ParallelWrapper,
                                         ReduceScatterAccumulator)

f32 = jnp.float32


@pytest.fixture(autouse=True)
def _clean_profiler():
    OpProfiler.get().reset()
    yield


def tree_bitwise(a, b):
    la = jax.tree.leaves(jax.device_get(a))
    lb = jax.tree.leaves(jax.device_get(b))
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def stack(policy=None, updater=None, depth=3, width=32, flat_backward=True,
          seed=11):
    b = NeuralNetConfiguration.builder().seed(seed)
    b = b.updater(updater if updater is not None else Sgd(0.05))
    if policy is not None:
        b = b.remat_policy(policy)
    lb = b.list()
    for _ in range(depth):
        lb = lb.layer(L.DenseLayer(n_out=width, activation="relu"))
    conf = (lb.layer(L.OutputLayer(n_out=5, activation="softmax",
                                   loss="mcxent"))
            .set_input_type(InputType.feed_forward(16)).build())
    conf.global_conf.flat_backward = flat_backward
    return MultiLayerNetwork(conf).init()


def fit_data(n=64):
    rng = np.random.default_rng(3)
    return DataSet(rng.normal(size=(n, 16)).astype(np.float32),
                   np.eye(5, dtype=np.float32)[rng.integers(0, 5, n)])


def zero1_fit(model, epochs=1):
    """Where the flat path lives: the state sharded as flat buckets over
    two (virtual CPU) devices."""
    pw = (ParallelWrapper.Builder(model).workers(2)
          .gradients_accumulator(ReduceScatterAccumulator()).build())
    pw.fit(fit_data(), epochs=epochs, batch_size=32)
    return model


# ---------------------------------------------------------------------------
# policy numerics: remat must be a pure recompute — never a reassociation
# ---------------------------------------------------------------------------

class TestPolicyParity:
    # the selective list checkpoints blocks 0 and 2 only — the
    # open-ended fourth policy form
    POLICIES = ["full", "dots_only",
                "checkpoint_dots_with_no_batch_dims", [0, 2]]

    @pytest.mark.parametrize("policy", POLICIES,
                             ids=["full", "dots", "dots_nb", "selective"])
    def test_loss_and_params_bitwise_vs_none(self, policy):
        """Rematerialization replays the SAME ops in the same order —
        on CPU every policy must reproduce the "none" run bit for bit,
        loss sequence and final params alike."""
        ds = fit_data()
        base, rem = stack(policy=None), stack(policy=policy)
        base_losses, rem_losses = [], []
        for _ in range(4):
            base.fit(ds, epochs=1, batch_size=32)
            rem.fit(ds, epochs=1, batch_size=32)
            base_losses.append(float(base.score(ds)))
            rem_losses.append(float(rem.score(ds)))
        assert base_losses == rem_losses
        assert tree_bitwise(base._params, rem._params)

    def test_parity_holds_with_fused_epilogue(self):
        """Policy × fused flat-backward (ZeRO-1) compose: still bitwise."""
        base = zero1_fit(stack(policy=None), epochs=3)
        rem = zero1_fit(stack(policy="dots_only"), epochs=3)
        assert tree_bitwise(base._params, rem._params)

    def test_unknown_policy_rejected_at_build(self):
        with pytest.raises(ValueError, match="remat"):
            NeuralNetConfiguration.builder().remat_policy("everything")

    def test_legacy_gradient_checkpointing_maps_to_full(self):
        m = stack(policy=None)
        gc = m.conf.global_conf
        assert effective_remat_policy(gc) == "none"
        gc.gradient_checkpointing = True
        assert effective_remat_policy(gc) == "full"
        gc.remat_policy = "dots_only"   # explicit policy wins
        assert effective_remat_policy(gc) == "dots_only"


# ---------------------------------------------------------------------------
# retrace accounting: a flip is ONE recompile, then steady again
# ---------------------------------------------------------------------------

class TestPolicyFlip:
    def test_flip_then_refit_retraces_exactly_once(self):
        ds = fit_data()
        m = stack(policy=None)
        m.fit(ds, epochs=2, batch_size=32)
        prof = OpProfiler.get()
        assert prof.counter_value("trace/mln_fit_step") == 1
        m.set_remat_policy("dots_only")
        assert m._fit_step is None      # flip invalidates the step...
        m.fit(ds, epochs=1, batch_size=32)
        assert prof.counter_value("trace/mln_fit_step") == 2
        # ...exactly once: the refit loop is steady state again
        with tracecheck.steady_state("post-flip refit",
                                     max_host_syncs=None):
            m.fit(ds, epochs=2, batch_size=32)
        assert prof.counter_value("trace/mln_fit_step") == 2

    def test_same_policy_flip_is_free(self):
        m = stack(policy="dots_only")
        m.fit(fit_data(), epochs=1, batch_size=32)
        step = m._fit_step
        m.set_remat_policy("dots_only")
        assert m._fit_step is step      # no-op flip keeps the executable


# ---------------------------------------------------------------------------
# structure: the policy really lands in the lowered program
# ---------------------------------------------------------------------------

class TestJaxprStructure:
    def _grad_jaxpr(self, policy):
        m = stack(policy=policy)
        ds = fit_data()
        x = jnp.asarray(ds.features)
        y = jnp.asarray(ds.labels)
        key = jax.random.PRNGKey(0)

        def loss_fn(params):
            loss, _ = m._loss(params, m._states, x, y, None, True, key)
            return loss

        return jax.make_jaxpr(jax.grad(loss_fn))(m._params)

    @staticmethod
    def _remat_eqns(jaxpr):
        return sum(1 for eq in jaxpr.jaxpr.eqns
                   if eq.primitive.name == "remat2")

    def test_remat_primitive_present_per_policy(self):
        assert self._remat_eqns(self._grad_jaxpr(None)) == 0
        for pol in ("full", "dots_only",
                    "checkpoint_dots_with_no_batch_dims"):
            assert self._remat_eqns(self._grad_jaxpr(pol)) > 0, pol
        # selective list: only the named blocks are wrapped
        assert self._remat_eqns(self._grad_jaxpr([1])) >= 1

    def test_remat_wrap_none_is_identity(self):
        gc = stack(policy=None).conf.global_conf

        def f(x):
            return x * 2

        assert remat_wrap(gc, f) is f

    def test_policy_registry_closed(self):
        assert set(REMAT_POLICIES) == {
            "none", "full", "dots_only",
            "checkpoint_dots_with_no_batch_dims"}


# ---------------------------------------------------------------------------
# memory: the HBM watermark claim (hardware-gated, like test_l6_features)
# ---------------------------------------------------------------------------

class TestWatermark:
    def test_dots_only_lowers_temp_bytes_on_tpu(self):
        """dots_only keeps matmul outputs and recomputes the cheap
        elementwise tail — the compiled grad step's temp (activation)
        buffers must shrink vs "none" ON TPU. The CPU scheduler shows
        the INVERSE (its remat graph allocates more temp — same
        documented property test_l6_features gates on), so this
        assertion only runs on hardware."""
        if jax.devices()[0].platform != "tpu":
            pytest.skip("memory win is a TPU-scheduling property")

        B, D = 2048, 1024

        def temp_bytes(policy):
            m = stack(policy=policy, depth=8, width=D)
            x = jnp.asarray(np.random.RandomState(0)
                            .randn(B, 16).astype(np.float32))
            y = jnp.asarray(np.eye(5, dtype=np.float32)[
                np.random.RandomState(1).randint(0, 5, B)])
            key = jax.random.PRNGKey(0)

            def loss_fn(params):
                loss, _ = m._loss(params, m._states, x, y, None, True,
                                  key)
                return loss

            comp = jax.jit(jax.grad(loss_fn)).lower(m._params).compile()
            return comp.memory_analysis().temp_size_in_bytes

        none_t, dots_t = temp_bytes(None), temp_bytes("dots_only")
        assert dots_t < none_t, (none_t, dots_t)


# ---------------------------------------------------------------------------
# fused backward epilogue: ledger + A/B parity vs the legacy dense step
# ---------------------------------------------------------------------------

class TestFusedEpilogue:
    def test_fused_fit_sets_grads_flat_gauge(self):
        zero1_fit(stack(updater=Sgd(0.05)))
        stats = OpProfiler.get().precision_stats()
        assert stats.get("grads_flat_in_step") == 1

    def test_legacy_path_reports_dense_grads(self):
        zero1_fit(stack(updater=Sgd(0.05), flat_backward=False))
        stats = OpProfiler.get().precision_stats()
        assert stats.get("grads_flat_in_step") == 0

    @pytest.mark.parametrize("updater", [lambda: Sgd(0.05),
                                         lambda: Adam(1e-3)],
                             ids=["sgd", "adam"])
    def test_flat_backward_ab_bitwise(self, updater):
        """The flat cotangent is the EXACT concatenation of the dense
        leaf cotangents (Zero1Plan.unflatten_diff spells out the
        adjoint), so flat-backward vs legacy dense-then-flatten is
        bitwise — for Adam too, not just ulp-bounded."""
        a = zero1_fit(stack(updater=updater(), flat_backward=False),
                      epochs=3)
        b = zero1_fit(stack(updater=updater(), flat_backward=True),
                      epochs=3)
        assert tree_bitwise(a._params, b._params)
        assert tree_bitwise(a._updater_state, b._updater_state)

    def test_unflatten_diff_adjoint_matches_autodiff(self):
        """The hand adjoint (flatten) is bitwise against jax's own
        transpose of unflatten — on a ragged multi-dtype tree."""
        from deeplearning4j_tpu.parallel.sharding import Zero1Plan

        k = jax.random.PRNGKey(4)
        tree = [{"W": jax.random.normal(k, (7, 3), f32),
                 "b": jnp.ones((3,), f32)},
                {"W": jax.random.normal(jax.random.fold_in(k, 1),
                                        (3, 2), f32)}]
        plan = Zero1Plan(tree, 1)
        flats = plan.flatten(tree)

        def loss_auto(f):
            return sum(jnp.sum(l ** 2)
                       for l in jax.tree.leaves(plan.unflatten(f)))

        def loss_hand(f):
            return sum(jnp.sum(l ** 2)
                       for l in jax.tree.leaves(plan.unflatten_diff(f)))

        ga = jax.grad(loss_auto)(flats)
        gh = jax.grad(loss_hand)(flats)
        assert tree_bitwise(ga, gh)
