"""Mixed-precision training tier (ISSUE 8): stochastic-rounding
unbiasedness, bf16 updater state (tolerance-bounded parity + halved
footprint), the fused flat-bucket update kernel (bitwise vs the per-leaf
fp32 reference), the ZeRO-1 compose (reshard with bf16 state), the
checkpoint state-dtype contract, and the fused BN epilogue."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.common.profiler import OpProfiler
from deeplearning4j_tpu.data import NDArrayDataSetIterator
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.learning import precision
from deeplearning4j_tpu.learning.updaters import (AdaDelta, Adam, AdamW,
                                                  AMSGrad, GradientUpdater,
                                                  Nesterovs, Sgd)
from deeplearning4j_tpu.ndarray.rng import set_default_seed
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                         ComputationGraphConfiguration,
                                         ElementWiseVertex)
from deeplearning4j_tpu.ops import pallas_epilogue, pallas_update
from deeplearning4j_tpu.ops.registry import get_op
from deeplearning4j_tpu.parallel import (ReduceScatterAccumulator,
                                         ParallelWrapper, Zero1Plan)
from deeplearning4j_tpu.parallel.sharding import is_flat_state

f32 = jnp.float32
BF16 = jnp.bfloat16


@pytest.fixture(autouse=True)
def _clean_profiler():
    OpProfiler.get().reset()
    yield


def tree_bitwise(a, b):
    la = jax.tree.leaves(jax.device_get(a))
    lb = jax.tree.leaves(jax.device_get(b))
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def small_params(key=1):
    k = jax.random.PRNGKey(key)
    return [{"W": jax.random.normal(k, (37, 13), f32),
             "b": jnp.zeros((13,), f32)},
            {"W": jax.random.normal(jax.random.fold_in(k, 1), (13, 5), f32)}]


def small_grads(params, scale=0.01):
    k = jax.random.PRNGKey(9)
    return jax.tree.map(
        lambda a: (jax.random.normal(k, a.shape, f32) * scale).astype(f32),
        params)


# ---------------------------------------------------------------------------
# stochastic rounding
# ---------------------------------------------------------------------------

class TestStochasticRounding:
    def test_unbiased_estimator(self):
        """E[SR(x)] == x: the mean over draws converges to the fp32
        value, where round-to-nearest is stuck a half-ulp away."""
        # values straddling bf16 grid points at various exponents
        xs = jnp.asarray([1.004, -3.013, 0.12307, 257.3, 1e-4 * 1.007], f32)
        K = 4096
        keys = jax.random.split(jax.random.PRNGKey(0), K)
        bits = jax.vmap(
            lambda k: jax.random.bits(k, xs.shape, dtype=jnp.uint32))(keys)
        draws = jax.vmap(
            lambda b: precision.stochastic_round(xs, b).astype(f32))(bits)
        mean = jnp.mean(draws, axis=0)
        ulp = jnp.abs(xs) * 2.0 ** -8 + 1e-12
        # SR noise is bounded by one ulp per draw → SE ~ ulp/sqrt(K)
        assert np.all(np.asarray(jnp.abs(mean - xs)) <=
                      np.asarray(ulp) * 4 / np.sqrt(K) + 1e-9)
        # round-to-nearest is measurably biased on the same values
        rtn = xs.astype(BF16).astype(f32)
        assert float(jnp.max(jnp.abs(mean - xs))) < \
            float(jnp.max(jnp.abs(rtn - xs)))

    def test_exact_values_pass_through(self):
        xs = jnp.asarray([1.0, -2.5, 0.0, 384.0], f32)   # bf16-exact
        bits = jnp.full(xs.shape, 0xFFFF, jnp.uint32)    # max round-up push
        out = precision.stochastic_round(xs, bits)
        assert np.array_equal(np.asarray(out.astype(f32)), np.asarray(xs))

    def test_nonfinite_pass_through(self):
        xs = jnp.asarray([jnp.inf, -jnp.inf, jnp.nan], f32)
        out = precision.stochastic_round(
            xs, jnp.zeros(xs.shape, jnp.uint32))
        o = np.asarray(out.astype(f32))
        assert np.isposinf(o[0]) and np.isneginf(o[1]) and np.isnan(o[2])

    def test_deterministic_per_key(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (128,), f32)
        b = jax.random.bits(jax.random.PRNGKey(4), x.shape, jnp.uint32)
        assert np.array_equal(
            np.asarray(precision.stochastic_round(x, b)),
            np.asarray(precision.stochastic_round(x, b)))

    def test_non_bf16_target_refused(self):
        with pytest.raises(NotImplementedError):
            precision.stochastic_round(
                jnp.ones((2,), f32), jnp.zeros((2,), jnp.uint32),
                jnp.float16)

    def test_ema_does_not_stall(self):
        """The motivating failure: a bf16 EMA fed increments below its
        rounding ulp stops moving under round-to-nearest but tracks the
        fp32 EMA in expectation under SR."""
        beta, inc, steps = 0.999, 1e-4, 800
        v32 = 1.0
        v_rtn = jnp.asarray(1.0, BF16)
        v_sr = jnp.asarray(1.0, BF16)
        key = jax.random.PRNGKey(7)
        for t in range(steps):
            v32 = beta * v32 + (1 - beta) * inc
            v_rtn = (beta * v_rtn.astype(f32)
                     + (1 - beta) * inc).astype(BF16)
            key, sub = jax.random.split(key)
            nxt = beta * v_sr.astype(f32) + (1 - beta) * inc
            v_sr = precision.stochastic_round(
                nxt, jax.random.bits(sub, (), jnp.uint32))
        # RTN never leaves 1.0; SR follows the decay toward ~0.45
        assert float(v_rtn) == 1.0
        assert abs(float(v_sr) - v32) < 0.15 * v32


# ---------------------------------------------------------------------------
# where the rounding gets its bits: one threefry block a parameter
# ---------------------------------------------------------------------------

def bf16_state(updater):
    updater.state_dtype = "bfloat16"
    return updater


class Slots(GradientUpdater):
    """An elementwise updater with any number of mirroring slots: slot
    ``j`` becomes ``state + 2**j * g``, the parameters stay."""
    elementwise = True

    def __init__(self, names):
        self.names = list(names)
        self.learning_rate = 0.1
        self.state_dtype = "bfloat16"

    def init(self, params):
        return {n: self._zeros_like(params) for n in self.names}

    def apply(self, grads, state, params, iteration):
        return params, {
            n: jax.tree.map(lambda s, g, j=j: s + 2.0 ** j * g, state[n],
                            grads)
            for j, n in enumerate(self.names)}


def sr_counters():
    prof = OpProfiler.get()
    return tuple(int(prof.counter_value(f"precision/{k}"))
                 for k in ("sr_blocks", "sr_draws", "sr_elements"))


class TestBlockDraws:
    N = 37 * 13 + 13 + 13 * 5          # elements of small_params()

    @pytest.mark.parametrize("name,mk,slots", [
        ("adam", lambda: Adam(1e-3), 2),
        ("adamw", lambda: AdamW(1e-3), 2),
        ("nesterovs", lambda: Nesterovs(0.1, momentum=0.9), 1),
        ("adadelta", lambda: AdaDelta(), 2),
        ("amsgrad", lambda: AMSGrad(1e-3), 3),
        ("five_slots", lambda: Slots("abcde"), 5)])
    def test_counters_follow_the_shapes(self, name, mk, slots):
        """One traced step bakes in one block a parameter element for up
        to four slots; the used-bit share is 16·elements / 64·blocks."""
        upd = bf16_state(mk())
        params = small_params()
        step = jax.jit(lambda g, s, p, k: precision.apply_updater(
            upd, g, s, p, 0, k))
        _, new = step(small_grads(params), upd.init(params), params,
                      jax.random.PRNGKey(0))
        assert all(l.dtype == BF16 for l in jax.tree.leaves(new))
        blocks, words, elements = sr_counters()
        assert blocks == self.N * (1 if slots <= 4 else 2)
        assert words == 2 * blocks
        assert elements == self.N * slots
        share = 16 * elements / (64 * blocks)
        assert share == min(slots, 4) / 4 if slots <= 4 else share >= 0.5
        if slots == 2:
            assert share >= 0.5

    def test_fused_buckets_are_counted_too(self):
        upd = bf16_state(Adam(1e-3))
        params = small_params()
        plan = Zero1Plan(params, 1)
        flat = plan.flatten(params)
        pallas_update.fused_apply(
            upd, flat, plan.flatten(small_grads(params)),
            upd.init(flat), 0, jax.random.PRNGKey(0), mode="xla")
        n = sum(int(v.size) for v in flat.values())
        blocks, words, elements = sr_counters()
        assert (blocks, words, elements) == (n, n, 2 * n)

    @pytest.mark.parametrize("mk", [
        lambda: Adam(1e-3), lambda: AdaDelta(),
        lambda: Nesterovs(0.1, momentum=0.9), lambda: Slots("abcd")],
        ids=["adam", "adadelta", "nesterovs", "four_slots"])
    def test_stored_moments_unbiased_through_apply_updater(self, mk):
        """Mean over many keys of every stored bf16 slot == the f32
        update, in every halfword position: a slot fed a constant
        halfword (or none) rounds with a bias and fails."""
        upd = bf16_state(mk())
        xs = jnp.asarray([1.004, -3.013, 0.12307, 257.3, 1e-4 * 1.007], f32)
        params = {"w": jnp.stack([xs, xs[::-1]]),
                  "w2": jnp.stack([xs[::-1], xs]) * 1.7}
        grads = jax.tree.map(lambda a: a * 0.37, params)
        state = upd.init(params)
        _, want = upd.apply(grads, precision.cast_floating(state, f32),
                            params, 0)
        K = 4096
        keys = jax.random.split(jax.random.PRNGKey(1), K)
        got = jax.jit(jax.vmap(lambda k: precision.apply_updater(
            upd, grads, state, params, 0, k)[1]))(keys)
        for name, leaf in ((n, l) for n in want for l in params):
            w = np.asarray(want[name][leaf])
            assert got[name][leaf].dtype == BF16
            mean = np.asarray(jnp.mean(got[name][leaf].astype(f32), axis=0))
            ulp = np.abs(w) * 2.0 ** -8 + 1e-12
            assert np.all(np.abs(mean - w) <= ulp * 4 / np.sqrt(K) + 1e-9), \
                (name, leaf)
            # and the slot really is rounded both ways, not truncated
            assert np.any(np.asarray(got[name][leaf].astype(f32)) != mean)

    def test_slots_of_one_parameter_round_independently(self):
        """Sample correlation of the round-up indicators of each pair of
        the four halfword positions over 2^16 elements: within 3 sigma of
        0 (a halfword shared by two slots reads 1)."""
        upd = Slots("abcd")
        n = 1 << 16
        # halfway between two bf16 neighbours at every slot's scale:
        # round-up probability 1/2
        g = jnp.full((256, 256), 1.0 + 2.0 ** -8, f32)
        params = {"w": g}
        _, new = jax.jit(lambda k: precision.apply_updater(
            upd, {"w": g}, upd.init(params), params, 0, k))(
                jax.random.PRNGKey(11))
        ups = []
        for j, name in enumerate("abcd"):
            want = np.asarray(g) * 2.0 ** j
            up = (np.asarray(new[name]["w"].astype(f32)) > want).ravel()
            assert abs(up.mean() - 0.5) < 3 * 0.5 / np.sqrt(n)
            ups.append(up.astype(np.float64))
        for a in range(4):
            for b in range(a + 1, 4):
                r = np.corrcoef(ups[a], ups[b])[0, 1]
                assert abs(r) < 3 / np.sqrt(n), (a, b, r)

    def test_halfword_order_is_fixed(self):
        """Slot names sorted take word 0 low, word 0 high, word 1 low,
        word 1 high of ONE block on fold_in(key, leaf index)."""
        key = jax.random.PRNGKey(5)
        shape = (7, 5)
        w0, w1 = precision.threefry_words(key, shape)
        bits = precision.slot_bits(key, shape, 4)
        want = [w0 & 0xFFFF, w0 >> 16, w1 & 0xFFFF, w1 >> 16]
        for b, w in zip(bits, want):
            assert np.array_equal(np.asarray(b) & 0xFFFF, np.asarray(w))
        # the block is jax's own threefry2x32; an element's counter is its
        # row-major index plus ndim times the key's first word
        from jax.extend.random import threefry2x32_p
        count = (np.arange(35, dtype=np.uint64).reshape(shape)
                 + 2 * int(key[0])).astype(np.uint32)
        want0, want1 = threefry2x32_p.bind(
            key[0], key[1], jnp.zeros(shape, jnp.uint32), jnp.asarray(count))
        assert np.array_equal(np.asarray(w0), np.asarray(want0))
        assert np.array_equal(np.asarray(w1), np.asarray(want1))
        # through the state: leaf i of a mirroring state draws on
        # fold_in(key, i)
        x = jax.random.normal(key, shape, f32)
        state = {"m": [x, x], "v": [2 * x, 2 * x]}
        out = precision.sr_cast_state(state, BF16, key, [x, x])
        for i in range(2):
            u0, _ = precision.threefry_words(jax.random.fold_in(key, i),
                                             shape)
            assert np.array_equal(
                np.asarray(out["m"][i]),
                np.asarray(precision.stochastic_round(x, u0)))
            assert np.array_equal(
                np.asarray(out["v"][i]),
                np.asarray(precision.stochastic_round(2 * x, u0 >> 16)))
        assert not np.array_equal(np.asarray(out["m"][0]),
                                  np.asarray(out["m"][1]))

    @pytest.mark.parametrize("shape", [(7, 5), (13,), (), (1, 3), (3, 2, 5)])
    def test_odd_small_and_scalar_leaves(self, shape):
        """An odd leading dimension, a 1-D leaf and a scalar round with
        the right shapes, and no two slots or elements share bits."""
        key = jax.random.PRNGKey(2)
        bits = precision.slot_bits(key, shape, 2)
        assert [b.shape for b in bits] == [shape, shape]
        halves = np.stack([np.asarray(b) & 0xFFFF for b in bits]).ravel()
        assert len(set(halves.tolist())) == halves.size
        upd = bf16_state(AdamW(1e-3))
        params = {"p": jnp.ones(shape, f32) * 1.004}
        _, new = precision.apply_updater(
            upd, {"p": jnp.full(shape, 0.3, f32)}, upd.init(params), params,
            0, key)
        for slot in ("m", "v"):
            assert new[slot]["p"].shape == shape
            assert new[slot]["p"].dtype == BF16

    def test_state_that_does_not_mirror_still_rounds_every_leaf(self):
        """A coupled updater's state (a scalar, a list of another shape,
        a counter): one draw per floating leaf, integers left alone."""
        state = {"scale": jnp.asarray(1.004, f32),
                 "hist": [jnp.full((4, 3), -3.013, f32),
                          jnp.full((5,), 0.12307, f32)],
                 "count": jnp.asarray(3, jnp.int32)}
        params = {"w": jnp.ones((6, 2), f32)}
        key = jax.random.PRNGKey(4)
        out = precision.sr_cast_state(state, BF16, key, params)
        assert out["count"].dtype == jnp.int32 and int(out["count"]) == 3
        for leaf in (out["scale"], *out["hist"]):
            assert leaf.dtype == BF16
        blocks, _, elements = sr_counters()
        assert blocks == elements == 1 + 12 + 5
        # each leaf both ways over keys: rounded, not cast
        outs = jax.vmap(lambda k: precision.sr_cast_state(
            state, BF16, k, params))(jax.random.split(key, 64))
        for leaf in (outs["scale"], *outs["hist"]):
            v = np.asarray(leaf.astype(f32))
            assert v.min() < v.max()
        # a slot of another shape than its parameter does not mirror either
        odd = {"m": {"w": jnp.full((3, 2), 1.004, f32)}}
        assert precision.sr_cast_state(
            odd, BF16, key, params)["m"]["w"].dtype == BF16

    def test_a_typed_key_draws_the_raw_key_s_bits(self):
        raw = jax.random.PRNGKey(9)
        typed = jax.random.wrap_key_data(raw, impl="threefry2x32")
        a = precision.threefry_words(raw, (4, 3))
        b = precision.threefry_words(typed, (4, 3))
        assert all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(a, b))
        with pytest.raises(ValueError, match="threefry"):
            precision.threefry_words(jnp.zeros((4,), jnp.uint32), (2,))


# ---------------------------------------------------------------------------
# fused flat-bucket update kernel
# ---------------------------------------------------------------------------

UPDATERS = [("sgd", lambda: Sgd(0.1)),
            # keyword on purpose: the dataclass field order puts the
            # inherited `elementwise` second, so Nesterovs(0.1, 0.9)
            # would bind 0.9 to elementwise, not momentum
            ("nesterovs", lambda: Nesterovs(0.1, momentum=0.9)),
            ("adam", lambda: Adam(1e-3)),
            ("adamw", lambda: AdamW(1e-3))]


class TestFusedKernel:
    @pytest.mark.parametrize("name,mk", UPDATERS)
    @pytest.mark.parametrize("mode", ["xla", "interpret"])
    def test_fp32_bitwise_vs_per_leaf(self, name, mk, mode):
        upd = mk()
        params = small_params()
        grads = small_grads(params)
        state = upd.init(params)
        ref_p, ref_s = upd.apply(grads, state, params, 3)
        plan = Zero1Plan(params, 1)
        fs = plan.flatten_state(state, xp=jnp) if state else state
        nf, ns = pallas_update.fused_apply(
            upd, plan.flatten(params), plan.flatten(grads), fs, 3, None,
            mode=mode)
        got_p = plan.unflatten(nf)
        got_s = ({k: plan.unflatten(v, xp=jnp) for k, v in ns.items()}
                 if state else ns)
        if mode == "xla":
            # the production CPU mode: same expressions through the same
            # compiler — bitwise vs the per-leaf reference
            assert tree_bitwise(ref_p, got_p)
            if state:
                assert tree_bitwise(ref_s, got_s)
        else:
            # kernel modes may fma-contract the mul-add chains (environ-
            # ment-dependent instruction selection) — ≤ a couple ulp,
            # documented in pallas_update
            for a, b in zip(jax.tree.leaves((ref_p, ref_s)),
                            jax.tree.leaves((got_p, got_s))):
                assert float(jnp.max(jnp.abs(a - b))) <= 2.4e-7

    def test_bf16_state_same_bits_across_modes(self):
        """The SR bits are generated OUTSIDE the kernel, so every mode
        consumes identical randomness: params agree to fp32 ulp and the
        bf16 moments to bf16 ulp (exactly when the kernel's fma noise
        does not straddle a 16-bit rounding boundary)."""
        upd = Adam(1e-3)
        upd.state_dtype = "bfloat16"
        params = small_params()
        grads = small_grads(params)
        plan = Zero1Plan(params, 1)
        fs = plan.flatten_state(upd.init(params), xp=jnp)
        key = jax.random.PRNGKey(11)
        (p_x, s_x), (p_i, s_i) = [pallas_update.fused_apply(
            upd, plan.flatten(params), plan.flatten(grads), fs, 0, key,
            mode=m) for m in ("xla", "interpret")]
        for a, b in zip(jax.tree.leaves(p_x), jax.tree.leaves(p_i)):
            assert float(jnp.max(jnp.abs(a - b))) <= 2.4e-7
        for a, b in zip(jax.tree.leaves(s_x), jax.tree.leaves(s_i)):
            assert a.dtype == BF16 and b.dtype == BF16
            d = jnp.abs(a.astype(f32) - b.astype(f32))
            assert float(jnp.max(d)) <= 2.0 ** -8 * (
                float(jnp.max(jnp.abs(a.astype(f32)))) + 1e-6)

    def test_bf16_state_requires_key(self):
        upd = Adam(1e-3)
        upd.state_dtype = "bfloat16"
        params = small_params()
        plan = Zero1Plan(params, 1)
        with pytest.raises(ValueError, match="RNG key"):
            pallas_update.fused_apply(
                upd, plan.flatten(params), plan.flatten(small_grads(params)),
                plan.flatten_state(upd.init(params), xp=jnp), 0, None)

    def test_unsupported_updater_falls_back_ledgered(self):
        from deeplearning4j_tpu.learning.updaters import AdaGrad

        upd = AdaGrad(0.1)     # elementwise, but no fused kernel
        params = small_params()
        grads = small_grads(params)
        plan = Zero1Plan(params, 1)
        fs = plan.flatten_state(upd.init(params), xp=jnp)
        assert not pallas_update.supports_fused(upd)
        ref_p, _ = upd.apply(grads, upd.init(params), params, 0)
        nf, _ = pallas_update.apply_flat_updater(
            upd, plan.flatten(params), plan.flatten(grads), fs, 0, None)
        assert tree_bitwise(ref_p, plan.unflatten(nf))
        assert OpProfiler.get().counter_value(
            "precision/fused_fallbacks") == 1


# ---------------------------------------------------------------------------
# fit-level integration (fused_update knob + bf16 state)
# ---------------------------------------------------------------------------

def mln(updater, fused=False, seed=7):
    b = NeuralNetConfiguration.builder().seed(seed).updater(updater)
    if fused:
        b = b.fused_update()
    conf = (b.list()
            .layer(L.DenseLayer(n_out=24, activation="relu"))
            .layer(L.OutputLayer(n_out=5, activation="softmax",
                                 loss="mcxent"))
            .set_input_type(InputType.feed_forward(12)).build())
    return MultiLayerNetwork(conf).init()


def fit_data(n=48):
    rng = np.random.default_rng(0)
    return DataSet(rng.normal(size=(n, 12)).astype(np.float32),
                   np.eye(5, dtype=np.float32)[rng.integers(0, 5, n)])


class TestFitIntegration:
    def test_sgd_fused_fit_bitwise(self):
        a, b = mln(Sgd(0.1)), mln(Sgd(0.1), fused=True)
        ds = fit_data()
        a.fit(ds, epochs=2, batch_size=16)
        b.fit(ds, epochs=2, batch_size=16)
        assert tree_bitwise(a._params, b._params)

    def test_adam_fused_fit_ulp_bound(self):
        """Documented: inside a full step XLA may fma-contract the flat
        shape differently — Adam drifts ≤ a few ulp, never more."""
        a, b = mln(Adam(1e-3)), mln(Adam(1e-3), fused=True)
        ds = fit_data()
        a.fit(ds, epochs=2, batch_size=16)
        b.fit(ds, epochs=2, batch_size=16)
        for x, y in zip(jax.tree.leaves(a._params),
                        jax.tree.leaves(b._params)):
            assert float(jnp.max(jnp.abs(x - y))) <= 1e-7
        assert tree_bitwise(a._updater_state, b._updater_state)

    @pytest.mark.parametrize("fused", [False, True])
    def test_bf16_state_parity_within_documented_bound(self, fused):
        """learning/precision.py's envelope: bf16 moments + SR track the
        fp32-state run as zero-mean noise, |Δparam| small after a short
        horizon; the state itself halves."""
        u16 = Adam(1e-3)
        u16.state_dtype = "bfloat16"
        a, b = mln(Adam(1e-3), fused=fused), mln(u16, fused=fused)
        ds = fit_data()
        a.fit(ds, epochs=3, batch_size=16)
        b.fit(ds, epochs=3, batch_size=16)
        assert {str(l.dtype) for l in jax.tree.leaves(b._updater_state)} \
            == {"bfloat16"}
        # compounding SR noise wanders chaotically; the bound is the
        # gross-divergence one (the per-step loss envelope is benched)
        for x, y in zip(jax.tree.leaves(a._params),
                        jax.tree.leaves(b._params)):
            assert float(jnp.max(jnp.abs(x - y))) <= \
                0.01 + 0.1 * float(jnp.max(jnp.abs(x)))
        ba = precision.updater_state_bytes(jax.device_get(a._updater_state))
        bb = precision.updater_state_bytes(jax.device_get(b._updater_state))
        assert bb["total"] <= 0.55 * ba["total"]

    def test_trace_stable_one_compile(self):
        prof = OpProfiler.get()
        u = Adam(1e-3)
        u.state_dtype = "bfloat16"
        m = mln(u, fused=True)
        m.fit(fit_data(), epochs=3, batch_size=16)
        assert prof.trace_counts() == {"trace/mln_fit_step": 1}

    def test_non_elementwise_updater_has_nothing_to_fall_back_from(
            self, caplog):
        """On unsharded state ``fused_update`` selects nothing (PR 27):
        a coupled updater is applied leaf by leaf like any other — no
        warning, no ledgered fallback, the same bits. (ZeRO-1 refuses
        it at build.)"""
        import logging

        class Coupled(GradientUpdater):
            elementwise = False

            def __init__(self):
                self.learning_rate = 0.1
                self.state_dtype = None

            def init(self, params):
                return {}

            def apply(self, grads, state, params, iteration):
                return jax.tree.map(lambda p, g: p - 0.1 * g,
                                    params, grads), {}

        with caplog.at_level(logging.WARNING, "deeplearning4j_tpu"):
            m = mln(Coupled(), fused=True)
            m.fit(fit_data(), epochs=1, batch_size=16)
        assert not any("elementwise" in r.message for r in caplog.records)
        assert OpProfiler.get().counter_value(
            "precision/fused_fallbacks") == 0
        ref = mln(Coupled())
        ref.fit(fit_data(), epochs=1, batch_size=16)
        assert tree_bitwise(ref._params, m._params)

    def test_sr_rng_does_not_touch_dropout_stream(self):
        """state_dtype derives SR bits by fold_in tag — the model's
        dropout draws must be identical with and without it. Proven by
        training a dropout model with fp32 state twice, once through a
        builder that ALSO threads the key to apply_updater (any leak
        would shift the dropout stream and change the loss sequence)."""
        def build(sd):
            u = Adam(1e-3)
            u.state_dtype = sd
            conf = (NeuralNetConfiguration.builder().seed(5).updater(u)
                    .list()
                    .layer(L.DenseLayer(n_out=16, activation="relu"))
                    .layer(L.DropoutLayer(rate=0.5))
                    .layer(L.OutputLayer(n_out=5, activation="softmax",
                                         loss="mcxent"))
                    .set_input_type(InputType.feed_forward(12)).build())
            return MultiLayerNetwork(conf).init()

        set_default_seed(42)
        a = build(None)
        a.fit(fit_data(), epochs=1, batch_size=16)
        set_default_seed(42)
        b = build("bfloat16")
        b.fit(fit_data(), epochs=1, batch_size=16)
        # same dropout stream → the two runs differ ONLY by state
        # rounding noise, which stays far below gross divergence
        for x, y in zip(jax.tree.leaves(a._params),
                        jax.tree.leaves(b._params)):
            assert float(jnp.max(jnp.abs(x - y))) <= \
                0.01 + 0.1 * float(jnp.max(jnp.abs(x)))


# ---------------------------------------------------------------------------
# ZeRO-1 compose
# ---------------------------------------------------------------------------

def wrapper_model(state_dtype=None, seed=5):
    u = Adam(learning_rate=0.05)
    u.state_dtype = state_dtype
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(u)
            .activation("tanh").list()
            .layer(L.DenseLayer(n_out=9))
            .layer(L.OutputLayer(n_out=3, loss="mcxent",
                                 activation="softmax"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def wrapper_iter(n=64, batch=16):
    rng = np.random.RandomState(7)
    x = rng.randn(n, 4).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, n)]
    return NDArrayDataSetIterator(x, y, batch_size=batch, shuffle=True,
                                  seed=3)


def run_zero1(model, workers=4, epochs=2, resume_from=None, listeners=()):
    from deeplearning4j_tpu.optimize.listeners import (
        CollectScoresIterationListener)

    set_default_seed(99)
    scores = CollectScoresIterationListener()
    pw = (ParallelWrapper.Builder(model).workers(workers)
          .gradients_accumulator(ReduceScatterAccumulator()).build())
    pw.set_listeners(scores, *listeners)
    pw.fit(wrapper_iter(), epochs=epochs, resume_from=resume_from)
    return [s for _, s in scores.scores], model


class TestDenseReplicasStayIdentical:
    @pytest.mark.parametrize("fused", [False, True])
    def test_bf16_state_draws_are_shared_across_replicas(self, fused):
        """Dense data-parallel applies the FULL update on every replica:
        with bf16 state the stochastic-rounding draws must be the same on
        all of them, or the replicas' moments and params drift apart (a
        per-replica key did exactly that)."""
        model = wrapper_model("bfloat16")
        model.conf.global_conf.fused_update = fused
        set_default_seed(99)
        pw = ParallelWrapper.Builder(model).workers(4).build()
        pw.fit(wrapper_iter(), epochs=2)
        for tree in (model._params, model._updater_state):
            for leaf in jax.tree.leaves(tree):
                copies = [np.asarray(s.data)
                          for s in leaf.addressable_shards]
                assert len(copies) == 4
                assert all(np.array_equal(copies[0], c)
                           for c in copies[1:])


class TestZero1Compose:
    def test_plan_reshard_preserves_bf16_state_bitwise(self):
        """The flat layout is replica-count-independent: bf16 moments
        flattened for 4 shards, densified, and re-flattened for 2 are
        the same bytes."""
        upd = Adam(1e-3)
        upd.state_dtype = "bfloat16"
        params = small_params()
        state = upd.init(params)
        p4, p2 = Zero1Plan(params, 4), Zero1Plan(params, 2)
        flat4 = p4.flatten_state(state, xp=jnp)
        dense = p4.unflatten_state(jax.device_get(flat4))
        flat2 = p2.flatten_state(dense, xp=np)
        dense2 = p2.unflatten_state(flat2)
        assert tree_bitwise(dense, dense2)
        assert {str(np.asarray(l).dtype)
                for l in jax.tree.leaves(dense)} == {"bfloat16"}

    def test_bf16_state_is_sharded_and_half_width(self):
        prof = OpProfiler.get()
        _, m = run_zero1(wrapper_model("bfloat16"), workers=4, epochs=1)
        assert is_flat_state(m._updater_state)
        assert {str(l.dtype) for l in jax.tree.leaves(m._updater_state)} \
            == {"bfloat16"}
        bf16_bytes = prof.counter_value(
            "precision/updater_state_bytes_bfloat16")
        _, m32 = run_zero1(wrapper_model(None), workers=4, epochs=1)
        # the gauges are LIVE state (last fit wins; the stale bf16 gauge
        # zeroes) — so compare the capture against the fp32 run's gauge
        assert prof.counter_value(
            "precision/updater_state_bytes_bfloat16") == 0
        assert bf16_bytes * 2 == prof.counter_value(
            "precision/updater_state_bytes_float32")

    def test_bf16_kill_resume_same_count_exact(self, tmp_path):
        """RNG stream (and so the SR draws) checkpoints with the run: a
        resumed bf16-state ZeRO-1 fit replays the uninterrupted loss
        sequence exactly."""
        from deeplearning4j_tpu.common import faultinject
        from deeplearning4j_tpu.optimize.listeners import (
            CheckpointListener)

        base, _ = run_zero1(wrapper_model("bfloat16"))
        cl = CheckpointListener(str(tmp_path), save_every_n_iterations=3,
                                keep_last=2)
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "train/step", "index": 5, "kind": "crash"}]))
        with pytest.raises(faultinject.SimulatedCrash):
            run_zero1(wrapper_model("bfloat16"), listeners=[cl])
        faultinject.clear_plan()
        cl.close()
        last = CheckpointListener.last_checkpoint(str(tmp_path))
        assert last is not None
        resumed, _ = run_zero1(wrapper_model("bfloat16", seed=17),
                               resume_from=last)
        assert resumed == base

    def test_bf16_reshard_4_to_2_continues(self, tmp_path):
        """The 4→2 compose: a bf16-state checkpoint taken under 4
        workers restores into a 2-worker fit (dense on-disk layout →
        re-flattened for the new count), keeps its dtype, and trains."""
        from deeplearning4j_tpu.common import faultinject
        from deeplearning4j_tpu.optimize.listeners import (
            CheckpointListener)

        cl = CheckpointListener(str(tmp_path), save_every_n_iterations=3,
                                keep_last=2)
        faultinject.set_plan(faultinject.FaultPlan(
            [{"site": "train/step", "index": 5, "kind": "crash"}]))
        with pytest.raises(faultinject.SimulatedCrash):
            run_zero1(wrapper_model("bfloat16"), workers=4, listeners=[cl])
        faultinject.clear_plan()
        cl.close()
        last = CheckpointListener.last_checkpoint(str(tmp_path))
        scores, m = run_zero1(wrapper_model("bfloat16", seed=17), workers=2,
                              resume_from=last)
        assert all(np.isfinite(scores))
        assert {str(l.dtype) for l in jax.tree.leaves(m._updater_state)} \
            == {"bfloat16"}
        for leaf in jax.tree.leaves(m._updater_state):
            assert len(leaf.sharding.device_set) == 2


# ---------------------------------------------------------------------------
# checkpoint state-dtype contract
# ---------------------------------------------------------------------------

class TestCheckpointStateDtype:
    def _fit_ckpt(self, tmp_path, state_dtype):
        from deeplearning4j_tpu.util import checkpoint as ckpt

        u = Adam(1e-3)
        u.state_dtype = state_dtype
        m = mln(u, fused=True)
        m.fit(fit_data(), epochs=1, batch_size=16)
        snap = ckpt.snapshot_training_state(m)
        data = ckpt.serialize_snapshot(snap)
        path = ckpt.commit_checkpoint(str(tmp_path), "t0", data, 2, 3,
                                      state_dtype=snap["state_dtype"])
        return m, snap, path

    def test_roundtrip_preserves_bf16(self, tmp_path):
        from deeplearning4j_tpu.util import checkpoint as ckpt

        m, snap, path = self._fit_ckpt(tmp_path, "bfloat16")
        assert snap["state_dtype"] == "bfloat16"
        assert ckpt.read_manifest(str(tmp_path))[0]["state_dtype"] == \
            "bfloat16"
        u = Adam(1e-3)
        u.state_dtype = "bfloat16"
        m2 = mln(u, fused=True)
        ckpt.restore_training_state(m2, path)
        assert tree_bitwise(m._updater_state, m2._updater_state)
        assert {str(l.dtype) for l in jax.tree.leaves(m2._updater_state)} \
            == {"bfloat16"}

    def test_silent_flip_refused_both_ways(self, tmp_path):
        from deeplearning4j_tpu.util import checkpoint as ckpt

        _, _, path16 = self._fit_ckpt(tmp_path, "bfloat16")
        with pytest.raises(ValueError, match="state dtype mismatch"):
            ckpt.restore_training_state(mln(Adam(1e-3)), path16)
        _, _, path32 = self._fit_ckpt(tmp_path, None)
        u = Adam(1e-3)
        u.state_dtype = "bfloat16"
        with pytest.raises(ValueError, match="state dtype mismatch"):
            ckpt.restore_training_state(mln(u), path32)

    def test_explicit_convert_path(self, tmp_path):
        from deeplearning4j_tpu.util import checkpoint as ckpt

        m, _, path16 = self._fit_ckpt(tmp_path, "bfloat16")
        m2 = mln(Adam(1e-3))
        ckpt.restore_training_state(m2, path16, convert_state_dtype=True)
        assert {str(l.dtype) for l in jax.tree.leaves(m2._updater_state)} \
            == {"float32"}
        # widening bf16→f32 is exact
        assert tree_bitwise(
            jax.tree.map(lambda l: l.astype(f32),
                         jax.device_get(m._updater_state)),
            m2._updater_state)
        # and the converted model trains on
        m2.fit(fit_data(), epochs=1, batch_size=16)


# ---------------------------------------------------------------------------
# fused BN epilogue
# ---------------------------------------------------------------------------

class TestEpilogueKernel:
    def _case(self, shape, C, residual):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=shape), f32)
        args = (jnp.asarray(rng.normal(size=C), f32),
                jnp.asarray(rng.uniform(0.5, 2.0, size=C), f32),
                jnp.asarray(rng.normal(size=C), f32),
                jnp.asarray(rng.normal(size=C), f32))
        res = jnp.asarray(rng.normal(size=shape), f32) if residual else None
        return x, args, res

    @pytest.mark.parametrize("shape,axis", [((2, 256, 7, 7), 1),
                                            ((16, 128), 1)])
    @pytest.mark.parametrize("residual", [False, True])
    def test_parity_vs_dense_ops(self, shape, axis, residual):
        x, (mean, var, gamma, beta), res = self._case(shape, shape[1],
                                                      residual)
        dense = get_op("batchnorm").fn(x, mean, var, gamma, beta,
                                       epsilon=1e-5, axis=axis)
        if res is not None:
            dense = dense + res
        dense = jnp.maximum(dense, 0)
        for mode in ("xla", "interpret"):
            out = pallas_epilogue.bn_act(x, mean, var, gamma, beta,
                                         epsilon=1e-5, axis=axis,
                                         act="relu", residual=res,
                                         mode=mode)
            assert out is not None and out.shape == x.shape
            # reassociated affine: tolerance-bounded, never bitwise
            assert np.allclose(np.asarray(out), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)

    def test_cross_mode_ulp_bound(self):
        x, (mean, var, gamma, beta), _ = self._case((4, 128, 5, 5), 128,
                                                    False)
        a = pallas_epilogue.bn_act(x, mean, var, gamma, beta, axis=1,
                                   act="relu", mode="xla")
        b = pallas_epilogue.bn_act(x, mean, var, gamma, beta, axis=1,
                                   act="relu", mode="interpret")
        scale = float(jnp.max(jnp.abs(a))) + 1.0
        assert float(jnp.max(jnp.abs(a - b))) <= 2 ** -22 * scale

    def test_shape_gate_refusals_ledgered(self):
        prof = OpProfiler.get()
        x, (mean, var, gamma, beta), _ = self._case((2, 65, 4, 4), 65,
                                                    False)
        assert pallas_epilogue.bn_act(x, mean, var, gamma, beta, axis=1,
                                      act="relu") is None
        x2, (m2, v2, g2, b2), _ = self._case((2, 128, 4, 4), 128, False)
        assert pallas_epilogue.bn_act(x2, m2, v2, g2, b2, axis=1,
                                      act="tanh") is None
        assert prof.counter_value("precision/epilogue_fallbacks") == 2

    def test_no_gamma_beta(self):
        x, (mean, var, _, _), _ = self._case((8, 128), 128, False)
        out = pallas_epilogue.bn_act(x, mean, var, None, None, axis=1,
                                     act="identity", mode="xla")
        dense = get_op("batchnorm").fn(x, mean, var, None, None, axis=1)
        assert np.allclose(np.asarray(out), np.asarray(dense),
                           rtol=1e-5, atol=1e-5)


def residual_graph(fused, channels=128, seed=3):
    b = NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.01))
    if fused:
        b = b.fused_epilogue()
    gb = ComputationGraphConfiguration.graph_builder(b).add_inputs("in")
    gb.add_layer("c1", L.ConvolutionLayer(
        n_out=channels, kernel_size=(3, 3), padding=(1, 1), has_bias=False,
        activation="identity"), "in")
    gb.add_layer("bn3", L.BatchNormalization(activation="identity"), "c1")
    gb.add_layer("sc", L.ConvolutionLayer(
        n_out=channels, kernel_size=(1, 1), has_bias=False,
        activation="identity"), "in")
    gb.add_layer("scbn", L.BatchNormalization(activation="identity"), "sc")
    gb.add_vertex("add", ElementWiseVertex(op="add"), "bn3", "scbn")
    gb.add_layer("relu", L.ActivationLayer(activation="relu"), "add")
    gb.add_layer("out", L.OutputLayer(n_out=5, activation="softmax",
                                      loss="mcxent"), "relu")
    gb.set_outputs("out")
    gb.set_input_types(InputType.convolutional(8, 8, 4))
    return ComputationGraph(gb.build()).init()


def graph_data(n=8):
    rng = np.random.default_rng(1)
    return DataSet(rng.normal(size=(n, 4, 8, 8)).astype(np.float32),
                   np.eye(5, dtype=np.float32)[rng.integers(0, 5, n)])


class TestEpilogueGraphFusion:
    def test_plan_matches_residual_chain(self):
        g = residual_graph(True)
        plan = g._epilogue_fusion_plan()
        assert plan == {"bn": {"bn3"}, "add": {"add": ("bn3", "scbn")},
                        "act": {"relu": ("bn3", "add")}}
        assert residual_graph(False)._epilogue_fusion_plan() is None

    def test_training_is_untouched_bitwise(self):
        a, b = residual_graph(False), residual_graph(True)
        ds = graph_data()
        a.fit(ds, epochs=2, batch_size=4)
        b.fit(ds, epochs=2, batch_size=4)
        assert tree_bitwise(a._params, b._params)
        assert tree_bitwise(a._states, b._states)

    def test_inference_parity_with_trained_stats(self):
        a, b = residual_graph(False), residual_graph(True)
        ds = graph_data()
        a.fit(ds, epochs=2, batch_size=4)
        b.fit(ds, epochs=2, batch_size=4)
        x = np.random.default_rng(0).normal(
            size=(2, 4, 8, 8)).astype(np.float32)
        oa, ob = np.asarray(a.output(x)[0]), np.asarray(b.output(x)[0])
        assert np.allclose(oa, ob, rtol=1e-5, atol=1e-5)
        assert OpProfiler.get().counter_value(
            "precision/epilogue_residual_hits") >= 1

    def test_shape_gate_falls_back_to_dense_replay_bitwise(self):
        """channels=48 refuses the kernel: the fused-plan replay path
        must reproduce the unfused graph EXACTLY (same ops, same rng
        stream)."""
        a, b = residual_graph(False, channels=48), \
            residual_graph(True, channels=48)
        ds = graph_data()
        a.fit(ds, epochs=1, batch_size=4)
        b.fit(ds, epochs=1, batch_size=4)
        x = np.random.default_rng(0).normal(
            size=(2, 4, 8, 8)).astype(np.float32)
        oa, ob = np.asarray(a.output(x)[0]), np.asarray(b.output(x)[0])
        assert np.array_equal(oa, ob)

    def test_per_layer_opt_out_respected_in_chain(self):
        """A BN built with fused_epilogue=False stays dense even when
        the global knob is on: the plan must not defer it (the chain may
        still fuse through the OTHER add input, which remains opted in)."""
        g = residual_graph(True)
        g.conf.nodes["bn3"].layer.fused_epilogue = False
        plan = g._epilogue_fusion_plan()
        assert plan["bn"] == {"scbn"}    # bn3 never deferred
        g.conf.nodes["scbn"].layer.fused_epilogue = False
        assert g._epilogue_fusion_plan() is None

    def test_self_residual_add_left_dense(self):
        """relu(bn(x) + bn(x)) — the same node as both add inputs must
        not enter the plan (deferring the BN would starve the 'other'
        operand)."""
        b = NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.01))
        b = b.fused_epilogue()
        gb = ComputationGraphConfiguration.graph_builder(b).add_inputs("in")
        gb.add_layer("c1", L.ConvolutionLayer(
            n_out=128, kernel_size=(1, 1), has_bias=False,
            activation="identity"), "in")
        gb.add_layer("bn3", L.BatchNormalization(activation="identity"),
                     "c1")
        gb.add_vertex("add", ElementWiseVertex(op="add"), "bn3", "bn3")
        gb.add_layer("relu", L.ActivationLayer(activation="relu"), "add")
        gb.add_layer("out", L.OutputLayer(n_out=5, activation="softmax",
                                          loss="mcxent"), "relu")
        gb.set_outputs("out")
        gb.set_input_types(InputType.convolutional(4, 4, 3))
        g = ComputationGraph(gb.build()).init()
        assert g._epilogue_fusion_plan() is None
        x = np.random.default_rng(0).normal(
            size=(2, 3, 4, 4)).astype(np.float32)
        assert np.isfinite(np.asarray(g.output(x)[0])).all()

    def test_stateless_updater_skips_sr_draws(self):
        """Sgd + state_dtype has no moments to round: the fused path
        must not pay threefry for unused bits."""
        prof = OpProfiler.get()
        upd = Sgd(0.1)
        upd.state_dtype = "bfloat16"
        params = small_params()
        plan = Zero1Plan(params, 1)
        pallas_update.fused_apply(
            upd, plan.flatten(params), plan.flatten(small_grads(params)),
            {}, 0, jax.random.PRNGKey(0), mode="xla")
        assert prof.counter_value("precision/sr_draws") == 0

    def test_resnet50_blocks_all_fuse(self):
        from deeplearning4j_tpu.models import ResNet50

        m = ResNet50(num_classes=10, image_size=32).init()
        # post-build enablement: flip the global knob AND re-cascade onto
        # the BN layers (the builder's .fused_epilogue() does this at
        # build time; the zoo model was built with the default off)
        m.conf.global_conf.fused_epilogue = True
        for name in m.conf.order:
            node = m.conf.nodes[name]
            if node.kind == "layer" and isinstance(
                    node.layer, L.BatchNormalization):
                node.layer.fused_epilogue = True
        plan = m._epilogue_fusion_plan()
        assert plan is not None and len(plan["act"]) == 16


# ---------------------------------------------------------------------------
# ledger / health / shared cast
# ---------------------------------------------------------------------------

class TestLedger:
    def test_precision_stats_populated(self):
        """The bucket kernel's ledger, where it still runs: ZeRO-1."""
        prof = OpProfiler.get()
        run_zero1(wrapper_model("bfloat16"), workers=2, epochs=1)
        stats = prof.precision_stats()
        assert stats["fused_hits"] >= 1
        assert stats["sr_draws"] > 0
        assert stats["updater_state_bytes_bfloat16"] > 0
        assert stats["updater_state_bytes_total"] == \
            stats["updater_state_bytes_bfloat16"]

    def test_health_endpoint_has_precision_section(self):
        from deeplearning4j_tpu.ui.server import UIServer

        run_zero1(wrapper_model("bfloat16"), workers=2, epochs=1)
        ui = UIServer()
        h = ui.health()
        assert "precision" in h and h["precision"]["fused_hits"] >= 1

    def test_stale_dtype_gauge_zeroed(self):
        prof = OpProfiler.get()
        state32 = {"m": np.zeros((10,), np.float32)}
        precision.note_state_bytes(state32)
        assert prof.counter_value(
            "precision/updater_state_bytes_float32") == 40
        state16 = {"m": np.zeros(
            (10,), np.asarray(jnp.zeros(1, BF16)).dtype)}
        precision.note_state_bytes(state16)
        assert prof.counter_value(
            "precision/updater_state_bytes_float32") == 0
        assert prof.counter_value(
            "precision/updater_state_bytes_bfloat16") == 20

    def test_serving_cast_is_the_shared_helper(self):
        from deeplearning4j_tpu.parallel import serving

        assert serving._cast_floating is precision.cast_floating
