"""The routed-expert ops (``ops/moe.py``), the four layers that
``models.Lfm2Moe`` wires (``RMSNormLayer``, ``ShortConvLayer``,
``RotaryAttentionLayer``, ``RoutedExpertsLayer``) and the model's first
steps against the plain reference of ``benchmarks/configs/lfm2_moe.py``,
which imports nothing of the package.

Tolerances. float32 against float32 at ``highest``: both sides compute the
same sums in another order (sorted rows against every expert on every token,
online softmax against one softmax), so they differ by rounding alone: 1e-5
of the largest magnitude of what is compared (2e-5 for gradients). The
three-step ``fit`` comparison uses the benchmark's own gaps
(``benchmarks/compare.py``): 1e-4 in float32, and in bfloat16 limits that a
float8 cast of the reference's operands fails.
"""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deeplearning4j_tpu.common.profiler import OpProfiler
from deeplearning4j_tpu.models import Lfm2Moe
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.inputs import RNNInput
from deeplearning4j_tpu.ops import moe

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
for p in (BENCH, os.path.join(BENCH, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

import compare  # noqa: E402  (benchmarks/compare.py)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CONF = _load(os.path.join(BENCH, "configs", "lfm2_moe.py"), "bench_conf_lfm2")
CFG = json.load(open(os.path.join(BENCH, "configs", "lfm2_moe.json")))
SIZES = CONF.sizes_of(CFG, True)    # d=64, heads 4/2 of 16, experts 32 wide
REF = CONF.ref_ops(SIZES, compare.EXACT)
D, T, B = SIZES["hidden_size"], 32, 2
E, K, FF = (SIZES["router_width"], SIZES["num_experts_per_tok"],
            SIZES["moe_intermediate_size"])
F32 = jnp.float32
BIAS = jnp.asarray(SIZES["expert_bias"], F32)


def _close(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-30), \
        np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)


def _tree_close(a, b, tol=1e-5):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        _close(x, y, tol)


def _layer(layer, wide=15.0, t=T):
    """The layer with its input type set and its matrices drawn wide enough
    (std 0.3) that every term of its output matters."""
    layer.set_input_type(RNNInput(D, t))
    params = layer.init_params(jax.random.PRNGKey(3))
    return layer, jax.tree.map(
        lambda a: a * wide if a.ndim >= 2 and a.shape[-2] > 8 else a, params)


def _x(seed=0, t=T):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, t, D), F32)


def _routed(first=0, held=SIZES["num_experts"], bias=None, t=T):
    layer = L.RoutedExpertsLayer(
        n_routed=E, n_experts=held, first_expert=first, n_ff=FF, top_k=K,
        selection_bias=SIZES["expert_bias"] if bias is None else bias)
    return _layer(layer, t=t)


def _ref_routed(p, x, first, held):
    xt = x.reshape(-1, D)
    experts, weights, _ = REF.route(p, BIAS, xt)
    return REF.experts_of(p, xt, experts, weights,
                          held=(first, first + held)).reshape(x.shape)


def _case(name):
    """(layer, params, the reference as f(params, x))."""
    if name == "rms_norm":
        layer, p = _layer(L.RMSNormLayer(eps=SIZES["norm_eps"]))
        p = {"gain": 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(5),
                                                   (D,), F32)}
        return layer, p, lambda p, x: REF.rms(p["gain"], x)
    if name == "short_conv":
        layer, p = _layer(L.ShortConvLayer(taps=SIZES["conv_L_cache"]))
        p["conv_w"] = p["conv_w"] * 15.0
        return layer, p, REF.short_conv
    if name == "rotary_attention":
        layer, p = _layer(L.RotaryAttentionLayer(
            n_heads=SIZES["num_attention_heads"],
            n_kv_heads=SIZES["num_key_value_heads"],
            head_dim=SIZES["head_dim"], rope_theta=SIZES["rope_theta"],
            eps=SIZES["norm_eps"]))
        for i, g in enumerate(("q_norm", "k_norm")):
            p[g] = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(6 + i),
                                                 p[g].shape, F32)
        return layer, p, REF.attention
    layer, p = _routed()
    return layer, p, lambda p, x: _ref_routed(p, x, 0, SIZES["num_experts"])


@pytest.mark.parametrize("name", ["rms_norm", "short_conv",
                                  "rotary_attention", "routed_experts"])
def test_layer_matches_reference(name):
    """Forward, and the gradients of a random projection of the output with
    respect to every parameter and the input."""
    layer, params, ref = _case(name)
    x = _x()

    def prog(p, x):
        return layer.apply(p, x, layer.init_state(), True, None)[0]

    _close(jax.jit(prog)(params, x), jax.jit(ref)(params, x))
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape, F32)
    scalar = lambda f: lambda p, x: jnp.sum(f(p, x) * w)     # noqa: E731
    _tree_close(jax.jit(jax.grad(scalar(prog), (0, 1)))(params, x),
                jax.jit(jax.grad(scalar(ref), (0, 1)))(params, x), 2e-5)


def test_short_conv_first_positions_see_zeros_before_the_sequence():
    """c_0 = w_2 v_0 and c_1 = w_1 v_0 + w_2 v_1: nothing wraps around."""
    layer, p, _ = _case("short_conv")
    x = _x(1)
    y = layer.apply(p, x, {}, True, None)[0]
    b, c, u = jnp.split(jnp.dot(x, p["W_in"], precision="highest"), 3, -1)
    v, w = b * u, p["conv_w"]
    conv = jnp.stack([w[2] * v[:, 0], w[1] * v[:, 0] + w[2] * v[:, 1]], 1)
    _close(y[:, :2], jnp.dot(c[:, :2] * conv, p["W_out"],
                             precision="highest"))
    # and the output at t does not move when a later input does
    y2 = layer.apply(p, x.at[:, 5:].add(1.0), {}, True, None)[0]
    _close(y2[:, :5], y[:, :5])


def test_rotary_embedding_matches_reference_and_keeps_norms():
    x = jax.random.normal(jax.random.PRNGKey(2), (B, 4, T, 16), F32)
    got = moe.rotary_embedding(x, jnp.arange(T), theta=1e6)
    _close(got, REF.rotary(x, 1e6))
    _close(jnp.linalg.norm(got, axis=-1), jnp.linalg.norm(x, axis=-1))
    _close(got[:, :, 0], x[:, :, 0])            # position 0: no rotation
    # the score of a query and a key depends on their distance alone
    q, k = x[0, 0, :1], x[0, 1, :1]
    rot = lambda a, pos: moe.rotary_embedding(      # noqa: E731
        a, jnp.asarray([pos]), theta=1e6)
    _close(jnp.sum(rot(q, 7) * rot(k, 3)), jnp.sum(rot(q, 24) * rot(k, 20)),
           1e-4)


def test_route_topk_matches_reference():
    x = _x(3).reshape(-1, D)
    wg = jax.random.normal(jax.random.PRNGKey(4), (D, E), F32) * 0.3
    experts, weights, load = moe.route_topk(x, wg, BIAS, K, scale=1.0)
    r_experts, r_weights, r_load = REF.route({"Wg": wg}, BIAS, x)
    assert np.array_equal(np.asarray(experts), np.asarray(r_experts))
    _close(weights, r_weights)
    assert np.array_equal(np.asarray(load), np.asarray(r_load))
    assert float(load.sum()) == x.shape[0] * K
    # the bias selects and is not in the weight: a large bias on expert 5
    # puts it in every selection and leaves its weight a plain score's share
    big = jnp.zeros((E,), F32).at[5].set(10.0)
    e2, w2, l2 = moe.route_topk(x, wg, big, K)
    assert float(l2[5]) == x.shape[0]
    s = jax.nn.sigmoid(jnp.dot(x, wg, precision="highest"))
    picked = jnp.take_along_axis(s, e2, -1)
    _close(w2, picked / (picked.sum(-1, keepdims=True) + 1e-6))
    g = jax.grad(lambda wg: jnp.sum(moe.route_topk(x, wg, BIAS, K)[1] ** 2))(wg)
    g_ref = jax.grad(lambda wg: jnp.sum(REF.route({"Wg": wg}, BIAS, x)[1] ** 2))(wg)
    _close(g, g_ref, 2e-5)


def _loop_gmm(x, w, sizes):
    out, r = np.zeros((x.shape[0], w.shape[2]), np.float64), 0
    for g, n in enumerate(sizes):
        out[r:r + n] = np.asarray(x[r:r + n], np.float64) @ np.asarray(
            w[g], np.float64)
        r += n
    return out


GROUPS = {
    "uneven": [10, 0, 33, 7, 20],           # an empty group, shared tiles
    "one_group_holds_all": [0, 0, 96, 0],
    "total_below_the_buffer": [5, 3],
    "nothing_routed": [0, 0, 0],
    "tile_aligned": [16] * 6,
    "single_rows": [1] * 8,
}


@pytest.mark.parametrize("case", sorted(GROUPS))
def test_grouped_matmul_kernel_ragged_dot_and_loop_agree(case):
    """The Pallas kernels in interpret mode, ``lax.ragged_dot`` and a loop
    over the groups: forward, input gradient and weight gradient."""
    sizes = GROUPS[case]
    rng = np.random.RandomState(len(sizes))
    m, k, n = 96, 128, 256
    x = jnp.asarray(rng.randn(m, k), F32)
    w = jnp.asarray(rng.randn(len(sizes), k, n) * 0.1, F32)
    c = jnp.asarray(rng.randn(m, n), F32)
    gs = jnp.asarray(sizes, jnp.int32)
    before = OpProfiler.get().counter_value("moe/gmm_kernel")
    kern = lambda x, w: moe.grouped_matmul(          # noqa: E731
        x, w, gs, row_tile=16, interpret=True)
    plain = lambda x, w: lax.ragged_dot(x, w, gs)    # noqa: E731
    y = kern(x, w)
    assert OpProfiler.get().counter_value("moe/gmm_kernel") == before + 1
    _close(y, _loop_gmm(x, w, sizes))
    _close(y, plain(x, w))
    assert not np.asarray(y[sum(sizes):]).any()     # rows of no group: zeros
    gk = jax.grad(lambda x, w: jnp.sum(kern(x, w) * c), (0, 1))(x, w)
    gp = jax.grad(lambda x, w: jnp.sum(plain(x, w) * c), (0, 1))(x, w)
    _tree_close(gk, gp, 2e-5)
    empty = [g for g, s in enumerate(sizes) if s == 0]
    assert not np.asarray(gk[1])[empty].any()
    assert not np.asarray(gk[0][sum(sizes):]).any()


def test_grouped_matmul_takes_the_xla_path_off_the_tiling():
    """Widths off the 128-lane tiling, and the CPU by default: counted as a
    fallback, same numbers."""
    prof = OpProfiler.get()
    before = prof.counter_value("moe/gmm_fallback")
    x = jnp.ones((8, 24), F32)
    w = jnp.ones((2, 24, 40), F32)
    y = moe.grouped_matmul(x, w, jnp.asarray([3, 2]), interpret=True)
    assert prof.counter_value("moe/gmm_fallback") == before + 1
    assert not moe.supports_gmm_kernel(24, 40, 4)
    assert moe.supports_gmm_kernel(2048, 3072, 2)
    _close(y, np.concatenate([np.full((5, 40), 24.0), np.zeros((3, 40))]))
    moe.grouped_matmul(jnp.ones((8, 128), F32), jnp.ones((2, 128, 128), F32),
                       jnp.asarray([3, 2]))
    assert prof.counter_value("moe/gmm_fallback") == before + 2
    assert set(prof.moe_stats()) >= {"gmm_fallback", "gmm_kernel"}


# --- the gated MLP as one op (``ops.moe.grouped_gated_mlp``) ---------------------

GATED_GROUPS = {
    **GROUPS,
    "empty_group_first": [0, 40, 30],
    "empty_group_in_the_middle": [30, 0, 0, 40],
    "empty_group_last": [40, 30, 0],
    "three_groups_share_a_tile": [3, 4, 5, 60],
    "total_is_the_buffer": [50, 46],
    "total_off_the_row_tile": [20, 17],
}


def _loop_gated(rows, w1, w2, sizes):
    """Group by group, no kernel and no ragged product: the live rows only."""
    ff, outs, r = w2.shape[1], [], 0
    for g, n in enumerate(sizes):
        h = jnp.dot(rows[r:r + n], w1[g], precision="highest")
        outs.append(jnp.dot(jax.nn.silu(h[:, :ff]) * h[:, ff:], w2[g],
                            precision="highest"))
        r += n
    return jnp.concatenate(outs)


@pytest.mark.parametrize("case", sorted(GATED_GROUPS))
def test_grouped_gated_mlp_kernels_match_a_loop_over_the_groups(case):
    """The six kernels in interpret mode against a per-group loop: the
    forward on the live rows, the gradients to ``rows`` (live rows), ``w1``
    and ``w2`` (an empty group's are zero). The rows beyond the total are
    not defined and are not looked at (interpret mode leaves them NaN)."""
    sizes = GATED_GROUPS[case]
    rng = np.random.RandomState(len(sizes))
    m, d, ff, total = 96, 128, 128, sum(sizes)
    rows = jnp.asarray(rng.randn(m, d), F32)
    w1 = jnp.asarray(rng.randn(len(sizes), d, 2 * ff) * 0.1, F32)
    w2 = jnp.asarray(rng.randn(len(sizes), ff, d) * 0.1, F32)
    c = jnp.asarray(rng.randn(m, d), F32)
    gs = jnp.asarray(sizes, jnp.int32)
    kern = lambda r, a, b: moe.grouped_gated_mlp(       # noqa: E731
        r, a, b, gs, row_tile=16, interpret=True)[:total]
    loop = lambda r, a, b: _loop_gated(r, a, b, sizes)  # noqa: E731
    assert kern(rows, w1, w2).shape == (total, d)
    gk = jax.grad(lambda *a: jnp.sum(kern(*a) * c[:total]), (0, 1, 2))(
        rows, w1, w2)
    empty = [g for g, n in enumerate(sizes) if n == 0]
    assert not np.asarray(gk[1])[empty].any()
    assert not np.asarray(gk[2])[empty].any()
    if not total:
        return
    _close(kern(rows, w1, w2), loop(rows, w1, w2))
    gl = jax.grad(lambda *a: jnp.sum(loop(*a) * c[:total]), (0, 1, 2))(
        rows, w1, w2)
    _close(gk[0][:total], gl[0][:total], 2e-5)
    _tree_close(gk[1:], gl[1:], 2e-5)


def test_grouped_gated_mlp_counts_its_path_and_its_two_products():
    """Once a call site as the program is traced: ``moe/gated_kernel`` or
    ``moe/gated_fallback``, and the two grouped products it replaces still
    count as ``moe/gmm_kernel`` / ``moe/gmm_fallback``, so the benchmark's
    ``*_kernel_fallbacks`` readers find their counters."""
    prof = OpProfiler.get()
    names = ("gated_kernel", "gated_fallback", "gmm_kernel", "gmm_fallback")
    rows, gs = jnp.ones((32, 128), F32), jnp.asarray([20, 5])
    w1, w2 = jnp.ones((2, 128, 256), F32), jnp.ones((2, 128, 128), F32)

    def bumped(fn, *args):
        before = prof.moe_stats()
        jitted = jax.jit(fn)
        jitted(*args), jitted(*args)        # traced once, run twice
        return tuple(prof.moe_stats().get(n, 0) - before.get(n, 0)
                     for n in names)

    assert bumped(lambda *a: moe.grouped_gated_mlp(
        *a, gs, interpret=True), rows, w1, w2) == (1, 0, 2, 0)
    # the CPU's default, and widths off the 128-lane tiling anywhere
    assert bumped(lambda *a: moe.grouped_gated_mlp(*a, gs),
                  rows, w1, w2) == (0, 1, 0, 2)
    assert bumped(lambda *a: moe.grouped_gated_mlp(*a, gs, interpret=True),
                  rows[:, :24], w1[:, :24, :80], w2[:, :40, :24]) \
        == (0, 1, 0, 2)
    assert not moe.supports_gated_kernel(24, 40, 4)
    assert moe.supports_gated_kernel(2048, 1536, 2)
    assert moe.supports_gated_kernel(2048, 768, 2)
    # a second matrix too large for one block: the two products apart
    assert moe.supports_gmm_kernel(4096, 4096, 2)
    assert not moe.supports_gated_kernel(4096, 4096, 2)


WIDE, WIDE_T = 128, 128      # widths on the 128-lane tiling: the kernel path


def _wide_routed():
    """Four held experts of sixteen, top-2, 256 tokens: a buffer of 512 rows
    (no other axis here is 512 long) of which the held take about a
    quarter."""
    layer = L.RoutedExpertsLayer(n_routed=16, n_experts=4, first_expert=2,
                                 n_ff=WIDE, top_k=2)
    layer.set_input_type(RNNInput(WIDE, WIDE_T))
    p = jax.tree.map(lambda a: a * 5.0,
                     layer.init_params(jax.random.PRNGKey(3), F32))
    x = jax.random.normal(jax.random.PRNGKey(8), (B, WIDE_T, WIDE), F32)
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape, F32)
    loss = lambda p, x: jnp.sum(layer.apply(        # noqa: E731
        p, x, layer.init_state(), True, None)[0] * w)
    return layer, p, x, loss


@pytest.fixture
def kernel_path(monkeypatch):
    """The layer on its kernel path here: the op in interpret mode."""
    import functools

    from deeplearning4j_tpu.nn.conf import layers_seq

    monkeypatch.setattr(layers_seq, "grouped_gated_mlp", functools.partial(
        moe.grouped_gated_mlp, interpret=True))


def test_routed_layer_on_the_kernels_reads_no_row_beyond_the_total(
        monkeypatch):
    """The layer's output and every gradient on the kernel path equal the
    fallback path's with the buffer's undefined rows POISONED: every array
    a ``moe_gmm`` kernel hands back (the first product, the result, the
    first product's cotangent, the buffer's cotangent) has its rows from
    the routed total on set to NaN, the boundary tile's too. Nothing reads
    them: the next kernel masks by its group's rows, the layer gathers by a
    live pair's row."""
    import functools

    from deeplearning4j_tpu.nn.conf import layers_seq

    layer, p, x, loss = _wide_routed()
    prof = OpProfiler.get()
    ref = jax.jit(jax.value_and_grad(loss, (0, 1)))(p, x)   # the CPU's path
    assert prof.counter_value("moe/gated_fallback") >= 1
    real, poisoned = moe._gmm_pallas, []

    def poison(x_, w_, items, *a, **kw):
        y = real(x_, w_, items, *a, **kw)
        poisoned.append(y.shape)
        return jnp.where(jnp.arange(y.shape[0])[:, None] < items[3][-1], y,
                         jnp.nan)

    monkeypatch.setattr(moe, "_gmm_pallas", poison)
    monkeypatch.setattr(layers_seq, "grouped_gated_mlp", functools.partial(
        moe.grouped_gated_mlp, interpret=True))
    before = prof.counter_value("moe/gated_kernel")
    got = jax.jit(jax.value_and_grad(loss, (0, 1)))(p, x)
    assert prof.counter_value("moe/gated_kernel") == before + 1
    assert sorted(poisoned) == [(512, 128)] * 2 + [(512, 256)] * 2
    assert np.isfinite(np.asarray(got[0]))
    _close(got[0], ref[0])
    _tree_close(got[1], ref[1], 2e-5)
    # the held experts took under a half of the buffer: the rest was NaN
    _, st = layer.apply(p, x, layer.init_state(), True, None)
    assert 0 < float(st["expert_load"][2:6].sum()) < 256


def _eqns(jaxpr, outer=""):
    """Every equation with its whole name stack, a Pallas call's body left
    out (what runs inside a kernel is a tile's, not the buffer's)."""
    for e in jaxpr.eqns:
        stack = outer + "/" + str(e.source_info.name_stack)
        yield e, stack
        if e.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from _eqns(sub, stack)


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "gradient"])
def test_no_buffer_sized_pass_between_the_dispatch_and_the_combine(
        kernel_path, grad):
    """On the kernel path the scope ``moe_experts`` holds the kernels, the
    work items' arithmetic and the clear of an empty group's gradient: no
    equation but a ``pallas_call`` takes or gives an array with the
    buffer's 512 rows — no ``select_n``, ``mul`` or ``logistic`` over it
    comes back with a later edit without failing here."""
    layer, p, x, loss = _wide_routed()
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)) if grad else loss)(p, x)
    seen = {"pallas_call": 0}
    for e, stack in _eqns(jaxpr.jaxpr):
        if "moe_experts" not in stack:
            continue
        rows = [v.aval.shape for v in (*e.invars, *e.outvars)
                if getattr(v.aval, "shape", ()) and v.aval.shape[0] == 512]
        if e.primitive.name == "pallas_call":
            seen["pallas_call"] += 1
            assert rows and "moe_gmm" in stack
        elif jax.core.jaxprs_in_params(e.params):
            continue        # a call: its body is walked
        else:
            assert not rows, (e.primitive.name, stack, rows)
    assert seen["pallas_call"] == (6 if grad else 2)
    # the reader is not blind: the other scopes do run over the buffer
    assert any("moe_combine" in stack and e.primitive.name == "gather"
               and any(getattr(v.aval, "shape", ())[:1] == (512,)
                       for v in e.invars)
               for e, stack in _eqns(jaxpr.jaxpr))


@pytest.mark.parametrize("t", [T, 256])
def test_routed_experts_dropless_when_every_token_goes_to_the_held_experts(t):
    """A bias that sends all four selections of every token to the eight
    held experts: the dispatch buffer (k x tokens rows, the worst case, one
    a layer) is full, no row is left out, and the result is still the
    reference's; so are the gradients, there and under the file's wave,
    which fills an eighth of it."""
    bias = [10.0 if e < 8 else 0.0 for e in range(E)]
    layer, p = _routed(bias=bias, t=t)
    x = _x(4, t)
    before = OpProfiler.get().counter_value("moe/dispatch_rows")
    run = jax.jit(lambda p, x: layer.apply(p, x, layer.init_state(), True,
                                           None))
    y, st = run(p, x)
    buffers = OpProfiler.get().counter_value("moe/dispatch_rows") - before
    assert buffers == -(-B * t * K // moe.GMM_ROW_TILE) * moe.GMM_ROW_TILE
    load = np.asarray(st["expert_load"])
    assert load[:8].sum() == B * t * K and not load[8:].any()
    def ref(p, x, bias):
        xt = x.reshape(-1, D)
        experts, weights, _ = REF.route(p, jnp.asarray(bias, F32), xt)
        return REF.experts_of(p, xt, experts, weights,
                              held=(0, 8)).reshape(x.shape)

    _close(y, ref(p, x, bias))
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape, F32)
    for b_ in (bias, SIZES["expert_bias"]):     # a full buffer, an eighth
        lay, _ = _routed(bias=b_, t=t)
        prog = lambda p, x: jnp.sum(lay.apply(      # noqa: E731
            p, x, lay.init_state(), True, None)[0] * w)
        _tree_close(
            jax.jit(jax.grad(prog, (0, 1)))(p, x),
            jax.jit(jax.grad(lambda p, x: jnp.sum(ref(p, x, b_) * w),
                             (0, 1)))(p, x), 2e-5)
    # a token that selects no held expert gets zero
    none, p2 = _routed(first=8, bias=bias, t=t)
    y2, st2 = none.apply(p2, x, none.init_state(), True, None)
    assert not np.asarray(y2).any()
    assert np.asarray(st2["expert_load"])[:8].sum() == B * t * K


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The share test: eight layers that hold experts 8c..8c+7 of the same
    64, behind the same router, give outputs that add up to the uncut
    reference layer's, and every share counts the same ``expert_load``."""
    whole, p = _routed(held=E)
    x = _x(5)
    xt = x.reshape(-1, D)
    experts, weights, load = REF.route(p, BIAS, xt)
    uncut = REF.experts_of(p, xt, experts, weights, held=(0, E))
    total, loads = 0.0, []
    for c in range(E // 8):
        share, _ = _routed(first=8 * c, held=8)
        ps = {"Wg": p["Wg"], "W1": p["W1"][8 * c:8 * c + 8],
              "W2": p["W2"][8 * c:8 * c + 8]}
        y, st = share.apply(ps, x, share.init_state(), True, None)
        total = total + y
        loads.append(np.asarray(st["expert_load"]))
    _close(total.reshape(-1, D), uncut)
    assert all(np.array_equal(l, np.asarray(load)) for l in loads)
    y, _ = whole.apply(p, x, whole.init_state(), True, None)
    _close(y, total)


# --- the share test at the DeepSeek-V3 family's form ----------------------------

JOYAI = _load(os.path.join(BENCH, "configs", "joyai_llm_flash.py"),
              "bench_conf_joyai_shares")
JOYAI_SIZES = JOYAI.sizes_of(json.load(open(os.path.join(
    BENCH, "configs", "joyai_llm_flash.json"))), True)


def test_the_sixteen_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """``joyai_llm_flash``'s cut: sixteen layers that hold experts
    16c..16c+15 of the same 256 behind the same router (top-8, scale 2.5,
    epsilon 1e-20) give routed parts that, with the shared expert — which
    every chip computes alike — counted ONCE, add up to the uncut reference
    layer's output; and every share counts the same ``expert_load``."""
    sz = JOYAI_SIZES
    ref = JOYAI.ref_ops(sz, compare.EXACT)
    e, n, ff = sz["router_width"], sz["n_routed_experts"], \
        sz["moe_intermediate_size"]
    assert (e, n, sz["num_experts_per_tok"], sz["hidden_size"]) == (
        256, 16, 8, D)
    bias = jnp.asarray(sz["expert_bias"], F32)

    def routed(first, held):
        return _layer(L.RoutedExpertsLayer(
            n_routed=e, n_experts=held, first_expert=first, n_ff=ff,
            top_k=sz["num_experts_per_tok"],
            scale=sz["routed_scaling_factor"], norm_eps=sz["route_norm_eps"],
            selection_bias=sz["expert_bias"]))

    whole, p = routed(0, e)
    shared, ps = _layer(L.GatedMLPLayer(n_ff=sz["n_shared_experts"] * ff,
                                        scope="shared_expert"))
    x = _x(5)
    xt = x.reshape(-1, D)
    experts, weights, load = ref.route(p, bias, xt)
    uncut = (ref.experts_of(p, xt, experts, weights, held=(0, e))
             + ref.mlp(ps, xt))
    total, loads = shared.apply(ps, x, {}, True, None)[0], []
    for c in range(e // n):
        share, _ = routed(n * c, n)
        pc = {"Wg": p["Wg"], "W1": p["W1"][n * c:n * c + n],
              "W2": p["W2"][n * c:n * c + n]}
        y, st = share.apply(pc, x, share.init_state(), True, None)
        total = total + y
        loads.append(np.asarray(st["expert_load"]))
    _close(total.reshape(-1, D), uncut)
    assert all(np.array_equal(l, np.asarray(load)) for l in loads)
    assert float(np.asarray(load).sum()) == B * T * sz["num_experts_per_tok"]
    y, _ = whole.apply(p, x, whole.init_state(), True, None)
    _close(y.reshape(-1, D) + ref.mlp(ps, xt), uncut)


# --- the five-layer model through ComputationGraph.fit ------------------------

SEQ = 32
MIX = {"batch": 2, "seq": SEQ, "batches": 3, "first_steps": 3}
SEED = 11


def _batches():
    gen = _load(os.path.join(BENCH, "traffic", "token_stream.py"), "bench_gen")
    return gen.make(MIX, SIZES, SEED, 3)


def _drive(cfg):
    job = CONF.build(cfg, SIZES, 1, MIX)
    batches = _batches()
    w0 = CONF.make_weights(cfg, SIZES, SEED)
    w0_host = jax.device_get(w0)
    job.reset(w0)
    traced = OpProfiler.get().counter_value("trace/graph_fit_step")
    prog = compare.drive_first_steps(job, batches, w0_host)
    traced = OpProfiler.get().counter_value("trace/graph_fit_step") - traced
    ref = compare.reference_norms(CONF.reference(cfg, SIZES, SEED, batches))
    return {"job": job, "prog": prog, "ref": ref, "batches": batches,
            "traced": traced}


@pytest.fixture(scope="module")
def float32_run():
    return _drive({**CFG, "compute_dtype": "", "updater_state_dtype": ""})


@pytest.fixture(scope="module")
def bfloat16_run():
    return _drive(CFG)


def test_param_tree_is_the_layer_table_and_counts_469m():
    full = CONF.sizes_of(CFG, False)
    shapes = CONF.param_shapes(CFG, full)
    assert sum(int(np.prod(s)) for leaves in shapes.values()
               for s in leaves.values()) == 469_284_992
    mix = {"seq": 8192}
    assert CONF.expert_flops(CFG, full, CONF.balanced_rows(full, 8192)) \
        == 18.0 * 4 * 4096 * 2048 * 1536
    total = CONF.model_flops(CFG, full, mix)
    assert 9.9e12 < total < 10.1e12
    assert 0.08 < CONF.expert_flops(
        CFG, full, CONF.balanced_rows(full, 8192)) / total < 0.10


def test_fit_three_steps_float32_matches_reference(float32_run):
    """Losses, the first gradient and the parameters' change per leaf and
    the routed layers' expert_load, by the benchmark's gaps."""
    found = compare.gaps(float32_run["prog"], float32_run["ref"])
    assert set(found) >= {"loss_step3", "first_gradient", "param_change",
                          "buffer_change"}
    for name, (gap, where) in found.items():
        assert gap <= 1e-4, (name, gap, where)
    assert found["buffer_change"][0] <= 1e-6    # the same selections


def test_fit_three_steps_bfloat16_inside_limits_that_float8_fails(bfloat16_run):
    import precisions

    r = bfloat16_run
    found = compare.gaps(r["prog"], r["ref"])
    low = compare.gaps(compare.reference_norms(CONF.reference(
        CFG, SIZES, SEED, r["batches"],
        lower=precisions.get(CFG["control_precision"]))), r["ref"])
    limits = CFG["limits_tiny"]
    ok, rows = compare.judge(found, limits)
    assert ok, rows
    ok8, rows8 = compare.judge(low, limits)
    assert not ok8, rows8


def test_expert_load_advances_once_a_step_under_full_remat(bfloat16_run):
    """``remat_policy="full"`` runs a routed layer's forward twice a step;
    its state comes out of the first run only: after three steps of 2 x 32
    tokens every routed layer has counted 3 x 64 x 4 selections. Read when
    asked for; cleared on request."""
    m = bfloat16_run["job"].model
    assert m.conf.global_conf.remat_policy == "full"
    loads = m.expert_load()
    assert sorted(loads) == ["l2_ffn", "l3_ffn", "l4_ffn", "l5_ffn"]
    for load in loads.values():
        assert load.shape == (E,) and load.sum() == 3 * MIX["batch"] * SEQ * K
    # the bias is state too, and stays what the configuration wrote
    _close(m._states["l3_ffn"]["bias"], BIAS)
    assert bfloat16_run["traced"] == 1
    m.expert_load(reset=True)
    assert not any(v.any() for v in m.expert_load().values())


def test_zoo_model_defaults_are_the_published_sizes():
    z = Lfm2Moe()
    assert (z.d, z.ff, z.moe_ff, z.heads, z.kv_heads, z.experts, z.top_k) == (
        2048, 11776, 1536, 32, 8, 64, 4)
    assert [l for l in range(40) if z.is_attention(l)] == list(range(2, 40, 4))
    assert [l for l in range(40) if z.is_attention(l)] == [
        l for l, t in enumerate(CFG["layer_types"]) if t == "full_attention"]


def test_router_takes_its_gradient_through_the_held_experts():
    """The routing weights are part of the backward pass: ``Wg`` takes the
    reference's gradient, and what reaches the input through the scores
    alone (the whole input gradient less the one with the weights held
    constant) is the reference's too."""
    layer, p = _routed()
    x = _x(7)
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape, F32)

    def ref(p, x, hold):
        xt = x.reshape(-1, D)
        experts, weights, _ = REF.route(p, BIAS, xt)
        if hold:
            weights = lax.stop_gradient(weights)
        return jnp.sum(REF.experts_of(p, xt, experts, weights,
                                      held=(0, 8)).reshape(x.shape) * w)

    g = jax.grad(lambda p, x: jnp.sum(layer.apply(
        p, x, layer.init_state(), True, None)[0] * w), (0, 1))(p, x)
    whole = jax.grad(lambda p, x: ref(p, x, False), (0, 1))(p, x)
    held = jax.grad(lambda p, x: ref(p, x, True), (0, 1))(p, x)
    assert np.asarray(whole[0]["Wg"]).any() and not np.asarray(
        held[0]["Wg"]).any()
    _close(g[0]["Wg"], whole[0]["Wg"], 2e-5)
    through = np.asarray(whole[1] - held[1])
    assert np.max(np.abs(through)) > 1e-2 * np.max(np.abs(whole[1]))
    _close(g[1] - held[1], through, 1e-3)
    assert "train_router" not in CFG and "train_router" not in SIZES


def test_eval_mode_does_not_count_load():
    layer, p = _routed()
    _, st = layer.apply(p, _x(6), layer.init_state(), False, None)
    assert not np.asarray(st["expert_load"]).any()
