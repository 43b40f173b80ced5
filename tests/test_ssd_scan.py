"""``ops.ssm.ssd_scan``, the state-space dual scan of Mamba-2: its XLA path
and its Pallas kernels (interpret mode) against the recurrence written one
step at a time, for the output and the gradients of every input — several
chunks, a length the chunk does not divide, heads sharing one B/C group and
several groups — and a float64 gradient check of the XLA path. The kernels
at the chip's shapes are compiled in ``tests/test_tpu_compile_seq.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.common.profiler import OpProfiler
from deeplearning4j_tpu.ops import ssm
from deeplearning4j_tpu.ops.ssm import ssd_scan


def per_step(x, dt, A, B, C):
    """H_t = exp(dt_t A) H_{t-1} + dt_t X_t (x) B_t; Y_t = H_t C_t, one step
    at a time; head h reads group h // (H/G)."""
    H, G = x.shape[2], B.shape[2]
    Bh, Ch = jnp.repeat(B, H // G, 2), jnp.repeat(C, H // G, 2)

    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = (jnp.exp(dt_t * A)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    h0 = jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:], x.dtype)
    _, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(a, 1, 0)
                                        for a in (x, dt, Bh, Ch)))
    return jnp.moveaxis(y, 0, 1)


def _inputs(b, T, H, P, G, N, seed=0, dtype=np.float32):
    r = np.random.RandomState(seed)
    x = r.randn(b, T, H, P)
    dt = np.log1p(np.exp(r.randn(b, T, H) - 1.0))
    A = -np.exp(0.5 * r.randn(H))
    B = 0.5 * r.randn(b, T, G, N)
    C = 0.5 * r.randn(b, T, G, N)
    return [jnp.asarray(a, dtype) for a in (x, dt, A, B, C)]


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)


CASES = {       # (batch, T, heads, head width, groups, state, chunk)
    "several_chunks": (1, 64, 4, 8, 1, 16, 16),
    "t_not_a_multiple": (1, 40, 4, 8, 1, 16, 16),
    "heads_share_a_group": (1, 48, 8, 16, 1, 16, 16),
    "two_groups_two_rows": (2, 48, 4, 8, 2, 16, 16),
    # the kernels' lane walk: three pairs of 64-wide heads in a 128-lane
    # tile each, and heads of 128 one to a tile
    "three_pairs_of_64": (1, 48, 6, 64, 1, 16, 16),
    "heads_of_128": (1, 48, 4, 128, 1, 16, 16),
}


@pytest.mark.parametrize("interpret", [False, True])
@pytest.mark.parametrize("case", sorted(CASES))
def test_output_and_gradients_match_the_recurrence(case, interpret):
    b, T, H, P, G, N, L = CASES[case]
    args = _inputs(b, T, H, P, G, N)
    weights = jax.random.normal(jax.random.key(1), (b, T, H, P))
    want = per_step(*args)
    got = ssd_scan(*args, chunk=L, interpret=interpret)
    assert got.shape == want.shape and got.dtype == args[0].dtype
    assert _rel(got, want) < 2e-6
    loss = lambda f: lambda *a: jnp.sum(f(*a) * weights)   # noqa: E731
    g_want = jax.grad(loss(per_step), argnums=range(5))(*args)
    g_got = jax.grad(loss(lambda *a: ssd_scan(*a, chunk=L,
                                              interpret=interpret)),
                     argnums=range(5))(*args)
    for name, a, w in zip(("x", "dt", "A", "B", "C"), g_got, g_want):
        assert a.shape == w.shape, name
        assert _rel(a, w) < 5e-6, (name, _rel(a, w))


def test_the_kernels_in_bfloat16_stay_near_the_float32_path():
    """bfloat16 operands to the products, float32 decays and state: the
    kernels' output and gradients within bfloat16's rounding of the float32
    XLA path's."""
    args = _inputs(1, 64, 8, 16, 1, 16, seed=3)
    low = [a.astype(jnp.bfloat16) if i in (0, 3, 4) else a
           for i, a in enumerate(args)]
    weights = jax.random.normal(jax.random.key(4), (1, 64, 8, 16))
    run = lambda interpret: lambda *a: jnp.sum(ssd_scan(     # noqa: E731
        *a, chunk=16, interpret=interpret).astype(jnp.float32) * weights)
    y = ssd_scan(*low, chunk=16, interpret=True)
    assert y.dtype == jnp.bfloat16
    assert _rel(y, per_step(*args)) < 3e-2
    g_k = jax.grad(run(True), argnums=range(5))(*low)
    g_x = jax.grad(run(False), argnums=range(5))(*args)
    for name, a, w in zip(("x", "dt", "A", "B", "C"), g_k, g_x):
        assert _rel(a, w) < 3e-2, (name, _rel(a, w))


def test_float64_gradient_check():
    """The XLA path in float64 against central differences, in a random
    direction of every input at once."""
    with jax.enable_x64(True):
        args = _inputs(1, 20, 2, 4, 1, 8, seed=5, dtype=np.float64)
        weights = jax.random.normal(jax.random.key(6), (1, 20, 2, 4),
                                    jnp.float64)
        f = lambda *a: jnp.sum(ssd_scan(*a, chunk=8) * weights)  # noqa: E731
        grads = jax.grad(f, argnums=range(5))(*args)
        r = np.random.RandomState(7)
        direction = [jnp.asarray(r.randn(*a.shape)) for a in args]
        eps = 1e-6
        up = f(*[a + eps * d for a, d in zip(args, direction)])
        down = f(*[a - eps * d for a, d in zip(args, direction)])
        numeric = (up - down) / (2 * eps)
        analytic = sum(jnp.sum(g * d) for g, d in zip(grads, direction))
        assert abs(float(numeric - analytic)) <= 1e-7 * abs(float(numeric))


def test_zero_steps_pad_and_leave_the_state_alone():
    """A step of dt = 0 neither decays nor adds: the steps after it see the
    same state as if it were not there (the padding of a T the chunk does
    not divide)."""
    x, dt, A, B, C = _inputs(1, 24, 2, 4, 1, 8, seed=8)
    y = ssd_scan(x, dt, A, B, C, chunk=8)
    gap = lambda a: jnp.concatenate(                     # noqa: E731
        [a[:, :10], jnp.zeros_like(a[:, :1]), a[:, 10:]], 1)
    y_gap = ssd_scan(gap(x), gap(dt), A, gap(B), gap(C), chunk=8)
    assert _rel(jnp.delete(y_gap, 10, axis=1), y) < 1e-6


def test_each_call_site_is_counted_by_its_path():
    prof = OpProfiler.get()
    args = _inputs(1, 32, 8, 64, 1, 128)
    before = (prof.counter_value("seq/ssd_kernel"),
              prof.counter_value("seq/ssd_fallback"))
    jax.jit(lambda *a: ssd_scan(*a, chunk=16, interpret=True)).lower(*args)
    jax.jit(lambda *a: ssd_scan(*a, chunk=16)).lower(*args)
    assert (prof.counter_value("seq/ssd_kernel") - before[0],
            prof.counter_value("seq/ssd_fallback") - before[1]) == (1, 1)


def test_the_kernel_shapes_it_supports():
    """A group's heads in whole sublane tiles, heads of 64 lanes or more, a
    group of whole 128-lane tiles, a state of whole lane tiles and a chunk of
    them: the cell's 64 heads of 64 on one group of 128 at chunks of 256 are
    taken in bfloat16 and, since the token-major blocks carry no padded
    64-lane head, in float32; 128 heads in float32 outgrow the VMEM
    budget."""
    assert ssm.supports_ssd_kernel(64, 1, 64, 128, 256, 2)
    assert ssm.supports_ssd_kernel(64, 1, 64, 128, 256, 4)
    assert not ssm.supports_ssd_kernel(128, 1, 64, 128, 256, 4)
    assert ssm.supports_ssd_kernel(32, 1, 64, 128, 256, 4)
    assert ssm.supports_ssd_kernel(16, 1, 128, 128, 256, 2)
    assert not ssm.supports_ssd_kernel(64, 3, 64, 128, 256, 2)
    assert not ssm.supports_ssd_kernel(4, 1, 64, 128, 256, 2)
    assert not ssm.supports_ssd_kernel(3, 1, 64, 128, 256, 2)
    assert not ssm.supports_ssd_kernel(64, 1, 64, 16, 256, 2)
    assert not ssm.supports_ssd_kernel(64, 1, 64, 128, 96, 2)


def test_a_pack_fills_whole_lane_tiles():
    """Two heads of 64 share a 128-lane tile, heads of 128 or wider take
    their own; the tests' narrow heads pack up to the group."""
    assert ssm._ssd_walk(64, 64) == (8, 2)
    assert ssm._ssd_walk(6, 64) == (6, 2)
    assert ssm._ssd_walk(16, 128) == (8, 1)
    assert ssm._ssd_walk(8, 16) == (8, 8)
    assert ssm._ssd_walk(4, 8) == (4, 4)
