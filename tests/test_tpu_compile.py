"""The main path's Pallas kernels compile for the chip, at real widths.

Interpret mode (every other kernel test) cannot see what the TPU's
compiler refuses: a block shape off the (8, 128) tiling, too much VMEM, an
op Mosaic has no lowering for. The compiler is installed with jax and
compiles for a chip that is DESCRIBED, not attached, so these cases cost no
chip time and guard every later PR. A compile that passes is not a chip
run: ``chip_smoke.py`` is what runs the kernels on the device.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.learning import Adam, AdamW, Nesterovs
from deeplearning4j_tpu.learning.precision import apply_updater
from deeplearning4j_tpu.ops.pallas_attention import flash_attention
from deeplearning4j_tpu.ops.pallas_epilogue import bn_act
from deeplearning4j_tpu.ops.pallas_update import fused_apply


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no libtpu: nothing to compile for
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without the chip: the next
    # run would warn and recompile
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _flash(B, T, bias=False, grad=False):
    bf16, f32 = jnp.bfloat16, jnp.float32
    shapes = [((B, 12, T, 64), bf16)] * 3
    if bias:
        shapes.append(((B, 1, 1, T), f32))

    def fwd(q, k, v, b=None):
        return flash_attention(q, k, v, bias=b, interpret=False)

    if grad:
        def fn(*a):
            return jax.grad(lambda *a: fwd(*a).astype(f32).sum(),
                            argnums=(0, 1, 2))(*a)
        return fn, shapes
    return fwd, shapes


def _bn_epilogue():
    bf16, f32 = jnp.bfloat16, jnp.float32
    x = ((32, 256, 56, 56), bf16)
    c = ((256,), f32)

    def fn(x, mean, var, gamma, beta, res):
        return bn_act(x, mean, var, gamma, beta, act="relu", residual=res,
                      mode="pallas")
    return fn, [x, c, c, c, c, x]


def _fused_update(updater, state_dtype=None):
    # ResNet-50's one f32 bucket: 25.6M parameters
    L = 25_600_000
    f32 = jnp.float32
    slots = {Adam: ("m", "v"), Nesterovs: ("v",)}[type(updater)]
    updater.state_dtype = state_dtype
    sdt = jnp.dtype(state_dtype) if state_dtype else f32

    def fn(p, g, key, *state):
        st = {n: {"flat::float32": s} for n, s in zip(slots, state)}
        return fused_apply(updater, {"flat::float32": p},
                           {"flat::float32": g}, st, jnp.asarray(3), key,
                           mode="pallas")
    return fn, [((L,), f32), ((L,), f32), ((2,), jnp.uint32)] + \
        [((L,), sdt)] * len(slots)


CASES = {
    "flash_fwd_b32_t128": lambda: _flash(32, 128),
    "flash_fwd_bias_b32_t128": lambda: _flash(32, 128, bias=True),
    "flash_bwd_t512": lambda: _flash(4, 512, grad=True),
    "flash_fwd_t4096": lambda: _flash(1, 4096),
    "bn_relu_residual_epilogue": _bn_epilogue,
    "fused_apply_adam": lambda: _fused_update(Adam(1e-3)),
    "fused_apply_nesterovs": lambda: _fused_update(Nesterovs(0.1, 0.9)),
    # the flagship cell's setting: bf16 moments, stochastic rounding
    "fused_apply_nesterovs_bf16_state": lambda: _fused_update(
        Nesterovs(0.1, 0.9), "bfloat16"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, v5e):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # the Mosaic kernel is in the executable, not an XLA stand-in
    assert "tpu_custom_call" in compiled.as_text()


def _leaf_update(updater, shapes, v5e):
    """The per-leaf update of ``shapes`` with bf16 moments, compiled."""
    updater.state_dtype = "bfloat16"
    params = {f"l{i}": jax.ShapeDtypeStruct(s, jnp.float32, sharding=v5e)
              for i, s in enumerate(shapes)}
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
        jax.eval_shape(updater.init, params))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e)
    return jax.jit(
        lambda g, s, p, k: apply_updater(updater, g, s, p, jnp.asarray(3), k),
        donate_argnums=(1, 2)).lower(params, state, params, key).compile()


@pytest.mark.parametrize("mk", [lambda: AdamW(1e-4), lambda: Nesterovs(0.1, 0.9)],
                         ids=["adamw", "nesterovs"])
def test_leaf_rounding_writes_no_bit_array(mk, v5e):
    """The threefry block of an element is computed where it is consumed.
    A bit array laid out apart from the leaf (flat then reshaped, or two
    words concatenated along an axis) becomes a temporary the size of the
    leaf in HBM (PR 27, PR 33)."""
    shape = (2048, 1024)
    compiled = _leaf_update(mk(), [shape], v5e)
    assert compiled.memory_analysis().temp_size_in_bytes < \
        shape[0] * shape[1] * 2


@pytest.mark.parametrize("shape", [(2048, 1024), (8, 256, 512), (64, 64, 3, 3)],
                         ids=["matrix", "experts", "conv"])
def test_same_shaped_leaves_round_in_fusions_of_their_own(shape, v5e):
    """Three leaves of one shape share no index vector, so XLA does not
    merge their roundings into one loop fusion — which would hold the
    three gradients until the last exists (PR 33: +0.64 GB of scratch)."""
    text = _leaf_update(AdamW(1e-4), [shape] * 3, v5e).as_text()
    moments = "bf16[" + ",".join(map(str, shape)) + "]"
    fusions = [line.split(" fusion(")[0]
               for line in text[text.index("ENTRY"):].splitlines()
               if re.match(r"\s+%\S+ = .* fusion\(", line)]
    widest = max(f.count(moments) for f in fusions)
    assert 1 <= widest <= 2, widest        # m and v of ONE leaf at most
