"""The sequence kernels compile for the chip at the shapes of the cell
``phi4_mini_flash.train_s8k``: the selective scan forward and backward
(``ops/ssm.py``) and the banded attention forward and backward with grouped
heads, a window and bfloat16 operands
(``ops/pallas_attention.causal_attention``); and at the shapes of
``lfm2_moe.train_b2_s8k``: the grouped expert products forward and backward
(``ops/moe.py``: 65,536 dispatch rows, 8 experts of 2048 x 3072 and 1536 x
2048; apart, and as the one gated op the routed layer calls) and the
attention with 32 query heads over 8 key/value heads; and at the shapes of ``joyai_llm_flash.train_b2_s8k``: the attention with 32 heads
whose keys are 192 wide and whose values are 128 wide (latent attention as it
is trained), and the grouped products over 131,072 dispatch rows and 16
experts of 2048 x 1536 and 768 x 2048; and at the shapes of
``trinity_mini.train_s16k``: the attention forward and backward with 8 query
heads of 128 a key/value head at 16,384 tokens, a window of 2,048 and none
(the backward there walks query block first, the key/value head's dk and dv
resident in VMEM); and at the shapes of ``granite_4_h_micro.train_s16k``:
the state-space dual scan forward and backward (``ops/ssm.ssd_scan``: 16,384
steps, 64 heads of 64 on one B/C group, a state of 128, chunks of 256; the
widest groups ``supports_ssd_kernel`` takes, and the float32 group it
refuses failing for want of VMEM), the Mamba-2 mixer around it with no
layout copy of x, y or their cotangents between the layer and the kernels,
and the attention with 32 query heads of 64 over 8 key/value heads at the
family's softmax scale. As ``tests/test_tpu_compile.py``:
the compiler is installed with jax and compiles for a chip that is DESCRIBED,
not attached; a compile that passes is not a chip run.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.ops import pallas_attention as pa
from deeplearning4j_tpu.ops import ssm

T, D_INNER, D_STATE = 8192, 5120, 16        # the cell's sequence and widths
T16 = 16384                                 # trinity_mini.train_s16k's


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
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _scan(grad):
    f32 = jnp.float32
    wide, narrow = ((1, T, D_INNER), f32), ((1, T, D_STATE), f32)

    def fwd(u, dt, A, Bm, Cm):
        return ssm._scan(u, dt, A, Bm, Cm, ssm.DEFAULT_CHUNK, True, False)

    def both(*a):
        return jax.grad(lambda *a: fwd(*a).sum(), argnums=(0, 1, 2, 3, 4))(*a)

    return (both if grad else fwd), [wide, wide, ((D_INNER, D_STATE), f32),
                                     narrow, narrow]


def _ssd(grad, heads=64, dtype=jnp.bfloat16, t=T16):
    # granite_4_h_micro: x [1, T, 64, 64] and B, C [1, T, 1, 128] in
    # bfloat16, dt float32 after its softplus
    f32 = jnp.float32
    shapes = [((1, t, heads, 64), dtype), ((1, t, heads), f32),
              ((heads,), f32), ((1, t, 1, 128), dtype),
              ((1, t, 1, 128), dtype)]

    def fwd(*a):
        return ssm._ssd(*a, ssm.SSD_CHUNK, True, False)

    def both(*a):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2, 3, 4))(*a)

    return (both if grad else fwd), shapes


def _gqa4_nope(grad):
    # granite_4_h_micro's attention layer: one 16,384-token sequence, 32
    # query heads of 64 in groups of 4 over 8 key/value heads, the softmax
    # scaled by the family's attention_multiplier; a group's dq (4 x 16,384
    # x 64 at 4 + 2 x 2 bytes) is 32 MiB, half the limit: key block first
    bf16 = jnp.bfloat16
    shapes = [((1, 8, 4, T16, 64), bf16), ((1, 8, T16, 64), bf16),
              ((1, 8, T16, 64), bf16)]

    def fwd(q, k, v):
        return pa._band(q, k, v, 0.015625, None, pa.BAND_BLOCK, True, False)

    def both(*a):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(*a)

    return (both if grad else fwd), shapes


def _attention(window, grad, plain=None, t=T):
    # the two score maps on the batch axis: 2 x 20 query heads of 64 over
    # 2 x 10 key heads, the pair's 128-wide value; ``plain``: float32, equal
    # head counts and a value as wide as the head (what
    # ``flash_attention(causal=True)`` hands over), at that width
    bf16 = jnp.bfloat16
    shapes = [((2, 10, 2, t, 64), bf16), ((2, 10, t, 64), bf16),
              ((2, 10, t, 128), bf16)]
    if plain:
        shapes = [((2, 4, 1, 2048, plain), jnp.float32)] + [
            ((2, 4, 2048, plain), jnp.float32)] * 2

    def fwd(q, k, v):
        return pa._band(q, k, v, 0.125, window, pa.BAND_BLOCK, True, False)

    def both(*a):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(*a)

    return (both if grad else fwd), shapes


def _grouped(k, n, grad, rows=65536, held=8):
    # the cell's dispatch buffer: 2 x 8,192 tokens x 4 selections, 8 held
    # experts, bfloat16 (joyai_llm_flash: x 8 selections, 16 held)
    bf16 = jnp.bfloat16
    shapes = [((rows, k), bf16), ((held, k, n), bf16), ((held,), jnp.int32)]

    def fwd(x, w, sizes):
        return moe._gmm(x, w, sizes, moe.GMM_ROW_TILE, False)

    def both(x, w, sizes):
        return jax.grad(lambda x, w: fwd(x, w, sizes).astype(
            jnp.float32).sum(), argnums=(0, 1))(x, w)

    return (both if grad else fwd), shapes


def _gated(ff, grad, rows=65536, held=8, d=2048):
    # the one op the routed layer calls: two kernels forward; the gradient
    # keeps the first of them and runs four more (the second product's
    # result is no residual)
    bf16 = jnp.bfloat16
    shapes = [((rows, d), bf16), ((held, d, 2 * ff), bf16),
              ((held, ff, d), bf16), ((held,), jnp.int32)]

    def fwd(x, w1, w2, sizes):
        return moe._gated(x, w1, w2, sizes, moe.GMM_ROW_TILE, False)

    def both(x, w1, w2, sizes):
        return jax.grad(lambda x, w1, w2: fwd(x, w1, w2, sizes).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(x, w1, w2)

    return (both if grad else fwd), shapes


def _gqa(grad):
    # lfm2_moe: batch 2, 32 query heads of 64 in groups of 4 over 8 heads
    bf16 = jnp.bfloat16
    shapes = [((2, 8, 4, T, 64), bf16), ((2, 8, T, 64), bf16),
              ((2, 8, T, 64), bf16)]

    def fwd(q, k, v):
        return pa._band(q, k, v, 0.125, None, pa.BAND_BLOCK, True, False)

    def both(*a):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(*a)

    return (both if grad else fwd), shapes


def _mla(grad):
    # joyai_llm_flash: batch 2, 32 heads, keys [k_nope (128) ; k_rope (64)]
    # expanded per head, values 128 wide: 192 is one and a half passes of a
    # 128-wide unit
    bf16 = jnp.bfloat16
    shapes = [((2, 32, 1, T, 192), bf16), ((2, 32, T, 192), bf16),
              ((2, 32, T, 128), bf16)]

    def fwd(q, k, v):
        return pa._band(q, k, v, 192 ** -0.5, None, pa.BAND_BLOCK, True, False)

    def both(*a):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(*a)

    return (both if grad else fwd), shapes


def _gqa8(window, grad=False):
    # trinity_mini: one 16,384-token sequence, 32 query heads of 128 in
    # groups of 8 over 4 key/value heads; a window layer and a full one. The
    # group's blocks and scratch exceed the default scoped VMEM, so the
    # forward asks for more; the backward walks query block first, the
    # key/value head's dk and dv resident (a group's dq does not fit)
    bf16 = jnp.bfloat16
    shapes = [((1, 4, 8, T16, 128), bf16), ((1, 4, T16, 128), bf16),
              ((1, 4, T16, 128), bf16)]

    def fwd(q, k, v):
        return pa._band(q, k, v, 128 ** -0.5, window, pa.BAND_BLOCK, True,
                        False)

    def both(*a):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(*a)

    return (both if grad else fwd), shapes


CASES = {
    "ssd_scan_fwd": (lambda: _ssd(False), 1),
    "ssd_scan_fwd_bwd": (lambda: _ssd(True), 2),
    "ssd_scan_fwd_bwd_96_heads_bf16": (lambda: _ssd(True, 96, t=4096), 2),
    "ssd_scan_fwd_bwd_56_heads_f32": (
        lambda: _ssd(True, 56, jnp.float32, 4096), 2),
    # the cell's group in float32: its token-major blocks pad no head
    "ssd_scan_fwd_bwd_64_heads_f32": (lambda: _ssd(True, 64, jnp.float32), 2),
    # the widest groups supports_ssd_kernel takes at these widths
    "ssd_scan_fwd_bwd_152_heads_bf16": (lambda: _ssd(True, 152), 2),
    "ssd_scan_fwd_bwd_88_heads_f32": (lambda: _ssd(True, 88, jnp.float32), 2),
    "attention_gqa4_d64_t16384_nope_fwd_bwd": (lambda: _gqa4_nope(True), 2),
    "attention_gqa8_d128_t16384_full_fwd": (lambda: _gqa8(None), 1),
    "attention_gqa8_d128_t16384_window_fwd": (lambda: _gqa8(2048), 1),
    "attention_gqa8_d128_t16384_full_fwd_bwd": (
        lambda: _gqa8(None, True), 2),
    "attention_gqa8_d128_t16384_window_fwd_bwd": (
        lambda: _gqa8(2048, True), 2),
    "attention_mla_d192_dv128_fwd": (lambda: _mla(False), 1),
    "attention_mla_d192_dv128_fwd_bwd": (lambda: _mla(True), 2),
    "moe_gmm_e16_up_fwd_bwd": (
        lambda: _grouped(2048, 1536, True, 131072, 16), 2),
    "moe_gmm_e16_down_fwd_bwd": (
        lambda: _grouped(768, 2048, True, 131072, 16), 2),
    "moe_gated_mlp_fwd": (lambda: _gated(1536, False), 2),
    "moe_gated_mlp_fwd_bwd": (lambda: _gated(1536, True), 5),
    "moe_gated_mlp_e16_fwd": (lambda: _gated(768, False, 131072, 16), 2),
    "moe_gated_mlp_e16_fwd_bwd": (lambda: _gated(768, True, 131072, 16), 5),
    "moe_gmm_up_fwd": (lambda: _grouped(2048, 3072, False), 1),
    # input-gradient and weight-gradient kernels (the forward's result is
    # not needed for them and is dropped)
    "moe_gmm_up_bwd": (lambda: _grouped(2048, 3072, True), 2),
    "moe_gmm_down_fwd": (lambda: _grouped(1536, 2048, False), 1),
    "moe_gmm_down_bwd": (lambda: _grouped(1536, 2048, True), 2),
    "attention_gqa4_fwd": (lambda: _gqa(False), 1),
    "attention_gqa4_fwd_bwd": (lambda: _gqa(True), 2),
    "selective_scan_fwd": (lambda: _scan(False), 1),
    "selective_scan_fwd_bwd": (lambda: _scan(True), 2),
    "attention_full_fwd": (lambda: _attention(None, False), 1),
    "attention_window_fwd": (lambda: _attention(512, False), 1),
    # the longest sequence ``supports_band_kernel`` promises the forward
    # (``tests/test_seq_layers.py``): 128 blocks, a prefetched list of 8,256
    # pairs in scalar memory; the backward there is the XLA loops
    "attention_full_t65536_fwd": (
        lambda: _attention(None, False, t=65536), 1),
    # forward + backward kernel (``flash_attention_bwd``): the backward's
    # shape predicate holds at all four (``supports`` says so below)
    "attention_full_fwd_bwd": (lambda: _attention(None, True), 2),
    "attention_window_fwd_bwd": (lambda: _attention(512, True), 2),
    "attention_plain_f32_d64_fwd_bwd": (lambda: _attention(None, True, 64), 2),
    "attention_plain_f32_d32_fwd_bwd": (lambda: _attention(None, True, 32), 2),
}


@pytest.mark.parametrize("T_", [8192, T16])
def test_a_group_of_8_heads_of_128_takes_the_kv_resident_walk(T_):
    """The key-block-first walk keeps a key/value head's dq in VMEM: 8 x T x
    128 at 4 + 2 x 2 bytes an element is 64 MiB at 8k and 128 MiB at 16k
    against the 32 MiB that ``supports_band_bwd_kernel`` allows. The
    query-block-first walk keeps the head's dk and dv instead, T x (128 +
    128) x 8 bytes, 16 and 32 MiB whatever the group: the cell's attention
    backward is that walk (``seq/attn_bwd_kv_resident``)."""
    need = 8 * T_ * 128 * (4 + 2 * 2)
    assert need == (64 if T_ == 8192 else 128) * 2 ** 20
    assert not pa.supports_band_bwd_kernel(T_, 128, 8, 2), (
        f"dq of 8 heads x {T_} x 128 = {need / 2 ** 20:.0f} MiB "
        f"> {pa._BWD_VMEM_LIMIT // 2 / 2 ** 20:.0f} MiB")
    assert T_ * 256 * 8 == (16 if T_ == 8192 else 32) * 2 ** 20
    assert pa.supports_band_bwd_kv_resident(T_, 128, 128, 2)
    assert pa._bwd_walk(T_, 128, 128, 8, 2) == "kv_resident"
    assert pa.supports_band_kernel(T_, 128, 128, pa.BAND_BLOCK)


@pytest.mark.parametrize("T_,d,dv,g,walk", [
    (T, 64, 128, 2, "key_first"),       # phi4_mini_flash.train_s8k
    (T, 64, 64, 4, "key_first"),        # lfm2_moe.train_b2_s8k
    (T, 192, 128, 1, "key_first"),      # joyai_llm_flash.train_b2_s8k
    (65536, 64, 128, 2, None)])         # neither walk fits: the XLA loops
def test_the_other_decoder_cells_keep_the_key_first_walk(T_, d, dv, g, walk):
    """The walk is chosen by shape alone: the other decoder cells' groups fit
    the key-block-first walk and keep it (their kernels lower as before), and
    at 65,536 tokens of 64-wide heads neither the group's dq (64 MiB) nor the
    head's dk and dv (96 MiB) fit, so the backward stays the XLA loops."""
    assert pa._bwd_walk(T_, d, dv, g, 2) == walk
    assert pa.supports_band_kernel(T_, d, dv, pa.BAND_BLOCK)


@pytest.mark.parametrize("case", sorted(CASES))
def test_sequence_kernel_compiles_for_v5e(case, v5e):
    build, kernels = CASES[case]
    fn, shapes = build()
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # the Mosaic kernels are in the executable, not an XLA stand-in
    assert compiled.as_text().count("tpu_custom_call") >= kernels
    assert supports(case)


def test_the_ssd_shape_it_refuses_does_not_fit_vmem(v5e):
    """128 heads of 64 in float32: ``supports_ssd_kernel`` refuses them, and
    the backward kernel would need 66.8 MB of scoped VMEM against the 64 it
    asks for (its count reads within 2 MiB of Mosaic's; its cut at three
    quarters of the limit keeps every shape it takes inside, as the widest
    cases above compile)."""
    assert not ssm.supports_ssd_kernel(128, 1, 64, 128, ssm.SSD_CHUNK, 4)
    fn, shapes = _ssd(True, 128, jnp.float32, 4096)
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    with pytest.raises(Exception, match="vmem"):
        jax.jit(fn).lower(*args).compile()


def _big_copies(text, scopes, least=64 * 2 ** 20):
    """The compiled module's ``copy`` instructions of ``least`` bytes or more
    whose ``op_name`` holds one of ``scopes``: (bytes, shape, op_name)."""
    size = {"bf16": 2, "f32": 4}
    found = []
    for m in re.finditer(r"= (\w+)\[([\d,]+)\]\{[^}]*\} copy\(.*?"
                         r'op_name="([^"]*)"', text):
        dtype, dims, name = m.groups()
        n = size.get(dtype, 4)
        for d in dims.split(","):
            n *= int(d)
        if n >= least and any(s in name for s in scopes):
            found.append((n, f"{dtype}[{dims}]", name))
    return found


def test_the_mamba2_mixer_keeps_the_token_major_layout(v5e, monkeypatch):
    """One ``Mamba2Layer`` at granite_4_h_micro's widths (16,384 tokens, d
    2048, 64 heads of 64, state 128, bfloat16 operands), value and gradient
    under ``jax.checkpoint`` as per-vertex remat runs it: the scan's kernels
    read x and dy and write y and dx in the layer's ``[b, T, H*P]`` layout,
    so no copy of 64 MiB or more turns them head-major and back (the
    head-major kernels cost ten such copies a mixer, 2.5 GiB written)."""
    from deeplearning4j_tpu.common.profiler import OpProfiler
    from deeplearning4j_tpu.nn.conf import layers_seq

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = layers_seq.Mamba2Layer(d_inner=4096, n_heads=64, d_state=128,
                                   n_groups=1, d_conv=4)
    layer.n_in = 2048
    shapes = jax.eval_shape(lambda: layer.init_params(jax.random.key(0)))
    params = {k: jax.ShapeDtypeStruct(
        v.shape, jnp.float32 if k in layer.full_precision_params
        else jnp.bfloat16, sharding=v5e) for k, v in shapes.items()}
    x = jax.ShapeDtypeStruct((1, T16, 2048), jnp.bfloat16, sharding=v5e)

    def loss(p, x):
        run = jax.checkpoint(lambda p, x: layer.apply(p, x, None, True,
                                                      None)[0])
        return run(p, x).astype(jnp.float32).sum()

    before = OpProfiler.get().counter_value("seq/ssd_kernel")
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    assert OpProfiler.get().counter_value("seq/ssd_kernel") > before
    assert text.count("tpu_custom_call") >= 3
    assert _big_copies(text, ("mamba2/ssd", "mamba2/reshape", "mamba2/add",
                              "gated_norm/reshape")) == []


def supports(case):
    if case.startswith("ssd_scan"):
        m = re.search(r"_(\d+)_heads_(bf16|f32)$", case)
        heads, itemsize = ((int(m.group(1)), 2 if m.group(2) == "bf16" else 4)
                           if m else (64, 2))
        return ssm.supports_ssd_kernel(heads, 1, 64, 128, ssm.SSD_CHUNK,
                                       itemsize)
    if "gqa4_d64_t16384" in case:
        return (pa.supports_band_kernel(T16, 64, 64, pa.BAND_BLOCK)
                and pa._bwd_walk(T16, 64, 64, 4, 2) == "key_first")
    if "gqa8" in case:
        return (pa.supports_band_kernel(T16, 128, 128, pa.BAND_BLOCK)
                and (not case.endswith("bwd")
                     or pa._bwd_walk(T16, 128, 128, 8, 2) == "kv_resident"))
    if "mla" in case:
        return (pa.supports_band_kernel(T, 192, 128, pa.BAND_BLOCK)
                and pa.supports_band_bwd_kernel(T, 192, 1, 2))
    if case.startswith("moe_gated"):
        return moe.supports_gated_kernel(
            2048, 768 if "e16" in case else 1536, 2)
    if case.startswith("moe_gmm_e16"):
        return (moe.supports_gmm_kernel(2048, 1536, 2)
                and moe.supports_gmm_kernel(768, 2048, 2))
    if case.startswith("moe_gmm"):
        return (moe.supports_gmm_kernel(2048, 3072, 2)
                and moe.supports_gmm_kernel(1536, 2048, 2))
    if "gqa4" in case:
        return (pa.supports_band_kernel(T, 64, 64, pa.BAND_BLOCK)
                and pa.supports_band_bwd_kernel(T, 64, 4, 2))
    if case.startswith("selective_scan"):
        return ssm.supports_scan_kernel(D_INNER, D_STATE)
    if "t65536" in case:
        return (pa.supports_band_kernel(65536, 64, 128, pa.BAND_BLOCK)
                and not pa.supports_band_bwd_kernel(65536, 64, 2, 2))
    if "plain" in case:
        d = int(case.split("_d")[1].split("_")[0])
        return (pa.supports_band_kernel(2048, d, d, pa.BAND_BLOCK)
                and pa.supports_band_bwd_kernel(2048, d, 1, 4))
    return (pa.supports_band_kernel(T, 64, 128, pa.BAND_BLOCK)
            and (not case.endswith("bwd")
                 or pa.supports_band_bwd_kernel(T, 64, 2, 2)))
