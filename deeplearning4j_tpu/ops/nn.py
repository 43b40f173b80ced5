"""Neural-net ops: convolutions, pooling, normalization, attention.

Reference: libnd4j ``include/ops/declarable/generic/nn/**`` (conv2d/conv3d/
deconv2d/depthwiseConv2d, pooling, batchnorm, lrn,
multi_head_dot_product_attention) and their CPU/CUDA helper impls
(im2col+GEMM). On TPU every conv lowers straight onto the MXU via
``lax.conv_general_dilated`` — no im2col, no vendor-lib seam needed; XLA is
the single "platform helper" (SURVEY.md §2.2).

Weight layouts follow the reference's param initializers (dl4j-nn
``org/deeplearning4j/nn/params/ConvolutionParamInitializer``):
conv W = [out, in, kH, kW] (OIHW); dense W = [nIn, nOut]. Data format default
NCHW like DL4J, with NHWC supported (NHWC is marginally friendlier to TPU
vector layout; zoo models use it internally where shapes allow).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .registry import op


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (tuple, list)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def _conv_padding(padding, kernel, strides, dilation=(1, 1)):
    """DL4J uses explicit pad amounts + a 'same mode' flag; map both."""
    if isinstance(padding, str):
        return padding.upper()  # "SAME" / "VALID"
    ph, pw = _pair(padding)
    return ((ph, ph), (pw, pw))


@op("conv2d", "nn")
def conv2d(x, w, b=None, strides=(1, 1), padding=(0, 0), dilation=(1, 1),
           data_format: str = "NCHW", groups: int = 1):
    """2D convolution. x: NCHW or NHWC; w: OIHW (reference layout).

    ``groups`` maps to XLA's ``feature_group_count`` (ONNX Conv ``group``
    semantics: w is [O, I/groups, kH, kW], output channels blocked by
    group)."""
    sh, sw = _pair(strides)
    dh, dw = _pair(dilation)
    dn = lax.conv_dimension_numbers(
        x.shape, w.shape,
        ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else ("NHWC", "OIHW", "NHWC"),
    )
    # no preferred_element_type: the TPU MXU already accumulates bf16 inputs
    # in fp32, and mixing it with AD breaks the transpose-conv dtype rule
    out = lax.conv_general_dilated(
        x, w, window_strides=(sh, sw), padding=_conv_padding(padding, w.shape[2:], (sh, sw)),
        rhs_dilation=(dh, dw), dimension_numbers=dn,
        feature_group_count=int(groups),
    )
    if b is not None:
        bshape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        out = out + b.reshape(bshape).astype(out.dtype)
    return out.astype(x.dtype)


@op("conv1d", "nn")
def conv1d(x, w, b=None, stride: int = 1, padding=0, dilation: int = 1,
           data_format: str = "NCW"):
    """x: [N, C, W]; w: [O, I, K]."""
    x4 = jnp.expand_dims(x, -1 if data_format == "NCW" else -2)
    w4 = jnp.expand_dims(w, -1)
    if data_format == "NCW":
        out = conv2d(x4, w4, b, strides=(stride, 1),
                     padding=padding if isinstance(padding, str) else (padding, 0),
                     dilation=(dilation, 1), data_format="NCHW")
        return jnp.squeeze(out, -1)
    out = conv2d(x4, w4, b, strides=(stride, 1),
                 padding=padding if isinstance(padding, str) else (padding, 0),
                 dilation=(dilation, 1), data_format="NHWC")
    return jnp.squeeze(out, -2)


@op("conv3d", "nn")
def conv3d(x, w, b=None, strides=(1, 1, 1), padding=(0, 0, 0), dilation=(1, 1, 1),
           data_format: str = "NCDHW"):
    """x: NCDHW; w: [O, I, kD, kH, kW]."""
    s = tuple(int(v) for v in strides)
    d = tuple(int(v) for v in dilation)
    if isinstance(padding, str):
        pad = padding.upper()
    else:
        pad = tuple((int(p), int(p)) for p in padding)
    dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NCDHW", "OIDHW", "NCDHW"))
    out = lax.conv_general_dilated(x, w, window_strides=s, padding=pad,
                                   rhs_dilation=d, dimension_numbers=dn)
    if b is not None:
        out = out + b.reshape(1, -1, 1, 1, 1).astype(out.dtype)
    return out.astype(x.dtype)


@op("deconv2d", "nn")
def deconv2d(x, w, b=None, strides=(1, 1), padding=(0, 0), data_format: str = "NCHW"):
    """Transposed conv (reference Deconvolution2D). w: [I, O, kH, kW] —
    the reference DeconvolutionParamInitializer layout [inDepth, outDepth, k, k].
    Implemented as lhs-dilated conv with the spatially-flipped, IO-swapped
    kernel, which XLA maps straight onto the MXU."""
    sh, sw = _pair(strides)
    kh, kw = w.shape[2], w.shape[3]
    if isinstance(padding, str) and padding.upper() == "SAME":
        # SAME transposed conv (output = input×stride, TF/Keras contract):
        # the gradient-of-forward-SAME-conv padding, pb_t = k-1-pb_f with
        # pb_f = max(k-s,0)//2 — lax can't take a string here because the
        # lhs is dilated
        pad = []
        for k, s in ((kh, sh), (kw, sw)):
            tot_f = max(k - s, 0)
            pb_f = tot_f // 2
            pe_f = tot_f - pb_f
            pad.append((k - 1 - pb_f, k - 1 - pe_f + max(s - k, 0)))
        pad = tuple(pad)
    else:
        ph, pw = _pair(padding)
        pad = ((kh - 1 - ph, kh - 1 - ph), (kw - 1 - pw, kw - 1 - pw))
    wt = jnp.flip(w, axis=(2, 3)).transpose(1, 0, 2, 3)  # -> [O, I, kh, kw]
    dn = lax.conv_dimension_numbers(
        x.shape, wt.shape,
        ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else ("NHWC", "OIHW", "NHWC"),
    )
    out = lax.conv_general_dilated(x, wt, window_strides=(1, 1),
                                   padding=pad, lhs_dilation=(sh, sw),
                                   dimension_numbers=dn)
    if b is not None:
        bshape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        out = out + b.reshape(bshape).astype(out.dtype)
    return out.astype(x.dtype)


@op("depthwise_conv2d", "nn")
def depthwise_conv2d(x, w, b=None, strides=(1, 1), padding=(0, 0), dilation=(1, 1),
                     data_format: str = "NCHW"):
    """w: [depthMult, C, kH, kW] (reference layout) — grouped conv on MXU."""
    mult, c = w.shape[0], w.shape[1]
    sh, sw = _pair(strides)
    dh, dw = _pair(dilation)
    # jax wants [O, I/groups, kH, kW] with groups=C: O = C*mult, I/groups = 1
    wg = w.transpose(1, 0, 2, 3).reshape(c * mult, 1, w.shape[2], w.shape[3])
    dn = lax.conv_dimension_numbers(
        x.shape, wg.shape,
        ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else ("NHWC", "OIHW", "NHWC"),
    )
    out = lax.conv_general_dilated(
        x, wg, window_strides=(sh, sw), padding=_conv_padding(padding, wg.shape[2:], (sh, sw)),
        rhs_dilation=(dh, dw), dimension_numbers=dn, feature_group_count=c,
    )
    if b is not None:
        bshape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        out = out + b.reshape(bshape).astype(out.dtype)
    return out.astype(x.dtype)


@op("sconv2d", "nn")
def sconv2d(x, depth_w, point_w=None, b=None, strides=(1, 1), padding=(0, 0),
            data_format: str = "NCHW"):
    """Separable conv: depthwise then 1x1 pointwise (reference sconv2d)."""
    out = depthwise_conv2d(x, depth_w, None, strides, padding, data_format=data_format)
    if point_w is not None:
        out = conv2d(out, point_w, None, (1, 1), (0, 0), data_format=data_format)
    if b is not None:
        bshape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        out = out + b.reshape(bshape).astype(out.dtype)
    return out


def _pool(x, kind: str, kernel, strides, padding, data_format: str = "NCHW"):
    kh, kw = _pair(kernel)
    sh, sw = _pair(strides)
    if data_format == "NCHW":
        dims, strides_full = (1, 1, kh, kw), (1, 1, sh, sw)
    else:
        dims, strides_full = (1, kh, kw, 1), (1, sh, sw, 1)
    if isinstance(padding, str):
        pad = padding.upper()
    else:
        ph, pw = _pair(padding)
        pad = ((0, 0), (0, 0), (ph, ph), (pw, pw)) if data_format == "NCHW" else \
              ((0, 0), (ph, ph), (pw, pw), (0, 0))
    if kind == "max":
        init, fn = -jnp.inf, lax.max
        out = lax.reduce_window(x, init, fn, dims, strides_full, pad)
        return out
    # avg: sum then divide by actual window size (DL4J divides by kernel area,
    # excluding padding only in 'exclude padding' mode; default includes)
    out = lax.reduce_window(x, 0.0, lax.add, dims, strides_full, pad)
    return out / (kh * kw)


@op("maxpool2d", "nn")
def maxpool2d(x, kernel=(2, 2), strides=(2, 2), padding=(0, 0), data_format: str = "NCHW"):
    return _pool(x, "max", kernel, strides, padding, data_format)


@op("avgpool2d", "nn")
def avgpool2d(x, kernel=(2, 2), strides=(2, 2), padding=(0, 0), data_format: str = "NCHW"):
    return _pool(x, "avg", kernel, strides, padding, data_format)


@op("pnormpool2d", "nn")
def pnormpool2d(x, kernel=(2, 2), strides=(2, 2), padding=(0, 0), pnorm: int = 2,
                data_format: str = "NCHW"):
    kh, kw = _pair(kernel)
    sh, sw = _pair(strides)
    dims = (1, 1, kh, kw) if data_format == "NCHW" else (1, kh, kw, 1)
    strd = (1, 1, sh, sw) if data_format == "NCHW" else (1, sh, sw, 1)
    ph, pw = _pair(padding) if not isinstance(padding, str) else (0, 0)
    pad = padding.upper() if isinstance(padding, str) else (
        ((0, 0), (0, 0), (ph, ph), (pw, pw)) if data_format == "NCHW"
        else ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    s = lax.reduce_window(jnp.abs(x) ** pnorm, 0.0, lax.add, dims, strd, pad)
    return s ** (1.0 / pnorm)


@op("maxpool3d", "nn")
def maxpool3d(x, kernel=(2, 2, 2), strides=(2, 2, 2), padding=(0, 0, 0)):
    k = tuple(int(v) for v in kernel)
    s = tuple(int(v) for v in strides)
    p = tuple((int(v), int(v)) for v in padding)
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1) + k, (1, 1) + s,
                             ((0, 0), (0, 0)) + p)


@op("avgpool3d", "nn")
def avgpool3d(x, kernel=(2, 2, 2), strides=(2, 2, 2), padding=(0, 0, 0)):
    k = tuple(int(v) for v in kernel)
    s = tuple(int(v) for v in strides)
    p = tuple((int(v), int(v)) for v in padding)
    out = lax.reduce_window(x, 0.0, lax.add, (1, 1) + k, (1, 1) + s,
                            ((0, 0), (0, 0)) + p)
    return out / (k[0] * k[1] * k[2])


@op("global_avgpool", "nn")
def global_avgpool(x, data_format: str = "NCHW"):
    axes = (2, 3) if data_format == "NCHW" else (1, 2)
    return jnp.mean(x, axis=axes)


@op("upsampling2d", "nn")
def upsampling2d(x, factor=(2, 2), data_format: str = "NCHW"):
    fh, fw = _pair(factor)
    if data_format == "NCHW":
        return jnp.repeat(jnp.repeat(x, fh, axis=2), fw, axis=3)
    return jnp.repeat(jnp.repeat(x, fh, axis=1), fw, axis=2)


@op("upsampling3d", "nn")
def upsampling3d(x, factor=(2, 2, 2)):
    f = tuple(int(v) for v in factor)
    x = jnp.repeat(x, f[0], axis=2)
    x = jnp.repeat(x, f[1], axis=3)
    return jnp.repeat(x, f[2], axis=4)


@op("im2col", "nn")
def im2col(x, kernel=(2, 2), strides=(1, 1), padding=(0, 0), dilation=(1, 1)):
    """Kept for reference parity/tests; convs do NOT go through im2col on TPU."""
    kh, kw = _pair(kernel)
    sh, sw = _pair(strides)
    ph, pw = _pair(padding)
    dh, dw = _pair(dilation)
    n, c, h, w = x.shape
    xp = jnp.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw)))
    oh = (h + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    ow = (w + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    patches = []
    for i in range(kh):
        for j in range(kw):
            patches.append(xp[:, :, i * dh:i * dh + oh * sh:sh, j * dw:j * dw + ow * sw:sw])
    out = jnp.stack(patches, axis=2).reshape(n, c, kh, kw, oh, ow)
    return out


@op("batchnorm", "nn")
def batchnorm(x, mean, var, gamma=None, beta=None, epsilon: float = 1e-5, axis: int = 1):
    """Inference-form batchnorm over `axis` (channel dim; NCHW → 1)."""
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    inv = lax.rsqrt(var.reshape(shape) + epsilon)
    out = (x - mean.reshape(shape)) * inv
    if gamma is not None:
        out = out * gamma.reshape(shape)
    if beta is not None:
        out = out + beta.reshape(shape)
    return out.astype(x.dtype)


def _bn_axes_shape(ndim, channel_shape, axis):
    axes = tuple(i for i in range(ndim) if i != (axis % ndim))
    shape = [1] * ndim
    shape[axis] = channel_shape
    return axes, tuple(shape)


def _bn_fwd_impl(x, gamma, beta, pivot, axis, epsilon):
    axes, shape = _bn_axes_shape(x.ndim, x.shape[axis], axis)
    n = 1.0
    for a in axes:
        n *= x.shape[a]
    x32 = x.astype(jnp.float32)
    # SIBLING reductions over one shared input: XLA merges them into a single
    # multi-output fusion (one read of x, often fused into the producing
    # conv's epilogue). jnp.var's (x-mean)^2 form costs a second dependent
    # pass; profiled on v5e it is ~10% of the whole ResNet-50 step.
    # The sums are taken about a per-channel PIVOT so the E[d^2]-E[d]^2 form
    # does not cancel catastrophically when |mean| >> std. The pivot must be
    # INDEPENDENT of x (the BN layer passes its running mean): a pivot
    # gathered from x itself re-introduces a dependency that breaks the
    # conv-epilogue fusion (measured: +8.5 ms on the ResNet-50 v5e step).
    d = x32 - pivot.reshape(shape)
    s = jnp.sum(d, axis=axes)
    ss = jnp.sum(jnp.square(d), axis=axes)
    mean_c = s / n
    var = jnp.maximum(ss / n - jnp.square(mean_c), 0.0)
    mean = mean_c + pivot
    inv = lax.rsqrt(var + epsilon)
    out = ((x - mean.reshape(shape).astype(x.dtype))
           * (inv * gamma.astype(jnp.float32)).reshape(shape).astype(x.dtype)
           + beta.reshape(shape).astype(x.dtype))
    return (out, mean, var), (x, gamma, mean, inv)


def _bn_bwd_impl(axis, epsilon, res, cts):
    dx, dgamma, dbeta = _bn_bwd_math(axis, res, cts)
    return dx, dgamma, dbeta, jnp.zeros_like(res[2])  # pivot gets no gradient


def _bn_bwd_math(axis, res, cts):
    dy = cts[0]  # cotangents for (mean, var) are dropped: running stats are
    #              detached buffers, as in the reference (BatchNormalization
    #              running mean/var never backprop into the graph)
    x, gamma, mean, inv = res
    axes, shape = _bn_axes_shape(x.ndim, x.shape[axis], axis)
    n = 1.0
    for a in axes:
        n *= x.shape[a]
    xhat = (x - mean.reshape(shape).astype(x.dtype)) \
        * inv.reshape(shape).astype(x.dtype)
    dy = dy.astype(x.dtype)
    # sibling reduces again: one pass over (dy, dy*xhat)
    sdy = jnp.sum(dy.astype(jnp.float32), axis=axes)
    sdyx = jnp.sum((dy * xhat).astype(jnp.float32), axis=axes)
    gi = (gamma.astype(jnp.float32) * inv).reshape(shape).astype(x.dtype)
    dx = gi * (dy
               - (sdy / n).reshape(shape).astype(x.dtype)
               - xhat * (sdyx / n).reshape(shape).astype(x.dtype))
    return dx, sdyx.astype(gamma.dtype), sdy.astype(gamma.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _batchnorm_train_core(x, gamma, beta, pivot, axis, epsilon):
    return _bn_fwd_impl(x, gamma, beta, pivot, axis, epsilon)[0]


_batchnorm_train_core.defvjp(_bn_fwd_impl, _bn_bwd_impl)


@op("batchnorm_train", "nn")
def batchnorm_train(x, gamma=None, beta=None, epsilon: float = 1e-5,
                    axis: int = 1, pivot=None):
    """Training-form batchnorm: returns (out, batch_mean, batch_var).

    Reference: libnd4j generic/nn/batchnorm.cpp training path +
    dl4j-nn layers/normalization/BatchNormalization. Hand-written VJP keeps
    the statistics and gradient reductions to ONE fused pass each (profiled:
    the naive autodiff form spends ~46% of a ResNet-50 v5e step in separate
    reduction passes). batch_mean/var are float32 and detached (running-stat
    buffers do not receive gradients, matching the reference).

    ``pivot`` (optional, [C] float32, x-independent — the BN layer passes its
    running mean) recenters the single-pass variance so it stays accurate for
    |mean| >> std inputs; it receives no gradient.
    """
    if gamma is None:
        gamma = jnp.ones((x.shape[axis],), jnp.float32)
    if beta is None:
        beta = jnp.zeros((x.shape[axis],), jnp.float32)
    if pivot is None:
        pivot = jnp.zeros((x.shape[axis],), jnp.float32)
    return _batchnorm_train_core(x, gamma, beta,
                                 pivot.astype(jnp.float32), axis,
                                 float(epsilon))


@op("layer_norm", "nn")
def layer_norm(x, gain=None, bias=None, axis=-1, epsilon: float = 1e-5):
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.var(x, axis=axis, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + epsilon)
    if gain is not None:
        out = out * gain
    if bias is not None:
        out = out + bias
    return out.astype(x.dtype)


@op("lrn", "nn")
def lrn(x, depth: int = 5, bias: float = 1.0, alpha: float = 1.0, beta: float = 0.5):
    """Local response normalization across channels (NCHW)."""
    half = depth // 2
    sq = jnp.square(x)
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    windows = sum(padded[:, i:i + x.shape[1]] for i in range(depth))
    return x / jnp.power(bias + alpha * windows, beta)


@op("dropout", "nn")
def dropout(x, key, rate: float, inverted: bool = True):
    """Inverted dropout (train-time scaling), jax key passed explicitly."""
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    if inverted:
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)
    return jnp.where(mask, x, 0.0).astype(x.dtype)


@op("alpha_dropout", "nn")
def alpha_dropout(x, key, rate: float):
    """SELU-preserving dropout (reference AlphaDropout)."""
    alpha_p = -1.7580993408473766
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    a = (keep + alpha_p ** 2 * keep * (1 - keep)) ** -0.5
    b = -a * alpha_p * (1 - keep)
    return (a * jnp.where(mask, x, alpha_p) + b).astype(x.dtype)


@op("gaussian_dropout", "nn")
def gaussian_dropout(x, key, rate: float):
    std = jnp.sqrt(rate / (1.0 - rate))
    return (x * (1.0 + std * jax.random.normal(key, x.shape, dtype=x.dtype))).astype(x.dtype)


@op("gaussian_noise", "nn")
def gaussian_noise(x, key, stddev: float):
    return (x + stddev * jax.random.normal(key, x.shape, dtype=x.dtype)).astype(x.dtype)


@op("linear", "nn")
def linear(x, w, b=None):
    """xW+b — dense W = [nIn, nOut] (reference layout). MXU matmul."""
    out = x @ w
    if b is not None:
        out = out + b
    return out


@op("bias_add", "nn")
def bias_add(x, b, data_format: str = "NCHW"):
    if x.ndim == 4:
        shape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        return x + b.reshape(shape)
    return x + b


@op("embedding_lookup", "nn")
def embedding_lookup(table, ids):
    return jnp.take(table, ids, axis=0)


@op("dot_product_attention", "nn")
def dot_product_attention(q, k, v, mask=None, scaled: bool = True):
    """Single-head attention: q,k,v = [..., T, d]."""
    d = q.shape[-1]
    scores = jnp.einsum("...qd,...kd->...qk", q, k)
    if scaled:
        scores = scores / jnp.sqrt(jnp.asarray(d, dtype=scores.dtype))
    if mask is not None:
        scores = jnp.where(mask.astype(bool), scores, jnp.asarray(-1e9, dtype=scores.dtype))
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", weights, v)


@op("multi_head_dot_product_attention", "nn")
def multi_head_dot_product_attention(q, k, v, wq, wk, wv, wo, mask=None,
                                     num_heads: int = 1, scaled: bool = True):
    """Reference multi_head_dot_product_attention
    (libnd4j generic/nn/multi_head_dot_product_attention.cpp):
    q,k,v = [B, T, dModel]; per-head projections then fused attention."""
    b, tq, _ = q.shape
    tk = k.shape[1]

    def split_heads(x, w):
        proj = x @ w  # [B, T, H*dh]
        return proj.reshape(b, x.shape[1], num_heads, -1).transpose(0, 2, 1, 3)

    qh, kh, vh = split_heads(q, wq), split_heads(k, wk), split_heads(v, wv)
    out = None
    if tq == tk:
        # self-attention routes through the Pallas flash kernel on TPU
        # (no T×T buffer; its speed against the dense op is not measured
        # on this chip); a padding mask rides as an additive logits bias
        # streamed block-by-block, so the masked path is the SAME fused
        # kernel. The dense path remains the reference semantics
        # everywhere else.
        from ..common.environment import Environment
        from .pallas_attention import flash_attention, supports_flash

        if (Environment.get().allow_pallas()
                and jax.default_backend() == "tpu"
                and supports_flash(tq, qh.shape[-1])):
            scale = (qh.shape[-1] ** -0.5) if scaled else 1.0
            bias = None
            if mask is not None:
                bias = jnp.where(mask.reshape(b, 1, 1, tk).astype(bool),
                                 jnp.float32(0.0), jnp.float32(-1e9))
            out = flash_attention(qh, kh, vh, sm_scale=scale, bias=bias,
                                  interpret=False)
    if out is None:
        m = None
        if mask is not None:
            m = mask.reshape(b, 1, 1, tk)
        out = dot_product_attention(qh, kh, vh, m, scaled)  # [B, H, Tq, dh]
    out = out.transpose(0, 2, 1, 3).reshape(b, tq, -1)
    return out @ wo


@op("xw_plus_b", "nn")
def xw_plus_b(x, w, b):
    return x @ w + b


@op("relu_layer", "nn")
def relu_layer(x, w, b):
    return jnp.maximum(x @ w + b, 0)


@op("log_sigmoid", "nn")
def log_sigmoid(x):
    return jax.nn.log_sigmoid(x)


@op("softmax_bp", "nn")
def softmax_bp(x, grad, axis: int = -1):
    """VJP of softmax — exposed as an op for reference parity tests."""
    s = jax.nn.softmax(x, axis=axis)
    return s * (grad - jnp.sum(grad * s, axis=axis, keepdims=True))
