#!/usr/bin/env python
"""ResNet-50 MFU audit (round-3 verdict item 1).

Measures a MINIMAL hand-rolled ResNet-50 train step in raw jax — same math as
the zoo model (bottleneck v1, BN training mode, Nesterov momentum + L2) — with
two knobs the framework stack currently hard-codes:

  --layout {NHWC,NCHW}   activation layout (framework today: NCHW everywhere)
  --params {f32,bf16}    parameter storage dtype (framework today: fp32 with
                         per-step bf16 casts)

Purpose: isolate how much of the framework's 25% MFU is layout/dtype (fixable
in the framework) vs the XLA ceiling (not). Timing methodology == bench.py
(value-fenced chunks); FLOPs from XLA cost analysis of the compiled step.

Also reports transpose/convert op counts in the optimized HLO so the layout
hypothesis is checked against the compiler's actual output, not guessed.

Usage: python tools/mfu_audit.py --layout NHWC --params bf16 [--batch 128]
"""
import argparse
import json
import re
import sys
import time
import statistics
from functools import partial

import numpy as np

sys.path.insert(0, ".")
from bench import _timed_steps, CHUNK  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402


def conv(x, w, stride, padding, layout):
    if layout == "NHWC":
        dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NHWC", "HWIO", "NHWC"))
    else:
        dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))
    return lax.conv_general_dilated(x, w, window_strides=stride, padding=padding,
                                    dimension_numbers=dn)


def bn_train(x, gamma, beta, layout, eps=1e-5):
    axes = (0, 1, 2) if layout == "NHWC" else (0, 2, 3)
    mean = jnp.mean(x.astype(jnp.float32), axis=axes)
    var = jnp.var(x.astype(jnp.float32), axis=axes)
    shape = (1, 1, 1, -1) if layout == "NHWC" else (1, -1, 1, 1)
    inv = lax.rsqrt(var + eps).reshape(shape).astype(x.dtype)
    mean = mean.reshape(shape).astype(x.dtype)
    return (x - mean) * inv * gamma.reshape(shape) + beta.reshape(shape)


# ---- fused BN: minimum activation passes --------------------------------
# Forward: ONE variadic reduce computes (sum, sum_sq) reading x once.
# Backward: ONE variadic reduce computes (sum dy, sum dy*xhat) reading dy,x
# once; then one elementwise pass for dx. The naive autodiff version above
# costs ~2 reduce passes fwd + ~3 passes bwd; the profiler shows those
# reduces are 46% of the resnet50 step.

def _moments_1pass(x, axes):
    """E[x], Var[x] via SIBLING reductions sharing one input: XLA's fusion
    pass merges sibling reduces into one multi-output fusion = one read of x.
    (jnp.var's (x-mean)^2 form is two DEPENDENT passes; a variadic lax.reduce
    lowers to a slow compare/select path on TPU — both measured worse.)"""
    n = 1.0
    for a in axes:
        n *= x.shape[a]
    x32 = x.astype(jnp.float32)
    s = jnp.sum(x32, axis=axes)
    ss = jnp.sum(jnp.square(x32), axis=axes)
    mean = s / n
    var = ss / n - jnp.square(mean)
    return mean, var, n


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def bn_train_fused(x, gamma, beta, layout, eps=1e-5):
    out, _ = _bn_fwd(x, gamma, beta, layout, eps)
    return out


def _bn_fwd(x, gamma, beta, layout, eps):
    axes = (0, 1, 2) if layout == "NHWC" else (0, 2, 3)
    shape = (1, 1, 1, -1) if layout == "NHWC" else (1, -1, 1, 1)
    mean, var, n = _moments_1pass(x, axes)
    inv = lax.rsqrt(var + eps)
    xhat_scale = inv.reshape(shape).astype(x.dtype)
    mean_b = mean.reshape(shape).astype(x.dtype)
    out = (x - mean_b) * xhat_scale * gamma.reshape(shape) + beta.reshape(shape)
    return out, (x, gamma, mean, inv)


def _bn_bwd(layout, eps, res, dy):
    x, gamma, mean, inv = res
    axes = (0, 1, 2) if layout == "NHWC" else (0, 2, 3)
    shape = (1, 1, 1, -1) if layout == "NHWC" else (1, -1, 1, 1)
    n = 1.0
    for a in axes:
        n *= x.shape[a]
    mean_b = mean.reshape(shape).astype(x.dtype)
    inv_b = inv.reshape(shape).astype(x.dtype)
    xhat = (x - mean_b) * inv_b
    # sibling reduces over dy / dy*xhat -> one multi-output fusion pass
    sdy = jnp.sum(dy.astype(jnp.float32), axis=axes)
    sdyx = jnp.sum((dy * xhat).astype(jnp.float32), axis=axes)
    dgamma = sdyx
    dbeta = sdy
    g_b = gamma.reshape(shape).astype(x.dtype)
    dx = (g_b * inv_b) * (dy
                          - (sdy / n).reshape(shape).astype(x.dtype)
                          - xhat * (sdyx / n).reshape(shape).astype(x.dtype))
    return dx, dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype)


bn_train_fused.defvjp(lambda x, g, b, layout, eps: _bn_fwd(x, g, b, layout, eps),
                      _bn_bwd)


def make_params(key, layout, pdtype):
    """ResNet-50 bottleneck v1 params as a flat dict."""
    p = {}
    init = jax.nn.initializers.he_normal()

    def wconv(name, kh, kw, cin, cout):
        k = jax.random.fold_in(key, hash(name) % (2**31))
        if layout == "NHWC":
            p[name] = init(k, (kh, kw, cin, cout), pdtype)
        else:
            p[name] = init(k, (cout, cin, kh, kw), pdtype)

    def wbn(name, c):
        p[name + "_g"] = jnp.ones((c,), pdtype)
        p[name + "_b"] = jnp.zeros((c,), pdtype)

    wconv("stem", 7, 7, 3, 64); wbn("stem_bn", 64)
    stages = [(3, 64, 256, 1), (4, 128, 512, 2), (6, 256, 1024, 2),
              (3, 512, 2048, 2)]
    cin = 64
    for s, (blocks, mid, cout, _) in enumerate(stages):
        for b in range(blocks):
            n = f"s{s}b{b}"
            wconv(n + "_c1", 1, 1, cin, mid); wbn(n + "_bn1", mid)
            wconv(n + "_c2", 3, 3, mid, mid); wbn(n + "_bn2", mid)
            wconv(n + "_c3", 1, 1, mid, cout); wbn(n + "_bn3", cout)
            if b == 0:
                wconv(n + "_sc", 1, 1, cin, cout); wbn(n + "_scbn", cout)
            cin = cout
    kf = jax.random.fold_in(key, 999)
    p["fc_w"] = (jax.random.normal(kf, (2048, 1000), pdtype) * 0.01)
    p["fc_b"] = jnp.zeros((1000,), pdtype)
    return p


def forward(p, x, layout, fused_bn=False):
    cd = jnp.bfloat16

    def c(name, x, stride=(1, 1), padding="SAME"):
        return conv(x, p[name].astype(cd), stride, padding, layout)

    def bn(name, x):
        fn = bn_train_fused if fused_bn else bn_train
        return fn(x, p[name + "_g"].astype(cd), p[name + "_b"].astype(cd),
                  layout)

    x = x.astype(cd)
    x = jax.nn.relu(bn("stem_bn", c("stem", x, (2, 2))))
    window = (1, 3, 3, 1) if layout == "NHWC" else (1, 1, 3, 3)
    strides = (1, 2, 2, 1) if layout == "NHWC" else (1, 1, 2, 2)
    x = lax.reduce_window(x, -jnp.inf, lax.max, window, strides, "SAME")
    stages = [(3, 64, 256, 1), (4, 128, 512, 2), (6, 256, 1024, 2),
              (3, 512, 2048, 2)]
    for s, (blocks, mid, cout, first_stride) in enumerate(stages):
        for b in range(blocks):
            n = f"s{s}b{b}"
            stride = (first_stride, first_stride) if b == 0 else (1, 1)
            y = jax.nn.relu(bn(n + "_bn1", c(n + "_c1", x, stride)))
            y = jax.nn.relu(bn(n + "_bn2", c(n + "_c2", y)))
            y = bn(n + "_bn3", c(n + "_c3", y))
            sc = bn(n + "_scbn", c(n + "_sc", x, stride)) if b == 0 else x
            x = jax.nn.relu(y + sc)
    axes = (1, 2) if layout == "NHWC" else (2, 3)
    x = jnp.mean(x, axis=axes)
    return x.astype(jnp.float32) @ p["fc_w"].astype(jnp.float32) + p["fc_b"].astype(jnp.float32)


def loss_fn(p, x, y, layout, fused_bn=False):
    logits = forward(p, x, layout, fused_bn)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.sum(y * logp, axis=-1))


@partial(jax.jit, static_argnums=(4, 5), donate_argnums=(0, 1))
def train_step(p, mom, x, y, layout, fused_bn=False):
    loss, g = jax.value_and_grad(loss_fn)(p, x, y, layout, fused_bn)
    lr, mu, wd = 0.1, 0.9, 1e-4

    def upd(p_, g_, m_):
        g_ = g_.astype(jnp.float32) + wd * p_.astype(jnp.float32)
        m_new = mu * m_ + g_
        p_new = p_.astype(jnp.float32) - lr * (g_ + mu * m_new)  # nesterov
        return p_new.astype(p_.dtype), m_new

    out = jax.tree.map(upd, p, g, mom)
    p_new = {k: v[0] for k, v in out.items()}
    m_new = {k: v[1] for k, v in out.items()}
    return p_new, m_new, loss


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layout", default="NHWC", choices=["NHWC", "NCHW"])
    ap.add_argument("--params", default="bf16", choices=["f32", "bf16"])
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--hlo", action="store_true", help="dump HLO op stats")
    ap.add_argument("--fusedbn", action="store_true",
                    help="single-pass variadic-reduce BN with custom VJP")
    args = ap.parse_args()

    pdtype = jnp.bfloat16 if args.params == "bf16" else jnp.float32
    key = jax.random.PRNGKey(0)
    p = make_params(key, args.layout, pdtype)
    mom = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p)
    rng = np.random.RandomState(0)
    shape = ((args.batch, 224, 224, 3) if args.layout == "NHWC"
             else (args.batch, 3, 224, 224))
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    y = jnp.asarray(np.eye(1000, dtype=np.float32)[rng.randint(0, 1000, args.batch)])

    state = {"p": p, "m": mom, "loss": None}

    def run():
        state["p"], state["m"], state["loss"] = train_step(
            state["p"], state["m"], x, y, args.layout, args.fusedbn)

    times = _timed_steps(run, lambda: float(state["loss"]), warmup=3,
                         steps=args.steps)
    med = statistics.median(times)

    lowered = jax.jit(train_step.__wrapped__, static_argnums=(4, 5)).lower(
        state["p"], state["m"], x, y, args.layout, args.fusedbn)
    flops = None
    try:
        cost = lowered.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        flops = float(cost.get("flops", 0)) or None
    except Exception:
        pass
    hlo_stats = {}
    if args.hlo:
        try:
            txt = lowered.compile().as_text()
            for opname in ("transpose(", "convert(", "fusion(", "convolution("):
                hlo_stats[opname.rstrip("(")] = len(re.findall(re.escape(opname), txt))
        except Exception as e:
            hlo_stats["error"] = str(e)

    out = {
        "config": f"minimal-resnet50 {args.layout} params={args.params}",
        "batch": args.batch,
        "img_per_sec": round(args.batch / med, 1),
        "step_ms_median": round(med * 1e3, 2),
        "step_ms_p10": round(float(np.percentile(times, 10)) * 1e3, 2),
        "step_ms_p90": round(float(np.percentile(times, 90)) * 1e3, 2),
        "final_loss": float(state["loss"]),
        "platform": jax.devices()[0].platform,
    }
    if flops:
        out["effective_tflops"] = round(flops / med / 1e12, 1)
        from deeplearning4j_tpu.common.xprof import device_peaks

        out["mfu_vs_bf16_peak"] = round(flops / med / device_peaks()[0], 4)
    if hlo_stats:
        out["hlo_op_counts"] = hlo_stats
    print(json.dumps(out))


if __name__ == "__main__":
    main()
