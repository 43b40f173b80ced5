"""phi4_mini_flash: the system under test, its plain reference and its counts.

Phi-4-mini-flash-reasoning ("SambaY", Ren et al., arXiv:2507.06607) cut to
one chip as ``phi4_mini_flash.json`` states: published layers ``layers_kept``
at every published width, and an eighth of the tied vocabulary. Three parts,
which share only the layer table below:

- ``build`` wraps ``deeplearning4j_tpu.models.Phi4MiniFlash`` and drives
  ``ComputationGraph.fit`` — the only part that imports the program;
- ``make_weights`` draws the initial weights on the device from the seed;
- ``reference`` is the same training step in plain ``jax.numpy``: float32,
  every product at ``highest``, a ``lax.scan`` over single time steps for the
  state-space layers, attention as an explicit masked softmax in blocks of
  query rows, autodiff for every gradient, AdamW written out with float32
  moments. It imports nothing of the program. At the cell's size its
  weights, gradients and moments are 11.2 GB, so layers run under
  ``jax.checkpoint``, the head's loss runs in token blocks, and the moments
  and the update live on the host in numpy.
"""

from __future__ import annotations

import functools
import math

import numpy as np

STEP_PROGRAM = "jit_step"       # name of the compiled step in the device trace

_SIZE_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "sliding_window", "mb_per_layer",
              "vocab_size", "layer_norm_eps", "layers_kept", "d_state",
              "d_conv", "expand", "dt_rank")


def sizes_of(cfg: dict, tiny: bool) -> dict:
    s = {k: cfg[k] for k in _SIZE_KEYS}
    s["boundary"] = cfg["published"]["num_hidden_layers"] // 2
    if tiny:
        s.update(cfg["tiny"])
    s["d_inner"] = s["expand"] * s["hidden_size"]
    s["head_dim"] = s["hidden_size"] // s["num_attention_heads"]
    return s


# ---------------------------------------------------------------------------
# the layer table: shapes and counts derive from it
# ---------------------------------------------------------------------------

def kind_of(sizes: dict, l: int) -> str:
    """mamba | attn_window | attn_full | gmu | attn_cross of published layer l."""
    b = sizes["boundary"]
    if l % sizes["mb_per_layer"] == 0:
        return "mamba" if l <= b else "gmu"
    return "attn_window" if l < b else "attn_full" if l == b + 1 else "attn_cross"


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def param_shapes(cfg: dict, sizes: dict) -> dict:
    """{node: {leaf: shape}} as ``models.Phi4MiniFlash`` names them. Dense
    weights are [in, out]."""
    d, ff, di = sizes["hidden_size"], sizes["intermediate_size"], sizes["d_inner"]
    n, r, hd = sizes["d_state"], sizes["dt_rank"], sizes["head_dim"]
    nq = sizes["num_attention_heads"] * hd
    nkv = sizes["num_key_value_heads"] * hd
    norm = {"gain": (d,), "bias": (d,)}
    out = {"embed": {"W": (sizes["vocab_size"], d)}}
    for l in sizes["layers_kept"]:
        kind = kind_of(sizes, l)
        out[f"l{l}_ln1"] = dict(norm)
        if kind == "mamba":
            mix = {"W_in": (d, 2 * di), "conv_w": (sizes["d_conv"], di),
                   "conv_b": (di,), "W_x": (di, r + 2 * n), "W_dt": (r, di),
                   "b_dt": (di,), "A_log": (di, n), "D": (di,),
                   "W_out": (di, d)}
        elif kind == "gmu":
            mix = {"W1": (d, di), "W2": (di, d)}
        else:
            mix = {"Wq": (d, nq), "bq": (nq,), "Wo": (nq, d), "bo": (d,),
                   "subln": (2 * hd,), "lambda_q1": (hd,), "lambda_k1": (hd,),
                   "lambda_q2": (hd,), "lambda_k2": (hd,)}
            if kind != "attn_cross":
                mix.update(Wk=(d, nkv), bk=(nkv,), Wv=(d, nkv), bv=(nkv,))
        out[f"l{l}_mix"] = mix
        out[f"l{l}_ln2"] = dict(norm)
        out[f"l{l}_mlp"] = {"W1": (d, 2 * ff), "W2": (ff, d)}
    out["final_ln"] = dict(norm)
    return out


_MATRICES = ("W", "W1", "W2", "W_in", "W_x", "W_dt", "W_out", "Wq", "Wk",
             "Wv", "Wo")


def _matmul_params(cfg: dict, sizes: dict) -> int:
    """Weights that a token passes through in a matrix product (the
    embedding's table counts once: as the head)."""
    return sum(int(np.prod(shape)) for leaves in param_shapes(cfg, sizes).values()
               for leaf, shape in leaves.items() if leaf in _MATRICES)


def _attention_pairs(sizes: dict, mix: dict) -> dict:
    """{layer: (query, key) pairs a map of one head has to score}."""
    T, W = mix["seq"], sizes["sliding_window"]
    band = T * W - W * (W - 1) // 2 if W < T else T * (T + 1) // 2
    return {l: band if kind_of(sizes, l) == "attn_window" else T * (T + 1) // 2
            for l in sizes["layers_kept"] if kind_of(sizes, l).startswith("attn")}


def attention_fwd_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs a sequence's attention forward requires: per layer and score
    map (two a pair-head), q.k over head_dim once and p.v over the
    2*head_dim-wide value, for the causal pairs or the band only."""
    hd = sizes["head_dim"]
    maps = sizes["num_attention_heads"]        # pair-heads x 2 maps
    return float(sum(2.0 * pairs * (hd + 2 * hd) * maps
                     for pairs in _attention_pairs(sizes, mix).values()))


def mxu_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs per sequence that the step puts through the matrix unit in
    XLA's own fusions (what ``trace_reduce.is_mxu`` times): three products a
    weight (6 FLOPs a weight a token: projections, gated MLPs, W_x/W_dt, the
    head) and the attention backward's four required products (dV, dP: the
    value width; dQ, dK: the head width), which this program runs as XLA
    loops. The attention forward is a Pallas call and is not counted; nor is
    any recomputation."""
    return (6.0 * _matmul_params(cfg, sizes) * mix["seq"]
            + 2.0 * attention_fwd_flops(cfg, sizes, mix))


def model_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs per sequence that forward and backward require: ``mxu_flops``
    and the attention forward's products. The scan, the norms and the update
    are not matrix products and are left out, as is usual."""
    return mxu_flops(cfg, sizes, mix) + attention_fwd_flops(cfg, sizes, mix)


def scan_bytes(cfg: dict, sizes: dict, mix: dict) -> float:
    """Bytes per sequence that the selective scans have to move at the
    configuration's bfloat16: the forward reads u, dt, B, C and writes y; the
    backward reads u, dt, dy, B, C and writes du, ddt, dB, dC. The state
    never leaves the chip; the gate (z) and the skip stay outside the scan and
    are not counted; nor is the recomputed forward."""
    wide = 2.0 * mix["seq"] * sizes["d_inner"]
    narrow = 2.0 * mix["seq"] * sizes["d_state"]
    layers = sum(kind_of(sizes, l) == "mamba" for l in sizes["layers_kept"])
    return layers * ((3 + 5) * wide + (2 + 4) * narrow)


# ---------------------------------------------------------------------------
# weights from the seed (one jitted call on the device)
# ---------------------------------------------------------------------------

def make_weights(cfg: dict, sizes: dict, seed: int, mix: dict = None):
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(cfg, sizes)
    f32 = jnp.float32

    @jax.jit
    def draw(key):
        out = {}
        for i, (node, leaves) in enumerate(shapes.items()):
            out[node] = {}
            for j, (leaf, shape) in enumerate(leaves.items()):
                k = jax.random.fold_in(jax.random.fold_in(key, i), j)
                if leaf in _MATRICES or leaf == "conv_w":
                    w = jax.random.normal(k, shape, f32) * 0.02
                elif leaf.startswith("lambda_"):
                    w = jax.random.normal(k, shape, f32) * 0.1
                elif leaf in ("gain", "subln", "D"):
                    w = jnp.ones(shape, f32)
                elif leaf == "A_log":
                    w = jnp.broadcast_to(jnp.log(jnp.arange(
                        1, shape[1] + 1, dtype=f32)), shape)
                elif leaf == "b_dt":    # softplus^-1 of a step in [1e-3, 1e-1]
                    dt = jnp.exp(jax.random.uniform(k, shape, f32)
                                 * (math.log(1e-1) - math.log(1e-3))
                                 + math.log(1e-3))
                    w = dt + jnp.log(-jnp.expm1(-dt))
                else:                   # every other bias
                    w = jnp.zeros(shape, f32)
                out[node][leaf] = w
        return out

    return draw(jax.random.key(int(seed) % (2 ** 63), impl="threefry2x32"))


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

class Job:
    """``ComputationGraph.fit(DataSet, epochs, batch_size)`` and what the
    comparison reads of its state."""

    def __init__(self, cfg: dict, sizes: dict, chips: int, mix: dict):
        from deeplearning4j_tpu.models import Phi4MiniFlash

        if chips != 1:
            raise RuntimeError("phi4_mini_flash is cut to one chip")
        opt = cfg["optimizer"]
        self.model = Phi4MiniFlash(
            layers=sizes["layers_kept"], vocab_rows=sizes["vocab_size"],
            hidden_size=sizes["hidden_size"],
            intermediate_size=sizes["intermediate_size"],
            num_attention_heads=sizes["num_attention_heads"],
            num_key_value_heads=sizes["num_key_value_heads"],
            sliding_window=sizes["sliding_window"],
            mb_per_layer=sizes["mb_per_layer"],
            num_hidden_layers=cfg["published"]["num_hidden_layers"],
            layer_norm_eps=sizes["layer_norm_eps"], d_state=sizes["d_state"],
            d_conv=sizes["d_conv"], expand=sizes["expand"],
            dt_rank=sizes["dt_rank"], seq_len=mix["seq"],
            compute_dtype=cfg["compute_dtype"] or None,
            state_dtype=cfg["updater_state_dtype"] or None,
            remat_policy=cfg["remat_policy"],
            learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"]).init()
        up = self.model.conf.global_conf.updater
        if (up.beta1, up.beta2, up.epsilon) != (opt["beta1"], opt["beta2"],
                                                opt["epsilon"]):
            raise RuntimeError("the zoo model's optimizer is not the "
                               "configuration's")
        self.beta1 = opt["beta1"]

    def reset(self, weights) -> None:
        """Start from the benchmark's weights: fresh moments, iteration 0.
        ``weights`` is consumed (the step donates its parameters)."""
        import jax

        m = self.model
        shapes = lambda t: jax.tree.map(lambda a: a.shape, t)  # noqa: E731
        if shapes(weights) != shapes(_with_leaves(m._params)):
            raise RuntimeError("the model's parameter tree is not the "
                               "layer table's")
        m._params = {name: weights.get(name, {}) for name in m._params}
        m._updater_state = None
        m._iteration = 0

    def feed(self, batches: list):
        from deeplearning4j_tpu.data import DataSet

        self.batch = batches[0]["ids"].shape[0]
        return DataSet(np.concatenate([b["ids"] for b in batches]),
                       np.concatenate([b["labels"] for b in batches]))

    def fit(self, data, epochs: int) -> None:
        self.model.fit(data, epochs=epochs, batch_size=self.batch)

    def loss(self) -> float:
        return float(self.model.score_value)

    def params(self):
        return _with_leaves(self.model._params)

    def buffers(self):
        return {}

    def first_gradient_state(self):
        """(state, scale): the gradient as the optimizer got it at step 1 is
        ``scale`` times its state after that step, Adam's m1 = (1-beta1) g."""
        return (_with_leaves(self.model._updater_state["m"]),
                1.0 / (1.0 - self.beta1))

    def fence(self) -> None:
        import jax

        jax.block_until_ready(self.model._params)
        float(self.model._score_dev)

    def free(self) -> None:
        self.model = None


def _with_leaves(tree: dict) -> dict:
    return {name: sub for name, sub in tree.items() if sub}


def build(cfg: dict, sizes: dict, chips: int, mix: dict) -> Job:
    return Job(cfg, sizes, chips, mix)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def ref_ops(sizes: dict, lower):
    """The six kinds of layer and the head's loss as plain functions of
    float32 arrays ``[B, T, F]``: every product at ``highest``, nothing
    fused, nothing of the program. ``lower.operand`` rounds the operands of
    every matrix product (the control); exact in every benchmark run."""
    import types

    import jax
    import jax.numpy as jnp
    from jax import lax

    q = lower.operand
    hi = lax.Precision.HIGHEST
    eps = sizes["layer_norm_eps"]
    n, r, hd = sizes["d_state"], sizes["dt_rank"], sizes["head_dim"]
    hp, gp = sizes["num_attention_heads"] // 2, sizes["num_key_value_heads"] // 2

    def mm(a, w):
        return jnp.dot(q(a), q(w), precision=hi)

    def ln(p, x):
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
        return (x - mean) * lax.rsqrt(var + eps) * p["gain"] + p["bias"]

    def mlp(p, x):
        g, u = jnp.split(mm(x, p["W1"]), 2, axis=-1)
        return mm(u * jax.nn.silu(g), p["W2"])

    def scan(u, dt, A, Bm, Cm):
        """h_t = exp(dt_t A) h_{t-1} + dt_t u_t (x) B_t; y_t = h_t . C_t, one
        step at a time; blocks of steps under jax.checkpoint so that the
        [T, d_inner, d_state] states are never all alive."""
        B, T, _ = u.shape

        def step(h, x):
            u_t, dt_t, b_t, c_t = x
            h = (jnp.exp(dt_t[..., None] * A) * h
                 + (dt_t * u_t)[..., None] * b_t[:, None, :])
            return h, jnp.sum(h * c_t[:, None, :], -1)

        blk = math.gcd(T, 128)
        cut = lambda a: jnp.moveaxis(a, 1, 0).reshape(   # noqa: E731
            (T // blk, blk) + (a.shape[0], a.shape[2]))
        _, y = lax.scan(jax.checkpoint(lambda h, xs: lax.scan(step, h, xs)),
                        jnp.zeros((B, u.shape[-1], A.shape[-1]), u.dtype),
                        (cut(u), cut(dt), cut(Bm), cut(Cm)))
        return jnp.moveaxis(y.reshape(T, B, -1), 0, 1)

    def mamba(p, x):
        """-> (the mixer's output, the scan's output before the gate)."""
        T = x.shape[1]
        u, z = jnp.split(mm(x, p["W_in"]), 2, axis=-1)
        k = p["conv_w"].shape[0]
        padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
        # causal depthwise convolution: tap j reads k-1-j steps back
        u = p["conv_b"] + sum(padded[:, j:j + T] * p["conv_w"][j]
                              for j in range(k))
        u = jax.nn.silu(u)
        proj = mm(u, p["W_x"])
        dt = jax.nn.softplus(mm(proj[..., :r], p["W_dt"]) + p["b_dt"])
        y = scan(u, dt, -jnp.exp(p["A_log"]), proj[..., r:r + n],
                 proj[..., r + n:]) + p["D"] * u
        return mm(y * jax.nn.silu(z), p["W_out"]), y

    def softmax_rows(qh, kh, vh, window=None):
        """softmax(q k^T / sqrt(hd) + mask) v, a block of query rows at a
        time; qh [B, H, T, hd]; kh, vh already repeated to the H heads."""
        B, H, T, _ = qh.shape
        rows = math.gcd(T, 512)
        kpos = jnp.arange(T)[None, :]

        def block(i0):
            qi = lax.dynamic_slice_in_dim(qh, i0, rows, 2)
            s = jnp.einsum("bhqd,bhkd->bhqk", q(qi), q(kh),
                           precision=hi) / math.sqrt(qh.shape[-1])
            qpos = i0 + jnp.arange(rows)[:, None]
            ok = kpos <= qpos
            if window:      # query i sees keys j with i - window < j <= i
                ok = ok & (qpos - kpos < window)
            p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", q(p), q(vh), precision=hi)

        out = lax.map(jax.checkpoint(block), jnp.arange(0, T, rows))
        return jnp.moveaxis(out, 0, 2).reshape(B, H, T, vh.shape[-1])

    def diff_attention(p, x, l, k=None, v=None, window=None):
        """-> (output, k, v); ``k``, ``v`` given: cross-attention over
        them. ``l``: the published layer index (lambda_init)."""
        B, T, _ = x.shape
        if k is None:
            k, v = mm(x, p["Wk"]) + p["bk"], mm(x, p["Wv"]) + p["bv"]
        qq = (mm(x, p["Wq"]) + p["bq"]).reshape(B, T, hp, 2, hd)
        kk = k.reshape(B, T, gp, 2, hd)
        vv = jnp.repeat(v.reshape(B, T, gp, 2 * hd).transpose(0, 2, 1, 3),
                        hp // gp, axis=1)
        maps = []
        for i in (0, 1):
            kh = jnp.repeat(kk[:, :, :, i].transpose(0, 2, 1, 3), hp // gp,
                            axis=1)
            maps.append(softmax_rows(qq[:, :, :, i].transpose(0, 2, 1, 3),
                                     kh, vv, window))
        lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
               - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))
               + lambda_init(l))
        o = maps[0] - lam * maps[1]
        o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        o = o * p["subln"] * (1.0 - lambda_init(l))
        o = o.transpose(0, 2, 1, 3).reshape(B, T, hp * 2 * hd)
        return mm(o, p["Wo"]) + p["bo"], k, v

    def gmu(p, x, m):
        return mm(m * jax.nn.silu(mm(x, p["W1"])), p["W2"])

    def head_loss(E, x, labels, weight):
        """sum of weight * cross-entropy of the tied head's logits x E^T, a
        block of tokens at a time: 8192 x 25008 float32 logits are never
        whole."""
        x = x.reshape(-1, x.shape[-1])
        tb = math.gcd(x.shape[0], 1024)

        def block(args):
            xb, yb, wb = args
            logits = jnp.dot(q(xb), q(E).T, precision=hi)
            picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
            return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * wb)

        return jnp.sum(lax.map(jax.checkpoint(block), (
            x.reshape(-1, tb, x.shape[-1]), labels.reshape(-1, tb),
            weight.reshape(-1, tb))))

    return types.SimpleNamespace(
        ln=ln, mlp=mlp, scan=scan, mamba=mamba, softmax_rows=softmax_rows,
        diff_attention=diff_attention, gmu=gmu, head_loss=head_loss)


def _ref_loss(cfg, sizes, lower, fault, params, ids, labels):
    """Mean next-token cross-entropy of one batch; every layer under
    ``jax.checkpoint``."""
    import jax
    import jax.numpy as jnp

    ops, act = ref_ops(sizes, lower), lower.activation
    B, T = ids.shape
    x = params["embed"]["W"][ids]
    memory = keys = values = None
    for l in sizes["layers_kept"]:
        kind, p = kind_of(sizes, l), params[f"l{l}_mix"]
        h = ops.ln(params[f"l{l}_ln1"], x)
        if kind == "mamba":
            y, mem = jax.checkpoint(ops.mamba)(p, h)
            if l == sizes["boundary"]:
                memory = mem
        elif kind == "gmu":
            y = jax.checkpoint(ops.gmu)(p, h, memory)
        elif kind == "attn_cross":
            y, _, _ = jax.checkpoint(functools.partial(
                ops.diff_attention, l=l))(p, h, k=keys, v=values)
        else:
            y, k, v = jax.checkpoint(functools.partial(
                ops.diff_attention, l=l,
                window=sizes["sliding_window"] if kind == "attn_window"
                else None))(p, h)
            if kind == "attn_full":
                keys, values = k, v
        x = x + act(y)
        x = x + act(jax.checkpoint(ops.mlp)(params[f"l{l}_mlp"],
                                            ops.ln(params[f"l{l}_ln2"], x)))
    weight = jnp.ones((B, T), jnp.float32)
    if fault == "half_batch":   # the second half of each sequence left out
        weight = weight * (jnp.arange(T) < T // 2)[None, :]
    return ops.head_loss(params["embed"]["W"],
                         ops.ln(params["final_ln"], x), labels,
                         weight / jnp.sum(weight))


@functools.lru_cache(maxsize=None)
def _ref_grad(cfg_key: str, sizes_key: str, lower, fault: str):
    import json

    import jax

    cfg, sizes = json.loads(cfg_key), json.loads(sizes_key)
    return jax.jit(jax.value_and_grad(
        functools.partial(_ref_loss, cfg, sizes, lower, fault)))


@functools.lru_cache(maxsize=None)
def _adamw(opt_key: str, lower):
    """One leaf's AdamW step, jitted; it runs where its arguments live (the
    host's CPU device). Decoupled decay on every leaf, as the program's
    updater applies it; moments float32 (``lower.state`` rounds them in a
    witness)."""
    import json

    import jax
    import jax.numpy as jnp

    opt = json.loads(opt_key)
    lr, b1, b2 = opt["learning_rate"], opt["beta1"], opt["beta2"]
    eps, wd = opt["epsilon"], opt["weight_decay"]

    def step(p, g, m, v, t):
        m = lower.state(b1 * m + (1 - b1) * g)
        v = lower.state(b2 * v + (1 - b2) * jnp.square(g))
        update = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - lr * (update + wd * p), m, v

    return jax.jit(step, donate_argnums=(0, 2, 3))


def reference(cfg: dict, sizes: dict, seed: int, batches: list,
              lower=None, fault: str = "") -> dict:
    """Run the first ``len(batches)`` steps plainly and return what the
    comparison reads: each step's loss, the first gradient and the
    parameters' change. Loss and gradient on the accelerator; the master
    weights, the float32 moments and the AdamW update on the host (XLA's CPU
    device, a leaf at a time): at the cell's size weights, gradient and
    moments are 11.2 GB and do not fit beside the activations."""
    import json

    import jax
    import jax.numpy as jnp

    from compare import EXACT

    lower = lower or EXACT
    grad = _ref_grad(json.dumps(cfg, sort_keys=True),
                     json.dumps(sizes, sort_keys=True), lower,
                     "" if fault == "state_unchanged" else fault)
    update = _adamw(json.dumps(cfg["optimizer"], sort_keys=True), lower)
    host, chip = jax.devices("cpu")[0], jax.devices()[0]
    w0 = jax.device_put(make_weights(cfg, sizes, seed), host)
    params = jax.tree.map(jnp.copy, w0)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for t, b in enumerate(batches, 1):
        loss, g = grad(jax.device_put(params, chip), jnp.asarray(b["ids"]),
                       jnp.asarray(b["labels"]))
        losses.append(float(loss))
        g = jax.device_put(g, host)
        if t == 1:
            first_grad = g
        if fault == "state_unchanged":
            continue
        for node, leaves in params.items():
            for leaf in leaves:
                leaves[leaf], m[node][leaf], v[node][leaf] = update(
                    leaves[leaf], g[node][leaf], m[node][leaf],
                    v[node][leaf], np.float32(t))
        del g
    return {"loss": losses, "first_gradient": first_grad,
            "param_change": jax.tree.map(jnp.subtract, params, w0)}
