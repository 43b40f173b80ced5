"""lfm2_moe: the system under test, its plain reference and its counts.

LFM2-24B-A2B (``model_type`` ``lfm2_moe``; LiquidAI's ``config.json``; the
family's modelling code is ``models/lfm2_moe`` of Hugging Face
``transformers``) cut to one chip as ``lfm2_moe.json`` states: published
layers ``layers_kept`` at every published width, experts ``experts_held`` of
each routed layer's 64 behind a router of the published width, and an eighth
of the tied vocabulary. Three parts, which share only the layer table below:

- ``build`` wraps ``deeplearning4j_tpu.models.Lfm2Moe`` and drives
  ``ComputationGraph.fit`` — the only part that imports the program;
- ``make_weights`` draws the initial weights on the device from the seed;
- ``reference`` is the same training step in plain ``jax.numpy``: float32,
  every product at ``highest``, RMSNorm, the convolution's taps and the rotary
  embedding written out, attention as an explicit masked softmax in blocks of
  query rows, the router with ``lax.top_k``, **each held expert applied to
  every token and masked by whether the token selected it** (no sorting, no
  grouping, no kernel), autodiff for every gradient, AdamW written out with
  float32 moments. It imports nothing of the program. Layers run under
  ``jax.checkpoint``, the experts and the head's loss in token blocks, and
  the moments and the update live on the host.
"""

from __future__ import annotations

import functools
import math

import numpy as np

STEP_PROGRAM = "jit_step"       # name of the compiled step in the device trace

_SIZE_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_key_value_heads", "num_experts",
              "num_experts_per_tok", "router_width", "experts_held",
              "routed_scaling_factor", "num_dense_layers", "conv_L_cache",
              "norm_eps", "vocab_size", "layers_kept", "layer_types")


def sizes_of(cfg: dict, tiny: bool) -> dict:
    s = {k: cfg[k] for k in _SIZE_KEYS}
    s["rope_theta"] = cfg["rope_parameters"]["rope_theta"]
    if tiny:
        s.update(cfg["tiny"])
    s["head_dim"] = s["hidden_size"] // s["num_attention_heads"]
    wave = cfg["expert_bias"]
    s["expert_bias"] = [
        wave["amplitude"] * math.cos(2 * math.pi * e / wave["period"])
        for e in range(s["router_width"])]
    return s


# ---------------------------------------------------------------------------
# the layer table: shapes and counts derive from it
# ---------------------------------------------------------------------------

def is_attention(sizes: dict, l: int) -> bool:
    return sizes["layer_types"][l] == "full_attention"


def is_routed(sizes: dict, l: int) -> bool:
    return l >= sizes["num_dense_layers"]


def routed_layers(sizes: dict) -> list:
    return [l for l in sizes["layers_kept"] if is_routed(sizes, l)]


def param_shapes(cfg: dict, sizes: dict) -> dict:
    """{node: {leaf: shape}} as ``models.Lfm2Moe`` names them. Dense weights
    are [in, out]; an expert's ``W1`` is ``[W1_e | W3_e]``."""
    d, ff, mff = (sizes["hidden_size"], sizes["intermediate_size"],
                  sizes["moe_intermediate_size"])
    hd, held = sizes["head_dim"], sizes["num_experts"]
    nq = sizes["num_attention_heads"] * hd
    nkv = sizes["num_key_value_heads"] * hd
    out = {"embed": {"W": (sizes["vocab_size"], d)}}
    for l in sizes["layers_kept"]:
        out[f"l{l}_ln1"] = {"gain": (d,)}
        if is_attention(sizes, l):
            out[f"l{l}_op"] = {"Wq": (d, nq), "Wk": (d, nkv), "Wv": (d, nkv),
                               "Wo": (nq, d), "q_norm": (hd,),
                               "k_norm": (hd,)}
        else:
            out[f"l{l}_op"] = {"W_in": (d, 3 * d),
                               "conv_w": (sizes["conv_L_cache"], d),
                               "W_out": (d, d)}
        out[f"l{l}_ln2"] = {"gain": (d,)}
        if is_routed(sizes, l):
            out[f"l{l}_ffn"] = {"Wg": (d, sizes["router_width"]),
                                "W1": (held, d, 2 * mff),
                                "W2": (held, mff, d)}
        else:
            out[f"l{l}_ffn"] = {"W1": (d, 2 * ff), "W2": (ff, d)}
    out["final_ln"] = {"gain": (d,)}
    return out


_MATRICES = ("W", "W1", "W2", "W_in", "W_out", "Wq", "Wk", "Wv", "Wo", "Wg")


def _dense_matmul_params(cfg: dict, sizes: dict) -> int:
    """Weights that EVERY token passes through in a matrix product of XLA's
    own: the operators' and the attention's projections, the dense MLP, the
    routers and the head (the embedding's table counts once: as the head).
    Not the experts."""
    return sum(int(np.prod(shape))
               for node, leaves in param_shapes(cfg, sizes).items()
               for leaf, shape in leaves.items()
               if leaf in _MATRICES and len(shape) == 2)


def attention_fwd_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs a sequence's attention forward requires: per attention layer
    and query head the causal half's (query, key) pairs, q.k and p.v over
    the head's width."""
    T, hd = mix["seq"], sizes["head_dim"]
    layers = sum(is_attention(sizes, l) for l in sizes["layers_kept"])
    return float(layers * sizes["num_attention_heads"]
                 * 2.0 * (T * (T + 1) // 2) * (hd + hd))


def expert_flops(cfg: dict, sizes: dict, rows: float) -> float:
    """FLOPs that ``rows`` routed rows (token, held expert pairs, summed
    over the routed layers) require: nine grouped products a row — x W1, x W3
    and h W2 forward, their three input gradients and their three weight
    gradients — of 2 x hidden x moe_intermediate each. No recomputation."""
    return 18.0 * rows * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def balanced_rows(sizes: dict, tokens: int) -> float:
    """Rows the held experts of all routed layers get from ``tokens`` tokens
    when the load is balanced: k x held / router_width a token a layer."""
    return (len(routed_layers(sizes)) * tokens * sizes["num_experts_per_tok"]
            * sizes["num_experts"] / sizes["router_width"])


def mxu_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs per sequence that the step puts through the matrix unit in
    XLA's own fusions (what ``trace_reduce.is_mxu`` times): three products a
    weight (6 FLOPs a weight a token) for the operators' and the attention's
    projections, the dense MLP, the routers and the head. The experts'
    grouped products and the attention are Pallas calls, which ``is_mxu``
    never times, and are not counted; nor is any recomputation."""
    return 6.0 * _dense_matmul_params(cfg, sizes) * mix["seq"]


def model_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs per sequence that forward and backward require: ``mxu_flops``,
    the attention's forward and its backward's four required products (3 x
    ``attention_fwd_flops``) and ``expert_flops`` at the BALANCED load (seq x
    4 x 8/64 rows a routed layer) — from shapes only. The expert bias is a
    wave whose mean over a chip's eight experts is zero, so at the initial
    weights the realised load is within a few percent of the balanced one
    (``PERF.md`` section 6: -2% to +3% by seed); it grows from there while
    the cell trains its routers (the same section), and this count does not
    follow it: the FLOPs a sequence REQUIRES are stated from shapes.
    ``moe_gmm_roofline_share`` counts the realised rows. Norms, the
    convolution's taps, the rotary embedding and the update are not matrix
    products and are left out, as is usual."""
    return (mxu_flops(cfg, sizes, mix)
            + 3.0 * attention_fwd_flops(cfg, sizes, mix)
            + expert_flops(cfg, sizes, balanced_rows(sizes, mix["seq"])))


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
                    out[node][leaf] = jax.random.normal(k, shape, f32) * 0.02
                else:                   # gains
                    out[node][leaf] = jnp.ones(shape, f32)
        return out

    return draw(jax.random.key(int(seed) % (2 ** 63), impl="threefry2x32"))


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

class Job:
    """``ComputationGraph.fit(DataSet, epochs, batch_size)`` and what the
    comparison reads of its state."""

    def __init__(self, cfg: dict, sizes: dict, chips: int, mix: dict):
        from deeplearning4j_tpu.models import Lfm2Moe

        if chips != 1:
            raise RuntimeError("lfm2_moe is cut to one chip")
        opt = cfg["optimizer"]
        first, end = sizes["experts_held"]
        if end - first != sizes["num_experts"]:
            raise RuntimeError("experts_held does not hold num_experts")
        self.model = Lfm2Moe(
            layers=sizes["layers_kept"], vocab_rows=sizes["vocab_size"],
            experts_held=(first, end - first),
            hidden_size=sizes["hidden_size"],
            intermediate_size=sizes["intermediate_size"],
            moe_intermediate_size=sizes["moe_intermediate_size"],
            num_attention_heads=sizes["num_attention_heads"],
            num_key_value_heads=sizes["num_key_value_heads"],
            num_experts=sizes["router_width"],
            num_experts_per_tok=sizes["num_experts_per_tok"],
            routed_scaling_factor=sizes["routed_scaling_factor"],
            num_dense_layers=sizes["num_dense_layers"],
            num_hidden_layers=cfg["published"]["num_hidden_layers"],
            conv_L_cache=sizes["conv_L_cache"], norm_eps=sizes["norm_eps"],
            rope_theta=sizes["rope_theta"], expert_bias=sizes["expert_bias"],
            seq_len=mix["seq"], compute_dtype=cfg["compute_dtype"] or None,
            state_dtype=cfg["updater_state_dtype"] or None,
            remat_policy=cfg["remat_policy"],
            learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"])
        if [self.model.is_attention(l) for l in sizes["layers_kept"]] != [
                is_attention(sizes, l) for l in sizes["layers_kept"]]:
            raise RuntimeError("the zoo model's layer pattern is not the "
                               "configuration's layer_types")
        self.model = self.model.init()
        up = self.model.conf.global_conf.updater
        if (up.beta1, up.beta2, up.epsilon) != (opt["beta1"], opt["beta2"],
                                                opt["epsilon"]):
            raise RuntimeError("the zoo model's optimizer is not the "
                               "configuration's")
        self.beta1 = opt["beta1"]
        self.bias = sizes["expert_bias"]
        self.routed = [f"l{l}_ffn" for l in routed_layers(sizes)]

    def reset(self, weights) -> None:
        """Start from the benchmark's weights: fresh moments, iteration 0,
        the expert load cleared and the selection bias as the file states
        it. ``weights`` is consumed (the step donates its parameters)."""
        import jax
        import jax.numpy as jnp

        m = self.model
        shapes = lambda t: jax.tree.map(lambda a: a.shape, t)  # noqa: E731
        if shapes(weights) != shapes(_with_leaves(m._params)):
            raise RuntimeError("the model's parameter tree is not the "
                               "layer table's")
        m._params = {name: weights.get(name, {}) for name in m._params}
        for name in self.routed:
            m._states[name] = {
                "bias": jnp.asarray(self.bias, jnp.float32),
                "expert_load": jnp.zeros((len(self.bias),), jnp.float32)}
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
        """Each routed layer's ``expert_load``: the tokens that selected
        each of the router's experts since ``reset``."""
        return {name: {"expert_load": self.model._states[name]["expert_load"]}
                for name in self.routed}

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
        """Drop the model. The buffers' last reading stays, on the host:
        ``metrics/moe_gmm_roofline_share.py`` takes the traced call's rows
        from it, the harness having no hook between that call and here."""
        import jax

        self.last_buffers = jax.device_get(self.buffers())
        self.model = None


def _with_leaves(tree: dict) -> dict:
    return {name: sub for name, sub in tree.items() if sub}


def build(cfg: dict, sizes: dict, chips: int, mix: dict) -> Job:
    return Job(cfg, sizes, chips, mix)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def ref_ops(sizes: dict, lower):
    """The kinds of layer and the head's loss as plain functions of float32
    arrays ``[B, T, F]``: every product at ``highest``, nothing fused,
    nothing of the program. ``lower.operand`` rounds the operands of every
    matrix product (the control); exact in every benchmark run."""
    import types

    import jax
    import jax.numpy as jnp
    from jax import lax

    q = lower.operand
    hi = lax.Precision.HIGHEST
    eps, hd = sizes["norm_eps"], sizes["head_dim"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    k_top, scale = sizes["num_experts_per_tok"], sizes["routed_scaling_factor"]
    first, end = sizes["experts_held"]

    def mm(a, w):
        return jnp.dot(q(a), q(w), precision=hi)

    def rms(gain, x):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain

    def mlp(p, x):
        """(silu(x W1) * x W3) W2 with ``p["W1"]`` = [W1 | W3]."""
        g, u = jnp.split(mm(x, p["W1"]), 2, axis=-1)
        return mm(jax.nn.silu(g) * u, p["W2"])

    def short_conv(p, x):
        T = x.shape[1]
        b, c, u = jnp.split(mm(x, p["W_in"]), 3, axis=-1)
        v = b * u
        taps = p["conv_w"].shape[0]
        padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
        # c_t = sum_j w_j v_{t-(taps-1)+j}: tap j reads taps-1-j steps back
        conv = sum(padded[:, j:j + T] * p["conv_w"][j] for j in range(taps))
        return mm(c * conv, p["W_out"])

    def rotary(x, theta):
        """x ``[B, H, T, hd]`` at positions 0..T-1, rotate-half."""
        T, d = x.shape[-2], x.shape[-1]
        inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
        half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
        return x * cos + half * sin

    def softmax_rows(qh, kh, vh):
        """softmax(q k^T / sqrt(hd) + causal mask) v, a block of query rows
        at a time; all ``[B, H, T, hd]``, kh and vh already repeated."""
        B, H, T, _ = qh.shape
        rows = math.gcd(T, 256)
        kpos = jnp.arange(T)[None, :]

        def block(i0):
            qi = lax.dynamic_slice_in_dim(qh, i0, rows, 2)
            s = jnp.einsum("bhqd,bhkd->bhqk", q(qi), q(kh),
                           precision=hi) / math.sqrt(qh.shape[-1])
            ok = kpos <= i0 + jnp.arange(rows)[:, None]
            p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", q(p), q(vh), precision=hi)

        out = lax.map(jax.checkpoint(block), jnp.arange(0, T, rows))
        return jnp.moveaxis(out, 0, 2).reshape(B, H, T, vh.shape[-1])

    def attention(p, x):
        B, T, _ = x.shape
        heads = lambda a, n: a.reshape(B, T, n, hd).transpose(0, 2, 1, 3)  # noqa: E731
        qh = rotary(rms(p["q_norm"], heads(mm(x, p["Wq"]), nh)),
                    sizes["rope_theta"])
        kh = rotary(rms(p["k_norm"], heads(mm(x, p["Wk"]), nkv)),
                    sizes["rope_theta"])
        vh = heads(mm(x, p["Wv"]), nkv)
        o = softmax_rows(qh, jnp.repeat(kh, nh // nkv, axis=1),
                         jnp.repeat(vh, nh // nkv, axis=1))
        return mm(o.transpose(0, 2, 1, 3).reshape(B, T, nh * hd), p["Wo"])

    def route(p, bias, x):
        """x ``[N, d]`` -> (experts [N, k], weights [N, k], load [E])."""
        s = jax.nn.sigmoid(mm(x, p["Wg"]))
        _, experts = lax.top_k(s + bias, k_top)
        picked = jnp.take_along_axis(s, experts, axis=-1)
        weights = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-6) * scale
        load = jnp.sum(jax.nn.one_hot(experts, s.shape[-1],
                                      dtype=jnp.float32), axis=(0, 1))
        return experts, weights, load

    def experts_of(p, x, experts, weights, held=(first, end)):
        """sum over the held experts e of [e in S] p_e E_e(x): every held
        expert applied to every token, masked by the selection. x ``[N,
        d]``; ``p["W1"][i]``, ``p["W2"][i]`` are expert ``held[0] + i``'s."""
        y = jnp.zeros_like(x)
        for i, e in enumerate(range(*held)):
            w_e = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
            y = y + w_e[:, None] * mlp({"W1": p["W1"][i], "W2": p["W2"][i]},
                                       x)
        return y

    def routed(p, bias, x):
        """-> (y ``[B, T, d]``, load ``[E]``), the experts in token blocks."""
        B, T, d = x.shape
        xt = x.reshape(B * T, d)
        experts, weights, load = route(p, bias, xt)
        tb = math.gcd(B * T, 2048)
        y = lax.map(jax.checkpoint(lambda a: experts_of(p, *a)), (
            xt.reshape(-1, tb, d), experts.reshape(-1, tb, k_top),
            weights.reshape(-1, tb, k_top)))
        return y.reshape(B, T, d), load

    def head_loss(E, x, labels, weight):
        """sum of weight * cross-entropy of the tied head's logits x E^T, a
        block of tokens at a time."""
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
        rms=rms, mlp=mlp, short_conv=short_conv, rotary=rotary,
        softmax_rows=softmax_rows, attention=attention, route=route,
        experts_of=experts_of, routed=routed, head_loss=head_loss)


def _ref_loss(cfg, sizes, lower, fault, params, ids, labels):
    """(mean next-token cross-entropy of one batch, {routed layer: tokens
    that selected each expert}); every layer under ``jax.checkpoint``."""
    import jax
    import jax.numpy as jnp

    ops, act = ref_ops(sizes, lower), lower.activation
    B, T = ids.shape
    bias = jnp.asarray(sizes["expert_bias"], jnp.float32)
    x = params["embed"]["W"][ids]
    loads = {}
    for l in sizes["layers_kept"]:
        h = ops.rms(params[f"l{l}_ln1"]["gain"], x)
        op = ops.attention if is_attention(sizes, l) else ops.short_conv
        x = x + act(jax.checkpoint(op)(params[f"l{l}_op"], h))
        h = ops.rms(params[f"l{l}_ln2"]["gain"], x)
        if is_routed(sizes, l):
            y, load = jax.checkpoint(ops.routed)(params[f"l{l}_ffn"], bias, h)
            loads[f"l{l}_ffn"] = {"expert_load": load}
        else:
            y = jax.checkpoint(ops.mlp)(params[f"l{l}_ffn"], h)
        x = x + act(y)
    weight = jnp.ones((B, T), jnp.float32)
    if fault == "half_batch":   # the second half of each sequence left out
        weight = weight * (jnp.arange(T) < T // 2)[None, :]
    loss = ops.head_loss(params["embed"]["W"],
                         ops.rms(params["final_ln"]["gain"], x), labels,
                         weight / jnp.sum(weight))
    return loss, loads


@functools.lru_cache(maxsize=None)
def _ref_grad(cfg_key: str, sizes_key: str, lower, fault: str):
    import json

    import jax

    cfg, sizes = json.loads(cfg_key), json.loads(sizes_key)
    return jax.jit(jax.value_and_grad(
        functools.partial(_ref_loss, cfg, sizes, lower, fault), has_aux=True))


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
    comparison reads: each step's loss, the first gradient, the parameters'
    change and the buffers' change (each routed layer's ``expert_load``:
    the reference's own counts of its own selections, summed over the
    steps). Loss and gradient on the accelerator; the master weights, the
    float32 moments and the AdamW update on the host (XLA's CPU device, a
    leaf at a time)."""
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
    losses, first_grad, loads = [], None, None
    for t, b in enumerate(batches, 1):
        (loss, load), g = grad(jax.device_put(params, chip),
                               jnp.asarray(b["ids"]), jnp.asarray(b["labels"]))
        losses.append(float(loss))
        load = jax.device_get(load)
        loads = load if loads is None else jax.tree.map(np.add, loads, load)
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
            "param_change": jax.tree.map(jnp.subtract, params, w0),
            "buffer_change": loads}
