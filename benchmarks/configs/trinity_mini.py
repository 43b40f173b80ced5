"""trinity_mini: the system under test, its plain reference and its counts.

Trinity-Mini (``model_type`` ``afmoe``, Arcee's 26B-A3B; arcee-ai's
``config.json``; the family's modelling code is ``models/afmoe`` of Hugging
Face ``transformers``) cut to one chip as ``trinity_mini.json`` states:
published layers ``layers_kept`` at every published width, experts
``experts_held`` of each routed layer's 128 behind a router of the published
width, and an eighth of the untied vocabulary. Three parts, which share only
the layer table below:

- ``build`` wraps ``deeplearning4j_tpu.models.TrinityMini`` and drives
  ``ComputationGraph.fit`` — the only part that imports the program;
- ``make_weights`` draws the initial weights on the device from the seed;
- ``reference`` is the same training step in plain ``jax.numpy``: float32,
  every product at ``highest``, the embedding's scale, the four norms of a
  block, RMSNorm and the rotate-half rotary embedding written out, the
  attention as an explicit masked softmax in blocks of query rows (a window
  layer over its band of keys only, a full layer over every key, without
  rotation) with the key/value heads repeated for their groups and the
  output gate, the router with ``lax.top_k``, each held expert applied to
  every token and masked by whether the token selected it, the shared expert
  beside them, the untied head's loss in token blocks, autodiff for every
  gradient, the balance rule and AdamW written out with float32 moments. It
  imports nothing of the program. Layers run under ``jax.checkpoint``; the
  moments, the update and the balance rule live on the host.

Departures from the published description (each also in the file's
``assumed``): weight decay on every leaf, as the framework's AdamW applies
it; one chip's share of the experts and of the vocabulary, in program and
reference alike, and the balance rule over this chip's counts.
"""

from __future__ import annotations

import functools
import math

import numpy as np

STEP_PROGRAM = "jit_step"       # name of the compiled step in the device trace

_SIZE_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "num_experts", "num_shared_experts", "num_experts_per_tok",
              "router_width", "experts_held", "route_scale",
              "route_norm_eps", "num_dense_layers", "layer_types",
              "sliding_window", "rms_norm_eps", "rope_theta", "vocab_size",
              "layers_kept", "load_balance_coeff", "mup_enabled")


def sizes_of(cfg: dict, tiny: bool) -> dict:
    s = {k: cfg[k] for k in _SIZE_KEYS}
    if tiny:
        s.update(cfg["tiny"])
    return s


# ---------------------------------------------------------------------------
# the layer table: shapes and counts derive from it
# ---------------------------------------------------------------------------

def blocks(sizes: dict) -> list:
    """(node prefix, routed, sliding) of every block in order."""
    return [(f"l{l}", l >= sizes["num_dense_layers"],
             sizes["layer_types"][l] == "sliding_attention")
            for l in sizes["layers_kept"]]


def routed_nodes(sizes: dict) -> list:
    return [f"{name}_ffn" for name, routed, _ in blocks(sizes) if routed]


def attention_nodes(sizes: dict, sliding: bool) -> list:
    """The attention vertices of the window layers (``sliding``) or of the
    full layers."""
    return [f"{name}_attn" for name, _, s in blocks(sizes) if s == sliding]


def param_shapes(cfg: dict, sizes: dict) -> dict:
    """{node: {leaf: shape}} as ``models.TrinityMini`` names them. Dense
    weights are [in, out]; the head's ``W`` is [vocabulary, hidden] as the
    embedding's; an expert's ``W1`` is ``[W1_e | W3_e]``."""
    d, ff, mff = (sizes["hidden_size"], sizes["intermediate_size"],
                  sizes["moe_intermediate_size"])
    h, kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    held, shared = sizes["num_experts"], sizes["num_shared_experts"]
    out = {"embed": {"W": (sizes["vocab_size"], d)}}
    for name, routed, _ in blocks(sizes):
        out[f"{name}_ln1"] = {"gain": (d,)}
        out[f"{name}_attn"] = {
            "Wq": (d, h * hd), "Wk": (d, kv * hd), "Wv": (d, kv * hd),
            "Wo": (h * hd, d), "q_norm": (hd,), "k_norm": (hd,),
            "W_gate": (d, h * hd)}
        out[f"{name}_post_ln1"] = {"gain": (d,)}
        out[f"{name}_ln2"] = {"gain": (d,)}
        if routed:
            out[f"{name}_ffn"] = {"Wg": (d, sizes["router_width"]),
                                  "W1": (held, d, 2 * mff),
                                  "W2": (held, mff, d)}
            out[f"{name}_shared"] = {"W1": (d, 2 * shared * mff),
                                     "W2": (shared * mff, d)}
        else:
            out[f"{name}_ffn"] = {"W1": (d, 2 * ff), "W2": (ff, d)}
        out[f"{name}_post_ln2"] = {"gain": (d,)}
    out["final_ln"] = {"gain": (d,)}
    out["head"] = {"W": (sizes["vocab_size"], d)}
    return out


_MATRICES = ("W", "W1", "W2", "Wq", "Wk", "Wv", "Wo", "W_gate", "Wg")


def _dense_matmul_params(cfg: dict, sizes: dict) -> int:
    """Weights that EVERY token passes through in a matrix product of XLA's
    own: the attention's five projections (the gate's among them), the dense
    MLP, the routers, the shared experts and the head. Not the embedding (a
    gather), not the routed experts."""
    shapes = param_shapes(cfg, sizes)
    return sum(int(np.prod(shape))
               for node, leaves in shapes.items() if node != "embed"
               for leaf, shape in leaves.items()
               if leaf in _MATRICES and len(shape) == 2)


def attention_pairs(T: int, window) -> int:
    """(query, key) pairs a score map holds: the causal half, or the band of
    a window (query i sees i - window < j <= i)."""
    if window is None or window >= T:
        return T * (T + 1) // 2
    return window * (window + 1) // 2 + (T - window) * window


def attention_fwd_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs a sequence's attention forward requires: per layer and query
    head the pairs of its mask (a window layer's band, a full layer's causal
    half), q.k and p.v over the 128-wide head."""
    T = mix["seq"]
    pairs = sum(attention_pairs(T, sizes["sliding_window"] if s else None)
                for _, _, s in blocks(sizes))
    return float(sizes["num_attention_heads"] * 2.0 * pairs
                 * 2 * sizes["head_dim"])


def expert_flops(cfg: dict, sizes: dict, rows: float) -> float:
    """FLOPs that ``rows`` routed rows (token, held expert pairs, summed
    over the routed layers) require: nine grouped products a row — x W1, x W3
    and h W2 forward, their three input gradients and their three weight
    gradients — of 2 x hidden x moe_intermediate each. No recomputation."""
    return 18.0 * rows * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def balanced_rows(sizes: dict, tokens: int) -> float:
    """Rows the held experts of all routed layers get from ``tokens`` tokens
    when the load is balanced: k x held / router_width a token a layer."""
    return (len(routed_nodes(sizes)) * tokens * sizes["num_experts_per_tok"]
            * sizes["num_experts"] / sizes["router_width"])


def mxu_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs per sequence that the step puts through the matrix unit in
    XLA's own fusions (what ``trace_reduce.is_mxu`` times): three products a
    weight (6 FLOPs a weight a token) for ``_dense_matmul_params``, and the
    attention backward's four required products (2 x
    ``attention_fwd_flops``), which this program runs as XLA loops: a
    group of 8 query heads of 128 does not fit the backward kernel's VMEM
    budget (``supports_band_bwd_kernel``). The experts' grouped products and
    the attention forward are Pallas calls, which ``is_mxu`` never times, and
    are not counted; nor is any recomputation."""
    return (6.0 * _dense_matmul_params(cfg, sizes) * mix["seq"]
            + 2.0 * attention_fwd_flops(cfg, sizes, mix))


def model_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs per sequence that forward and backward require: the dense
    products (6 a weight a token), the attention's forward and its
    backward's four required products (3 x ``attention_fwd_flops``) and
    ``expert_flops`` at the BALANCED load (seq x 8 x 16/128 rows a routed
    layer) — from shapes only; the realised load moves while the cell trains
    its routers and this count does not follow it. Norms, the rotary
    embedding, the gate's sigmoid, the embedding's gather and the update are
    not matrix products and are left out, as is usual."""
    return (6.0 * _dense_matmul_params(cfg, sizes) * mix["seq"]
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
                if leaf in _MATRICES:
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
        from deeplearning4j_tpu.models import TrinityMini

        if chips != 1:
            raise RuntimeError("trinity_mini is cut to one chip")
        if cfg["rope_scaling"] or not cfg["route_norm"] \
                or cfg["score_func"] != "sigmoid":
            raise RuntimeError("the model and the reference route by "
                               "normalised sigmoid scores at unscaled "
                               "positions")
        if (cfg["n_group"], cfg["topk_group"]) != (1, 1):
            raise RuntimeError("the model and the reference route over one "
                               "group")
        opt = cfg["optimizer"]
        first, end = sizes["experts_held"]
        if end - first != sizes["num_experts"]:
            raise RuntimeError("experts_held does not hold num_experts")
        self.model = TrinityMini(
            layers=sizes["layers_kept"], vocab_rows=sizes["vocab_size"],
            experts_held=(first, end - first),
            hidden_size=sizes["hidden_size"],
            intermediate_size=sizes["intermediate_size"],
            moe_intermediate_size=sizes["moe_intermediate_size"],
            num_attention_heads=sizes["num_attention_heads"],
            num_key_value_heads=sizes["num_key_value_heads"],
            head_dim=sizes["head_dim"], num_experts=sizes["router_width"],
            num_experts_per_tok=sizes["num_experts_per_tok"],
            num_shared_experts=sizes["num_shared_experts"],
            route_scale=sizes["route_scale"],
            num_dense_layers=sizes["num_dense_layers"],
            num_hidden_layers=cfg["published"]["num_hidden_layers"],
            layer_types=sizes["layer_types"],
            sliding_window=sizes["sliding_window"],
            rms_norm_eps=sizes["rms_norm_eps"],
            rope_theta=sizes["rope_theta"],
            mup_enabled=sizes["mup_enabled"],
            load_balance_coeff=sizes["load_balance_coeff"],
            seq_len=mix["seq"], compute_dtype=cfg["compute_dtype"] or None,
            state_dtype=cfg["updater_state_dtype"] or None,
            remat_policy=cfg["remat_policy"],
            learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"]).init()
        up = self.model.conf.global_conf.updater
        if (up.beta1, up.beta2, up.epsilon) != (opt["beta1"], opt["beta2"],
                                                opt["epsilon"]):
            raise RuntimeError("the zoo model's optimizer is not the "
                               "configuration's")
        eps = {self.model.conf.nodes[n].layer.norm_eps
               for n in routed_nodes(sizes)}
        if eps != {sizes["route_norm_eps"]}:
            raise RuntimeError("the zoo model's routing epsilon is not the "
                               "configuration's")
        self.beta1 = opt["beta1"]
        self.width = sizes["router_width"]
        self.routed = routed_nodes(sizes)

    def reset(self, weights) -> None:
        """Start from the benchmark's weights: fresh moments, iteration 0,
        the expert load cleared and the selection bias at zero.
        ``weights`` is consumed (the step donates its parameters)."""
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
                "bias": jnp.zeros((self.width,), jnp.float32),
                "expert_load": jnp.zeros((self.width,), jnp.float32)}
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
        """Each routed layer's selection bias, which the balance rule moves,
        and its ``expert_load``: the tokens that selected each of the
        router's experts since ``reset``."""
        return {name: {"bias": self.model._states[name]["bias"],
                       "expert_load": self.model._states[name]["expert_load"]}
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
        """Drop the model. The buffers' last reading stays, on the host, for
        ``metrics/moe_gmm_roofline_share.py``'s ``read`` (which
        ``trinity_moe_gmm_roofline_share`` takes): the harness has no hook
        between the traced call and here."""
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
    eps = sizes["rms_norm_eps"]
    nh, nkv, hd = (sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim"])
    k_top, scale = sizes["num_experts_per_tok"], sizes["route_scale"]
    first, end = sizes["experts_held"]

    def mm(a, w):
        return jnp.dot(q(a), q(w), precision=hi)

    def rms(gain, x):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain

    def mlp(p, x):
        """(silu(x W1) * x W3) W2 with ``p["W1"]`` = [W1 | W3]."""
        g, u = jnp.split(mm(x, p["W1"]), 2, axis=-1)
        return mm(jax.nn.silu(g) * u, p["W2"])

    def rotary(x):
        """x ``[B, H, T, hd]`` at positions 0..T-1, rotate-half: the pair
        (i, i + hd/2) turned by ``pos * theta^(-2i/hd)``."""
        T, d = x.shape[-2], x.shape[-1]
        inv_freq = sizes["rope_theta"] ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        a, b = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def softmax_rows(qh, kh, vh, window):
        """softmax(q k^T / sqrt(hd) + mask) v, a block of query rows at a
        time; qh, kh, vh ``[B, H, T, hd]`` (keys and values repeated for
        their groups). A window layer's block reads the ``window`` keys
        before it and its own: query i sees i - window < j <= i."""
        B, H, T, _ = qh.shape
        rows = math.gcd(T, 256)
        span = T if window is None else window + rows
        if window is not None:
            pad = ((0, 0), (0, 0), (window, 0), (0, 0))
            kh, vh = jnp.pad(kh, pad), jnp.pad(vh, pad)

        def block(i0):
            qi = lax.dynamic_slice_in_dim(qh, i0, rows, 2)
            ki = lax.dynamic_slice_in_dim(kh, i0, span, 2) \
                if window is not None else kh
            vi = lax.dynamic_slice_in_dim(vh, i0, span, 2) \
                if window is not None else vh
            s = jnp.einsum("bhqd,bhkd->bhqk", q(qi), q(ki),
                           precision=hi) / math.sqrt(hd)
            qpos = i0 + jnp.arange(rows)[:, None]
            kpos = jnp.arange(span)[None, :]
            if window is None:
                ok = kpos <= qpos
            else:
                kpos = kpos + i0 - window
                ok = (kpos <= qpos) & (kpos > qpos - window) & (kpos >= 0)
            p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", q(p), q(vi), precision=hi)

        out = lax.map(jax.checkpoint(block), jnp.arange(0, T, rows))
        return jnp.moveaxis(out, 0, 2).reshape(B, H, T, hd)

    def attention(p, x, sliding, rope=None, gate=True):
        """Gated grouped-query attention; a window layer rotates, a full
        layer has no position. ``rope`` / ``gate``: the planted faults
        (rotation on every layer, no gate)."""
        B, T, _ = x.shape

        def heads(a, n, gain=None):
            a = a.reshape(B, T, n, hd)
            if gain is not None:
                a = rms(gain, a)
            return a.transpose(0, 2, 1, 3)

        qh = heads(mm(x, p["Wq"]), nh, p["q_norm"])
        kh = heads(mm(x, p["Wk"]), nkv, p["k_norm"])
        vh = heads(mm(x, p["Wv"]), nkv)
        if sliding if rope is None else rope:
            qh, kh = rotary(qh), rotary(kh)
        group = nh // nkv
        kh, vh = jnp.repeat(kh, group, axis=1), jnp.repeat(vh, group, axis=1)
        o = softmax_rows(qh, kh, vh,
                         sizes["sliding_window"] if sliding else None)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, nh * hd)
        if gate:
            o = o * jax.nn.sigmoid(mm(x, p["W_gate"]))
        return mm(o, p["Wo"])

    def route(p, bias, x):
        """x ``[N, d]`` -> (experts [N, k], weights [N, k], load [E])."""
        s = jax.nn.sigmoid(mm(x, p["Wg"]))
        _, experts = lax.top_k(s + bias, k_top)
        picked = jnp.take_along_axis(s, experts, axis=-1)
        weights = picked / (jnp.sum(picked, -1, keepdims=True)
                            + sizes["route_norm_eps"]) * scale
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
        """The routed experts' part -> (y ``[B, T, d]``, load ``[E]``), the
        experts in token blocks."""
        B, T, d = x.shape
        xt = x.reshape(B * T, d)
        experts, weights, load = route(p, bias, xt)
        tb = math.gcd(B * T, 2048)
        y = lax.map(jax.checkpoint(lambda a: experts_of(p, *a)), (
            xt.reshape(-1, tb, d), experts.reshape(-1, tb, k_top),
            weights.reshape(-1, tb, k_top)))
        return y.reshape(B, T, d), load

    def head_loss(W, x, labels, weight):
        """sum of weight * cross-entropy of the head's logits x W^T, a block
        of tokens at a time."""
        x = x.reshape(-1, x.shape[-1])
        tb = math.gcd(x.shape[0], 1024)

        def block(args):
            xb, yb, wb = args
            logits = jnp.dot(q(xb), q(W).T, precision=hi)
            picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
            return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * wb)

        return jnp.sum(lax.map(jax.checkpoint(block), (
            x.reshape(-1, tb, x.shape[-1]), labels.reshape(-1, tb),
            weight.reshape(-1, tb))))

    return types.SimpleNamespace(
        mm=mm, rms=rms, mlp=mlp, rotary=rotary, softmax_rows=softmax_rows,
        attention=attention, route=route, experts_of=experts_of,
        routed=routed, head_loss=head_loss)


def balance_rule(sizes: dict, bias, load):
    """The selection bias after one step of auxiliary-loss-free balancing:
    ``δ = γ sign(mean(c) - c)``, ``b + δ - mean(δ)``; numpy, float32."""
    load = np.asarray(load, np.float32)
    delta = np.float32(sizes["load_balance_coeff"]) * np.sign(
        load.mean() - load)
    return (np.asarray(bias, np.float32) + delta - delta.mean()).astype(
        np.float32)


def ref_trunk(sizes, lower, fault, params, biases, ids):
    """(the final norm's output ``[B, T, d]``, {routed layer: tokens that
    selected each expert}) of one batch; every layer under
    ``jax.checkpoint``. ``biases``: {routed layer: selection bias}."""
    import jax

    ops, act = ref_ops(sizes, lower), lower.activation
    loads = {}
    attend = functools.partial(
        ops.attention, rope=True if fault == "rope_everywhere" else None,
        gate=fault != "no_gate")
    x = params["embed"]["W"][ids]
    if sizes["mup_enabled"]:
        x = x * math.sqrt(sizes["hidden_size"])
    for name, routed, sliding in blocks(sizes):
        h = ops.rms(params[f"{name}_ln1"]["gain"], x)
        a = jax.checkpoint(attend, static_argnums=(2,))(
            params[f"{name}_attn"], h, sliding)
        x = x + act(ops.rms(params[f"{name}_post_ln1"]["gain"], a))
        h = ops.rms(params[f"{name}_ln2"]["gain"], x)
        if routed:
            y, load = jax.checkpoint(ops.routed)(
                params[f"{name}_ffn"], biases[f"{name}_ffn"], h)
            loads[f"{name}_ffn"] = load
            y = y + jax.checkpoint(ops.mlp)(params[f"{name}_shared"], h)
        else:
            y = jax.checkpoint(ops.mlp)(params[f"{name}_ffn"], h)
        x = x + act(ops.rms(params[f"{name}_post_ln2"]["gain"], y))
    return ops.rms(params["final_ln"]["gain"], x), loads


def _ref_loss(cfg, sizes, lower, fault, params, biases, ids, labels):
    """(mean cross-entropy of one batch, the routed layers' counts)."""
    import jax.numpy as jnp

    h, loads = ref_trunk(sizes, lower, fault, params, biases, ids)
    every = jnp.ones(ids.shape, jnp.float32)
    if fault == "half_batch":   # the second half of each sequence left out
        every = every * (jnp.arange(ids.shape[1]) < ids.shape[1] // 2)[None, :]
    return (ref_ops(sizes, lower).head_loss(
        params["head"]["W"], h, labels, every / jnp.sum(every)), loads)


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
    change and the buffers' change (each routed layer's selection bias,
    moved after every step by the balance rule over the reference's own
    counts, and its ``expert_load``, those counts summed over the steps).
    Loss and gradient on the accelerator at ``highest``; the master weights,
    the float32 moments, the AdamW update and the balance rule on the host
    (XLA's CPU device, a leaf at a time). ``fault``: ``half_batch`` (the
    second half of every sequence left out of the loss), ``no_bias_rule``
    (the bias left at zero), ``rope_everywhere`` (the full layers rotated
    too), ``no_gate`` (the attention's output ungated),
    ``state_unchanged``."""
    import json

    import jax
    import jax.numpy as jnp

    from compare import EXACT

    lower = lower or EXACT
    grad = _ref_grad(json.dumps(cfg, sort_keys=True),
                     json.dumps(sizes, sort_keys=True), lower,
                     "" if fault in ("state_unchanged", "no_bias_rule")
                     else fault)
    update = _adamw(json.dumps(cfg["optimizer"], sort_keys=True), lower)
    host, chip = jax.devices("cpu")[0], jax.devices()[0]
    w0 = jax.device_put(make_weights(cfg, sizes, seed), host)
    params = jax.tree.map(jnp.copy, w0)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    width = sizes["router_width"]
    biases = {n: np.zeros((width,), np.float32) for n in routed_nodes(sizes)}
    counts = {n: np.zeros((width,), np.float32) for n in biases}
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for t, b in enumerate(batches, 1):
            (loss, load), g = grad(jax.device_put(params, chip),
                                   jax.device_put(biases, chip),
                                   jnp.asarray(b["ids"]),
                                   jnp.asarray(b["labels"]))
            losses.append(float(loss))
            for n, c in jax.device_get(load).items():
                counts[n] = counts[n] + c
                if fault != "no_bias_rule":
                    biases[n] = balance_rule(sizes, biases[n], c)
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
    if fault == "state_unchanged":
        biases = {n: np.zeros_like(b) for n, b in biases.items()}
        counts = {n: np.zeros_like(c) for n, c in counts.items()}
    return {"loss": losses, "first_gradient": first_grad,
            "param_change": jax.tree.map(jnp.subtract, params, w0),
            "buffer_change": {n: {"bias": biases[n], "expert_load": counts[n]}
                              for n in biases}}
