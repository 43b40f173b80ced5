"""joyai_llm_flash: the system under test, its plain reference and its counts.

JoyAI-LLM-Flash (``model_type`` ``joyai_llm_flash``, 48B-A2.7B; jdopensource's
``config.json``, which carries the DeepSeek-V3 key set: arXiv:2412.19437
sections 2.1-2.2 and the ``deepseek_v3`` modelling code of Hugging Face
``transformers``) cut to one chip as ``joyai_llm_flash.json`` states:
published layers ``layers_kept`` and the one multi-token-prediction module at
every published width, experts ``experts_held`` of each routed layer's 256
behind a router of the published width, and an eighth of the untied
vocabulary. Three parts, which share only the layer table below:

- ``build`` wraps ``deeplearning4j_tpu.models.JoyAILLMFlash`` and drives
  ``ComputationGraph.fit`` on a two-input, two-label ``MultiDataSet`` — the
  only part that imports the program;
- ``make_weights`` draws the initial weights on the device from the seed;
- ``reference`` is the same training step in plain ``jax.numpy``: float32,
  every product at ``highest``, RMSNorm and the rotary embedding written out
  (the interleaved pairs turned directly), latent attention as an explicit
  masked softmax in blocks of query rows over keys expanded per head, the
  router with ``lax.top_k``, each held expert applied to every token and
  masked by whether the token selected it, the shared expert beside them, the
  untied head's loss in token blocks, the multi-token-prediction module and
  its weighted loss, autodiff for every gradient, AdamW written out with
  float32 moments. It imports nothing of the program. Layers run under
  ``jax.checkpoint``; the moments and the update live on the host.

Departures from the published description (each also in the file's
``assumed``): the selection bias is a constant wave from the file (the rule
that moves it is a training recipe the ``config`` does not carry); weight
decay on every leaf, as the framework's AdamW applies it; one chip's share of
the experts and of the vocabulary, in program and reference alike.
"""

from __future__ import annotations

import functools
import math

import numpy as np

STEP_PROGRAM = "jit_step"       # name of the compiled step in the device trace

_SIZE_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
              "router_width", "experts_held", "routed_scaling_factor",
              "route_norm_eps", "first_k_dense_replace", "n_group",
              "topk_group", "rms_norm_eps", "rope_theta", "vocab_size", "layers_kept", "num_nextn_predict_layers",
              "mtp_loss_weight")


def sizes_of(cfg: dict, tiny: bool) -> dict:
    s = {k: cfg[k] for k in _SIZE_KEYS}
    if tiny:
        s.update(cfg["tiny"])
    wave = cfg["expert_bias"]
    s["expert_bias"] = [
        wave["amplitude"] * math.cos(2 * math.pi * e / wave["period"])
        for e in range(s["router_width"])]
    return s


# ---------------------------------------------------------------------------
# the layer table: shapes and counts derive from it
# ---------------------------------------------------------------------------

def blocks(sizes: dict) -> list:
    """(node prefix, routed) of every block in order: the trunk's layers and
    the multi-token-prediction module's one."""
    out = [(f"l{l}", l >= sizes["first_k_dense_replace"])
           for l in sizes["layers_kept"]]
    return out + [("mtp", True)] * sizes["num_nextn_predict_layers"]


def routed_nodes(sizes: dict) -> list:
    return [f"{name}_ffn" for name, routed in blocks(sizes) if routed]


def param_shapes(cfg: dict, sizes: dict) -> dict:
    """{node: {leaf: shape}} as ``models.JoyAILLMFlash`` names them. Dense
    weights are [in, out]; the head's ``W`` is [vocabulary, hidden] as the
    embedding's; an expert's ``W1`` is ``[W1_e | W3_e]``."""
    d, ff, mff = (sizes["hidden_size"], sizes["intermediate_size"],
                  sizes["moe_intermediate_size"])
    h, qr, kvr = (sizes["num_attention_heads"], sizes["q_lora_rank"],
                  sizes["kv_lora_rank"])
    nope, rope, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                      sizes["v_head_dim"])
    held, shared = sizes["n_routed_experts"], sizes["n_shared_experts"]
    out = {"embed": {"W": (sizes["vocab_size"], d)}}
    for name, routed in blocks(sizes):
        if name == "mtp":
            out["mtp_merge"] = {"e_norm": (d,), "h_norm": (d,),
                                "W_eh": (2 * d, d)}
        out[f"{name}_ln1"] = {"gain": (d,)}
        out[f"{name}_attn"] = {
            "W_qa": (d, qr), "q_norm": (qr,), "W_qb": (qr, h * (nope + rope)),
            "W_kva": (d, kvr + rope), "kv_norm": (kvr,),
            "W_kvb": (kvr, h * (nope + dv)), "W_o": (h * dv, d)}
        out[f"{name}_ln2"] = {"gain": (d,)}
        if routed:
            out[f"{name}_ffn"] = {"Wg": (d, sizes["router_width"]),
                                  "W1": (held, d, 2 * mff),
                                  "W2": (held, mff, d)}
            out[f"{name}_shared"] = {"W1": (d, 2 * shared * mff),
                                     "W2": (shared * mff, d)}
        else:
            out[f"{name}_ffn"] = {"W1": (d, 2 * ff), "W2": (ff, d)}
        if name == "mtp":
            out["mtp_final_ln"] = {"gain": (d,)}
        elif name == f"l{sizes['layers_kept'][-1]}":
            out["final_ln"] = {"gain": (d,)}
            out["head"] = {"W": (sizes["vocab_size"], d)}
    return out


_MATRICES = ("W", "W1", "W2", "W_qa", "W_qb", "W_kva", "W_kvb", "W_o", "Wg",
             "W_eh")


def _dense_matmul_params(cfg: dict, sizes: dict) -> int:
    """Weights that EVERY token passes through in a matrix product of XLA's
    own: the latent attention's five projections, the dense MLP, the routers,
    the shared experts, ``W_eh`` and the head — twice, once a use (the
    trunk's and the prediction module's). Not the embedding (a gather), not
    the routed experts."""
    shapes = param_shapes(cfg, sizes)
    total = sum(int(np.prod(shape))
                for node, leaves in shapes.items() if node != "embed"
                for leaf, shape in leaves.items()
                if leaf in _MATRICES and len(shape) == 2)
    return total + sizes["num_nextn_predict_layers"] * int(
        np.prod(shapes["head"]["W"]))


def attention_fwd_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs a sequence's attention forward requires: per block and head the
    causal half's (query, key) pairs, q.k over the head's width (nope + rope)
    and p.v over the value's."""
    T = mix["seq"]
    wide = (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
            + sizes["v_head_dim"])
    return float(len(blocks(sizes)) * sizes["num_attention_heads"]
                 * 2.0 * (T * (T + 1) // 2) * wide)


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
            * sizes["n_routed_experts"] / sizes["router_width"])


def mxu_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs per sequence that the step puts through the matrix unit in
    XLA's own fusions (what ``trace_reduce.is_mxu`` times): three products a
    weight (6 FLOPs a weight a token) for ``_dense_matmul_params``. The
    experts' grouped products and the attention, forward and backward, are
    Pallas calls, which ``is_mxu`` never times, and are not counted; nor is
    any recomputation."""
    return 6.0 * _dense_matmul_params(cfg, sizes) * mix["seq"]


def model_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs per sequence that forward and backward require: ``mxu_flops``,
    the attention's forward and its backward's four required products (3 x
    ``attention_fwd_flops``) and ``expert_flops`` at the BALANCED load (seq x
    8 x 16/256 rows a routed layer) — from shapes only; the realised load
    grows while the cell trains its routers (``PERF.md`` section 6, PR 32 and
    PR 34) and this count does not follow it. Norms, the rotary embedding,
    the embedding's gathers and the update are not matrix products and are
    left out, as is usual."""
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
                if leaf in _MATRICES:
                    out[node][leaf] = jax.random.normal(k, shape, f32) * 0.02
                else:                   # gains
                    out[node][leaf] = jnp.ones(shape, f32)
        return out

    return draw(jax.random.key(int(seed) % (2 ** 63), impl="threefry2x32"))


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def two_heads(ids, labels):
    """The generator's ``ids`` and next-token ``labels`` ``[B, T]`` as the
    model's two inputs, two label arrays and the second head's mask: the
    prediction module reads each position's next token (the main head's
    labels) and is held to the token after it, which the last position does
    not have."""
    after = np.concatenate([labels[:, 1:], np.zeros_like(labels[:, :1])], 1)
    mask = np.ones(labels.shape, np.float32)
    mask[:, -1] = 0.0
    return [ids, labels], [labels, after], [None, mask]


class Job:
    """``ComputationGraph.fit(MultiDataSet, epochs, batch_size)`` and what
    the comparison reads of its state."""

    def __init__(self, cfg: dict, sizes: dict, chips: int, mix: dict):
        from deeplearning4j_tpu.models import JoyAILLMFlash

        if chips != 1:
            raise RuntimeError("joyai_llm_flash is cut to one chip")
        if not cfg["rope_interleave"] or cfg["rope_scaling"]:
            raise RuntimeError("the model and the reference turn interleaved "
                               "pairs at unscaled positions")
        opt = cfg["optimizer"]
        first, end = sizes["experts_held"]
        if end - first != sizes["n_routed_experts"]:
            raise RuntimeError("experts_held does not hold n_routed_experts")
        self.model = JoyAILLMFlash(
            layers=sizes["layers_kept"], vocab_rows=sizes["vocab_size"],
            experts_held=(first, end - first),
            expert_bias=sizes["expert_bias"],
            mtp=bool(sizes["num_nextn_predict_layers"]),
            hidden_size=sizes["hidden_size"],
            intermediate_size=sizes["intermediate_size"],
            moe_intermediate_size=sizes["moe_intermediate_size"],
            num_attention_heads=sizes["num_attention_heads"],
            q_lora_rank=sizes["q_lora_rank"],
            kv_lora_rank=sizes["kv_lora_rank"],
            qk_nope_head_dim=sizes["qk_nope_head_dim"],
            qk_rope_head_dim=sizes["qk_rope_head_dim"],
            v_head_dim=sizes["v_head_dim"],
            n_routed_experts=sizes["router_width"],
            n_shared_experts=sizes["n_shared_experts"],
            num_experts_per_tok=sizes["num_experts_per_tok"],
            routed_scaling_factor=sizes["routed_scaling_factor"],
            first_k_dense_replace=sizes["first_k_dense_replace"],
            num_hidden_layers=cfg["published"]["num_hidden_layers"],
            n_group=sizes["n_group"], topk_group=sizes["topk_group"],
            rms_norm_eps=sizes["rms_norm_eps"],
            rope_theta=sizes["rope_theta"],
            mtp_loss_weight=sizes["mtp_loss_weight"],
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
        self.bias = sizes["expert_bias"]
        self.routed = routed_nodes(sizes)

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
        from deeplearning4j_tpu.data import MultiDataSet

        self.batch = batches[0]["ids"].shape[0]
        features, labels, masks = two_heads(
            np.concatenate([b["ids"] for b in batches]),
            np.concatenate([b["labels"] for b in batches]))
        return MultiDataSet(features, labels, labels_masks=masks)

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
        """Drop the model. The buffers' last reading stays, on the host, for
        ``metrics/moe_gmm_roofline_share.py``'s ``read`` (which
        ``mla_moe_gmm_roofline_share`` takes): the harness has no hook
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
    nh, r = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
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

    def rotary(x):
        """x ``[B, H, T, rope]`` at positions 0..T-1: the pair (2i, 2i+1)
        turned by ``pos * theta^(-2i/rope)`` (``rope_interleave`` true)."""
        T, d = x.shape[-2], x.shape[-1]
        inv_freq = sizes["rope_theta"] ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(x.shape)

    def softmax_rows(qh, kh, vh):
        """softmax(q k^T / sqrt(head width) + causal mask) v, a block of
        query rows at a time; qh, kh ``[B, H, T, nope + rope]``, vh ``[B, H,
        T, v_head_dim]``."""
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
        """Latent attention as it is trained: the keys expanded per head."""
        B, T, _ = x.shape
        heads = lambda a: a.reshape(B, T, nh, -1).transpose(0, 2, 1, 3)  # noqa: E731
        qh = heads(mm(rms(p["q_norm"], mm(x, p["W_qa"])), p["W_qb"]))
        qh = jnp.concatenate([qh[..., :nope], rotary(qh[..., nope:])], -1)
        kva = mm(x, p["W_kva"])
        kv = heads(mm(rms(p["kv_norm"], kva[..., :r]), p["W_kvb"]))
        k_rope = rotary(kva[:, None, :, r:])            # one a token
        kh = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_rope, (B, nh, T, rope))], -1)
        o = softmax_rows(qh, kh, kv[..., nope:])
        return mm(o.transpose(0, 2, 1, 3).reshape(B, T, -1), p["W_o"])

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


def _ref_loss(cfg, sizes, lower, fault, params, ids, labels):
    """(L_main + mtp_loss_weight * L_mtp of one batch, {routed layer: tokens
    that selected each expert}); every layer under ``jax.checkpoint``."""
    import jax
    import jax.numpy as jnp

    ops, act = ref_ops(sizes, lower), lower.activation
    B, T = ids.shape
    bias = jnp.asarray(sizes["expert_bias"], jnp.float32)
    loads = {}

    def block(name, routed, x):
        h = ops.rms(params[f"{name}_ln1"]["gain"], x)
        x = x + act(jax.checkpoint(ops.attention)(params[f"{name}_attn"], h))
        h = ops.rms(params[f"{name}_ln2"]["gain"], x)
        if routed:
            y, load = jax.checkpoint(ops.routed)(params[f"{name}_ffn"], bias,
                                                 h)
            loads[f"{name}_ffn"] = {"expert_load": load}
            y = y + jax.checkpoint(ops.mlp)(params[f"{name}_shared"], h)
        else:
            y = jax.checkpoint(ops.mlp)(params[f"{name}_ffn"], h)
        return x + act(y)

    every = jnp.ones((B, T), jnp.float32)
    if fault == "half_batch":   # the second half of each sequence left out
        every = every * (jnp.arange(T) < T // 2)[None, :]
    E, W = params["embed"]["W"], params["head"]["W"]
    x = E[ids]
    for name, routed in blocks(sizes):
        if name == "mtp":
            continue
        x = block(name, routed, x)
    h = ops.rms(params["final_ln"]["gain"], x)
    loss = ops.head_loss(W, h, labels, every / jnp.sum(every))
    if sizes["num_nextn_predict_layers"]:
        # position i: the trunk's h_i with the embedding of token i+1 (the
        # main head's label), held to token i+2; the last position has none
        p = params["mtp_merge"]
        m = ops.mm(jnp.concatenate(
            [ops.rms(p["e_norm"], E[labels]), ops.rms(p["h_norm"], h)], -1),
            p["W_eh"])
        x = block("mtp", True, act(m))
        after = jnp.concatenate([labels[:, 1:], labels[:, :1] * 0], 1)
        weight = every * (jnp.arange(T) < T - 1)[None, :]
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True) / B
        # the planted fault: the module runs and its loss counts for nothing
        share = 0.0 if fault == "no_mtp" else sizes["mtp_loss_weight"]
        loss = loss + share * ops.head_loss(
            W, ops.rms(params["mtp_final_ln"]["gain"], x), after, weight)
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
    leaf at a time). ``fault``: ``half_batch`` (the second half of every
    sequence left out of both losses), ``no_mtp`` (the prediction module's
    loss left out), ``state_unchanged``."""
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
