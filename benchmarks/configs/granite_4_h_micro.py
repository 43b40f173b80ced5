"""granite_4_h_micro: the system under test, its plain reference and its counts.

Granite-4.0-H-Micro (``model_type`` ``granitemoehybrid``; ibm-granite's
``config.json``; the family's modelling code is ``models/granitemoehybrid``
of Hugging Face ``transformers``) cut to one chip as
``granite_4_h_micro.json`` states: published layers ``layers_kept`` (one
whole period of ``layer_types``: nine Mamba-2 layers and one attention layer)
at every published width, and a quarter of the tied vocabulary. Three parts,
which share only the layer table below:

- ``build`` wraps ``deeplearning4j_tpu.models.GraniteHybrid`` and drives
  ``ComputationGraph.fit`` — the only part that imports the program;
- ``make_weights`` draws the initial weights on the device from the seed;
- ``reference`` is the same training step in plain ``jax.numpy``: float32,
  every product at ``highest``, the family's multipliers, RMSNorm written out,
  the Mamba-2 mixer with its scan as the PER-STEP recurrence (never the
  chunked dual form the program computes) in blocks of steps under
  ``jax.checkpoint``, the attention as an explicit masked softmax in blocks
  of query rows with the key/value heads repeated for their groups, the tied
  head's loss in token blocks, autodiff for every gradient and AdamW written
  out with float32 moments. It imports nothing of the program. Layers run
  under ``jax.checkpoint``; the moments and the update live on the host.

Departures from the published description (each also in the file's
``assumed``): weight decay on every leaf, as the framework's AdamW applies
it; a quarter of the vocabulary, in program and reference alike.
"""

from __future__ import annotations

import functools
import math

import numpy as np

STEP_PROGRAM = "jit_step"       # name of the compiled step in the device trace

_SIZE_KEYS = ("hidden_size", "shared_intermediate_size",
              "num_attention_heads", "num_key_value_heads",
              "attention_multiplier", "embedding_multiplier",
              "residual_multiplier", "logits_scaling", "mamba_n_heads",
              "mamba_d_head", "mamba_d_state", "mamba_n_groups",
              "mamba_d_conv", "mamba_chunk_size", "layer_types",
              "rms_norm_eps", "vocab_size", "layers_kept")


def sizes_of(cfg: dict, tiny: bool) -> dict:
    s = {k: cfg[k] for k in _SIZE_KEYS}
    if tiny:
        s.update(cfg["tiny"])
    return s


# ---------------------------------------------------------------------------
# the layer table: shapes and counts derive from it
# ---------------------------------------------------------------------------

def blocks(sizes: dict) -> list:
    """(node prefix, mixer node, attention?) of every block in order."""
    out = []
    for l in sizes["layers_kept"]:
        attn = sizes["layer_types"][l] == "attention"
        out.append((f"l{l}", f"l{l}_attn" if attn else f"l{l}_mamba", attn))
    return out


def mamba_nodes(sizes: dict) -> list:
    return [mixer for _, mixer, attn in blocks(sizes) if not attn]


def widths(sizes: dict) -> dict:
    """The Mamba-2 mixer's derived widths: d_inner (heads x head), the
    convolution's channels (X, B and C) and the input projection's."""
    di = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    conv = di + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    return {"d_inner": di, "conv": conv,
            "in_proj": di + conv + sizes["mamba_n_heads"]}


def param_shapes(cfg: dict, sizes: dict) -> dict:
    """{node: {leaf: shape}} as ``models.GraniteHybrid`` names them. Dense
    weights are [in, out]; the embedding's ``W`` is [vocabulary, hidden] and
    is the head's too (tied)."""
    d, ff = sizes["hidden_size"], sizes["shared_intermediate_size"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = d // nh
    h = sizes["mamba_n_heads"]
    w = widths(sizes)
    out = {"embed": {"W": (sizes["vocab_size"], d)}}
    for name, mixer, attn in blocks(sizes):
        out[f"{name}_ln1"] = {"gain": (d,)}
        if attn:
            out[mixer] = {"Wq": (d, nh * hd), "Wk": (d, nkv * hd),
                          "Wv": (d, nkv * hd), "Wo": (nh * hd, d)}
        else:
            out[mixer] = {
                "W_in": (d, w["in_proj"]),
                "conv_w": (sizes["mamba_d_conv"], w["conv"]),
                "conv_b": (w["conv"],), "dt_bias": (h,), "A_log": (h,),
                "D": (h,), "norm": (w["d_inner"],),
                "W_out": (w["d_inner"], d)}
        out[f"{name}_ln2"] = {"gain": (d,)}
        out[f"{name}_mlp"] = {"W1": (d, 2 * ff), "W2": (ff, d)}
    out["final_ln"] = {"gain": (d,)}
    return out


_MATRICES = ("W", "W1", "W2", "Wq", "Wk", "Wv", "Wo", "W_in", "W_out")


def matrix_params(cfg: dict, sizes: dict) -> int:
    """Weights that every token passes through in a matrix product: the
    projections, the MLPs and the tied table, which the head multiplies by
    (its gather at the input is no product). Not the convolution's taps."""
    shapes = param_shapes(cfg, sizes)
    return sum(int(np.prod(shape)) for leaves in shapes.values()
               for leaf, shape in leaves.items() if leaf in _MATRICES)


def attention_fwd_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs a sequence's attention forward requires: per attention layer
    and query head the causal half of the score map, q.k and p.v over the
    head."""
    T = mix["seq"]
    layers = sum(attn for _, _, attn in blocks(sizes))
    hd = sizes["hidden_size"] // sizes["num_attention_heads"]
    return float(layers * sizes["num_attention_heads"] * 2.0
                 * (T * (T + 1) // 2) * 2 * hd)


def ssd_fwd_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs a sequence's SSD scans require forward: in every chunk of L
    steps, ``C B^T`` once a group and each head's masked product at their
    causal half (L(L+1)/2 pairs), and each head's two state products in full
    (``C H_in`` and the state update, L x N x P each)."""
    T, L = mix["seq"], sizes["mamba_chunk_size"]
    N, P = sizes["mamba_d_state"], sizes["mamba_d_head"]
    H, G = sizes["mamba_n_heads"], sizes["mamba_n_groups"]
    half = L * (L + 1) / 2
    chunk = G * 2.0 * N * half + H * (2.0 * P * half + 2 * 2.0 * L * N * P)
    return len(mamba_nodes(sizes)) * math.ceil(T / L) * chunk


def ssd_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """A sequence's SSD FLOPs forward and backward: the backward counted as
    twice the forward; the forward that rematerialisation runs again is
    counted once."""
    return 3.0 * ssd_fwd_flops(cfg, sizes, mix)


def ssd_bytes(cfg: dict, sizes: dict, mix: dict) -> float:
    """Bytes a sequence's SSD scans have to move: X, B, C (bfloat16) and dt
    (float32) in, y out, and their cotangents (dy in; dX, dB, dC, ddt out);
    the float32 chunk-start states written by the forward and read back by
    the backward. Each counted once a layer; the forward that
    rematerialisation runs again is not counted."""
    T, L = mix["seq"], sizes["mamba_chunk_size"]
    N, P = sizes["mamba_d_state"], sizes["mamba_d_head"]
    H, G = sizes["mamba_n_heads"], sizes["mamba_n_groups"]
    x = 2.0 * T * H * P
    bc = 2.0 * T * G * N
    dt = 4.0 * T * H
    starts = 4.0 * math.ceil(T / L) * H * N * P
    per_layer = 2 * (x + 2 * bc + dt) + 2 * x + 2 * starts
    return len(mamba_nodes(sizes)) * per_layer


def mxu_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs per sequence that the step puts through the matrix unit in
    XLA's own fusions (what ``trace_reduce.is_mxu`` times): three products a
    weight (6 FLOPs a weight a token) for ``matrix_params``. The attention
    forward and backward and the SSD scans are Pallas calls, which
    ``is_mxu`` never times, and are not counted; nor is any
    recomputation."""
    return 6.0 * matrix_params(cfg, sizes) * mix["seq"]


def model_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs per sequence that forward and backward require: the dense
    products (6 a weight a token), the attention's forward and its
    backward's four required products (3 x ``attention_fwd_flops``) and the
    SSD's (``ssd_flops``). The convolution, the norms, the decays and the
    update are not matrix products and are left out, as is usual."""
    return (mxu_flops(cfg, sizes, mix)
            + 3.0 * attention_fwd_flops(cfg, sizes, mix)
            + ssd_flops(cfg, sizes, mix))


# ---------------------------------------------------------------------------
# weights from the seed (one jitted call on the device)
# ---------------------------------------------------------------------------

def make_weights(cfg: dict, sizes: dict, seed: int, mix: dict = None):
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(cfg, sizes)
    f32 = jnp.float32

    def leaf_value(leaf, shape, k):
        if leaf in _MATRICES or leaf == "conv_w":
            return jax.random.normal(k, shape, f32) * 0.02
        if leaf == "A_log":     # A = -(1..heads)
            return jnp.log(jnp.arange(1, shape[0] + 1, dtype=f32))
        if leaf == "conv_b":
            return jnp.zeros(shape, f32)
        return jnp.ones(shape, f32)     # gains, dt_bias, D

    @jax.jit
    def draw(key):
        out = {}
        for i, (node, leaves) in enumerate(shapes.items()):
            out[node] = {}
            for j, (leaf, shape) in enumerate(leaves.items()):
                k = jax.random.fold_in(jax.random.fold_in(key, i), j)
                out[node][leaf] = leaf_value(leaf, shape, k)
        return out

    return draw(jax.random.key(int(seed) % (2 ** 63), impl="threefry2x32"))


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

class Job:
    """``ComputationGraph.fit(DataSet, epochs, batch_size)`` and what the
    comparison reads of its state."""

    def __init__(self, cfg: dict, sizes: dict, chips: int, mix: dict):
        from deeplearning4j_tpu.models import GraniteHybrid

        if chips != 1:
            raise RuntimeError("granite_4_h_micro is cut to one chip")
        if (cfg["position_embedding_type"] != "nope"
                or cfg["num_local_experts"] or cfg["attention_bias"]
                or cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"]
                or not cfg["tie_word_embeddings"]):
            raise RuntimeError("the model and the reference are the dense "
                               "NoPE family with a tied head, a biased "
                               "convolution and no other bias")
        opt = cfg["optimizer"]
        self.model = GraniteHybrid(
            layers=sizes["layers_kept"], vocab_rows=sizes["vocab_size"],
            hidden_size=sizes["hidden_size"],
            shared_intermediate_size=sizes["shared_intermediate_size"],
            num_attention_heads=sizes["num_attention_heads"],
            num_key_value_heads=sizes["num_key_value_heads"],
            attention_multiplier=sizes["attention_multiplier"],
            embedding_multiplier=sizes["embedding_multiplier"],
            residual_multiplier=sizes["residual_multiplier"],
            logits_scaling=sizes["logits_scaling"],
            mamba_n_heads=sizes["mamba_n_heads"],
            mamba_d_head=sizes["mamba_d_head"],
            mamba_d_state=sizes["mamba_d_state"],
            mamba_n_groups=sizes["mamba_n_groups"],
            mamba_d_conv=sizes["mamba_d_conv"],
            mamba_chunk_size=sizes["mamba_chunk_size"],
            num_hidden_layers=cfg["published"]["num_hidden_layers"],
            layer_types=sizes["layer_types"],
            rms_norm_eps=sizes["rms_norm_eps"], seq_len=mix["seq"],
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

STEP_BLOCK = 256    # steps of the reference's scan under one checkpoint
TOKEN_BLOCK = 2048  # tokens of the reference's MLP under one checkpoint


def ref_ops(sizes: dict, lower):
    """The two kinds of mixer, the MLP and the head's loss as plain functions
    of float32 arrays ``[B, T, F]``: every product at ``highest``, nothing
    fused, nothing of the program. ``lower.operand`` rounds the operands of
    every product, the scan's among them (the control); exact in every
    benchmark run."""
    import types

    import jax
    import jax.numpy as jnp
    from jax import lax

    q = lower.operand
    hi = lax.Precision.HIGHEST
    eps = sizes["rms_norm_eps"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["hidden_size"] // nh
    H, P = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    G, N = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    w = widths(sizes)
    di = w["d_inner"]

    def mm(a, m):
        return jnp.dot(q(a), q(m), precision=hi)

    def rms(gain, x):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain

    def mlp(p, x):
        """(silu(x W1_g) * x W1_u) W2 with ``p["W1"]`` = [W1_g | W1_u], a
        block of ``TOKEN_BLOCK`` tokens at a time under jax.checkpoint."""
        rows = x.reshape(-1, x.shape[-1])
        tb = math.gcd(rows.shape[0], TOKEN_BLOCK)

        def block(xb):
            g, u = jnp.split(mm(xb, p["W1"]), 2, axis=-1)
            return mm(jax.nn.silu(g) * u, p["W2"])

        out = lax.map(jax.checkpoint(block),
                      rows.reshape(-1, tb, rows.shape[-1]))
        return out.reshape(x.shape)

    def scan(x, dt, A, Bm, Cm, drop_at=None):
        """H_t = exp(dt_t A) H_{t-1} + dt_t X_t (x) B_t; Y_t = H_t C_t, one
        step at a time, a head's state ``[P, N]``, head h reading group h //
        (H/G); blocks of ``STEP_BLOCK`` steps under jax.checkpoint. x ``[B,
        T, H, P]``, dt ``[B, T, H]``, Bm/Cm ``[B, T, G, N]``. ``drop_at``: the
        planted fault, the state carried into step ``drop_at`` dropped."""
        b, T = x.shape[:2]
        rep = H // G

        def step(h, xs):
            t, x_t, dt_t, b_t, c_t = xs
            if drop_at is not None:
                h = jnp.where(t == drop_at, 0.0, h)
            b_t, c_t = jnp.repeat(b_t, rep, 1), jnp.repeat(c_t, rep, 1)
            h = (jnp.exp(dt_t * A)[..., None, None] * h
                 + q(dt_t[..., None] * x_t)[..., None] * q(b_t)[:, :, None])
            return h, jnp.einsum("bhpn,bhn->bhp", q(h), q(c_t), precision=hi)

        blk = math.gcd(T, STEP_BLOCK)
        tm = lambda a: jnp.moveaxis(a, 1, 0).reshape(     # noqa: E731
            T // blk, blk, b, *a.shape[2:])
        _, y = lax.scan(
            jax.checkpoint(lambda h, xs: lax.scan(step, h, xs)),
            jnp.zeros((b, H, P, N), jnp.float32),
            (jnp.arange(T).reshape(T // blk, blk), tm(x), tm(dt), tm(Bm),
             tm(Cm)))
        return jnp.moveaxis(y.reshape(T, b, H, P), 0, 1)

    def mamba(p, x, drop_at=None):
        """The Mamba-2 mixer of ``Mamba2Layer``'s docstring, in float32."""
        B, T, _ = x.shape
        proj = mm(x, p["W_in"])
        z, xbc, dt = (proj[..., :di], proj[..., di:di + w["conv"]],
                      proj[..., di + w["conv"]:])
        k = p["conv_w"].shape[0]
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        xbc = jax.nn.silu(p["conv_b"] + sum(
            padded[:, i:i + T] * p["conv_w"][i] for i in range(k)))
        xs = xbc[..., :di].reshape(B, T, H, P)
        Bm = xbc[..., di:di + G * N].reshape(B, T, G, N)
        Cm = xbc[..., di + G * N:].reshape(B, T, G, N)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        y = scan(xs, dt, -jnp.exp(p["A_log"]), Bm, Cm, drop_at)
        y = (y + p["D"][:, None] * xs).reshape(B, T, di)
        return mm(rms(p["norm"], y * jax.nn.silu(z)), p["W_out"])

    def softmax_rows(qh, kh, vh):
        """softmax(q k^T * attention_multiplier + causal mask) v, a block of
        query rows at a time; qh, kh, vh ``[B, H, T, hd]`` (keys and values
        repeated for their groups)."""
        B, Hq, T, _ = qh.shape
        rows = math.gcd(T, 256)

        def block(i0):
            qi = lax.dynamic_slice_in_dim(qh, i0, rows, 2)
            s = jnp.einsum("bhqd,bhkd->bhqk", q(qi), q(kh),
                           precision=hi) * sizes["attention_multiplier"]
            ok = jnp.arange(T)[None, :] <= i0 + jnp.arange(rows)[:, None]
            pr = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", q(pr), q(vh), precision=hi)

        out = lax.map(jax.checkpoint(block), jnp.arange(0, T, rows))
        return jnp.moveaxis(out, 0, 2).reshape(B, Hq, T, hd)

    def attention(p, x):
        """Grouped-query attention without position, bias or per-head
        norm."""
        B, T, _ = x.shape
        heads = lambda a, n: a.reshape(B, T, n, hd).transpose(  # noqa: E731
            0, 2, 1, 3)
        group = nh // nkv
        kh = jnp.repeat(heads(mm(x, p["Wk"]), nkv), group, axis=1)
        vh = jnp.repeat(heads(mm(x, p["Wv"]), nkv), group, axis=1)
        o = softmax_rows(heads(mm(x, p["Wq"]), nh), kh, vh)
        return mm(o.transpose(0, 2, 1, 3).reshape(B, T, nh * hd), p["Wo"])

    def head_loss(W, x, labels, weight):
        """sum of weight * cross-entropy of the tied head's logits (x W^T) /
        logits_scaling, a block of tokens at a time."""
        x = x.reshape(-1, x.shape[-1])
        tb = math.gcd(x.shape[0], 1024)

        def block(args):
            xb, yb, wb = args
            logits = jnp.dot(q(xb), q(W).T, precision=hi) / sizes[
                "logits_scaling"]
            picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
            return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * wb)

        return jnp.sum(lax.map(jax.checkpoint(block), (
            x.reshape(-1, tb, x.shape[-1]), labels.reshape(-1, tb),
            weight.reshape(-1, tb))))

    return types.SimpleNamespace(
        mm=mm, rms=rms, mlp=mlp, scan=scan, mamba=mamba,
        softmax_rows=softmax_rows, attention=attention, head_loss=head_loss)


def drop_step(sizes: dict, T: int) -> int:
    """The step whose carried state the planted fault drops: the first step
    of the chunk that starts nearest the middle of the sequence."""
    L = sizes["mamba_chunk_size"]
    return (T // 2) // L * L


def ref_trunk(sizes, lower, fault, params, ids):
    """The final norm's output ``[B, T, d]`` of one batch. Every block, and
    every mixer inside it, under ``jax.checkpoint``: the backward keeps one
    ``[B, T, d]`` float32 stream a block, so that the reference's gradient
    fits one chip at 16k tokens beside its parameters. ``fault``
    ``drop_chunk_state``: the first Mamba-2 layer drops the state carried
    into one chunk (``drop_step``)."""
    import jax

    ops, act = ref_ops(sizes, lower), lower.activation
    r = sizes["residual_multiplier"]

    def block(mixer, ln1, p_mix, ln2, p_mlp, x):
        x = x + act(r * jax.checkpoint(mixer)(p_mix, ops.rms(ln1, x)))
        return x + act(r * ops.mlp(p_mlp, ops.rms(ln2, x)))

    x = params["embed"]["W"][ids] * sizes["embedding_multiplier"]
    dropped = fault != "drop_chunk_state"
    for name, mixer, attn in blocks(sizes):
        if attn:
            mix = ops.attention
        else:
            drop = None if dropped else drop_step(sizes, ids.shape[1])
            dropped = True
            mix = functools.partial(ops.mamba, drop_at=drop)
        x = jax.checkpoint(functools.partial(block, mix))(
            params[f"{name}_ln1"]["gain"], params[mixer],
            params[f"{name}_ln2"]["gain"], params[f"{name}_mlp"], x)
    return ops.rms(params["final_ln"]["gain"], x)


def _ref_loss(cfg, sizes, lower, fault, params, ids, labels):
    """Mean cross-entropy of one batch."""
    import jax.numpy as jnp

    h = ref_trunk(sizes, lower, fault, params, ids)
    every = jnp.ones(ids.shape, jnp.float32)
    if fault == "half_batch":   # the second half of each sequence left out
        every = every * (jnp.arange(ids.shape[1]) < ids.shape[1] // 2)[None, :]
    return ref_ops(sizes, lower).head_loss(
        params["embed"]["W"], h, labels, every / jnp.sum(every))


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
    parameters' change. Loss and gradient on the accelerator at ``highest``;
    the master weights, the float32 moments and the AdamW update on the host
    (XLA's CPU device, a leaf at a time). ``fault``: ``drop_chunk_state`` (the
    first Mamba-2 layer drops the state carried into the chunk at the middle
    of the sequence), ``half_batch`` (the second half of every sequence left
    out of the loss), ``state_unchanged``."""
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
    with jax.default_matmul_precision("highest"):
        for t, b in enumerate(batches, 1):
            loss, g = grad(jax.device_put(params, chip),
                           jnp.asarray(b["ids"]), jnp.asarray(b["labels"]))
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
