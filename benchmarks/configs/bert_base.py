"""bert_base: the system under test, its plain reference and its analytic counts.

- ``build`` makes a frozen TF GraphDef of the BERT-base encoder (the graph
  ``deeplearning4j_tpu.imports.tf_fixtures.build_bert_frozen_graph`` builds,
  copied here with every weight-consuming op named, so that each imported
  variable can be told from its name), imports it, grafts head, loss and Adam
  on as ``bench._bert_samediff`` does, and drives ``SameDiff.fit``;
- ``make_weights`` draws the weights on the device in one jitted call from
  the seed (the graph is built over zeros and the variables are then set);
- ``reference`` is the same fine-tuning step in plain ``jax.numpy``, float32
  at ``highest`` precision, and imports nothing of the program.
"""

from __future__ import annotations

import functools

import numpy as np

STEP_PROGRAM = "jit_step"       # name of the compiled step in the device trace
PLACEHOLDERS = {"ids": "input_ids", "types": "token_type_ids",
                "mask": "input_mask", "y": "labels"}
SIZE_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
             "intermediate_size", "vocab_size", "type_vocab_size",
             "max_position_embeddings", "num_classes")


def sizes_of(cfg: dict, tiny: bool) -> dict:
    s = {k: cfg[k] for k in SIZE_KEYS}
    if tiny:
        s.update(cfg["tiny"])
    return s


def param_shapes(cfg: dict, sizes: dict, seq: int) -> dict:
    """{logical name: shape} of every trained tensor, in graph order. The
    position table is the ``seq`` rows that the graph's slice keeps."""
    h, i = sizes["hidden_size"], sizes["intermediate_size"]
    out = {"word_emb": (sizes["vocab_size"], h),
           "type_emb": (sizes["type_vocab_size"], h),
           "pos_emb": (seq, h), "emb_ln_g": (h,), "emb_ln_b": (h,)}
    for l in range(sizes["num_hidden_layers"]):
        for name, shape in (("q", (h, h)), ("k", (h, h)), ("v", (h, h)),
                            ("o", (h, h)), ("up", (h, i)), ("down", (i, h))):
            out[f"l{l}_{name}_w"] = shape
            out[f"l{l}_{name}_b"] = (shape[1],)
        for ln in ("ln1", "ln2"):
            out[f"l{l}_{ln}_g"] = (h,)
            out[f"l{l}_{ln}_b"] = (h,)
    out.update(pool_w=(h, h), pool_b=(h,),
               cls_w=(h, sizes["num_classes"]), cls_b=(sizes["num_classes"],))
    return out


def _matmul_params(sizes: dict) -> int:
    """Parameters that multiply every token: the encoder's dense layers."""
    h, i = sizes["hidden_size"], sizes["intermediate_size"]
    return sizes["num_hidden_layers"] * (4 * h * h + 2 * h * i)


def mxu_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs per sequence that a training step has to put through the matrix
    unit: 6 x (dense parameters) x tokens for forward, weight gradient and
    input gradient, the attention products (scores and context: 2 x 2 x
    seq^2 x hidden a layer forward, three times that with the backward), and
    the pooler and classifier over the one [CLS] row. Padded positions count:
    the step computes them."""
    h, seq = sizes["hidden_size"], mix["seq"]
    dense = 6.0 * _matmul_params(sizes) * seq
    attention = 3.0 * sizes["num_hidden_layers"] * 4.0 * seq * seq * h
    head = 6.0 * (h * h + h * sizes["num_classes"])
    return dense + attention + head


def model_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs per sequence that forward and backward require: the matrix
    products (embedding look-ups, LayerNorm, GELU, softmax and Adam are
    bandwidth and are left out, as is usual)."""
    return mxu_flops(cfg, sizes, mix)


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def make_weights(cfg: dict, sizes: dict, seed: int, mix: dict):
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(cfg, sizes, mix["seq"])
    std = cfg["initializer_range"]

    @jax.jit
    def draw(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            if name.endswith("_g"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith("_b"):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                s = (float(np.sqrt(2.0 / sum(shape))) if name == "cls_w"
                     else std)
                out[name] = s * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return draw(jax.random.key(int(seed) % (2 ** 63), impl="threefry2x32"))


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def _frozen_graph(cfg: dict, sizes: dict, batch: int, seq: int):
    """The encoder as a frozen GraphDef over zero weights. Every op that
    consumes a weight is named ``<logical name>``, so the frozen constant is
    ``<logical name>/<argument>`` and the imported variable carries it."""
    import tensorflow as tf
    from tensorflow.python.framework.convert_to_constants import \
        convert_variables_to_constants_v2

    h, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    head_dim = h // heads
    shapes = param_shapes({}, sizes, sizes["max_position_embeddings"])
    w = {n: tf.constant(np.zeros(s, np.float32)) for n, s in shapes.items()
         if not n.startswith("cls_")}

    def layer_norm(x, name):
        mu = tf.reduce_mean(x, axis=-1, keepdims=True)
        var = tf.reduce_mean(tf.math.squared_difference(x, mu), axis=-1,
                             keepdims=True)
        y = (x - mu) * tf.math.rsqrt(var + cfg["layer_norm_eps"])
        return tf.add(tf.multiply(y, w[name + "_g"], name=name + "_g"),
                      w[name + "_b"], name=name + "_b")

    def dense(x, name):
        return tf.add(tf.matmul(x, w[name + "_w"], name=name + "_w"),
                      w[name + "_b"], name=name + "_b")

    def split_heads(x):
        return tf.transpose(tf.reshape(x, [batch, seq, heads, head_dim]),
                            [0, 2, 1, 3])

    @tf.function
    def bert(input_ids, token_type_ids, input_mask):
        x = (tf.gather(w["word_emb"], input_ids, name="word_emb")
             + tf.gather(w["type_emb"], token_type_ids, name="type_emb")
             + tf.strided_slice(w["pos_emb"], [0], [seq], name="pos_emb"))
        x = layer_norm(x, "emb_ln")
        bias = (1.0 - tf.cast(tf.reshape(input_mask, [batch, 1, 1, seq]),
                              tf.float32)) * -10000.0
        for l in range(sizes["num_hidden_layers"]):
            p = f"l{l}_"
            q, k, v = (split_heads(dense(x, p + n)) for n in "qkv")
            scores = tf.matmul(q, k, transpose_b=True) / float(np.sqrt(head_dim))
            ctx = tf.matmul(tf.nn.softmax(scores + bias), v)
            ctx = tf.reshape(tf.transpose(ctx, [0, 2, 1, 3]), [batch, seq, h])
            x = layer_norm(x + dense(ctx, p + "o"), p + "ln1")
            up = dense(x, p + "up")
            up = 0.5 * up * (1.0 + tf.math.erf(up / tf.sqrt(2.0)))
            x = layer_norm(x + dense(up, p + "down"), p + "ln2")
        return tf.tanh(dense(x[:, 0], "pool"))

    specs = [tf.TensorSpec([batch, seq], tf.int32, name=n)
             for n in ("input_ids", "token_type_ids", "input_mask")]
    frozen = convert_variables_to_constants_v2(bert.get_concrete_function(*specs))
    return frozen.graph.as_graph_def()


class Job:
    """``SameDiff.fit(list of {placeholder: array}, epochs)`` over the
    imported graph, and what the comparison reads of its state."""

    def __init__(self, cfg: dict, sizes: dict, chips: int, batch: int,
                 seq: int):
        if chips != 1:
            raise NotImplementedError("SameDiff.fit runs on one chip")
        from deeplearning4j_tpu.autodiff.samediff import TrainingConfig
        from deeplearning4j_tpu.imports import import_frozen_tf
        from deeplearning4j_tpu.learning import Adam

        sd = import_frozen_tf(_frozen_graph(cfg, sizes, batch, seq))
        sd.convert_to_variables()
        pooled = sd.get_variable(sd.tf_outputs[0])
        h, c = sizes["hidden_size"], sizes["num_classes"]
        cls_w = sd.var("cls_w", shape=(h, c), init="xavier")
        cls_b = sd.var("cls_b", shape=(c,), init="zeros")
        pooled.mmul(cls_w).add(cls_b).rename("logits")
        sd.placeholder("labels", shape=(batch, c))
        sd.ops.softmax_cross_entropy(sd.get_variable("logits"),
                                     sd.get_variable("labels"), name="loss")
        sd.set_loss_variables("loss")
        opt = cfg["optimizer"]
        self.updater = Adam(opt["learning_rate"], beta1=opt["beta1"],
                            beta2=opt["beta2"], epsilon=opt["epsilon"])
        sd.set_training_config(TrainingConfig(updater=self.updater,
                                              loss_name="loss"))
        self.sd = sd
        # logical name -> the variable the importer made of it
        variables = sd.variables()
        self.names = {}
        for logical in param_shapes(cfg, sizes, seq):
            found = [v for v in variables
                     if v == logical or v.split("/")[0] == logical
                     or v.rsplit("_", 1)[0] == logical]
            if len(found) != 1:
                raise RuntimeError(f"{logical}: imported as {found}")
            self.names[logical] = found[0]
        if len(self.names) != len(variables):
            raise RuntimeError("the import trains variables that the "
                               "parameter table does not list: "
                               f"{sorted(set(variables) - set(self.names.values()))}")
        self.history = None

    def reset(self, weights) -> None:
        sd = self.sd
        for logical, var in self.names.items():
            if tuple(np.shape(sd._vars[var].value)) != weights[logical].shape:
                raise RuntimeError(f"{logical}: the graph holds "
                                   f"{np.shape(sd._vars[var].value)}")
            sd._vars[var].value = weights[logical]
        sd._updater_state = None
        sd._iteration = 0

    def feed(self, batches: list):
        return [{PLACEHOLDERS[k]: v for k, v in b.items()} for b in batches]

    def fit(self, data, epochs: int) -> None:
        self.history = self.sd.fit(data, epochs=epochs)

    def loss(self) -> float:
        return float(self.history.final_loss())

    def _logical(self, by_var: dict) -> dict:
        return {logical: by_var[var] for logical, var in self.names.items()}

    def params(self):
        import jax.numpy as jnp

        return {logical: jnp.asarray(self.sd._vars[var].value)
                for logical, var in self.names.items()}

    def buffers(self):
        return {}

    def first_gradient_state(self):
        """(state, scale): the first gradient is ``scale`` times the state
        that Adam keeps after step 1, m1 = (1 - beta1) * g."""
        return (self._logical(self.sd._updater_state["m"]),
                1.0 / (1.0 - self.updater.beta1))

    def fence(self) -> None:
        import jax

        jax.block_until_ready(self.sd._updater_state)

    def free(self) -> None:
        self.sd = self.history = None


def build(cfg: dict, sizes: dict, chips: int, mix: dict) -> Job:
    return Job(cfg, sizes, chips, mix["batch"], mix["seq"])


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _ref_loss(cfg, sizes, lower, p, batch):
    """``lower`` (``compare.EXACT`` in every benchmark run) rounds the
    operands of every matrix product and what each layer hands on; the
    control and the witnesses compute them lower."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    ids, types, mask, y = batch
    q, act = lower.operand, lower.activation
    hi = lax.Precision.HIGHEST
    heads = sizes["num_attention_heads"]
    h = sizes["hidden_size"]
    b, t = ids.shape
    eps = cfg["layer_norm_eps"]

    def layer_norm(x, name):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return act((x - mu) * lax.rsqrt(var + eps) * p[name + "_g"]
                   + p[name + "_b"])

    def dense(x, name):
        return act(jnp.matmul(q(x), q(p[name + "_w"]), precision=hi)
                   + p[name + "_b"])

    def split(x):
        return x.reshape(b, t, heads, h // heads).transpose(0, 2, 1, 3)

    x = p["word_emb"][ids] + p["type_emb"][types] + p["pos_emb"][:t]
    x = layer_norm(x, "emb_ln")
    bias = (1.0 - mask.astype(jnp.float32)).reshape(b, 1, 1, t) * -10000.0
    for l in range(sizes["num_hidden_layers"]):
        n = f"l{l}_"
        qh, kh, vh = (split(dense(x, n + c)) for c in "qkv")
        scores = jnp.einsum("bhqd,bhkd->bhqk", q(qh), q(kh), precision=hi) \
            / float(np.sqrt(h // heads))
        probs = act(jax.nn.softmax(act(scores) + bias, axis=-1))
        ctx = act(jnp.einsum("bhqk,bhkd->bhqd", q(probs), q(vh),
                             precision=hi))
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, h)
        x = layer_norm(x + dense(ctx, n + "o"), n + "ln1")
        up = dense(x, n + "up")
        up = act(0.5 * up * (1.0 + lax.erf(up / float(np.sqrt(2.0)))))
        x = layer_norm(x + dense(up, n + "down"), n + "ln2")
    pooled = act(jnp.tanh(dense(x[:, 0], "pool")))
    logits = dense(pooled, "cls")
    return -jnp.mean(jnp.sum(y * jax.nn.log_softmax(logits, axis=-1), axis=-1))


@functools.lru_cache(maxsize=None)
def _ref_step(cfg_key: str, sizes_key: str, lower, fault: str):
    import json

    import jax
    import jax.numpy as jnp

    cfg, sizes = json.loads(cfg_key), json.loads(sizes_key)
    opt = cfg["optimizer"]
    lr, b1, b2, eps = (opt[k] for k in ("learning_rate", "beta1", "beta2",
                                        "epsilon"))

    def step(params, m, v, t, batch):
        if fault == "half_batch":   # half of the rows left out, mean over the rest
            batch = tuple(a[: a.shape[0] // 2] for a in batch)
        loss, grads = jax.value_and_grad(
            functools.partial(_ref_loss, cfg, sizes, lower))(params, batch)
        if fault == "state_unchanged":
            return params, m, v, loss, grads
        t = t.astype(jnp.float32) + 1.0
        m = jax.tree.map(lambda a, g: lower.state(b1 * a + (1 - b1) * g),
                         m, grads)
        v = jax.tree.map(lambda a, g: lower.state(b2 * a + (1 - b2) * g * g),
                         v, grads)
        params = jax.tree.map(
            lambda p, a, c: p - lr * (a / (1 - b1 ** t))
            / (jnp.sqrt(c / (1 - b2 ** t)) + eps), params, m, v)
        return params, m, v, loss, grads

    return jax.jit(step, donate_argnums=(0, 1, 2))


def reference(cfg: dict, sizes: dict, seed: int, batches: list,
              lower=None, fault: str = "") -> dict:
    import json

    import jax
    import jax.numpy as jnp

    from compare import EXACT, tree_diff

    step = _ref_step(json.dumps(cfg, sort_keys=True),
                     json.dumps(sizes, sort_keys=True), lower or EXACT, fault)
    w0 = make_weights(cfg, sizes, seed, {"seq": batches[0]["ids"].shape[1]})
    params = jax.tree.map(jnp.copy, w0)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for i, b in enumerate(batches):
        batch = tuple(jnp.asarray(b[k]) for k in ("ids", "types", "mask", "y"))
        params, m, v, loss, grads = step(params, m, v, jnp.asarray(i), batch)
        losses.append(float(loss))
        if i == 0:
            first_grad = grads
        del grads
    return {"loss": losses, "first_gradient": first_grad,
            "param_change": tree_diff(params, w0)}
