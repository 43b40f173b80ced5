"""resnet50: the system under test, its plain reference and its analytic counts.

Three parts, which share only the layer table below (He et al.,
arXiv:1512.03385, Table 1, 50-layer column, as ``resnet50.json`` states it):

- ``build`` wraps ``deeplearning4j_tpu.models.ResNet50`` as ``bench.py``'s
  flagship builds it (bf16 compute, f32 params, fused Pallas update, bf16
  momentum) and drives ``ComputationGraph.fit`` — the only part that imports
  the program;
- ``make_weights`` draws the initial weights on the device in one jitted call
  from the seed; the program and the reference are both handed them;
- ``reference`` is the same training step in plain ``jax.numpy``: float32 at
  ``highest`` precision, autodiff, no kernels, no flat buckets. It imports
  nothing of the program. ``lower`` computes it lower (the control and the
  witnesses of ``tests/precisions.py``) and ``fault`` plants a fault in it
  (the harness's own tests and readings).
"""

from __future__ import annotations

import functools

import numpy as np

STEP_PROGRAM = "jit_step"       # name of the compiled step in the device trace


# ---------------------------------------------------------------------------
# the layer table: everything below derives shapes and counts from it
# ---------------------------------------------------------------------------

def sizes_of(cfg: dict, tiny: bool) -> dict:
    s = {k: cfg[k] for k in ("image_size", "channels", "num_classes")}
    if tiny:
        s.update(cfg["tiny"])
    return s


def layer_table(cfg: dict, sizes: dict) -> list:
    """Every layer with weights or state, in forward order: dicts with
    ``name``, ``kind`` (conv | bn | dense), channel counts, kernel, stride,
    padding and the spatial size going in and coming out."""
    rows = []
    st = cfg["stem"]
    h = sizes["image_size"]

    def conv(name, cin, cout, k, stride, pad, hin):
        hout = (hin + 2 * pad - k) // stride + 1
        rows.append(dict(name=name, kind="conv", cin=cin, cout=cout, k=k,
                         stride=stride, pad=pad, hin=hin, hout=hout))
        return hout

    def bn(name, c, act):
        rows.append(dict(name=name, kind="bn", c=c, act=act))

    h = conv("stem_conv", sizes["channels"], st["out"], st["kernel"],
             st["stride"], st["pad"], h)
    bn("stem_bn", st["out"], "relu")
    h = (h + 2 * st["pool_pad"] - st["pool_kernel"]) // st["pool_stride"] + 1
    cin = st["out"]
    for s, (blocks, mid, cout, first_stride) in enumerate(cfg["stages"]):
        for b in range(blocks):
            n = f"s{s}b{b}"
            stride = first_stride if b == 0 else 1
            h1 = conv(f"{n}_c1", cin, mid, 1, stride, 0, h)
            bn(f"{n}_bn1", mid, "relu")
            conv(f"{n}_c2", mid, mid, 3, 1, 1, h1)
            bn(f"{n}_bn2", mid, "relu")
            conv(f"{n}_c3", mid, cout, 1, 1, 0, h1)
            bn(f"{n}_bn3", cout, "identity")
            if b == 0:
                conv(f"{n}_sc", cin, cout, 1, stride, 0, h)
                bn(f"{n}_scbn", cout, "identity")
            cin, h = cout, h1
    rows.append(dict(name="output", kind="dense", cin=cin,
                     cout=sizes["num_classes"]))
    return rows


def param_shapes(cfg: dict, sizes: dict) -> dict:
    """{layer: {param: shape}} — convolutions OIHW, dense [in, out]."""
    out = {}
    for r in layer_table(cfg, sizes):
        if r["kind"] == "conv":
            out[r["name"]] = {"W": (r["cout"], r["cin"], r["k"], r["k"])}
        elif r["kind"] == "bn":
            out[r["name"]] = {"gamma": (r["c"],), "beta": (r["c"],)}
        else:
            out[r["name"]] = {"W": (r["cin"], r["cout"]), "b": (r["cout"],)}
    return out


def _forward_macs(cfg: dict, sizes: dict) -> list:
    """(layer, multiply-accumulates per image in the forward pass)."""
    macs = []
    for r in layer_table(cfg, sizes):
        if r["kind"] == "conv":
            macs.append((r["name"], r["cout"] * r["cin"] * r["k"] ** 2
                         * r["hout"] ** 2))
        elif r["kind"] == "dense":
            macs.append((r["name"], r["cin"] * r["cout"]))
    return macs


def mxu_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs per image that a training step has to put through the matrix
    unit: forward, weight gradient and input gradient of every convolution
    and of the classifier (2 FLOPs a multiply-accumulate, three products a
    layer), less the stem's input gradient, which nothing consumes. No
    recomputation, nothing elementwise."""
    macs = _forward_macs(cfg, sizes)
    return 2.0 * (3 * sum(m for _, m in macs) - macs[0][1])


def model_flops(cfg: dict, sizes: dict, mix: dict) -> float:
    """FLOPs per image that the forward and backward passes require. For
    this model they are the matrix products alone (BatchNorm, ReLU, pooling
    and the update are bandwidth, not FLOPs, and are left out as is usual),
    so the count is ``mxu_flops``."""
    return mxu_flops(cfg, sizes, mix)


# ---------------------------------------------------------------------------
# weights from the seed (the benchmark's own; one jitted call on the device)
# ---------------------------------------------------------------------------

def make_weights(cfg: dict, sizes: dict, seed: int, mix: dict = None):
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(cfg, sizes)

    @jax.jit
    def draw(key):
        out = {}
        for i, (layer, ps) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if "gamma" in ps:
                out[layer] = {"gamma": jnp.ones(ps["gamma"], jnp.float32),
                              "beta": jnp.zeros(ps["beta"], jnp.float32)}
                continue
            shape = ps["W"]
            if len(shape) == 4:     # He normal on the fan-in
                std = float(np.sqrt(2.0 / (shape[1] * shape[2] * shape[3])))
            else:                   # Xavier normal
                std = float(np.sqrt(2.0 / (shape[0] + shape[1])))
            out[layer] = {"W": jax.random.normal(k, shape, jnp.float32) * std}
            if "b" in ps:
                out[layer]["b"] = jnp.zeros(ps["b"], jnp.float32)
        return out

    return draw(jax.random.key(int(seed) % (2 ** 63), impl="threefry2x32"))


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

class Job:
    """``ComputationGraph.fit(DataSet, epochs, batch_size)`` and what the
    comparison reads of its state. ``chips > 1`` wraps the model in
    ``ParallelWrapper`` (dense all-reduce), whose ``fit`` the calls then go
    through."""

    def __init__(self, cfg: dict, sizes: dict, chips: int):
        from deeplearning4j_tpu.models import ResNet50

        model = ResNet50(num_classes=sizes["num_classes"],
                         image_size=sizes["image_size"]).init()
        gc = model.conf.global_conf
        gc.compute_dtype = cfg["compute_dtype"]
        gc.fused_update = bool(cfg["fused_update"])
        gc.updater.state_dtype = cfg["updater_state_dtype"]
        opt = cfg["optimizer"]
        if (gc.updater.learning_rate, gc.updater.momentum, gc.l2) != (
                opt["learning_rate"], opt["momentum"], opt["l2"]):
            raise RuntimeError("the zoo model's optimizer is not the "
                               "configuration's")
        self.model = model
        self.lr = opt["learning_rate"]
        self.wrapper = None
        if chips > 1:
            from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

            self.wrapper = ParallelWrapper.Builder(model).workers(chips).build()

    def reset(self, weights) -> None:
        """Start from the benchmark's weights: fresh momentum, fresh
        BatchNorm statistics, iteration 0. ``weights`` is consumed (the
        step donates its parameters)."""
        import jax

        m = self.model
        shapes = lambda t: jax.tree.map(lambda a: a.shape, t)  # noqa: E731
        if shapes(weights) != shapes(_with_leaves(m._params)):
            raise RuntimeError("the model's parameter tree is not the "
                               "layer table's")
        # the model also lists its layers without parameters, as {}
        m._params = {name: weights.get(name, {}) for name in m._params}
        m._states = {name: m.conf.nodes[name].layer.init_state()
                     for name in m._states}
        m._updater_state = None
        m._iteration = 0

    def feed(self, batches: list):
        """Host batches -> what ``fit`` is given: one DataSet of all rows,
        which ``fit`` cuts into batches of ``batch_size`` itself."""
        from deeplearning4j_tpu.data import DataSet

        self.batch = batches[0]["x"].shape[0]
        if len(batches) == 1:
            return DataSet(batches[0]["x"], batches[0]["y"])
        return DataSet(np.concatenate([b["x"] for b in batches]),
                       np.concatenate([b["y"] for b in batches]))

    def fit(self, data, epochs: int) -> None:
        (self.wrapper or self.model).fit(data, epochs=epochs,
                                         batch_size=self.batch)

    def loss(self) -> float:
        return float(self.model.score_value)

    def params(self):
        return _with_leaves(self.model._params)

    def buffers(self):
        return _with_leaves(self.model._states)

    def first_gradient_state(self):
        """(state, scale): the gradient as the optimizer got it at step 1 is
        ``scale`` times its state after that step, Nesterov's v1 = -lr * g."""
        return _with_leaves(self.model._updater_state["v"]), -1.0 / self.lr

    def fence(self) -> None:
        import jax

        jax.block_until_ready(self.model._params)
        float(self.model._score_dev)

    def free(self) -> None:
        self.model = self.wrapper = None


def _with_leaves(tree: dict) -> dict:
    return {name: sub for name, sub in tree.items() if sub}


def build(cfg: dict, sizes: dict, chips: int, mix: dict) -> Job:
    return Job(cfg, sizes, chips)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _ref_loss(cfg, sizes, lower, params, buffers, x, y):
    """Loss (with the l2 penalty) and the new running statistics. Each
    bottleneck block is under ``jax.checkpoint``: float32 activations of 128
    images at 224x224 do not fit the chip otherwise, and BatchNorm couples
    the rows, so the batch cannot be cut into blocks of rows instead."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    q, act = lower.operand, lower.activation
    hi = lax.Precision.HIGHEST
    eps, decay = cfg["batch_norm"]["eps"], cfg["batch_norm"]["decay"]
    st = cfg["stem"]
    table = {r["name"]: r for r in layer_table(cfg, sizes)}

    def conv(name, p, a):
        r = table[name]
        return act(lax.conv_general_dilated(
            q(a), q(p[name]["W"]), (r["stride"],) * 2, [(r["pad"],) * 2] * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=hi))

    def bn(name, p, buf, new_buf, a):
        mean = jnp.mean(a, axis=(0, 2, 3))
        var = jnp.mean(jnp.square(a - mean[None, :, None, None]),
                       axis=(0, 2, 3))
        new_buf[name] = {
            "mean": decay * buf[name]["mean"]
            + (1 - decay) * lax.stop_gradient(mean),
            "var": decay * buf[name]["var"]
            + (1 - decay) * lax.stop_gradient(var)}
        scale = p[name]["gamma"] * lax.rsqrt(var + eps)
        out = ((a - mean[None, :, None, None]) * scale[None, :, None, None]
               + p[name]["beta"][None, :, None, None])
        return act(jax.nn.relu(out) if table[name]["act"] == "relu" else out)

    def stem(p, buf, a):
        new_buf = {}
        a = bn("stem_bn", p, buf, new_buf, conv("stem_conv", p, a))
        k, s, pad = st["pool_kernel"], st["pool_stride"], st["pool_pad"]
        a = lax.reduce_window(a, -jnp.inf, lax.max, (1, 1, k, k),
                              (1, 1, s, s),
                              ((0, 0), (0, 0), (pad, pad), (pad, pad)))
        return a, new_buf

    def block(n, first, p, buf, a):
        new_buf = {}
        y = bn(f"{n}_bn1", p, buf, new_buf, conv(f"{n}_c1", p, a))
        y = bn(f"{n}_bn2", p, buf, new_buf, conv(f"{n}_c2", p, y))
        y = bn(f"{n}_bn3", p, buf, new_buf, conv(f"{n}_c3", p, y))
        sc = (bn(f"{n}_scbn", p, buf, new_buf, conv(f"{n}_sc", p, a))
              if first else a)
        return act(jax.nn.relu(y + sc)), new_buf

    def part(prefix, tree):
        return {k: v for k, v in tree.items() if k.startswith(prefix)}

    a, new_buffers = jax.checkpoint(stem)(part("stem_", params),
                                          part("stem_", buffers), x)
    for si, (blocks, _mid, _cout, _stride) in enumerate(cfg["stages"]):
        for b in range(blocks):
            n = f"s{si}b{b}"
            a, nb = jax.checkpoint(functools.partial(block, n, b == 0))(
                part(n + "_", params), part(n + "_", buffers), a)
            new_buffers.update(nb)
    pooled = jnp.mean(a, axis=(2, 3))
    logits = jnp.dot(q(pooled), q(params["output"]["W"]),
                     precision=hi) + params["output"]["b"]
    data_loss = -jnp.mean(jnp.sum(y * jax.nn.log_softmax(logits), axis=-1))
    l2 = cfg["optimizer"]["l2"]
    penalty = 0.5 * l2 * sum(
        jnp.sum(jnp.square(w)) for ps in params.values()
        for name, w in ps.items() if name not in ("b", "beta"))
    return data_loss + penalty, new_buffers


def init_buffers(cfg: dict, sizes: dict):
    import jax.numpy as jnp

    return {r["name"]: {"mean": jnp.zeros((r["c"],), jnp.float32),
                        "var": jnp.ones((r["c"],), jnp.float32)}
            for r in layer_table(cfg, sizes) if r["kind"] == "bn"}


@functools.lru_cache(maxsize=None)
def _ref_step(cfg_key: str, sizes_key: str, lower, fault: str):
    """The jitted reference step for one (configuration, sizes, way of
    computing lower, fault)."""
    import json

    import jax

    cfg, sizes = json.loads(cfg_key), json.loads(sizes_key)
    lr = cfg["optimizer"]["learning_rate"]
    mu = cfg["optimizer"]["momentum"]

    def step(params, buffers, velocity, x, y):
        if fault == "half_batch":   # half of the rows left out, mean over the rest
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        loss_fn = functools.partial(_ref_loss, cfg, sizes, lower)
        (loss, new_buffers), grads = jax.value_and_grad(
            lambda p: loss_fn(p, buffers, x, y), has_aux=True)(params)
        if fault == "state_unchanged":
            return params, buffers, velocity, loss, grads

        def upd(p, g, v):
            v_new = lower.state(mu * v - lr * g)
            return p - mu * v + (1.0 + mu) * v_new, v_new

        both = jax.tree.map(upd, params, grads, velocity)
        pick = lambda i: jax.tree.map(          # noqa: E731
            lambda t: t[i], both, is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), new_buffers, pick(1), loss, grads

    return jax.jit(step, donate_argnums=(0, 1, 2))


def reference(cfg: dict, sizes: dict, seed: int, batches: list,
              lower=None, fault: str = "") -> dict:
    """Run the first ``len(batches)`` steps plainly and return what the
    comparison reads: each step's loss, the first gradient, the parameters'
    and the running statistics' change."""
    import json

    import jax
    import jax.numpy as jnp

    from compare import EXACT, tree_diff

    step = _ref_step(json.dumps(cfg, sort_keys=True),
                     json.dumps(sizes, sort_keys=True), lower or EXACT, fault)
    w0 = make_weights(cfg, sizes, seed)
    params = jax.tree.map(jnp.copy, w0)
    buffers = init_buffers(cfg, sizes)
    b0 = init_buffers(cfg, sizes)
    velocity = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for i, b in enumerate(batches):
        params, buffers, velocity, loss, grads = step(
            params, buffers, velocity, jnp.asarray(b["x"]),
            jnp.asarray(b["y"]))
        losses.append(float(loss))
        if i == 0:
            first_grad = grads
        del grads
    return {"loss": losses, "first_gradient": first_grad,
            "param_change": tree_diff(params, w0),
            "buffer_change": tree_diff(buffers, b0)}
