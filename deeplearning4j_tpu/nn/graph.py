"""ComputationGraph — named-vertex DAG models (ResNet-50 et al).

Reference: dl4j-nn ``org.deeplearning4j.nn.graph.ComputationGraph`` (~4.5k LoC)
+ ``conf.ComputationGraphConfiguration.GraphBuilder`` + vertex impls
``nn.graph.vertex.impl.*`` (SURVEY.md §2.3, §3.2). The reference executes
~2000 JNI-dispatched ops per ResNet-50 iteration; here the topologically-
sorted vertex walk is traced ONCE and the whole iteration (fwd+bwd+updater)
compiles to a single XLA module (SURVEY.md §7.1.1).

Vertices: Merge, ElementWise (add/sub/mul/avg/max), Subset, Scale, Shift,
L2Normalize, Stack, Unstack, Preprocessor — reference ``conf/graph/*``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..common import xprof
from ..common.profiler import OpProfiler
from ..data.dataset import DataSet, MultiDataSet
from ..ndarray.ndarray import NDArray
from ..ndarray.rng import get_random
from .conf import layers as L
from .conf.builder import (GlobalConf, MultiLayerConfiguration, _deser_obj,
                           _ser_obj, remat_wrap)
from .conf.inputs import CNNFlatInput, CNNInput, FFInput, InputType, RNNInput, cnn_to_ff, flat_to_cnn
from .train_step import (FORWARD, FitLoop, _fold_weights, chunk_program,
                         make_core, step_program, vertex_scope)


# --- graph vertices (reference conf/graph/*) ---------------------------------


@dataclass
class GraphVertex:
    def output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def apply(self, *inputs):
        raise NotImplementedError


@dataclass
class MergeVertex(GraphVertex):
    """Concat along the feature/channel dim (reference MergeVertex)."""

    def output_type(self, *ts):
        t0 = ts[0]
        if isinstance(t0, CNNInput):
            return CNNInput(sum(t.channels for t in ts), t0.height, t0.width)
        if isinstance(t0, FFInput):
            return FFInput(sum(t.size for t in ts))
        if isinstance(t0, RNNInput):
            return RNNInput(sum(t.size for t in ts), t0.timesteps)
        raise ValueError(f"cannot merge {ts}")

    def apply(self, *inputs):
        axis = 1 if inputs[0].ndim == 4 else -1
        return jnp.concatenate(inputs, axis=axis)


@dataclass
class ElementWiseVertex(GraphVertex):
    """reference ElementWiseVertex.Op: Add/Subtract/Product/Average/Max.
    ``branch_scale`` (add only): ``inputs[0] + branch_scale * (the rest)``,
    a residual add whose branch is scaled (the ``granitemoehybrid`` family's
    ``residual_multiplier``) without a vertex of its own."""

    op: str = "add"
    branch_scale: Optional[float] = None

    def apply(self, *inputs):
        op = self.op.lower()
        if op == "add":
            if self.branch_scale is not None:
                return inputs[0] + self.branch_scale * sum(inputs[1:])
            out = inputs[0]
            for v in inputs[1:]:
                out = out + v
            return out
        if op == "subtract":
            if len(inputs) != 2:
                raise ValueError(
                    f"ElementWiseVertex(subtract) needs exactly 2 inputs, got {len(inputs)}")
            return inputs[0] - inputs[1]
        if op in ("product", "mul"):
            out = inputs[0]
            for v in inputs[1:]:
                out = out * v
            return out
        if op in ("average", "avg"):
            return sum(inputs) / len(inputs)
        if op == "max":
            out = inputs[0]
            for v in inputs[1:]:
                out = jnp.maximum(out, v)
            return out
        if op == "min":
            out = inputs[0]
            for v in inputs[1:]:
                out = jnp.minimum(out, v)
            return out
        raise ValueError(f"unknown elementwise op {self.op!r}")


@dataclass
class DotProductVertex(GraphVertex):
    """Keras functional ``Dot`` merge (round-5 Keras-import tail): batched
    dot of two FF inputs over the feature axis, optionally L2-normalized
    (cosine proximity). Output is [B, 1]."""

    normalize: bool = False

    def output_type(self, *ts):
        if len(ts) != 2 or not all(isinstance(t, FFInput) for t in ts):
            raise ValueError("DotProductVertex needs two FF inputs")
        if ts[0].size != ts[1].size:
            raise ValueError(
                f"DotProductVertex inputs differ: {ts[0].size} vs "
                f"{ts[1].size}")
        return FFInput(1)

    def apply(self, a, b):
        if self.normalize:
            a = a / jnp.maximum(jnp.linalg.norm(a, axis=-1, keepdims=True),
                                1e-12)
            b = b / jnp.maximum(jnp.linalg.norm(b, axis=-1, keepdims=True),
                                1e-12)
        return jnp.sum(a * b, axis=-1, keepdims=True)


@dataclass
class SubsetVertex(GraphVertex):
    """Feature-dim slice [from, to] inclusive (reference SubsetVertex)."""

    from_idx: int = 0
    to_idx: int = 0

    def output_type(self, *ts):
        n = self.to_idx - self.from_idx + 1
        t = ts[0]
        if isinstance(t, FFInput):
            return FFInput(n)
        if isinstance(t, CNNInput):
            return CNNInput(n, t.height, t.width)
        if isinstance(t, RNNInput):
            return RNNInput(n, t.timesteps)
        raise ValueError(f"subset of {t}")

    def apply(self, *inputs):
        x = inputs[0]
        sl = slice(self.from_idx, self.to_idx + 1)
        if x.ndim == 4:
            return x[:, sl]
        return x[..., sl]


@dataclass
class ScaleVertex(GraphVertex):
    scale: float = 1.0

    def apply(self, *inputs):
        return inputs[0] * self.scale


@dataclass
class ShiftVertex(GraphVertex):
    shift: float = 0.0

    def apply(self, *inputs):
        return inputs[0] + self.shift


@dataclass
class L2NormalizeVertex(GraphVertex):
    eps: float = 1e-8

    def apply(self, *inputs):
        x = inputs[0]
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim)),
                                keepdims=True))
        return x / jnp.maximum(norm, self.eps)


@dataclass
class StackVertex(GraphVertex):
    """Stack along batch dim (reference StackVertex)."""

    def apply(self, *inputs):
        return jnp.concatenate(inputs, axis=0)


@dataclass
class UnstackVertex(GraphVertex):
    from_idx: int = 0
    stack_size: int = 1

    def apply(self, *inputs):
        x = inputs[0]
        n = x.shape[0] // self.stack_size
        return x[self.from_idx * n:(self.from_idx + 1) * n]


@dataclass
class ReshapeVertex(GraphVertex):
    shape: Tuple[int, ...] = ()

    def apply(self, *inputs):
        return inputs[0].reshape((inputs[0].shape[0],) + tuple(self.shape))


# register vertex dataclasses with the config serde (builder._CLASSES)
from .conf.builder import _CLASSES as _SERDE_CLASSES  # noqa: E402

for _v in (GraphVertex, MergeVertex, ElementWiseVertex, SubsetVertex, ScaleVertex,
           ShiftVertex, L2NormalizeVertex, StackVertex, UnstackVertex, ReshapeVertex):
    _SERDE_CLASSES[_v.__name__] = _v


# --- graph node wiring -------------------------------------------------------


@dataclass
class _Node:
    name: str
    kind: str                       # "input" | "layer" | "vertex"
    layer: Optional[L.Layer] = None
    vertex: Optional[GraphVertex] = None
    inputs: List[str] = field(default_factory=list)
    preprocessors: Dict[int, Any] = field(default_factory=dict)  # per-input adapters


class ComputationGraphConfiguration:
    def __init__(self, global_conf: GlobalConf):
        self.global_conf = global_conf
        self.network_inputs: List[str] = []
        self.network_outputs: List[str] = []
        self.nodes: Dict[str, _Node] = {}
        self.order: List[str] = []
        self.input_types: Dict[str, InputType] = {}
        self.node_output_types: Dict[str, InputType] = {}

    @staticmethod
    def graph_builder(builder=None) -> "GraphBuilder":
        from .conf.builder import Builder

        b = builder._conf if builder is not None else GlobalConf()
        return GraphBuilder(b)

    # --- shape inference ------------------------------------------------
    def set_input_types(self, *types: InputType) -> None:
        assert len(types) == len(self.network_inputs), "one InputType per input"
        self.input_types = dict(zip(self.network_inputs, types))
        self.node_output_types = {}
        for name in self.order:
            node = self.nodes[name]
            if node.kind == "input":
                t = self.input_types[name]
                if isinstance(t, CNNFlatInput):
                    node.preprocessors[0] = flat_to_cnn(t)
                    t = node.preprocessors[0].out_type
                self.node_output_types[name] = t
                continue
            in_types = [self.node_output_types[i] for i in node.inputs]
            if node.kind == "vertex":
                self.node_output_types[name] = node.vertex.output_type(*in_types)
                continue
            # layer node: insert CNN→FF adapter when needed (reference
            # automatic preprocessor insertion)
            t = in_types[0]
            ff_like = (L.DenseLayer, L.OutputLayer, L.ElementWiseMultiplicationLayer)
            if isinstance(t, CNNInput) and isinstance(node.layer, ff_like) \
                    and not isinstance(node.layer, L.RnnOutputLayer):
                node.preprocessors[0] = cnn_to_ff(t)
                t = node.preprocessors[0].out_type
            if node.layer.multi_input:
                t = tuple([t] + in_types[1:])
            out = node.layer.set_input_type(t)
            self.node_output_types[name] = out
            for extra, et in zip(node.layer.extra_outputs(),
                                 node.layer.extra_output_types(out)):
                self.node_output_types[f"{name}.{extra}"] = et

    # --- serde -----------------------------------------------------------
    def to_json(self) -> str:
        import json

        return json.dumps({
            "format_version": 1,
            "global": _ser_obj(self.global_conf),
            "inputs": self.network_inputs,
            "outputs": self.network_outputs,
            "order": self.order,
            "nodes": [
                {"name": n.name, "kind": n.kind,
                 "layer": _ser_obj(n.layer) if n.layer else None,
                 "vertex": _ser_obj(n.vertex) if n.vertex else None,
                 "inputs": n.inputs}
                for n in (self.nodes[nm] for nm in self.order)
            ],
            "input_types": {k: _ser_obj(v) for k, v in self.input_types.items()},
        }, indent=2)

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        import json

        d = json.loads(s)
        conf = ComputationGraphConfiguration(_deser_obj(d["global"]))
        conf.network_inputs = d["inputs"]
        conf.network_outputs = d["outputs"]
        for nd in d["nodes"]:
            node = _Node(nd["name"], nd["kind"],
                         _deser_obj(nd["layer"]) if nd["layer"] else None,
                         _deser_obj(nd["vertex"]) if nd["vertex"] else None,
                         nd["inputs"])
            conf.nodes[node.name] = node
            conf.order.append(node.name)
        if d.get("input_types"):
            conf.set_input_types(*[_deser_obj(v) for v in d["input_types"].values()])
        return conf


class GraphBuilder:
    """reference ComputationGraphConfiguration.GraphBuilder."""

    def __init__(self, global_conf: GlobalConf):
        self._conf = ComputationGraphConfiguration(global_conf)

    def add_inputs(self, *names: str) -> "GraphBuilder":
        for n in names:
            self._conf.network_inputs.append(n)
            self._conf.nodes[n] = _Node(n, "input")
            self._conf.order.append(n)
        return self

    addInputs = add_inputs

    def add_layer(self, name: str, layer: L.Layer, *inputs: str) -> "GraphBuilder":
        self._check_inputs(name, inputs)
        layer.name = name
        self._apply_defaults(layer)
        self._conf.nodes[name] = _Node(name, "layer", layer=layer, inputs=list(inputs))
        self._conf.order.append(name)
        return self

    addLayer = add_layer

    def add_vertex(self, name: str, vertex: GraphVertex, *inputs: str) -> "GraphBuilder":
        self._check_inputs(name, inputs)
        self._conf.nodes[name] = _Node(name, "vertex", vertex=vertex, inputs=list(inputs))
        self._conf.order.append(name)
        return self

    addVertex = add_vertex

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._conf.network_outputs = list(names)
        return self

    setOutputs = set_outputs

    def set_input_types(self, *types: InputType) -> "GraphBuilder":
        self._pending_types = types
        return self

    setInputTypes = set_input_types

    def build(self) -> ComputationGraphConfiguration:
        if not self._conf.network_outputs:
            raise ValueError("set_outputs(...) required")
        for out in self._conf.network_outputs:
            if out not in self._conf.nodes:
                raise ValueError(f"unknown output node {out!r}")
        types = getattr(self, "_pending_types", None)
        if types:
            self._conf.set_input_types(*types)
        return self._conf

    def _check_inputs(self, name: str, inputs: Sequence[str]) -> None:
        if name in self._conf.nodes:
            raise ValueError(f"duplicate node name {name!r}")
        if not inputs:
            raise ValueError(f"node {name!r} needs at least one input")
        for i in inputs:
            if i in self._conf.nodes:
                continue
            # a further output of a layer node: "<node>.<name>"
            node, _, extra = i.rpartition(".")
            owner = self._conf.nodes.get(node)
            if owner is None or owner.layer is None \
                    or extra not in owner.layer.extra_outputs():
                raise ValueError(f"node {name!r}: unknown input {i!r} "
                                 f"(declare nodes in topological order)")

    def _apply_defaults(self, l: L.Layer) -> None:
        from .conf.builder import apply_layer_defaults

        apply_layer_defaults(l, self._conf.global_conf)


class ComputationGraph(FitLoop):
    """Runtime twin of the configuration (reference ComputationGraph)."""

    _one_batch = (DataSet, MultiDataSet)
    _allow_multi = True

    def __init__(self, conf: ComputationGraphConfiguration):
        super().__init__(conf)
        self._params: Dict[str, Dict[str, jnp.ndarray]] = {}
        self._states: Dict[str, Dict[str, jnp.ndarray]] = {}

    # ------------------------------------------------------------------
    def init(self, seed: Optional[int] = None) -> "ComputationGraph":
        if not self.conf.node_output_types:
            raise ValueError("configuration needs set_input_types(...) before init()")
        with OpProfiler.get().time_section("build/init"):
            key = jax.random.PRNGKey(
                seed if seed is not None else self.conf.global_conf.seed)
            dtype = jnp.dtype(self.conf.global_conf.dtype)
            for name in self.conf.order:
                node = self.conf.nodes[name]
                if node.kind == "layer":
                    key, sub = jax.random.split(key)
                    self._params[name] = (node.layer.init_params(sub, dtype)
                                          if node.layer.has_params else {})
                    self._states[name] = node.layer.init_state()
        self._initialized = True
        return self

    # --- forward ---------------------------------------------------------
    def _epilogue_fusion_plan(self):
        """The resnet-block-tail chains ``BN(identity) →
        ElementWiseVertex(add, 2 inputs) → ActivationLayer(relu)`` that
        inference ``_forward`` collapses into one fused BN+residual+relu
        epilogue (ops/pallas_epilogue) when ``GlobalConf.fused_epilogue``
        is on. Conservative: every interior node must have exactly one
        consumer (the next link), no preprocessors on the add/act links,
        and neither interior node may be a network output — so skipping
        their dense materialization can never change any other node.
        Returns None when the knob is off or nothing matches; the chain
        falls back to the dense ops per call if the kernel's shape gate
        refuses at trace time."""
        if not getattr(self.conf.global_conf, "fused_epilogue", False):
            return None
        consumers: Dict[str, set] = {}
        for name in self.conf.order:
            for i in self.conf.nodes[name].inputs:
                consumers.setdefault(i, set()).add(name)
        outputs = set(self.conf.network_outputs)
        bn_nodes, add_nodes, act_nodes = set(), {}, {}
        for name in self.conf.order:
            node = self.conf.nodes[name]
            if (node.kind != "layer"
                    or not isinstance(node.layer, L.ActivationLayer)
                    or (node.layer.activation or "").lower() != "relu"
                    or len(node.inputs) != 1 or node.preprocessors):
                continue
            add_name = node.inputs[0]
            add_node = self.conf.nodes.get(add_name)
            if (add_node is None or add_node.kind != "vertex"
                    or not isinstance(add_node.vertex, ElementWiseVertex)
                    or add_node.vertex.op.lower() != "add"
                    or add_node.vertex.branch_scale is not None
                    or len(add_node.inputs) != 2
                    or add_name in outputs
                    or consumers.get(add_name) != {name}):
                continue
            if add_node.inputs[0] == add_node.inputs[1]:
                # relu(bn(x) + bn(x)): deferring the BN would starve the
                # "other" operand — leave the degenerate chain dense
                continue
            bn_name = None
            for cand, oth in (add_node.inputs, reversed(add_node.inputs)):
                bn = self.conf.nodes.get(cand)
                if (bn is not None and bn.kind == "layer"
                        and isinstance(bn.layer, L.BatchNormalization)
                        # honor a per-layer fused_epilogue=False opt-out
                        # even when the global knob is on
                        and bn.layer.fused_epilogue
                        and (bn.layer.activation
                             or "identity").lower() == "identity"
                        and cand not in outputs and cand not in bn_nodes
                        and consumers.get(cand) == {add_name}):
                    bn_name, other = cand, oth
                    break
            if bn_name is None:
                continue
            bn_nodes.add(bn_name)
            add_nodes[add_name] = (bn_name, other)
            act_nodes[name] = (bn_name, add_name)
        if not act_nodes:
            return None
        return {"bn": bn_nodes, "add": add_nodes, "act": act_nodes}

    def _forward(self, params, states, inputs: Dict[str, jnp.ndarray],
                 training: bool, rng, to_preout: bool = False):
        cd = self.conf.global_conf.compute_dtype
        if cd:
            ct = jnp.dtype(cd)
            cast = lambda a: (a.astype(ct)
                              if jnp.issubdtype(a.dtype, jnp.floating) else a)
            keep = {n: self.conf.nodes[n].layer.full_precision_params
                    for n in params}
            cast_params = {}
            for n, lp in params.items():
                with jax.named_scope(vertex_scope(n)):
                    cast_params[n] = {
                        k: (v if k in keep[n] else jax.tree.map(cast, v))
                        for k, v in lp.items()}
            params = cast_params
            inputs = {k: cast(v) for k, v in inputs.items()}
        acts: Dict[str, jnp.ndarray] = {}
        new_states = dict(states)
        out_set = set(self.conf.network_outputs)
        plan = None if training else self._epilogue_fusion_plan()
        pending_bn: Dict[str, Any] = {}
        pending_add: Dict[str, Any] = {}
        for name in self.conf.order:
            node = self.conf.nodes[name]
            if node.kind == "input":
                x = inputs[name]
                if 0 in node.preprocessors:
                    x = node.preprocessors[0](x)
                acts[name] = x
                continue
            if plan is not None and node.kind == "vertex" \
                    and name in plan["add"]:
                # fused-epilogue chain: defer the residual add to the relu
                bn_name, other = plan["add"][name]
                pending_add[name] = (pending_bn.pop(bn_name), acts[other])
                continue
            if plan is not None and name in plan["act"]:
                # the fused BN+residual+relu launch (rng split mirrors the
                # dense path's one-split-per-layer-node stream exactly)
                rng, sub = jax.random.split(rng)
                _, add_name = plan["act"][name]
                (xbn, bnp, bns, bnl), other = pending_add.pop(add_name)
                from ..ops.pallas_epilogue import bn_act

                with jax.named_scope(vertex_scope(name)):
                    y = bn_act(xbn, bns["mean"], bns["var"],
                               bnp.get("gamma"), bnp.get("beta"),
                               epsilon=bnl.eps,
                               axis=1 if xbn.ndim == 4 else -1, act="relu",
                               residual=other)
                    if y is None:
                        # shape gate refused: replay the dense chain verbatim
                        bn_out, _ = bnl.apply(bnp, xbn, bns, training, sub)
                        y, _ = node.layer.apply(params.get(name, {}),
                                                bn_out + other,
                                                states.get(name, {}),
                                                training, sub)
                acts[name] = y
                continue
            ins = [acts[i] for i in node.inputs]
            if node.kind == "vertex":
                with jax.named_scope(vertex_scope(name)):
                    acts[name] = node.vertex.apply(*ins)
                continue
            x = ins[0]
            if 0 in node.preprocessors:
                x = node.preprocessors[0](x)
            if node.layer.multi_input:
                x = (x, *ins[1:])
            lp = params.get(name, {})
            borrowed = node.layer.borrowed_params()
            if borrowed:    # leaves of another node, read here too
                lp = {**lp, **{k: params[n][leaf]
                               for k, (n, leaf) in borrowed.items()}}
            rng, sub = jax.random.split(rng)
            if plan is not None and name in plan["bn"]:
                # head of a fused chain: stash the raw input for the relu
                pending_bn[name] = (x, params.get(name, {}),
                                    states.get(name, {}), node.layer)
                continue
            if to_preout and name in out_set and isinstance(node.layer, (L.OutputLayer, L.LossLayer)):
                with jax.named_scope(vertex_scope(name)):
                    x = node.layer._maybe_dropout(x, training, sub)
                    head_params = lp
                    if hasattr(node.layer, "fused_score"):
                        # the head computes its own loss from its input, in
                        # blocks, and keeps its own precision rule
                        acts[name] = L.HeadInput(x, head_params)
                        continue
                    if cd:
                        # run the head matmul + downstream loss in fp32
                        # (matches the MultiLayerNetwork mixed-precision
                        # policy)
                        f32 = lambda a: (a.astype(jnp.float32)
                                         if jnp.issubdtype(a.dtype, jnp.floating) else a)
                        head_params = jax.tree.map(f32, head_params)
                        x = f32(x)
                    acts[name] = node.layer.pre_output(head_params, x)
            else:
                # the vertex's scope lies INSIDE what remat wraps, so the
                # recomputed forward carries it too
                @jax.named_scope(vertex_scope(name))
                def run(lp, xx, st, k, _l=node.layer):
                    return _l.apply(lp, xx, st, training, k)

                if training:
                    # rematerialize this node's activations in backward
                    # per the configured policy (GlobalConf.remat_policy /
                    # legacy gradient_checkpointing); selective lists
                    # match on the vertex NAME here
                    run = remat_wrap(self.conf.global_conf, run,
                                     block=name)
                y, st = run(lp, x, states.get(name, {}), sub)
                extras = node.layer.extra_outputs()
                if extras:
                    y, *more = y
                    for extra, tensor in zip(extras, more):
                        acts[f"{name}.{extra}"] = tensor
                acts[name] = y
                if st:
                    new_states[name] = st
        return acts, new_states

    def output(self, *inputs, training: bool = False) -> List[NDArray]:
        self._check_init()
        feed = self._bind_inputs(inputs)
        if self._infer_fn is None:
            def infer(params, states, ins, key, train: bool):
                acts, _ = self._forward(params, states, ins, train, key)
                return tuple(acts[o] for o in self.conf.network_outputs)

            self._infer_fn = xprof.register_jit(
                "graph/infer", jax.jit(infer, static_argnames=("train",)),
                static_argnames=("train",))
        outs = self._infer_fn(self._params, self._states, feed,
                              get_random().next_key(), train=training)
        return [NDArray(o) for o in outs]

    def _bind_inputs(self, inputs) -> Dict[str, jnp.ndarray]:
        names = self.conf.network_inputs
        if len(inputs) == 1 and isinstance(inputs[0], dict):
            return {k: jnp.asarray(v.value if isinstance(v, NDArray) else v)
                    for k, v in inputs[0].items()}
        if len(inputs) != len(names):
            raise ValueError(f"expected {len(names)} inputs {names}, got {len(inputs)}")
        return {n: jnp.asarray(v.value if isinstance(v, NDArray) else v)
                for n, v in zip(names, inputs)}

    # --- loss ------------------------------------------------------------
    def _loss(self, params, states, inputs, labels: Dict[str, jnp.ndarray],
              masks, training, rng, w=None, w_denom=None):
        acts, new_states = self._forward(params, states, inputs, training, rng,
                                         to_preout=True)
        total = 0.0
        for out_name in self.conf.network_outputs:
            node = self.conf.nodes[out_name]
            if not isinstance(node.layer, (L.OutputLayer, L.LossLayer)):
                continue
            pre = acts[out_name]
            mask = masks.get(out_name) if masks else None
            # a fused head's token-block loops are the vertex's own work;
            # every other head's score is the ``loss``
            with jax.named_scope(vertex_scope(out_name)
                                 if isinstance(pre, L.HeadInput) else "loss"):
                score = self._score_of(node.layer, pre, labels[out_name],
                                       mask, w, w_denom)
            # a LossLayer's loss_weight (an OutputLayer has none: 1)
            weight = getattr(node.layer, "loss_weight", 1.0)
            total = total + (score if weight == 1.0 else weight * score)
        gc = self.conf.global_conf
        reg = 0.0
        with jax.named_scope("loss"):
            for lname, lp in params.items():
                layer = self.conf.nodes[lname].layer
                l1 = layer.l1 if layer.l1 is not None else gc.l1
                l2 = layer.l2 if layer.l2 is not None else gc.l2
                for pname, w in lp.items():
                    if pname in ("b", "beta"):
                        continue
                    if l2:
                        reg = reg + 0.5 * l2 * jnp.sum(jnp.square(w))
                    if l1:
                        reg = reg + l1 * jnp.sum(jnp.abs(w))
        return total + reg, new_states

    def _score_of(self, layer, pre, labels, mask, w, w_denom):
        """One output's score from its pre-output (or its ``HeadInput``)."""
        if isinstance(pre, L.HeadInput):
            return _fused_head_score(layer, pre, labels, mask, w, w_denom)
        # under reduced-precision compute, reduce the loss in fp32;
        # leave fp64 runs (gradient checks) untouched
        if self.conf.global_conf.compute_dtype and \
                jnp.issubdtype(pre.dtype, jnp.floating):
            pre = pre.astype(jnp.float32)
        if w is None:
            return layer.loss.compute_score(labels, pre, layer.activation,
                                            mask, average=True)
        # example-weighted mean (shape-stable batching): pad rows
        # carry w=0 and the divisor is the real example count
        return layer.loss.compute_score(
            labels, pre, layer.activation, _fold_weights(mask, w),
            average=False) / (w_denom if w_denom is not None
                              else jnp.maximum(jnp.sum(w), 1.0))

    def _bind(self, ds):
        in_names = self.conf.network_inputs
        out_names = [o for o in self.conf.network_outputs
                     if isinstance(self.conf.nodes[o].layer, (L.OutputLayer, L.LossLayer))]
        if isinstance(ds, MultiDataSet):
            inputs = {n: jnp.asarray(f.value) for n, f in zip(in_names, ds.features)}
            labels = {n: jnp.asarray(l.value) for n, l in zip(out_names, ds.labels)}
            masks = {}
            if ds.labels_masks:
                masks = {n: jnp.asarray(m.value)
                         for n, m in zip(out_names, ds.labels_masks) if m is not None}
            return inputs, labels, masks
        inputs = {in_names[0]: jnp.asarray(ds.features.value)}
        labels = {out_names[0]: jnp.asarray(ds.labels.value)}
        masks = {}
        if ds.labels_mask is not None:
            masks = {out_names[0]: jnp.asarray(ds.labels_mask.value)}
        return inputs, labels, masks

    # --- training (the step and the loop are nn.train_step's) -------------
    def _keyed_layers(self):
        return [(name, self.conf.nodes[name].layer) for name in self._params]

    def scope_kinds(self) -> dict:
        """Every layer node and vertex node, those without parameters of
        their own too (a tied head, a merge)."""
        return {vertex_scope(name): type(node.layer if node.kind == "layer"
                                         else node.vertex).__name__
                for name, node in self.conf.nodes.items()
                if node.kind in ("layer", "vertex")}

    @jax.named_scope(FORWARD)
    def _loss_of(self, params, states, batch, key, *, training=True, w=None,
                 w_denom=None):
        """The loss of one batch ``(inputs, labels, masks)``."""
        return self._loss(params, states, *batch, training, key, w=w,
                          w_denom=w_denom)

    def _build_fit_step(self):
        step = step_program(make_core(self, self._telemetry),
                            "trace/graph_fit_step", batch_len=3)
        return xprof.register_jit(
            "graph/fit_step", jax.jit(step, donate_argnums=(0, 1, 2)),
            donate=(0, 1, 2))

    def _build_chunk_step(self):
        chunk = chunk_program(make_core(self, self._telemetry),
                              "trace/graph_fit_chunk")
        return xprof.register_jit(
            "graph/fit_chunk", jax.jit(chunk, donate_argnums=(0, 1, 2)),
            donate=(0, 1, 2))

    def expert_load(self, reset: bool = False) -> Dict[str, np.ndarray]:
        """{node: tokens that selected each expert} of the routed-expert
        layers, accumulated over the training steps since the last reset.
        Read here, when asked for: ``fit`` never fetches it."""
        names = [n for n, st in self._states.items() if "expert_load" in st]
        out = {n: np.asarray(self._states[n]["expert_load"]) for n in names}
        if reset:
            for n in names:
                self._states[n] = {**self._states[n], "expert_load":
                                   jnp.zeros_like(self._states[n]["expert_load"])}
        return out

    def evaluate(self, data):
        from ..eval.evaluation import Evaluation

        ev = Evaluation()
        for ds in self._iter_data(data):
            if isinstance(ds, MultiDataSet):
                out = self.output(*[f for f in ds.features])[0]
                ev.eval(ds.labels[0].to_numpy(), out.to_numpy())
            else:
                out = self.output(ds.features)[0]
                ev.eval(ds.labels.to_numpy(), out.to_numpy(),
                        ds.labels_mask.to_numpy() if ds.labels_mask is not None else None)
        return ev

    # --- persistence ------------------------------------------------------
    @staticmethod
    def load(path: str, load_updater: bool = False) -> "ComputationGraph":
        from ..util.model_serializer import restore_computation_graph

        return restore_computation_graph(path, load_updater)

    def summary(self) -> str:
        lines = [f"{'node':<28}{'kind':<10}{'out type':<34}{'params':<10}"]
        total = 0
        for name in self.conf.order:
            node = self.conf.nodes[name]
            n = (sum(int(np.prod(p.shape)) for p in jax.tree.leaves(self._params.get(name, {})))
                 if self._initialized else 0)
            total += n
            ot = self.conf.node_output_types.get(name, "?")
            kind = node.kind if node.kind != "layer" else type(node.layer).__name__
            lines.append(f"{name:<28}{kind[:24]:<10}{str(ot):<34}{n:<10}")
        lines.append(f"Total params: {total}")
        return "\n".join(lines)



def _fused_head_score(layer, head, labels, mask, w, w_denom):
    """The score of a head that computes its loss from its own input
    (``TiedOutputLayer``): each sequence's mean over its (unmasked)
    positions, then the mean over sequences, or their ``w``-weighted mean as
    every other head under shape-stable batching."""
    per_token = (jnp.ones(labels.shape, jnp.float32) if mask is None
                 else mask.astype(jnp.float32))
    per_token = per_token / jnp.maximum(
        jnp.sum(per_token, axis=-1, keepdims=True), 1.0)
    if w is None:
        denom = labels.shape[0]
    else:
        per_token = per_token * w.astype(jnp.float32)[:, None]
        denom = w_denom if w_denom is not None else jnp.maximum(jnp.sum(w), 1.0)
    return layer.fused_score(head.params, head.x, labels, per_token) / denom
