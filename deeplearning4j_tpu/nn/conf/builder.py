"""Network configuration builders.

Reference: dl4j-nn ``org.deeplearning4j.nn.conf.NeuralNetConfiguration.Builder``
→ ``.list()`` → ``MultiLayerConfiguration`` (SURVEY.md §2.3): global defaults
(updater, weight init, activation, l1/l2, seed) cascade onto layers that don't
set their own; ``setInputType`` walks the layer list inferring nIn and
inserting preprocessors. Configs serialize to JSON and are the model file's
topology section (ModelSerializer contract, §5.4).
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Union

import numpy as np

from ...learning.schedules import ISchedule
from ...learning.updaters import GradientUpdater, Sgd, _BY_NAME as _UPDATERS
from ..losses import ILossFunction
from . import layers as L
from .inputs import (CNNFlatInput, CNNInput, FFInput, InputType, Preprocessor,
                     RNNInput, cnn_to_ff, flat_to_cnn, rnn_to_ff)


@dataclass
class GlobalConf:
    seed: int = 12345
    updater: GradientUpdater = field(default_factory=lambda: Sgd(1e-1))
    weight_init: str = "xavier"
    activation: str = "identity"
    l1: float = 0.0
    l2: float = 0.0
    dropout: float = 0.0
    grad_normalization: Optional[str] = None      # clip modes
    grad_norm_threshold: float = 1.0
    dtype: str = "float32"                # parameter storage dtype
    # Mixed precision: forward/backward compute dtype (e.g. "bfloat16" for the
    # MXU) while params stay in `dtype` and the loss reduces in float32.
    compute_dtype: Optional[str] = None
    # Rematerialization: wrap each layer apply in jax.checkpoint so the
    # backward pass recomputes activations instead of storing them —
    # trades FLOPs for HBM (the TPU answer to big models / long context;
    # absent from the reference, whose workspaces only recycle, not
    # recompute). Gradients are bit-identical either way.
    gradient_checkpointing: bool = False
    # Named rematerialization policy (supersedes the blanket bool above
    # when set). One of:
    #   "none"  — store every residual (the jax default; bitwise-
    #             identical to leaving both knobs off);
    #   "full"  — recompute everything (what gradient_checkpointing=True
    #             has always meant);
    #   "dots_only" — save only matmul/conv outputs, recompute the cheap
    #             elementwise tail (jax.checkpoint_policies.checkpoint_
    #             dots): the classic FLOPs-for-HBM trade that keeps the
    #             MXU-expensive results;
    #   "checkpoint_dots_with_no_batch_dims" — save only contractions
    #             with no batch dims (weight-gradient-shaped matmuls),
    #             recompute activation-shaped ones: the most aggressive
    #             named policy short of "full";
    #   [block, ...] — selective: fully rematerialize ONLY the named
    #             blocks (layer indices for MultiLayerNetwork, vertex
    #             names for ComputationGraph); everything else stores.
    # All policies change WHICH residuals are stored, never the math:
    # loss sequences are bit-identical across policies on a fixed
    # platform (pinned by tests/test_remat_policies.py).
    remat_policy: Any = None
    # Accepted, and selects nothing: where params and updater state are
    # whole on the device as trees (ComputationGraph.fit,
    # MultiLayerNetwork.fit, ParallelWrapper's dense all-reduce) the step
    # updates them leaf by leaf in the layout they live in, whatever this
    # says. It used to flatten params/grads/state into Zero1Plan buckets
    # inside the step for one ops/pallas_update kernel per bucket; on a
    # TPU that round trip is a physical relayout of every leaf and cost
    # 46 ms of a 93 ms ResNet-50 step (PERF.md, PR 27). The bucket kernel
    # is ZeRO-1's (ReduceScatterAccumulator), which needs no switch. Kept
    # because configurations assign it; ROADMAP.md D3 removes it.
    fused_update: bool = False
    # Backward-epilogue fusion under ZeRO-1 (ParallelWrapper with
    # ReduceScatterAccumulator; nothing else reads it): differentiate
    # w.r.t. the plan's FLAT buckets so the cotangents accumulate
    # directly into the layout the reduce-scatter takes and the dense
    # grad pytree never materializes. Bitwise identical to the
    # dense-then-flatten step (Zero1Plan.unflatten_diff spells out the
    # adjoint). On by default; False forces dense-grads-then-flatten.
    # Auto-disabled when telemetry stats need the dense grads.
    flat_backward: bool = True
    # Fused inference epilogue (ops/pallas_epilogue): inference-mode
    # BatchNormalization + relu/identity collapse into one kernel, and
    # ComputationGraph additionally fuses the resnet block tail
    # BN(identity) → ElementWiseVertex(add) → relu into a single
    # BN+residual+relu launch. Opt-in (the folded per-channel affine is
    # a reassociation of the dense ops — tolerance-bounded parity, never
    # silently changed numerics); shape-gated per call with a dense
    # fallback, ledgered under precision/epilogue_*. Training-mode BN
    # (batch statistics + hand VJP) is never touched.
    fused_epilogue: bool = False


#: the named policies remat_wrap resolves (selective lists are the
#: fourth, open-ended form)
REMAT_POLICIES = ("none", "full", "dots_only",
                  "checkpoint_dots_with_no_batch_dims")


def effective_remat_policy(gc: GlobalConf):
    """The policy in force: ``remat_policy`` when set, else the legacy
    ``gradient_checkpointing`` bool mapped to "full"/"none"."""
    pol = getattr(gc, "remat_policy", None)
    if pol is not None:
        return pol
    return "full" if gc.gradient_checkpointing else "none"


def remat_wrap(gc: GlobalConf, fn, block=None):
    """Apply the configured rematerialization policy to one block's
    apply function (the three wrap sites: MLN layer apply, MLN TBPTT
    segment, graph vertex apply). ``block`` is the block's identity for
    selective lists — the layer index (MLN) or vertex name (graph).
    Returns ``fn`` untouched under "none" (zero-cost default) and the
    ``jax.checkpoint``-wrapped fn otherwise; unknown policy names raise
    at step-build time, never silently store-everything."""
    pol = effective_remat_policy(gc)
    if pol == "none":
        return fn
    import jax

    if isinstance(pol, (list, tuple, set)):
        return jax.checkpoint(fn) if block in pol else fn
    if pol == "full":
        return jax.checkpoint(fn)
    if pol == "dots_only":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    if pol == "checkpoint_dots_with_no_batch_dims":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies
            .checkpoint_dots_with_no_batch_dims)
    raise ValueError(
        f"unknown remat policy {pol!r}; expected one of "
        f"{sorted(REMAT_POLICIES)} or a selective block list")


class NeuralNetConfiguration:
    @staticmethod
    def builder() -> "Builder":
        return Builder()


class Builder:
    def __init__(self) -> None:
        self._conf = GlobalConf()

    def seed(self, s: int) -> "Builder":
        self._conf.seed = int(s)
        return self

    def updater(self, u: GradientUpdater) -> "Builder":
        self._conf.updater = u
        return self

    def weight_init(self, w: str) -> "Builder":
        self._conf.weight_init = w
        return self

    def activation(self, a: str) -> "Builder":
        self._conf.activation = a
        return self

    def l1(self, v: float) -> "Builder":
        self._conf.l1 = v
        return self

    def l2(self, v: float) -> "Builder":
        self._conf.l2 = v
        return self

    def dropout(self, v: float) -> "Builder":
        self._conf.dropout = v
        return self

    def gradient_normalization(self, mode: str, threshold: float = 1.0) -> "Builder":
        self._conf.grad_normalization = mode
        self._conf.grad_norm_threshold = threshold
        return self

    def data_type(self, dtype: str) -> "Builder":
        self._conf.dtype = dtype
        return self

    def compute_dtype(self, dtype: str) -> "Builder":
        """bf16 compute with fp32 master params (TPU mixed precision)."""
        self._conf.compute_dtype = dtype
        return self

    def gradient_checkpointing(self, v: bool = True) -> "Builder":
        """Rematerialize per-layer activations in backward
        (jax.checkpoint): ~constant activation memory in depth for extra
        forward FLOPs; gradients unchanged."""
        self._conf.gradient_checkpointing = bool(v)
        return self

    def remat_policy(self, policy) -> "Builder":
        """Named rematerialization policy ("none" | "full" | "dots_only"
        | "checkpoint_dots_with_no_batch_dims") or a selective list of
        block identifiers to fully rematerialize. Supersedes
        gradient_checkpointing(); see GlobalConf.remat_policy."""
        if isinstance(policy, str) and policy not in REMAT_POLICIES:
            raise ValueError(
                f"unknown remat policy {policy!r}; expected one of "
                f"{sorted(REMAT_POLICIES)} or a selective block list")
        self._conf.remat_policy = policy
        return self

    def fused_update(self, v: bool = True) -> "Builder":
        """Accepted for configurations that set it; selects nothing (the
        step updates each leaf in its own layout, and ZeRO-1 takes the
        bucket kernel unasked). See GlobalConf.fused_update."""
        self._conf.fused_update = bool(v)
        return self

    def fused_epilogue(self, v: bool = True) -> "Builder":
        """Fuse inference-mode BN + relu (+ the graph residual add) into
        one epilogue kernel (ops/pallas_epilogue). Tolerance-bounded vs
        the dense ops; see GlobalConf.fused_epilogue."""
        self._conf.fused_epilogue = bool(v)
        return self

    def list(self) -> "ListBuilder":
        return ListBuilder(self._conf)


class ListBuilder:
    def __init__(self, conf: GlobalConf) -> None:
        self._conf = conf
        self._layers: List[L.Layer] = []
        self._input_type: Optional[InputType] = None
        self._backprop_type = "Standard"
        self._tbptt_fwd = 20
        self._tbptt_back = 20

    def layer(self, idx_or_layer, maybe_layer: Optional[L.Layer] = None) -> "ListBuilder":
        layer = maybe_layer if maybe_layer is not None else idx_or_layer
        self._layers.append(layer)
        return self

    def set_input_type(self, input_type: InputType) -> "ListBuilder":
        self._input_type = input_type
        return self

    setInputType = set_input_type

    # -- truncated BPTT (reference: MultiLayerConfiguration.Builder
    # backpropType/tBPTTForwardLength/tBPTTBackwardLength) ---------------
    def backprop_type(self, bp: str) -> "ListBuilder":
        if bp not in ("Standard", "TruncatedBPTT"):
            raise ValueError("backprop_type must be Standard|TruncatedBPTT")
        self._backprop_type = bp
        return self

    def tbptt_fwd_length(self, k: int) -> "ListBuilder":
        self._tbptt_fwd = int(k)
        return self

    def tbptt_back_length(self, k: int) -> "ListBuilder":
        self._tbptt_back = int(k)
        return self

    def tbptt_length(self, k: int) -> "ListBuilder":
        return self.tbptt_fwd_length(k).tbptt_back_length(k)

    def build(self) -> "MultiLayerConfiguration":
        if self._backprop_type == "TruncatedBPTT" \
                and self._tbptt_fwd != self._tbptt_back:
            # DOCUMENTED DIVERGENCE: the reference supports back < fwd
            # (gradients truncated deeper than the forward segment); here one
            # lax.scan segment is both, so unequal lengths would silently do
            # something else — refuse rather than imply support.
            raise ValueError(
                "tbptt_fwd_length must equal tbptt_back_length (use "
                "tbptt_length(k)); unequal truncation windows are not "
                "supported")
        # cascade global defaults
        for l in self._layers:
            self._apply_defaults(l)
        mlc = MultiLayerConfiguration(self._conf, self._layers)
        mlc.backprop_type = self._backprop_type
        mlc.tbptt_fwd_length = self._tbptt_fwd
        mlc.tbptt_back_length = self._tbptt_back
        if self._input_type is not None:
            mlc.set_input_type(self._input_type)
        return mlc

    def _apply_defaults(self, l: L.Layer) -> None:
        apply_layer_defaults(l, self._conf)


def apply_layer_defaults(l: L.Layer, gc: GlobalConf) -> None:
    """Cascade global defaults onto a layer (shared by the list and graph
    builders — reference NeuralNetConfiguration.Builder inheritance)."""
    if l.activation is None and not isinstance(l, (L.OutputLayer, L.LossLayer)):
        l.activation = gc.activation
    if l.weight_init is None:
        l.weight_init = gc.weight_init
    if isinstance(l, L.BatchNormalization) and l.fused_epilogue is None:
        l.fused_epilogue = gc.fused_epilogue
    if l.l1 is None:
        l.l1 = gc.l1
    if l.l2 is None:
        l.l2 = gc.l2
    if l.dropout is None:
        l.dropout = gc.dropout
    inner = getattr(l, "layer", None)
    if isinstance(inner, L.Layer):
        apply_layer_defaults(inner, gc)


class MultiLayerConfiguration:
    def __init__(self, global_conf: GlobalConf, layers: List[L.Layer]):
        self.global_conf = global_conf
        self.layers = layers
        self.preprocessors: Dict[int, Preprocessor] = {}
        self.input_type: Optional[InputType] = None
        self.layer_output_types: List[InputType] = []
        self.backprop_type = "Standard"
        self.tbptt_fwd_length = 20
        self.tbptt_back_length = 20

    # --- shape inference + preprocessor insertion -----------------------
    def set_input_type(self, input_type: InputType) -> None:
        self.input_type = input_type
        self.preprocessors = {}
        self.layer_output_types = []
        cur = input_type
        for i, layer in enumerate(self.layers):
            pre = self._preprocessor_for(cur, layer)
            if pre is not None:
                self.preprocessors[i] = pre
                cur = pre.out_type
            cur = layer.set_input_type(cur)
            self.layer_output_types.append(cur)

    @staticmethod
    def _preprocessor_for(cur: InputType, layer: L.Layer) -> Optional[Preprocessor]:
        # frozen wrappers keep their inner layer's input contract
        # (transfer learning freezes CNN feature extractors whose Dense
        # heads still need the automatic CnnToFeedForward insertion)
        if isinstance(layer, L.FrozenLayer) and layer.layer is not None:
            layer = layer.layer
        ff_like = (L.DenseLayer, L.OutputLayer, L.ElementWiseMultiplicationLayer)
        if isinstance(cur, CNNFlatInput):
            return flat_to_cnn(cur)
        if isinstance(cur, CNNInput) and isinstance(layer, ff_like) \
                and not isinstance(layer, L.RnnOutputLayer):
            return cnn_to_ff(cur)
        from .inputs import CNN3DInput, cnn3d_to_ff
        if isinstance(cur, CNN3DInput) and isinstance(layer, ff_like) \
                and not isinstance(layer, L.RnnOutputLayer):
            return cnn3d_to_ff(cur)
        if isinstance(cur, RNNInput) and isinstance(layer, L.DenseLayer) \
                and not isinstance(layer, (L.OutputLayer,)):
            return rnn_to_ff(cur)
        return None

    # --- serde -----------------------------------------------------------
    def to_json(self) -> str:
        return json.dumps({
            "format_version": 1,
            "global": _ser_obj(self.global_conf),
            "layers": [_ser_obj(l) for l in self.layers],
            "input_type": _ser_obj(self.input_type) if self.input_type else None,
            "backprop_type": self.backprop_type,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
        }, indent=2)

    @staticmethod
    def from_json(s: str) -> "MultiLayerConfiguration":
        d = json.loads(s)
        gc = _deser_obj(d["global"])
        layers = [_deser_obj(ld) for ld in d["layers"]]
        mlc = MultiLayerConfiguration(gc, layers)
        mlc.backprop_type = d.get("backprop_type", "Standard")
        mlc.tbptt_fwd_length = d.get("tbptt_fwd_length", 20)
        mlc.tbptt_back_length = d.get("tbptt_back_length", 20)
        if d.get("input_type"):
            mlc.set_input_type(_deser_obj(d["input_type"]))
        return mlc


# --- generic dataclass (de)serialization for configs -------------------------

_CLASSES: Dict[str, type] = {}
for _mod in (L,):
    for _name in dir(_mod):
        _obj = getattr(_mod, _name)
        if isinstance(_obj, type) and dataclasses.is_dataclass(_obj):
            _CLASSES[_name] = _obj
_CLASSES["GlobalConf"] = GlobalConf
from .inputs import FFInput as _FF, RNNInput as _RNN, CNNInput as _CNN, CNNFlatInput as _CNNF  # noqa: E402
for _c in (_FF, _RNN, _CNN, _CNNF):
    _CLASSES[_c.__name__] = _c
from ...learning import schedules as _sched_mod  # noqa: E402
for _name in dir(_sched_mod):
    _obj = getattr(_sched_mod, _name)
    if isinstance(_obj, type) and dataclasses.is_dataclass(_obj):
        _CLASSES[_name] = _obj
from ...learning import updaters as _upd_mod  # noqa: E402
for _name in dir(_upd_mod):
    _obj = getattr(_upd_mod, _name)
    if isinstance(_obj, type) and dataclasses.is_dataclass(_obj):
        _CLASSES[_name] = _obj
from .. import losses as _loss_mod  # noqa: E402
for _name in dir(_loss_mod):
    _obj = getattr(_loss_mod, _name)
    if isinstance(_obj, type) and issubclass(_obj, ILossFunction) and _obj is not ILossFunction:
        _CLASSES[_name] = _obj


def _ser_obj(obj: Any) -> Any:
    if obj is None or isinstance(obj, (int, float, str, bool)):
        return obj
    if isinstance(obj, (list, tuple)):
        return {"__tuple__": [_ser_obj(v) for v in obj]} if isinstance(obj, tuple) \
            else [_ser_obj(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return {"__ndarray__": obj.tolist(), "dtype": str(obj.dtype)}
    if isinstance(obj, ILossFunction):
        return {"__class__": type(obj).__name__,
                "fields": {k: _ser_obj(v) for k, v in obj.__dict__.items()}}
    if dataclasses.is_dataclass(obj):
        fields = {}
        lambda_cls = _CLASSES.get("LambdaLayer")
        for f in dataclasses.fields(obj):
            v = getattr(obj, f.name)
            if (lambda_cls is not None and isinstance(obj, lambda_cls)
                    and f.name == "fn" and callable(v)):
                # LambdaLayer ONLY: function bodies are not serializable —
                # the reference pattern serializes the NAME and restores
                # through the registered-lambda lookup (register_lambda).
                # Other fn-bearing objects still fail loudly below.
                if not getattr(obj, "name", ""):
                    raise TypeError(
                        "cannot serialize an unnamed LambdaLayer — give it "
                        "a unique name=... so restore can look up the "
                        "registered implementation")
                fields[f.name] = {"__lambda__": obj.name}
            else:
                fields[f.name] = _ser_obj(v)
        return {"__class__": type(obj).__name__, "fields": fields}
    if isinstance(obj, GradientUpdater):
        return {"__class__": type(obj).__name__,
                "fields": {k: _ser_obj(v) for k, v in obj.__dict__.items()}}
    raise TypeError(f"cannot serialize config object {type(obj)}")


def _deser_obj(d: Any) -> Any:
    if d is None or isinstance(d, (int, float, str, bool)):
        return d
    if isinstance(d, list):
        return [_deser_obj(v) for v in d]
    if isinstance(d, dict):
        if "__tuple__" in d:
            return tuple(_deser_obj(v) for v in d["__tuple__"])
        if "__ndarray__" in d:
            return np.asarray(d["__ndarray__"], dtype=d["dtype"])
        if "__lambda__" in d:
            from ...imports.keras_import import resolve_lambda

            return resolve_lambda(d["__lambda__"])
        if "__class__" in d:
            cls = _CLASSES[d["__class__"]]
            fields = {k: _deser_obj(v) for k, v in d["fields"].items()}
            if dataclasses.is_dataclass(cls):
                known = {f.name for f in dataclasses.fields(cls)}
                init_args = {k: v for k, v in fields.items() if k in known}
                obj = cls(**init_args)
                for k, v in fields.items():
                    if k not in known:
                        setattr(obj, k, v)
                return obj
            obj = cls.__new__(cls)
            obj.__dict__.update(fields)
            return obj
        return {k: _deser_obj(v) for k, v in d.items()}
    return d
