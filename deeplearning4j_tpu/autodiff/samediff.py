"""SameDiff analog — symbolic DAG lowered to ONE compiled XLA module.

Reference: nd4j-api ``org.nd4j.autodiff.samediff.{SameDiff, SDVariable}``,
``internal/{AbstractSession, InferenceSession, TrainingSession}``,
``functions.DifferentialFunction`` (SURVEY.md §2.1, §3.3).

TPU-first design (SURVEY.md §7.1): where the reference walks the DAG op-by-op
through ``InferenceSession.doExec`` → one JNI crossing per op, here the DAG is
traced once into a single jax function and jit-compiled — the whole forward
(or train step, including gradients and the fused updater) is ONE XLA module.
This is the architecture the reference's own seldom-used native
``GraphExecutioner`` path (``SameDiff.asFlatBuffers`` → whole-graph C++ exec)
pointed at; on TPU it is the only path.

Autodiff: the reference builds a "grad" child graph by reverse-topo-walking
per-op ``doDiff`` rules. Here gradients come from ``jax.grad`` of the traced
function — the same reverse-mode math, derived by the compiler rather than
hand-written per op, so every differentiable registered op gets gradients for
free.

Control flow: TF1-style Enter/Exit/Merge/Switch frames are NOT reproduced;
``sd.cond`` / ``sd.while_loop`` wrap ``lax.cond`` / ``lax.while_loop`` for the
structured subset (documented divergence — XLA requires structured control
flow).
"""

from __future__ import annotations

import io
import json
import zipfile
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..common import xprof
from ..common.dtypes import DataType
from ..common.profiler import OpProfiler
from ..data.pipeline import timed_iter
from ..ndarray.ndarray import NDArray
from ..ndarray.rng import get_random
from ..learning.precision import apply_updater
from ..learning.schedules import ISchedule
from ..learning.updaters import Adam, GradientUpdater
from ..nn.train_step import FORWARD, UPDATE
from ..ops.registry import all_ops, get_op

# v2: control-flow nodes ("control" key) + scope-prefixed npz array keys
_FORMAT_VERSION = 2


class VariableType:
    VARIABLE = "VARIABLE"        # trainable
    PLACEHOLDER = "PLACEHOLDER"  # fed per call
    CONSTANT = "CONSTANT"
    ARRAY = "ARRAY"              # op output


@dataclass
class _Var:
    name: str
    vtype: str
    shape: Optional[Tuple[Optional[int], ...]] = None
    dtype: str = "float32"
    # materialized for VARIABLE/CONSTANT. A VARIABLE's value is a host array
    # until a call first needs it on the device and a jax.Array from then on
    # (SameDiff._params); assigning either kind is how a caller replaces it.
    value: Any = None
    producer: Optional[int] = None           # node id for ARRAY vars
    out_index: int = 0


@dataclass
class _Node:
    id: int
    op_name: str
    inputs: List[str]
    kwargs: Dict[str, Any]
    outputs: List[str]
    n_outputs: int = 1
    needs_rng: bool = False
    # Mixed positional spec: [("v", var_name) | ("s", static_value)]. Static
    # entries (shape tuples, axis ints) stay Python values so they remain
    # jit-static; None means every positional is a variable (legacy).
    arg_spec: Optional[List[Tuple[str, Any]]] = None
    # Structured control flow (op_name "__cond__"/"__while__"): nested
    # SameDiff graphs per branch + their placeholder/output name lists.
    subgraphs: Optional[Dict[str, "SameDiff"]] = None
    sub_inputs: Optional[Dict[str, List[str]]] = None
    sub_outputs: Optional[Dict[str, List[str]]] = None
    max_iters: Optional[int] = None


class SDVariable:
    """Symbolic handle into a SameDiff graph (reference SDVariable)."""

    def __init__(self, sd: "SameDiff", name: str):
        self.sd = sd
        self.name = name

    # --- metadata ------------------------------------------------------
    @property
    def shape(self):
        return self.sd._vars[self.name].shape

    def var_type(self) -> str:
        return self.sd._vars[self.name].vtype

    # --- evaluation ----------------------------------------------------
    def eval(self, placeholders: Optional[Dict[str, Any]] = None) -> NDArray:
        return self.sd.output(placeholders or {}, [self.name])[self.name]

    def arr(self) -> Optional[NDArray]:
        """The current value, as a copy of the caller's own: a variable's
        device buffer is donated to the next ``fit`` step, which would
        delete a handle that shared it."""
        v = self.sd._vars[self.name]
        return NDArray(jnp.array(v.value)) if v.value is not None else None

    # --- graph-building operators --------------------------------------
    def _bin(self, op: str, other, reverse: bool = False):
        other_v = self.sd._lift(other)
        a, b = (other_v, self) if reverse else (self, other_v)
        return self.sd._add_op(op, [a, b])

    def __add__(self, o):
        return self._bin("add", o)

    __radd__ = __add__

    def __sub__(self, o):
        return self._bin("subtract", o)

    def __rsub__(self, o):
        return self._bin("subtract", o, reverse=True)

    def __mul__(self, o):
        return self._bin("multiply", o)

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._bin("divide", o)

    def __rtruediv__(self, o):
        return self._bin("divide", o, reverse=True)

    def __pow__(self, o):
        return self._bin("pow", o)

    def __neg__(self):
        return self.sd._add_op("neg", [self])

    def __matmul__(self, o):
        return self._bin("matmul", o)

    # common math sugar (sd.math covers everything; these are convenience)
    def add(self, o):
        return self.__add__(o)

    def sub(self, o):
        return self.__sub__(o)

    def mul(self, o):
        return self.__mul__(o)

    def div(self, o):
        return self.__truediv__(o)

    def rsub(self, o):
        return self.__rsub__(o)

    def rdiv(self, o):
        return self.__rtruediv__(o)

    def mmul(self, o):
        return self.__matmul__(o)

    def dot(self, o):
        return self.sd._add_op("dot", [self, self.sd._lift(o)])

    def sum(self, *dims, keep_dims: bool = False):
        return self.sd._add_op("reduce_sum", [self],
                               dims=dims if dims else None, keep_dims=keep_dims)

    def mean(self, *dims, keep_dims: bool = False):
        return self.sd._add_op("reduce_mean", [self],
                               dims=dims if dims else None, keep_dims=keep_dims)

    def max(self, *dims, keep_dims: bool = False):
        return self.sd._add_op("reduce_max", [self],
                               dims=dims if dims else None, keep_dims=keep_dims)

    def min(self, *dims, keep_dims: bool = False):
        return self.sd._add_op("reduce_min", [self],
                               dims=dims if dims else None, keep_dims=keep_dims)

    def std(self, *dims, bias_corrected: bool = True):
        return self.sd._add_op("reduce_stdev", [self],
                               dims=dims if dims else None, bias_corrected=bias_corrected)

    def norm2(self, *dims):
        return self.sd._add_op("reduce_norm2", [self], dims=dims if dims else None)

    def argmax(self, dim: int = -1):
        return self.sd._add_op("argmax", [self], dims=dim)

    def reshape(self, *shape):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return self.sd._add_op("reshape", [self], shape=shape)

    def permute(self, *dims):
        return self.sd._add_op("permute", [self], dims=dims)

    def transpose(self):
        return self.sd._add_op("transpose", [self])

    def rename(self, new_name: str) -> "SDVariable":
        self.sd._rename(self.name, new_name)
        self.name = new_name
        return self

    def __repr__(self):
        v = self.sd._vars[self.name]
        return f"SDVariable(name={self.name!r}, type={v.vtype}, shape={v.shape})"


class _OpNamespace:
    """sd.math / sd.nn / sd.cnn / ... facade (reference codegen namespaces
    SDMath, SDNN, SDCNN, SDRNN, SDLoss, SDRandom, SDImage, SDLinalg,
    SDBitwise). Any registered op is reachable; the namespace is resolution
    sugar, not a gate."""

    def __init__(self, sd: "SameDiff"):
        self._sd = sd

    def __getattr__(self, op_name: str):
        if op_name.startswith("_"):
            raise AttributeError(op_name)
        desc = get_op(op_name)  # raises KeyError for unknown ops

        def call(*args, name: Optional[str] = None, **kwargs):
            # Lift only tensor-likes into the graph; ints/floats/tuples stay
            # static positionals (axis/shape args must not become tracers).
            mixed = [self._sd._lift(a)
                     if isinstance(a, (SDVariable, NDArray, np.ndarray, jnp.ndarray))
                     else a
                     for a in args]
            return self._sd._add_op(op_name, mixed, name=name, **kwargs)

        return call


class SameDiff:
    """Graph container (reference SameDiff.java ~6k LoC; SURVEY.md §2.1)."""

    def __init__(self) -> None:
        self._vars: Dict[str, _Var] = {}
        self._nodes: List[_Node] = []
        self._name_counter: Dict[str, int] = {}
        self._fn_cache: Dict[Tuple, Callable] = {}
        self._training_config = None
        self._updater_state = None
        self._iteration = 0
        self._epoch = 0
        self._fit_calls = 0
        self._loss_var: Optional[str] = None
        self.math = _OpNamespace(self)
        # All namespaces resolve the same registry; aliases for API parity.
        self.nn = self.cnn = self.rnn = self.loss_ops = self.image = self.math
        self.linalg = self.random_ops = self.bitwise = self.math
        self.ops = self.math

    # ------------------------------------------------------------------
    @staticmethod
    def create() -> "SameDiff":
        return SameDiff()

    def _unique(self, base: str) -> str:
        if base not in self._vars:
            return base
        i = self._name_counter.get(base, 0) + 1
        while f"{base}_{i}" in self._vars:
            i += 1
        self._name_counter[base] = i
        return f"{base}_{i}"

    def _rename(self, old: str, new: str) -> None:
        if new in self._vars:
            raise ValueError(f"variable {new!r} already exists")
        v = self._vars.pop(old)
        v.name = new
        self._vars[new] = v
        for n in self._nodes:
            n.inputs = [new if i == old else i for i in n.inputs]
            n.outputs = [new if o == old else o for o in n.outputs]
            if n.arg_spec is not None:
                n.arg_spec = [("v", new) if (k == "v" and v == old) else (k, v)
                              for k, v in n.arg_spec]
        if self._loss_var == old:
            self._loss_var = new
        self._fn_cache.clear()

    # --- variable creation ---------------------------------------------
    def var(self, name: str, shape: Optional[Sequence[int]] = None,
            init: Union[str, NDArray, np.ndarray, None] = "xavier",
            dtype: str = "float32") -> SDVariable:
        """Trainable variable (reference sd.var)."""
        name = self._unique(name)
        if isinstance(init, (NDArray, np.ndarray, jnp.ndarray)):
            value = np.asarray(init.value if isinstance(init, NDArray) else init)
            shape = value.shape
        else:
            if shape is None:
                raise ValueError("var() needs a shape or an initial value")
            value = _initialize(tuple(shape), init or "zeros", dtype)
        self._vars[name] = _Var(name, VariableType.VARIABLE, tuple(shape),
                                str(np.asarray(value).dtype), np.asarray(value))
        self._fn_cache.clear()
        return SDVariable(self, name)

    def placeholder(self, name: str, shape: Optional[Sequence[Optional[int]]] = None,
                    dtype: str = "float32") -> SDVariable:
        name = self._unique(name)
        self._vars[name] = _Var(name, VariableType.PLACEHOLDER,
                                tuple(shape) if shape else None, dtype)
        return SDVariable(self, name)

    # reference API spelling
    placeHolder = placeholder

    def constant(self, name_or_value, value=None) -> SDVariable:
        if value is None:
            name, value = "const", name_or_value
        else:
            name = name_or_value
        name = self._unique(name)
        raw = value.value if isinstance(value, NDArray) else value
        # Bare Python scalars must not inherit the x64 default (under
        # jax_enable_x64 np.asarray(2.0) is float64, silently promoting the
        # whole graph); pin them to the framework defaults. Exact-type checks
        # only: np.float64/np.float32 scalars keep their explicit dtype.
        if type(raw) is float:
            arr = np.asarray(raw, dtype=np.float32)
        elif type(raw) is int:
            arr = np.asarray(raw,
                             dtype=np.int32 if -2**31 <= raw < 2**31 else np.int64)
        else:
            arr = np.asarray(raw)
        self._vars[name] = _Var(name, VariableType.CONSTANT, arr.shape,
                                str(arr.dtype), arr)
        return SDVariable(self, name)

    def get_variable(self, name: str) -> SDVariable:
        if name not in self._vars:
            raise KeyError(f"no variable {name!r}")
        return SDVariable(self, name)

    def convert_to_variables(self, names: Optional[Sequence[str]] = None,
                             min_size: int = 2) -> List[str]:
        """Promote CONSTANT vars to trainable VARIABLEs (reference
        ``SameDiff.convertToVariables``). Frozen TF graphs import every weight
        as a constant; fine-tuning (the BERT north-star flow, SURVEY.md §3.4)
        promotes them back. Default: all float constants with >= min_size
        elements (scalars/axis vectors stay constant)."""
        promoted = []
        targets = set(names) if names is not None else None
        for n, v in self._vars.items():
            if v.vtype != VariableType.CONSTANT:
                continue
            if targets is not None:
                if n not in targets:
                    continue
            else:
                val = np.asarray(v.value)
                if val.size < min_size or not np.issubdtype(val.dtype, np.floating):
                    continue
            v.vtype = VariableType.VARIABLE
            promoted.append(n)
        self._fn_cache.clear()
        return promoted

    convertToVariables = convert_to_variables

    def variables(self) -> List[str]:
        return [n for n, v in self._vars.items() if v.vtype == VariableType.VARIABLE]

    def placeholders(self) -> List[str]:
        return [n for n, v in self._vars.items() if v.vtype == VariableType.PLACEHOLDER]

    # --- graph building -------------------------------------------------
    def _lift(self, value) -> SDVariable:
        if isinstance(value, SDVariable):
            if value.sd is not self:
                raise ValueError("SDVariable belongs to a different SameDiff instance")
            return value
        return self.constant(value)

    def _add_op(self, op_name: str, inputs: List[Any],
                name: Optional[str] = None, n_outputs: Optional[int] = None,
                **kwargs) -> Union[SDVariable, Tuple[SDVariable, ...]]:
        desc = get_op(op_name)
        nid = len(self._nodes)
        needs_rng = desc.family == "random" or op_name in (
            "dropout", "alpha_dropout", "gaussian_dropout", "gaussian_noise")
        n_out = n_outputs or _N_OUTPUTS.get(op_name, 1)
        out_names = [self._unique(name or op_name if i == 0 else f"{name or op_name}:{i}")
                     for i in range(n_out)]
        arg_spec: List[Tuple[str, Any]] = []
        var_inputs: List[str] = []
        for a in inputs:
            if isinstance(a, SDVariable):
                arg_spec.append(("v", a.name))
                var_inputs.append(a.name)
            else:
                arg_spec.append(("s", a))
        node = _Node(nid, op_name, var_inputs, dict(kwargs),
                     out_names, n_out, needs_rng, arg_spec)
        self._nodes.append(node)
        for i, out in enumerate(out_names):
            self._vars[out] = _Var(out, VariableType.ARRAY, producer=nid, out_index=i)
        self._fn_cache.clear()
        outs = tuple(SDVariable(self, o) for o in out_names)
        return outs if n_out > 1 else outs[0]

    # --- structured control flow (reference: SameDiff.ifCond/whileLoop;
    # the TF1 Enter/Exit/Merge frame machinery of AbstractSession is NOT
    # reproduced — XLA requires structured control flow, so these lower to
    # lax.cond / lax.while_loop / lax.scan) -------------------------------
    def _build_branch(self, fn: Callable, n_args: int, tag: str):
        """Trace a branch body into a NESTED SameDiff whose placeholders are
        the branch arguments. Branch bodies see ONLY their operands (pass
        outer variables explicitly) — a closure over outer graph variables
        raises inside the body when it touches an unknown name."""
        sub = SameDiff()
        phs = [sub.placeholder(f"{tag}_arg{i}") for i in range(n_args)]
        out = fn(sub, *phs)
        outs = out if isinstance(out, (tuple, list)) else (out,)
        for o in outs:
            if o.sd is not sub:
                raise ValueError(
                    f"{tag} body must return variables built in its own "
                    "scope (got one from the outer graph)")
        return sub, [p.name for p in phs], [o.name for o in outs]

    def _add_control(self, kind: str, inputs: List[SDVariable],
                     subgraphs, sub_inputs, sub_outputs, n_out: int,
                     name: Optional[str], max_iters: Optional[int] = None):
        nid = len(self._nodes)
        base = name or kind.strip("_")
        out_names = [self._unique(base if i == 0 else f"{base}:{i}")
                     for i in range(n_out)]
        node = _Node(nid, kind, [v.name for v in inputs], {}, out_names,
                     n_out, subgraphs=subgraphs, sub_inputs=sub_inputs,
                     sub_outputs=sub_outputs, max_iters=max_iters)
        self._nodes.append(node)
        for i, out in enumerate(out_names):
            self._vars[out] = _Var(out, VariableType.ARRAY, producer=nid,
                                   out_index=i)
        self._fn_cache.clear()
        outs = tuple(SDVariable(self, o) for o in out_names)
        return outs if n_out > 1 else outs[0]

    def cond(self, pred: SDVariable, true_fn: Callable, false_fn: Callable,
             *operands: SDVariable, name: Optional[str] = None):
        """``lax.cond`` over two traced branch bodies.

        ``true_fn(sub_sd, *args)`` / ``false_fn(sub_sd, *args)`` build their
        result from the given operands; both must return the same number of
        outputs. Differentiable — a graph containing ``cond`` trains.
        """
        pred = self._lift(pred)
        ops = [self._lift(o) for o in operands]
        sub_t, in_t, out_t = self._build_branch(true_fn, len(ops), "true")
        sub_f, in_f, out_f = self._build_branch(false_fn, len(ops), "false")
        if len(out_t) != len(out_f):
            raise ValueError(
                f"branches return different arity: {len(out_t)} vs "
                f"{len(out_f)}")
        return self._add_control(
            "__cond__", [pred] + ops,
            {"true": sub_t, "false": sub_f},
            {"true": in_t, "false": in_f},
            {"true": out_t, "false": out_f}, len(out_t), name)

    ifCond = cond

    def while_loop(self, cond_fn: Callable, body_fn: Callable,
                   *loop_vars: SDVariable, max_iters: Optional[int] = None,
                   name: Optional[str] = None):
        """``lax.while_loop`` over traced cond/body graphs.

        ``cond_fn(sub_sd, *vars) -> scalar bool``; ``body_fn(sub_sd, *vars)``
        returns the updated loop vars (same arity). Unbounded loops are
        forward-only (XLA's while has no reverse-mode rule); pass
        ``max_iters`` to lower to a masked ``lax.scan`` of fixed length,
        which IS differentiable and therefore trainable.
        """
        ops = [self._lift(v) for v in loop_vars]
        sub_c, in_c, out_c = self._build_branch(cond_fn, len(ops), "cond")
        if len(out_c) != 1:
            raise ValueError("cond_fn must return exactly one scalar")
        sub_b, in_b, out_b = self._build_branch(body_fn, len(ops), "body")
        if len(out_b) != len(ops):
            raise ValueError(
                f"body_fn must return {len(ops)} loop vars, got {len(out_b)}")
        return self._add_control(
            "__while__", ops,
            {"cond": sub_c, "body": sub_b},
            {"cond": in_c, "body": in_b},
            {"cond": out_c, "body": out_b}, len(ops), name,
            max_iters=max_iters)

    whileLoop = while_loop

    # --- lowering: DAG → one jax function -------------------------------
    def _topo_for(self, outputs: Sequence[str]) -> List[_Node]:
        needed: List[_Node] = []
        seen = set()

        def visit(var_name: str):
            v = self._vars.get(var_name)
            if v is None:
                raise KeyError(f"unknown variable {var_name!r}")
            if v.producer is None or v.producer in seen:
                return
            seen.add(v.producer)
            node = self._nodes[v.producer]
            for i in node.inputs:
                visit(i)
            needed.append(node)

        for o in outputs:
            visit(o)
        return needed

    def _make_fn(self, outputs: Tuple[str, ...], training: bool) -> Callable:
        """Build fn(params, placeholders, rng_key) -> tuple of outputs.
        The entire DAG becomes one traced function = one XLA module."""
        nodes = self._topo_for(outputs)
        consts = {n: jnp.asarray(v.value) for n, v in self._vars.items()
                  if v.vtype == VariableType.CONSTANT}

        def fn(params: Dict[str, jnp.ndarray], placeholders: Dict[str, jnp.ndarray],
               rng_key):
            env: Dict[str, Any] = {}
            env.update(consts)
            env.update(params)
            env.update(placeholders)
            key = rng_key
            for node in nodes:
                if node.op_name in ("__cond__", "__while__"):
                    key, sub = jax.random.split(key)
                    res = _lower_control(node, env, training, sub)
                    if node.n_outputs > 1:
                        for out_name, r in zip(node.outputs, res):
                            env[out_name] = r
                    else:
                        env[node.outputs[0]] = res[0]
                    continue
                desc = get_op(node.op_name)
                if node.arg_spec is not None:
                    args = [env[v] if kind == "v" else v
                            for kind, v in node.arg_spec]
                else:
                    args = [env[i] for i in node.inputs]
                kwargs = dict(node.kwargs)
                if node.needs_rng:
                    key, sub = jax.random.split(key)
                    if desc.family == "random":
                        args = [sub] + args
                    else:
                        args = [args[0], sub] + args[1:]
                if not training and node.op_name in _TRAIN_ONLY_IDENTITY:
                    res = args[0]
                else:
                    with jax.named_scope(_op_scope(node.outputs[0])):
                        res = desc.fn(*args, **kwargs)
                if node.n_outputs > 1:
                    for out_name, r in zip(node.outputs, res):
                        env[out_name] = r
                else:
                    env[node.outputs[0]] = res
            return tuple(env[o] for o in outputs)

        return fn

    def _params(self) -> Dict[str, jnp.ndarray]:
        """The trainable variables as the device arrays a compiled module
        takes. A value that is already a ``jax.Array`` is handed over as it
        is; a host array (a fresh ``var()``, an import, a ``load()``, a
        caller's assignment to ``.value``) is uploaded and the device array
        kept in ``.value``, so a variable is uploaded once in its life and
        not once a call. Counted in ``samediff/vars_resident`` and
        ``samediff/vars_uploaded``.

        The returned arrays ARE the variables' buffers, and ``fit``'s step
        donates them: hold them no longer than the call they were taken
        for (``SDVariable.arr`` hands out a copy)."""
        params, uploaded = {}, 0
        for n, v in self._vars.items():
            if v.vtype != VariableType.VARIABLE:
                continue
            if not isinstance(v.value, jax.Array):
                v.value = jnp.asarray(v.value)
                uploaded += 1
            params[n] = v.value
        prof = OpProfiler.get()
        prof.count("samediff/vars_resident", len(params) - uploaded)
        prof.count("samediff/vars_uploaded", uploaded)
        return params

    def _jitted(self, outputs: Tuple[str, ...], training: bool) -> Callable:
        cache_key = (outputs, training)
        if cache_key not in self._fn_cache:
            fn = self._make_fn(outputs, training)
            self._fn_cache[cache_key] = xprof.register_jit(
                "samediff/exec", jax.jit(fn))
        return self._fn_cache[cache_key]

    # --- execution -------------------------------------------------------
    def output(self, placeholders: Dict[str, Any], outputs: Sequence[str],
               training: bool = False) -> Dict[str, NDArray]:
        """Reference sd.output(map, names): run the compiled module."""
        outputs = tuple(outputs)
        ph = {k: jnp.asarray(v.value if isinstance(v, NDArray) else v)
              for k, v in placeholders.items()}
        fn = self._jitted(outputs, training)
        key = get_random().next_key()
        res = fn(self._params(), ph, key)
        return {name: NDArray(r) for name, r in zip(outputs, res)}

    def batch_output(self, placeholders=None, outputs=None):
        return self.output(placeholders or {}, outputs or [])

    # --- autodiff --------------------------------------------------------
    def calculate_gradients(self, placeholders: Dict[str, Any], loss: str,
                            wrt: Optional[Sequence[str]] = None) -> Dict[str, NDArray]:
        """Gradient of `loss` w.r.t. trainable vars (reference
        sd.calculateGradients). One jitted jax.grad module, cached per
        (loss, wrt) — no hand-built grad graph, no per-op dispatch."""
        wrt = tuple(wrt) if wrt is not None else tuple(self.variables())
        ph = {k: jnp.asarray(v.value if isinstance(v, NDArray) else v)
              for k, v in placeholders.items()}
        cache_key = ("grad", loss, wrt)
        if cache_key not in self._fn_cache:
            fn = self._make_fn((loss,), training=True)

            def grad_fn(sub, rest, ph_, key):
                def loss_fn(p):
                    full = dict(rest)
                    full.update(p)
                    return jnp.sum(fn(full, ph_, key)[0])

                return jax.grad(loss_fn)(sub)

            self._fn_cache[cache_key] = xprof.register_jit(
                "samediff/grad", jax.jit(grad_fn))
        params = self._params()
        sub = {n: params.pop(n) for n in wrt}
        grads = self._fn_cache[cache_key](sub, params, ph, jax.random.PRNGKey(0))
        return {n: NDArray(g) for n, g in grads.items()}

    def grad(self, var_name: str, loss: Optional[str] = None) -> NDArray:
        loss = loss or self._require_loss()
        return self.calculate_gradients({}, loss, [var_name])[var_name]

    def _require_loss(self) -> str:
        if self._loss_var is None:
            raise ValueError("no loss variable set; call set_loss_variables or pass loss=")
        return self._loss_var

    def set_loss_variables(self, *names: str) -> None:
        self._loss_var = names[0]

    setLossVariables = set_loss_variables

    # --- training --------------------------------------------------------
    def set_training_config(self, config: "TrainingConfig") -> None:
        self._training_config = config
        self._updater_state = None
        # invalidate cached train steps: a replaced config/updater must
        # never hit a step traced with the old hyperparameters
        self._tc_version = getattr(self, "_tc_version", 0) + 1
        for k in [k for k in self._fn_cache if k[0] == "train_step"]:
            del self._fn_cache[k]

    setTrainingConfig = set_training_config

    def _train_step_fn(self, loss_name: str, ph_names: Tuple[str, ...]):
        """One fused XLA module: forward + backward + updater (the reference's
        TrainingSession materialized per-op; here it is one executable).

        Cached in ``_fn_cache`` (invalidated with it on graph mutation):
        without this, every ``fit`` call wrapped a FRESH ``jax.jit`` and
        re-traced — ~1 s of host work per call, pathological for per-batch
        fit callers like the RL learners."""
        tc0 = self._training_config
        # key on a set_training_config version counter + the updater's
        # hyperparameters — NOT object ids (CPython reuses freed addresses,
        # silently resurrecting a step traced with old settings)
        upd0 = tc0.updater
        cache_key = ("train_step", loss_name, ph_names,
                     getattr(self, "_tc_version", 0),
                     type(upd0).__name__,
                     getattr(upd0, "learning_rate", None),
                     getattr(upd0, "momentum", None),
                     tc0.l1, tc0.l2, tc0.grad_clip_value)
        cached = self._fn_cache.get(cache_key)
        if cached is not None:
            return cached
        fn = self._make_fn((loss_name,), training=True)
        tc = self._training_config
        updater = tc.updater
        l1, l2 = tc.l1, tc.l2

        def step(params, upd_state, ph, key, iteration):
            # the scopes of nn.train_step's step: ``forward`` (each op under
            # its variable's directory, ``_op_scope``), ``update``
            @jax.named_scope(FORWARD)
            def loss_fn(p):
                loss = fn(p, ph, key)[0]
                with jax.named_scope("loss"):
                    reg = 0.0
                    if l2:
                        # DL4J L2: score += 0.5*l2*||w||^2 (grad = l2*w) —
                        # matches MultiLayerNetwork._loss
                        reg = reg + 0.5 * l2 * sum(
                            jnp.sum(jnp.square(w)) for w in p.values())
                    if l1:
                        reg = reg + l1 * sum(jnp.sum(jnp.abs(w))
                                             for w in p.values())
                    return jnp.sum(loss) + reg

            loss, grads = jax.value_and_grad(loss_fn)(params)
            with jax.named_scope(UPDATE):
                if tc.grad_clip_value:
                    with jax.named_scope("grad_norm"):
                        grads = jax.tree.map(
                            lambda g: jnp.clip(g, -tc.grad_clip_value,
                                               tc.grad_clip_value), grads)
                new_params, new_state = apply_updater(
                    updater, grads, upd_state, params, iteration, key)
            return new_params, new_state, loss

        jitted = xprof.register_jit(
            "samediff/fit_step", jax.jit(step, donate_argnums=(0, 1)),
            donate=(0, 1))
        self._fn_cache[cache_key] = jitted
        return jitted

    def fit(self, data=None, epochs: int = 1, batch_size: Optional[int] = None,
            feature_placeholder: Optional[str] = None,
            label_placeholder: Optional[str] = None,
            listeners: Optional[List] = None) -> "History":
        """Train against a DataSetIterator / DataSet / (features, labels) tuple.

        Placeholder binding follows the reference TrainingConfig data-layout
        contract: with exactly two placeholders, first=features, second=labels
        unless explicitly named.

        ``fit`` brings nothing to the host that nobody asked for. The
        trained values and the updater state stay on the device, as the
        variables' ``.value`` and ``_updater_state``, and the next ``fit``,
        ``output`` or ``calculate_gradients`` takes them from there; a host
        copy is made when a reader asks (``save``, ``np.asarray(v.value)``,
        ``SDVariable.arr().to_numpy()``). The epoch losses of the returned
        ``History`` are device scalars until they are read, so ``fit``
        returns once the last step is dispatched, not once it has run:
        reading a loss or a value is what waits for the device.
        """
        from ..data.dataset import DataSet
        from .history import History

        if self._training_config is None:
            raise ValueError("call set_training_config first")
        loss_name = self._training_config.loss_name or self._require_loss()

        phs = self.placeholders()
        dict_batches = isinstance(data, dict) or (
            isinstance(data, list) and data and isinstance(data[0], dict))
        if feature_placeholder is None and label_placeholder is None:
            if dict_batches:
                pass  # batches carry their own {placeholder: array} binding
            elif len(phs) == 2:
                feature_placeholder, label_placeholder = phs[0], phs[1]
            elif len(phs) == 1:
                feature_placeholder = phs[0]
            else:
                raise ValueError("ambiguous placeholders; name them explicitly "
                                 "or feed dict batches {placeholder: array}")
        elif feature_placeholder is None:
            remaining = [p for p in phs if p != label_placeholder]
            if len(remaining) != 1:
                raise ValueError("ambiguous feature placeholder; name it explicitly")
            feature_placeholder = remaining[0]
        # an explicitly passed binding is never overridden; a missing label
        # placeholder stays None (unsupervised losses)

        self._fit_calls += 1
        with OpProfiler.get().time_section("fit/enter",
                                           call=self._fit_calls):
            # host -> device only for what is not there yet (first call
            # after a build, an import, a load or a caller's assignment)
            params = self._params()
            if self._updater_state is None:
                self._updater_state = self._training_config.updater.init(
                    params)
            state = self._updater_state
            step = self._train_step_fn(loss_name, tuple(phs))
        return self._fit_loop(step, data, batch_size, epochs,
                              feature_placeholder, label_placeholder,
                              params, state, History(), listeners or [])

    def _hold(self, params, state) -> None:
        """Make ``params``/``state`` the model's own. Reference assignment
        only: nothing is copied and nothing is brought to the host."""
        for n, v in params.items():
            self._vars[n].value = v
        self._updater_state = state

    def _bound_batches(self, data, batch_size, feature_placeholder,
                       label_placeholder):
        """Each batch as the step's ``{placeholder: device array}``."""
        for ds in _iter_batches(data, batch_size):
            if isinstance(ds, dict):
                # multi-input binding (e.g. imported BERT: ids/types/mask
                # + labels): batches are {placeholder_name: array}
                ph = {k: jnp.asarray(v.value if isinstance(v, NDArray) else v)
                      for k, v in ds.items()}
            else:
                ph = {feature_placeholder: jnp.asarray(ds.features.value)}
                if label_placeholder is not None and ds.labels is not None:
                    ph[label_placeholder] = jnp.asarray(ds.labels.value)
            yield ph

    def _fit_loop(self, step, data, batch_size, epochs, feature_placeholder,
                  label_placeholder, params, state, history, listeners):
        # the sections carry the names ComputationGraph.fit's do
        # (data/pipeline.run_epochs), so one trace reader serves both
        prof = OpProfiler.get()
        try:
            for epoch in range(epochs):
                loss_sum, n_batches = None, 0
                for ph in timed_iter(self._bound_batches(
                        data, batch_size, feature_placeholder,
                        label_placeholder), step=self._iteration):
                    key = get_random().next_key()
                    with prof.time_section("pipeline/dispatch",
                                           step=self._iteration):
                        params, state, loss = step(
                            params, state, ph, key,
                            jnp.asarray(self._iteration))
                    self._iteration += 1
                    # device scalar all the way down: listeners receive it
                    # un-synced and decide when to read (the multilayer/
                    # ui.stats contract), and fit itself reads none: the
                    # epoch's mean is a running on-device sum (O(1) memory,
                    # no variadic stack) that History turns into a float
                    # when somebody asks. The reference's TrainingSession
                    # floats per step — that cost is invisible over JNI but
                    # a readback stalls dispatch here.
                    loss_sum = loss if loss_sum is None else loss_sum + loss
                    n_batches += 1
                    if listeners:
                        # a listener may checkpoint THIS model mid-fit (e.g.
                        # CheckpointListener): expose the live post-step
                        # buffers. The returned arrays are fresh (the donated
                        # ones were the inputs), so a save here serializes
                        # valid, current state.
                        self._hold(params, state)
                    for lst in listeners:
                        lst.iteration_done(self, self._iteration, loss)
                self._epoch += 1
                if loss_sum is None:
                    raise ValueError(
                        "training data yielded no batches this epoch "
                        "(exhausted iterator or empty dataset)")
                with prof.time_section("fit/epoch_end", epoch=epoch):
                    history.add_epoch(self._epoch, loss_sum / n_batches)
                    for lst in listeners:
                        if hasattr(lst, "epoch_done"):
                            lst.epoch_done(self, self._epoch)
        except BaseException:
            # The step donates its params/state inputs, so what the model
            # held at entry is deleted after the first step and there is no
            # host copy to go back to. What is live is the newest output of
            # the loop: a failure between steps (a listener, the data, a
            # Ctrl-C) or before a launch (a batch of the wrong shape) leaves
            # it intact, and the model goes on from the last finished step,
            # usable for a recovery save. A step that failed AFTER consuming
            # its inputs (OOM at run time, NaN panic) returned nothing:
            # those values exist nowhere any more, the variables stay
            # deleted (reading them raises) until the caller assigns or
            # loads new ones, and the momenta restart on the next fit.
            def _dead(a):
                return isinstance(a, jax.Array) and a.is_deleted()

            self._hold(
                {n: v for n, v in params.items() if not _dead(v)},
                None if any(_dead(l) for l in jax.tree.leaves(state))
                else state)
            raise
        # the trained values become the graph's (stateful shell), where
        # they are: on the device
        with prof.time_section("fit/exit", call=self._fit_calls):
            self._hold(params, state)
        return history

    # --- serialization ---------------------------------------------------
    def save(self, path: str, save_updater: bool = False,
             save_updater_state: bool = False) -> None:
        """Zip container: graph.json + vars.npz (+ updater.npz).

        The reference serializes FlatBuffers (FlatGraph) readable by its C++
        executor; the schema is not reproducible here (SURVEY.md §0), so the
        container is a versioned zip with the same content inventory:
        variables, op graph, training config, optional updater state.

        ``save_updater`` is the listener-SPI spelling (matches
        MultiLayerNetwork/ComputationGraph.save, used by CheckpointListener);
        ``save_updater_state`` is the original SameDiff spelling — either works.
        """
        arrays: Dict[str, np.ndarray] = {}
        graph = self._graph_dict(arrays, "")
        graph.update({
            "loss_var": self._loss_var,
            "iteration": self._iteration,
            "epoch": self._epoch,
            "training_config": self._training_config.to_json() if self._training_config else None,
        })
        with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as zf:
            zf.writestr("graph.json", json.dumps(graph))
            buf = io.BytesIO()
            np.savez(buf, **arrays)
            zf.writestr("vars.npz", buf.getvalue())
            if (save_updater or save_updater_state) and self._updater_state is not None:
                from ..util.model_serializer import _savez_leaves
                zf.writestr("updater.npz", _savez_leaves(self._updater_state))

    def _graph_dict(self, arrays: Dict[str, np.ndarray],
                    prefix: str) -> Dict[str, Any]:
        """JSON-able graph structure; arrays collected into ``arrays`` under
        ``prefix`` (nested control-flow subgraphs recurse with a deeper
        prefix so one flat npz holds every scope's tensors)."""
        for n, v in self._vars.items():
            if v.value is not None:
                arrays[prefix + n] = v.value
        nodes = []
        for n in self._nodes:
            d = {"id": n.id, "op": n.op_name, "inputs": n.inputs,
                 "kwargs": _jsonify(n.kwargs), "outputs": n.outputs,
                 "n_outputs": n.n_outputs,
                 "arg_spec": [[k, _jsonify({"v": v})["v"]] for k, v in n.arg_spec]
                 if n.arg_spec is not None else None}
            if n.subgraphs is not None:
                d["control"] = {
                    "max_iters": n.max_iters,
                    "sub_inputs": n.sub_inputs,
                    "sub_outputs": n.sub_outputs,
                    "branches": {
                        tag: sub._graph_dict(arrays,
                                             f"{prefix}n{n.id}.{tag}/")
                        for tag, sub in n.subgraphs.items()},
                }
            nodes.append(d)
        return {
            "format_version": _FORMAT_VERSION,
            "variables": [
                {"name": v.name, "type": v.vtype, "shape": v.shape,
                 "dtype": v.dtype, "producer": v.producer, "out_index": v.out_index}
                for v in self._vars.values()
            ],
            "nodes": nodes,
        }

    @staticmethod
    def _from_graph_dict(graph: Dict[str, Any], arrays,
                         prefix: str) -> "SameDiff":
        sd = SameDiff()
        for v in graph["variables"]:
            pname = prefix + v["name"]
            sd._vars[v["name"]] = _Var(
                v["name"], v["type"],
                tuple(v["shape"]) if v["shape"] else None, v["dtype"],
                arrays[pname] if pname in arrays else None,
                v["producer"], v["out_index"])
        for n in graph["nodes"]:
            spec = n.get("arg_spec")
            ctl = n.get("control")
            # JSON turns kwarg tuples into lists; ops normalize internally.
            needs_rng = False
            if not n["op"].startswith("__"):
                # recompute exactly as _add_op does — the flag is derived
                # state, so serializing it would just invite skew
                desc = get_op(n["op"])
                needs_rng = desc.family == "random" or n["op"] in (
                    "dropout", "alpha_dropout", "gaussian_dropout",
                    "gaussian_noise")
            node = _Node(
                n["id"], n["op"], n["inputs"], n["kwargs"],
                n["outputs"], n["n_outputs"], needs_rng=needs_rng,
                arg_spec=[(k, tuple(v) if isinstance(v, list) and k == "s" else v)
                          for k, v in spec] if spec is not None else None)
            if ctl is not None:
                node.max_iters = ctl.get("max_iters")
                node.sub_inputs = ctl["sub_inputs"]
                node.sub_outputs = ctl["sub_outputs"]
                node.subgraphs = {
                    tag: SameDiff._from_graph_dict(
                        sub, arrays, f"{prefix}n{n['id']}.{tag}/")
                    for tag, sub in ctl["branches"].items()}
            sd._nodes.append(node)
        return sd

    @staticmethod
    def load(path: str) -> "SameDiff":
        with zipfile.ZipFile(path) as zf:
            graph = json.loads(zf.read("graph.json"))
            arrays = np.load(io.BytesIO(zf.read("vars.npz")))
            if graph["format_version"] > _FORMAT_VERSION:
                raise ValueError("file written by a newer format version")
            sd = SameDiff._from_graph_dict(graph, arrays, "")
            sd._loss_var = graph.get("loss_var")
            sd._iteration = graph.get("iteration", 0)
            sd._epoch = graph.get("epoch", 0)
            tc = graph.get("training_config")
            if tc:
                sd._training_config = TrainingConfig.from_json(tc)
            if "updater.npz" in zf.namelist() and sd._training_config is not None:
                # rebuild the state treedef from updater.init over the loaded
                # params (the model_serializer._restore pattern — works for any
                # pytree an updater returns, no schema file needed)
                from ..util.model_serializer import _load_into_tree
                template = sd._training_config.updater.init(sd._params())
                sd._updater_state = _load_into_tree(
                    zf.read("updater.npz"), template, "updater state")
        return sd

    # --- structured control flow (documented divergence from TF1 frames) --
    def summary(self) -> str:
        lines = [f"SameDiff: {len(self._vars)} vars, {len(self._nodes)} ops"]
        for v in self._vars.values():
            if v.vtype != VariableType.ARRAY:
                lines.append(f"  {v.vtype:<12} {v.name:<24} {v.shape}")
        for n in self._nodes:
            lines.append(f"  op#{n.id:<4} {n.op_name:<24} {n.inputs} -> {n.outputs}")
        return "\n".join(lines)


@dataclass
class TrainingConfig:
    """Reference org.nd4j.autodiff.samediff.TrainingConfig."""

    updater: GradientUpdater = field(default_factory=Adam)
    l1: float = 0.0
    l2: float = 0.0
    loss_name: Optional[str] = None
    grad_clip_value: Optional[float] = None

    def to_json(self) -> Dict[str, Any]:
        import dataclasses

        cfg = {}
        for k, v in self.updater.__dict__.items():
            if isinstance(v, ISchedule):
                cfg[k] = {"__schedule__": type(v).__name__,
                          "config": dataclasses.asdict(v)}
            elif isinstance(v, (int, float, str, bool)):
                cfg[k] = v
        return {
            "updater": type(self.updater).__name__,
            "updater_config": cfg,
            "l1": self.l1, "l2": self.l2, "loss_name": self.loss_name,
            "grad_clip_value": self.grad_clip_value,
        }

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "TrainingConfig":
        from ..learning import schedules as _sched
        from ..learning.updaters import _BY_NAME

        cfg = {}
        for k, v in d.get("updater_config", {}).items():
            if isinstance(v, dict) and "__schedule__" in v:
                cfg[k] = getattr(_sched, v["__schedule__"])(**v["config"])
            else:
                cfg[k] = v
        upd_cls = _BY_NAME[d["updater"].lower()]
        return TrainingConfig(
            updater=upd_cls(**cfg),
            l1=d.get("l1", 0.0), l2=d.get("l2", 0.0),
            loss_name=d.get("loss_name"),
            grad_clip_value=d.get("grad_clip_value"),
        )


# ops whose multi-output arity the builder must know
_N_OUTPUTS = {
    "moments": 2, "lstm_layer": 2, "gru_layer": 2, "simple_rnn_layer": 2,
    "sru_layer": 2, "lstm_cell": 2, "qr": 2, "svd": 3, "lu": 2,
    "log_matrix_determinant": 2, "self_adjoint_eig": 2, "top_k": 2, "unique": 2,
    "normalize_moments": 2, "sufficient_statistics": 4,
}

# train-only stochastic ops that become identity at inference
_TRAIN_ONLY_IDENTITY = {"dropout", "alpha_dropout", "gaussian_dropout", "gaussian_noise"}


def _op_scope(name: str) -> str:
    """The ``jax.named_scope`` an op runs under: its output variable's
    directory, at most four components deep
    (``bert/encoder/layer_3/attention`` for an imported node
    ``bert/encoder/layer_3/attention/self/MatMul``); a name without a
    directory is its own scope."""
    parts = [p for p in name.split("/") if p]
    return "/".join(parts[:-1][:4] or parts) or "op"


def _lower_control(node: "_Node", env: Dict[str, Any], training: bool, key):
    """Lower a __cond__/__while__ node to lax control flow. Branch bodies
    are nested SameDiff graphs executed via their own _make_fn — the whole
    construct still traces into the ONE enclosing XLA module."""
    from jax import lax

    def branch_fn(tag: str):
        sub = node.subgraphs[tag]
        outs = tuple(node.sub_outputs[tag])
        fn = sub._make_fn(outs, training)
        names = node.sub_inputs[tag]

        def run(args, k):
            # a body's own variables are constants of the enclosing trace
            # (never trained, never donated): not _params(), which keeps
            # what it uploads, and here that would be a tracer
            held = {n: jnp.asarray(v.value) for n, v in sub._vars.items()
                    if v.vtype == VariableType.VARIABLE}
            return fn(held, dict(zip(names, args)), k)

        return run

    if node.op_name == "__cond__":
        pred = env[node.inputs[0]]
        args = tuple(env[n] for n in node.inputs[1:])
        tb, fb = branch_fn("true"), branch_fn("false")
        return lax.cond(jnp.asarray(pred).astype(bool).reshape(()),
                        lambda a: tb(a, key), lambda a: fb(a, key), args)

    # __while__ — the rng key rides the loop carry and splits per iteration
    # so random ops in the body draw FRESH values each step
    cond_run = branch_fn("cond")
    body_run = branch_fn("body")
    args = tuple(env[n] for n in node.inputs)

    def cond_scalar(vs, k):
        return jnp.asarray(cond_run(vs, k)[0]).astype(bool).reshape(())

    # rng scheme (IDENTICAL for both lowerings so bounded and unbounded
    # runs are statistically equivalent): per iteration, the carried key
    # derives DISTINCT cond and body streams, then advances
    def iter_keys(k):
        kc = jax.random.fold_in(k, 1)
        kb = jax.random.fold_in(k, 2)
        return kc, kb, jax.random.fold_in(k, 0)

    if node.max_iters is None:
        # exact while semantics; forward-only (no reverse-mode rule in XLA)
        def wcond(carry):
            vs, k = carry
            kc, _, _ = iter_keys(k)
            return cond_scalar(vs, kc)

        def wbody(carry):
            vs, k = carry
            _, kb, k_next = iter_keys(k)
            return body_run(vs, kb), k_next

        final, _ = lax.while_loop(wcond, wbody, (args, key))
        return final

    # bounded, DIFFERENTIABLE form: fixed-length scan, iterations after the
    # condition first fails hold their values (masked update)
    def scan_step(carry, _):
        vs, k = carry
        kc, kb, k_next = iter_keys(k)
        go = cond_scalar(vs, kc)
        new_vs = body_run(vs, kb)
        held = tuple(jnp.where(go, nv, v) for v, nv in zip(vs, new_vs))
        return (held, k_next), None

    (final, _), _ = lax.scan(scan_step, (args, key), None,
                             length=node.max_iters)
    return final


def _initialize(shape: Tuple[int, ...], init: str, dtype: str) -> np.ndarray:
    rng = get_random()
    init = init.lower()
    if init == "zeros":
        return np.zeros(shape, dtype=dtype)
    if init == "ones":
        return np.ones(shape, dtype=dtype)
    fan_in = shape[0] if shape else 1
    fan_out = shape[-1] if len(shape) > 1 else 1
    if init == "xavier":
        std = float(np.sqrt(2.0 / (fan_in + fan_out)))
        return np.asarray(rng.gaussian(shape, std=std).to_numpy(), dtype=dtype)
    if init in ("relu", "he"):
        std = float(np.sqrt(2.0 / fan_in))
        return np.asarray(rng.gaussian(shape, std=std).to_numpy(), dtype=dtype)
    if init == "normal":
        return np.asarray(rng.gaussian(shape).to_numpy(), dtype=dtype)
    if init == "uniform":
        lim = float(np.sqrt(1.0 / fan_in))
        return np.asarray(rng.uniform(shape, -lim, lim).to_numpy(), dtype=dtype)
    raise ValueError(f"unknown initializer {init!r}")


def _jsonify(kwargs: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for k, v in kwargs.items():
        if isinstance(v, (np.ndarray, jnp.ndarray)):
            out[k] = np.asarray(v).tolist()
        elif isinstance(v, tuple):
            out[k] = list(v)
        else:
            out[k] = v
    return out


def _iter_batches(data, batch_size):
    """Accept DataSetIterator-like, DataSet, or (features, labels) tuple."""
    from ..data.dataset import DataSet

    if isinstance(data, dict):
        yield data  # one multi-input batch: {placeholder_name: array}
        return
    if isinstance(data, list) and data and isinstance(data[0], dict):
        yield from data
        return
    if hasattr(data, "reset") and hasattr(data, "__iter__"):
        data.reset()
        yield from data
        return
    if isinstance(data, DataSet):
        if batch_size is None:
            yield data
        else:
            yield from data.batch_by(batch_size)
        return
    if isinstance(data, tuple) and len(data) == 2:
        ds = DataSet(data[0], data[1])
        yield from _iter_batches(ds, batch_size)
        return
    raise TypeError(f"cannot iterate training data of type {type(data)}")
