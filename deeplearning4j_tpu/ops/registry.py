"""Op registry + coverage ledger.

TPU-native analog of libnd4j's ``OpRegistrator`` (reference:
libnd4j/include/ops/declarable/OpRegistrator.h) fused with the op-validation
coverage ledger from ``org.nd4j.autodiff.opvalidation.OpValidation`` (SURVEY.md
§4.2): every op is registered by name; the test harness marks ops validated as
they are exercised, and a ledger test fails when a registered op was never
validated and isn't on the explicit skip list.

Ops are pure functions over raw jax arrays (+ static kwargs) so they are
jit-traceable; they never see the NDArray shell. The registry's name→fn table
is also the serialization contract — the SameDiff-analog graph stores op names
and rebuilds callables from here on load (the role the reference's
FlatBuffers op-num mapping plays in ``FlatBuffersMapper``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Set


@dataclass
class OpDescriptor:
    name: str
    fn: Callable
    family: str
    # Differentiable through jax autodiff (False for int/bool/shape-query ops).
    differentiable: bool = True
    doc: str = ""


_REGISTRY: Dict[str, OpDescriptor] = {}
_VALIDATED: Set[str] = set()


def op(name: str, family: str = "misc", differentiable: bool = True):
    """Decorator: register a pure-jax op under `name`."""

    def wrap(fn: Callable) -> Callable:
        if name in _REGISTRY:
            raise ValueError(f"duplicate op registration: {name}")
        _REGISTRY[name] = OpDescriptor(
            name=name, fn=fn, family=family, differentiable=differentiable,
            doc=next(iter((fn.__doc__ or "").strip().splitlines()), ""),
        )
        return fn

    return wrap


def get_op(name: str) -> OpDescriptor:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown op: {name!r} (registered: {len(_REGISTRY)})")
    return _REGISTRY[name]


def has_op(name: str) -> bool:
    _ensure_loaded()
    return name in _REGISTRY


def all_ops() -> Dict[str, OpDescriptor]:
    _ensure_loaded()
    return dict(_REGISTRY)


def exec_op(name: str, *args, **kwargs):
    """Execute a registered op by name, recording it as validated when called
    from the test harness (Nd4j.exec analog for raw arrays). Numpy args are
    promoted to jax arrays so ops can index them with tracers."""
    import numpy as _np
    import jax.numpy as _jnp

    desc = get_op(name)
    _VALIDATED.add(name)
    args = tuple(_jnp.asarray(a) if isinstance(a, _np.ndarray) else a for a in args)
    return desc.fn(*args, **kwargs)


def mark_validated(name: str) -> None:
    _VALIDATED.add(name)


def validated_ops() -> Set[str]:
    return set(_VALIDATED)


def coverage_report() -> Dict[str, Any]:
    _ensure_loaded()
    missing = sorted(set(_REGISTRY) - _VALIDATED)
    return {
        "registered": len(_REGISTRY),
        "validated": len(_VALIDATED & set(_REGISTRY)),
        "missing": missing,
    }


_loaded = False


def _ensure_loaded() -> None:
    """Import all op-family modules exactly once (registration side effects)."""
    global _loaded
    if _loaded:
        return
    _loaded = True
    from . import (  # noqa: F401
        broadcastable,
        transforms,
        reduce,
        shape,
        nn,
        recurrent,
        linalg,
        random,
        loss,
        image,
        pallas_attention,
        ssm,
        moe,
        bitwise,
        embeddings,
    )
