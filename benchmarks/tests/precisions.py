"""Ways of computing the plain reference lower than it is stated: the control
of every training cell and the witnesses that ``PERF.md`` reads beside it.
Only the tests and ``readings.py`` use them; a benchmark run computes its
reference as ``compare.EXACT``.

    get("float8_e4m3fn")              the control: the operands of every matrix
                                      product, and the cotangents that come back
                                      to them, rounded to the type by a plain cast
    get("float8_e4m3fn+scaled")       fp8 as fp8 training does it: e4m3 operands,
                                      e5m2 cotangents, each scaled by its own
                                      largest magnitude
    get("bfloat16+activations+state") operands, what every layer hands on and
                                      the optimizer's state in bfloat16
"""

from __future__ import annotations

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import compare  # noqa: E402


def _rounder(fwd: str, bwd: str, scaled: bool):
    """a -> a rounded to ``fwd`` (and back to float32), whose cotangent is
    rounded to ``bwd``."""
    import jax
    import jax.numpy as jnp

    def fake(a, name):
        dt = jnp.dtype(name)
        if not scaled:
            return a.astype(dt).astype(jnp.float32)
        scale = jnp.max(jnp.abs(a)) / float(jnp.finfo(dt).max)
        scale = jnp.where(scale > 0, scale, 1.0)
        return (a / scale).astype(dt).astype(jnp.float32) * scale

    @jax.custom_vjp
    def q(a):
        return fake(a, fwd)

    q.defvjp(lambda a: (fake(a, fwd), None), lambda _, g: (fake(g, bwd),))
    return q


class Lower(compare.Exact):
    def __init__(self, name: str):
        self.name = name
        kind, *more = name.split("+")
        scaled = "scaled" in more
        q = _rounder(kind, "float8_e5m2" if scaled else kind, scaled)
        self.operand = q
        if "activations" in more:
            self.activation = q
        if "state" in more:
            self.state = lambda a: a.astype(kind).astype("float32")


@functools.lru_cache(maxsize=None)
def get(name: str) -> Lower:
    """One object a name, so that the reference's jitted step is built once."""
    return Lower(name)
