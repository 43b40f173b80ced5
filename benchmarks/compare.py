"""The comparison that decides ``correct`` for a training cell.

The program's first steps (driven through its own ``fit`` in set-up) against
the plain reference's, by the numbers the benchmark's contract names: each
step's loss, the norm of the first gradient as the optimizer got it, and the
norm of the parameters' change (and of the running statistics' change, where
the model has any), the last three by the worst leaf. A gap is the distance
between the program's norm and the reference's — not the norm of their
difference — over the reference's norm of that leaf or of the median leaf,
whichever is larger, since some gradients are all but zero.
"""

from __future__ import annotations

import functools
import statistics

# leaves whose first gradient in the reference is under this share of the
# median leaf's are nought to rounding (a key's bias under softmax) and move
# under Adam by round-off alone: they are left out of the parameters' change
ZERO_GRADIENT_SHARE = 1e-3


class Exact:
    """How the plain reference computes: float32 throughout, every matrix
    product at ``highest``. A reference computed lower (the control and the
    witnesses of ``tests/precisions.py``; no benchmark run makes one) is a
    subclass that rounds one or more of these."""

    name = "float32"

    def operand(self, a):       # each operand of a matrix product
        return a

    def activation(self, a):    # what a layer hands to the next
        return a

    def state(self, a):         # the optimizer's state between steps
        return a


EXACT = Exact()


def _paths(tree) -> list:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


@functools.lru_cache(maxsize=None)
def _programs() -> tuple:
    """(leaf norms, tree difference), jitted once a process."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
         for a in jax.tree.leaves(t)]))
    diff = jax.jit(lambda x, y: jax.tree.map(
        lambda p, q: p.astype(jnp.float32) - q.astype(jnp.float32), x, y))
    return norms, diff


def leaf_norms(tree) -> dict:
    """{leaf path: l2 norm}, computed on the device in one program and
    fetched in one transfer."""
    import jax

    norms = jax.device_get(_programs()[0](tree))
    return dict(zip(_paths(tree), (float(v) for v in norms)))


def tree_diff(a, b):
    return _programs()[1](a, b)


def host_norms(tree) -> dict:
    """{leaf path: l2 norm} of a tree of host arrays."""
    import jax
    import numpy as np

    return dict(zip(_paths(tree), (
        float(np.sqrt(np.sum(np.square(np.asarray(a, np.float32)),
                             dtype=np.float64)))
        for a in jax.tree.leaves(tree))))


def host_diff(a, b):
    import jax
    import numpy as np

    return jax.tree.map(lambda p, q: np.asarray(p, np.float32)
                        - np.asarray(q, np.float32), a, b)


def drive_first_steps(job, batches: list, w0_host) -> dict:
    """Drive the job's own ``fit`` through its first steps, one batch a call,
    and read what the comparison needs as norms. Nothing of the check stays
    on the device while the program runs, so that the process's memory peak
    is the program's own: ``w0_host`` is a host copy of the weights the job
    started from, the parameters and running statistics are fetched and
    their changes taken on the host, and the first gradient's norms are
    reduced from the optimizer's state where it lies."""
    import jax

    b0 = jax.device_get(job.buffers())
    out = {"loss": []}
    for i, b in enumerate(batches):
        job.fit(job.feed([b]), epochs=1)
        out["loss"].append(job.loss())
        if i == 0:
            state, scale = job.first_gradient_state()
            out["first_gradient"] = {
                leaf: abs(scale) * n for leaf, n in leaf_norms(state).items()}
    out["param_change"] = host_norms(
        host_diff(jax.device_get(job.params()), w0_host))
    if jax.tree.leaves(b0):
        out["buffer_change"] = host_norms(
            host_diff(jax.device_get(job.buffers()), b0))
    return out


def reference_norms(ref: dict) -> dict:
    """The reference's readings with its trees reduced to leaf norms."""
    import jax

    out = {"loss": list(ref["loss"])}
    for k in ("first_gradient", "param_change", "buffer_change"):
        if k in ref and jax.tree.leaves(ref[k]):
            out[k] = leaf_norms(ref[k])
    return out


def _leaf_gaps(prog: dict, ref: dict, leave_out=()) -> dict:
    """{"worst": (gap, leaf), "median": (gap, "")} over the leaves."""
    if set(prog) != set(ref):
        raise RuntimeError(f"leaves differ: {sorted(set(prog) ^ set(ref))[:6]}")
    floor = statistics.median(ref.values())
    worst, where, every = 0.0, "", []
    for leaf, r in ref.items():
        if leaf in leave_out:
            continue
        gap = abs(prog[leaf] - r) / max(r, floor, 1e-30)
        every.append(gap)
        if not gap <= worst:        # a NaN gap is the worst there is
            worst, where = gap, leaf
    return {"worst": (worst, where), "median": (statistics.median(every), "")}


def gaps(prog: dict, ref: dict) -> dict:
    """{number: (gap, where)} of one side's norms against the reference's."""
    out = {}
    for i, (p, r) in enumerate(zip(prog["loss"], ref["loss"]), 1):
        out[f"loss_step{i}"] = (abs(p - r) / abs(r), "")
    g = ref["first_gradient"]
    floor = ZERO_GRADIENT_SHARE * statistics.median(g.values())
    still = {leaf for leaf, n in g.items() if n < floor}
    trees = [("first_gradient", ()), ("param_change", still)]
    if "buffer_change" in ref:
        trees.append(("buffer_change", ()))
    for name, leave_out in trees:
        found = _leaf_gaps(prog[name], ref[name], leave_out)
        out[name] = found["worst"]
        out[name + "_median_leaf"] = found["median"]
    return out


def judge(found: dict, limits: dict) -> tuple:
    """(correct, {number: {"value", "limit", "where"}}) — every number that
    has a limit is held to it; one without a limit is shown and not held."""
    rows, ok = {}, True
    for name, (gap, where) in found.items():
        limit = limits.get(name)
        rows[name] = {"value": gap, "limit": limit, "where": where}
        if limit is not None and not gap <= limit:
            ok = False
    missing = [n for n in limits if n not in found]
    if missing:
        raise RuntimeError(f"limits name numbers that were not compared: {missing}")
    return ok, rows
