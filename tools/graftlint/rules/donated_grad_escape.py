"""donated-grad-escape: grads consumed by the fused epilogue stay consumed.

The backward-epilogue fusion (PR-16) hands the flat grad buckets to
``apply_flat_updater`` / ``fused_apply`` INSIDE the jitted step, with
params and updater state donated at the jit boundary. On TPU the fused
kernel is free to update in place — a grad leaf read *after* the
consuming call is a use-after-donate hazard: it
compiles clean on CPU, then reads freed (or already-overwritten) HBM
the first time the real donation kicks in. The shipped near-miss is the
ZeRO-1 telemetry block in parallel/wrapper.py, which reads the reduced
grad shards after the apply — safe there (the read is in-graph, so XLA
keeps the value alive) and carrying the justified suppression this rule
demands for every such read.

Flagged shape (per function scope, statement order):

    new_p, new_s = apply_flat_updater(up, flat_p, flat_g, st, it, key)
    ...
    anything_reading(flat_g)          # <- finding

The grads argument is the third positional (or the ``flat_grads`` /
``grads`` keyword) of the recognized consumers. A consume that is
itself a ``return`` statement cannot leak (nothing executes after it in
that frame) and does not taint. Taint clears when the name is rebound;
a consume inside a branch conservatively taints everything after it —
exactly the hazard once that branch executes.
"""

from __future__ import annotations

import ast
from typing import Dict, List

from ..engine import Finding, ModuleContext, Project, Rule, call_name

# dotted-name tails that consume flat grads inside a step; the value is
# the positional index of the grads argument
_CONSUMERS = {"apply_flat_updater": 2, "fused_apply": 2}
_GRADS_KW = ("flat_grads", "grads")

# statement fields holding nested blocks (walked separately, in source
# order, with the shared taint state)
_BLOCK_FIELDS = ("body", "orelse", "finalbody")


def _consumer(call: ast.Call):
    tail = call_name(call).split(".")[-1]
    return tail if tail in _CONSUMERS else None


def _grads_arg(call: ast.Call, tail: str):
    for kw in call.keywords:
        if kw.arg in _GRADS_KW:
            return kw.value
    pos = _CONSUMERS[tail]
    return call.args[pos] if len(call.args) > pos else None


def _base_name(expr: ast.AST):
    """The identifier a grads argument resolves to: a plain name, or the
    base of a subscript/attribute chain (``g_sh[k]`` reads ``g_sh``)."""
    while isinstance(expr, (ast.Subscript, ast.Attribute, ast.Starred)):
        expr = expr.value
    return expr.id if isinstance(expr, ast.Name) else None


def _header_nodes(stmt: ast.stmt):
    """The statement's own expression nodes — nested statement blocks
    (and nested function/class scopes) excluded; those are visited as
    blocks/scopes of their own."""
    if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                         ast.ClassDef)):
        return
    blocks = []
    for field in _BLOCK_FIELDS:
        blocks.extend(getattr(stmt, field, []) or [])
    for handler in getattr(stmt, "handlers", []) or []:
        blocks.extend(handler.body)
    skip = {id(n) for b in blocks for n in ast.walk(b)}
    for node in ast.walk(stmt):
        if id(node) not in skip:
            yield node


class DonatedGradEscapeRule(Rule):
    name = "donated-grad-escape"
    description = ("a grad pytree/bucket referenced after "
                   "apply_flat_updater consumed it inside a jitted step "
                   "— use-after-donate hazard once the buffers donate")
    hint = ("read everything you need from the grads BEFORE the fused "
            "apply, or keep the read in-graph and suppress with the "
            "reason; after donation the bytes are gone")

    def check(self, mod: ModuleContext, project: Project) -> List[Finding]:
        findings: List[Finding] = []
        scopes = [mod.tree] + [
            n for n in ast.walk(mod.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for scope in scopes:
            self._walk_block(mod, list(getattr(scope, "body", [])), {},
                             findings)
        return findings

    def _walk_block(self, mod: ModuleContext, body: List[ast.stmt],
                    consumed: Dict[str, int],
                    findings: List[Finding]) -> None:
        for stmt in body:
            header = list(_header_nodes(stmt))
            # reads of already-consumed names in this statement
            for node in header:
                if isinstance(node, ast.Name) and \
                        isinstance(node.ctx, ast.Load) and \
                        node.id in consumed:
                    findings.append(self.finding(
                        mod, node,
                        f"grads {node.id!r} read after the fused epilogue "
                        f"consumed it on line {consumed[node.id]}"))
            # rebinding the name clears the taint
            for node in header:
                if isinstance(node, ast.Name) and \
                        isinstance(node.ctx, (ast.Store, ast.Del)):
                    consumed.pop(node.id, None)
            # record new consumes (a return-consume cannot leak: nothing
            # executes after it in this frame)
            if not isinstance(stmt, ast.Return):
                for node in header:
                    if isinstance(node, ast.Call):
                        tail = _consumer(node)
                        if tail is None:
                            continue
                        arg = _grads_arg(node, tail)
                        name = _base_name(arg) if arg is not None else None
                        if name is not None:
                            consumed[name] = node.lineno
            # nested blocks: each branch forks the pre-state (a consume
            # in the if-body must not taint the else-body — only one
            # executes), then the post-states union into the outer taint
            # so code AFTER the statement sees the hazard of every path
            if not isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.ClassDef)):
                branches = [getattr(stmt, f, None) for f in _BLOCK_FIELDS]
                branches += [h.body for h in
                             getattr(stmt, "handlers", []) or []]
                pre = dict(consumed)
                for blk in branches:
                    if not blk:
                        continue
                    state = dict(pre)
                    self._walk_block(mod, blk, state, findings)
                    consumed.update(state)
