"""tracer-hygiene: no Python control flow on traced values in jitted code.

Inside a `jax.jit`-traced function, a Python `if`/`while`/`assert`/`bool()`
on a device value either raises a ConcretizationTypeError at trace time
(best case) or — when the value is concrete during tracing, e.g. a shape
probe that later becomes a tracer — silently bakes one branch into the
compiled program and re-traces per value (the recompile-per-request family
again, one level down from the PartitionSpec spelling bug).

Detection is module-local and deliberately conservative (near-zero false
positives beats exhaustive):

- jit roots: functions passed to `jax.jit` / `jit` / `shard_map` / `pmap`
  in this module (unwrapping `partial(...)`), plus functions nested inside
  a jit root (scan/fori bodies);
- traced locals: names assigned from `jnp.*` / `jax.lax.*` / `jax.nn.*` /
  `jax.random.*` calls, or from expressions over already-traced names —
  a simple transitive closure. Function parameters and attribute reads
  are NOT assumed traced (config/static attributes dominate there).
- flagged: `if` / `while` / ternary / `assert` tests that reference a
  traced local or contain a device-namespace call directly, and
  `bool(...)` over either.

Also flags the unhashable-static-arg footgun: a call to a jitted function
whose `static_argnums` position receives a list/dict/set literal — that is
a guaranteed `TypeError: unhashable type` at the first dispatch.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from ..core import Finding, Rule, Source, register

_JIT_WRAPPERS = {"jit", "shard_map", "pmap"}
_DEVICE_BASES = {"jnp", "lax"}
_JAX_SUBMODULES = {"lax", "nn", "random", "numpy"}


def _callee_name(call: ast.Call) -> str:
    func = call.func
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


def _unwrap_partial(expr: ast.expr) -> Optional[str]:
    """The function NAME inside `f`, `partial(f, ...)`,
    `functools.partial(f, ...)` or `named_partial(f, ...)`."""
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Call) and _callee_name(expr) in (
            "partial", "named_partial"):
        if expr.args and isinstance(expr.args[0], ast.Name):
            return expr.args[0].id
    return None


def _is_device_call(node: ast.expr) -> bool:
    """jnp.xxx(...) / lax.xxx(...) / jax.lax.xxx / jax.nn.xxx /
    jax.random.xxx call."""
    if not isinstance(node, ast.Call):
        return False
    func = node.func
    if not isinstance(func, ast.Attribute):
        return False
    base = func.value
    if isinstance(base, ast.Name) and base.id in _DEVICE_BASES:
        return True
    if (
        isinstance(base, ast.Attribute)
        and isinstance(base.value, ast.Name)
        and base.value.id == "jax"
        and base.attr in _JAX_SUBMODULES
    ):
        return True
    return False


def _jit_static_info(
    tree: ast.Module,
) -> Tuple[Set[str], Dict[str, Tuple[int, ...]]]:
    """(jit-root function names, {jitted-binding-name: static_argnums})."""
    roots: Set[str] = set()
    statics: Dict[str, Tuple[int, ...]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if _callee_name(node) not in _JIT_WRAPPERS:
            continue
        if node.args:
            target = _unwrap_partial(node.args[0])
            if target is not None:
                roots.add(target)
        nums: Tuple[int, ...] = ()
        for kw in node.keywords:
            if kw.arg == "static_argnums":
                if isinstance(kw.value, (ast.Tuple, ast.List)):
                    nums = tuple(
                        e.value for e in kw.value.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, int)
                    )
                elif isinstance(kw.value, ast.Constant) and isinstance(
                    kw.value.value, int
                ):
                    nums = (kw.value.value,)
        if nums:
            parent = getattr(node, "parent", None)
            if isinstance(parent, ast.Assign):
                for t in parent.targets:
                    if isinstance(t, ast.Name):
                        statics[t.id] = nums
                    elif (
                        isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"
                    ):
                        statics[f"self.{t.attr}"] = nums
    return roots, statics


def _traced_locals(fn: ast.AST) -> Set[str]:
    """Transitive closure of locals assigned from device-namespace calls."""
    traced: Set[str] = set()
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            if _expr_traced(node.value, traced):
                for t in node.targets:
                    for name in _target_names(t):
                        if name not in traced:
                            traced.add(name)
                            changed = True
    return traced


def _target_names(t: ast.expr) -> List[str]:
    if isinstance(t, ast.Name):
        return [t.id]
    if isinstance(t, (ast.Tuple, ast.List)):
        out: List[str] = []
        for e in t.elts:
            out.extend(_target_names(e))
        return out
    return []


def _is_identity_test(expr: ast.expr) -> bool:
    """`x is None` / `x is not None`: identity never reads a tracer's
    value, so these are static under trace even on traced names."""
    return isinstance(expr, ast.Compare) and all(
        isinstance(op, (ast.Is, ast.IsNot)) for op in expr.ops
    )


def _expr_traced(expr: ast.expr, traced: Set[str]) -> bool:
    if _is_identity_test(expr):
        return False
    for node in ast.walk(expr):
        if _is_device_call(node):
            return True
        if isinstance(node, ast.Name) and node.id in traced:
            return True
    return False


@register
class TracerHygieneRule(Rule):
    name = "tracer-hygiene"
    description = (
        "Python control flow (if/while/assert/bool) over a traced value "
        "inside jit-reachable code, or a list/dict/set literal passed in a "
        "static_argnums position — trace-time errors and silent "
        "per-value recompiles"
    )

    def check(self, src: Source) -> List[Finding]:
        findings: List[Finding] = []
        roots, statics = _jit_static_info(src.tree)
        for node in ast.walk(src.tree):
            if (
                isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name in roots
            ):
                findings.extend(self._check_traced_fn(src, node))
        findings.extend(self._check_static_args(src, statics))
        return findings

    def _check_traced_fn(self, src: Source, fn: ast.AST) -> List[Finding]:
        findings: List[Finding] = []
        traced = _traced_locals(fn)
        for node in ast.walk(fn):
            test: Optional[ast.expr] = None
            what = ""
            if isinstance(node, (ast.If, ast.While)):
                test, what = node.test, type(node).__name__.lower()
            elif isinstance(node, ast.IfExp):
                test, what = node.test, "conditional expression"
            elif isinstance(node, ast.Assert):
                test, what = node.test, "assert"
            elif isinstance(node, ast.Call) and _callee_name(node) == "bool":
                if node.args and _expr_traced(node.args[0], traced):
                    findings.append(self.finding(
                        src, node,
                        "bool() over a traced value in jit-reachable code "
                        "— concretizes the tracer (trace error or silent "
                        "per-value recompile); use jnp.where / lax.cond",
                    ))
                continue
            if test is not None and _expr_traced(test, traced):
                findings.append(self.finding(
                    src, node,
                    f"Python {what} on a traced value in jit-reachable "
                    "code — the branch is baked in at trace time; use "
                    "jnp.where / lax.cond / lax.while_loop",
                ))
        return findings

    def _check_static_args(
        self, src: Source, statics: Dict[str, Tuple[int, ...]]
    ) -> List[Finding]:
        findings: List[Finding] = []
        if not statics:
            return findings
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            key = None
            if isinstance(func, ast.Name):
                key = func.id
            elif (
                isinstance(func, ast.Attribute)
                and isinstance(func.value, ast.Name)
                and func.value.id == "self"
            ):
                key = f"self.{func.attr}"
            nums = statics.get(key or "")
            if not nums:
                continue
            for i in nums:
                if i < len(node.args) and isinstance(
                    node.args[i], (ast.List, ast.Dict, ast.Set)
                ):
                    findings.append(self.finding(
                        src, node,
                        f"static_argnums position {i} of {key} receives an "
                        "unhashable literal (list/dict/set) — guaranteed "
                        "TypeError at dispatch; pass a tuple or hashable "
                        "config object",
                    ))
        return findings
