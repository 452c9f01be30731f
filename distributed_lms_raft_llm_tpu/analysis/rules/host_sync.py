"""no-host-sync-in-dispatch: device readbacks in engine hot paths must be
marked as intended.

The paged engine's throughput history is a history of accidental host
syncs: a reap-time `device_get` serialized the loop until the copies were
started asynchronously (engine/paged.step), and chunk=1 dispatch paid a
host round trip per token. A `.item()`, `float()`,
`np.asarray(...)` or `jax.device_get(...)` dropped into the dispatch path
is invisible in review and costs a full device round trip per call.

This rule flags host-sync constructs in the engine dispatch modules
(`engine/paged.py`, `engine/engine.py`, `engine/draft.py`) unless they sit
inside a `with guards.intended_transfer():` block — the SAME marker the
runtime transfer guard uses (utils/guards.py), so the static rule and the
TPU-side `jax.transfer_guard` assertion enforce one shared set of
sanctioned sync points.
"""

from __future__ import annotations

import ast
from typing import List

from ..core import Finding, Rule, Source, register

# Modules whose bodies ARE the dispatch hot path.
DISPATCH_MODULES = (
    "engine/paged.py",
    "engine/engine.py",
    "engine/draft.py",
    # The scoring tenant's quantum loop shares the serving chip: a bare
    # .item()/np.asarray there stalls interactive dispatch exactly like
    # a decode-path sync would.
    "engine/scoring.py",
)

_SYNC_ATTR_CALLS = {"item", "tolist", "block_until_ready"}
_NP_SYNC_FUNCS = {"asarray", "array", "copy"}
_NP_MODULE_NAMES = {"np", "numpy"}
_JAX_SYNC_FUNCS = {"device_get"}
_CAST_FUNCS = {"float", "int", "bool"}
_DEVICE_NAMESPACES = {"jnp", "jax", "lax"}


def _inside_intended_transfer(src: Source, node: ast.AST) -> bool:
    for anc in src.parents(node):
        if isinstance(anc, (ast.With, ast.AsyncWith)):
            for item in anc.items:
                expr = item.context_expr
                if isinstance(expr, ast.Call):
                    expr = expr.func
                name = (
                    expr.attr if isinstance(expr, ast.Attribute)
                    else expr.id if isinstance(expr, ast.Name) else ""
                )
                if name == "intended_transfer":
                    return True
    return False


def _is_device_ns_call(node: ast.expr) -> bool:
    """True for jnp.xxx(...) / jax.yyy.xxx(...) call results."""
    if not isinstance(node, ast.Call):
        return False
    func = node.func
    while isinstance(func, ast.Attribute):
        if isinstance(func.value, ast.Name) and func.value.id in _DEVICE_NAMESPACES:
            return True
        func = func.value  # type: ignore[assignment]
    return False


@register
class HostSyncInDispatchRule(Rule):
    name = "no-host-sync-in-dispatch"
    description = (
        "host<->device sync (.item/.tolist/np.asarray/jax.device_get/"
        "float-of-jnp) in an engine dispatch module outside a "
        "`with intended_transfer():` block — every unmarked sync is a "
        "hidden per-step device round trip"
    )

    def applies_to(self, rel: str) -> bool:
        return any(rel.endswith(m) for m in DISPATCH_MODULES)

    def check(self, src: Source) -> List[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            label = self._sync_label(node)
            if label is None:
                continue
            if _inside_intended_transfer(src, node):
                continue
            findings.append(
                self.finding(
                    src,
                    node,
                    f"{label} is a host sync in a dispatch module; wrap the "
                    "intended sync point in `with intended_transfer():` "
                    "(utils/guards.py) or move it off the hot path",
                )
            )
        return findings

    @staticmethod
    def _sync_label(node: ast.Call) -> str | None:
        func = node.func
        if isinstance(func, ast.Attribute):
            # x.item() / x.tolist() / x.block_until_ready()
            if func.attr in _SYNC_ATTR_CALLS and not node.args:
                return f".{func.attr}()"
            # np.asarray(...) / numpy.array(...)
            if (
                func.attr in _NP_SYNC_FUNCS
                and isinstance(func.value, ast.Name)
                and func.value.id in _NP_MODULE_NAMES
            ):
                return f"{func.value.id}.{func.attr}(...)"
            # jax.device_get(...)
            if (
                func.attr in _JAX_SYNC_FUNCS
                and isinstance(func.value, ast.Name)
                and func.value.id == "jax"
            ):
                return "jax.device_get(...)"
        elif isinstance(func, ast.Name):
            if func.id in _JAX_SYNC_FUNCS:
                return f"{func.id}(...)"
            # float(jnp.sum(x)) — a cast forcing a device value to host.
            if (
                func.id in _CAST_FUNCS
                and node.args
                and _is_device_ns_call(node.args[0])
            ):
                return f"{func.id}(<device value>)"
        return None
