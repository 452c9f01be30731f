"""donation-safety: a donated buffer is dead — nothing may read it after
dispatch.

Every engine state program donates its input (`donate_argnums` on
`_megastep`/`_stage`/`_grow`/`_decode`): XLA reuses the buffers in place,
which is the entire reason admission and decode don't copy the KV cache
every step. The contract is invisible at the call site, and breaking it
is a runtime crash ("array has been deleted") that only fires on backends
that actually alias — or worse, a silent read of reused memory. The
engine's own `reset()` docstring documents the failure mode; this rule
makes the contract structural.

Findings (analysis/absint.py supplies the jit-site scan and the
branch-aware statement ordering):

- **read-after-donate**: an argument at a donated position of a known
  donating callable is read later in the same function — on a path that
  executes after the dispatch — without an intervening rebinding.
- **alias-read**: the donated binding was aliased (`snap = state`) before
  the dispatch and the alias is read after it; two live names for one
  donated buffer is the same bug wearing a disguise.
- **loop-no-rebind**: the dispatch sits in a loop and nothing in the loop
  body rebinds the donated name — iteration 2 feeds the program a deleted
  buffer.
- **unbound-attr-donate**: a donated `self.<attr>` whose result does not
  rebind `self.<attr>` in the same statement. The attribute outlives the
  function, so the NEXT entry into any method reads deleted buffers; the
  live engine always writes `self.state = self._stage(self.state,
  ...)` in one statement.

Reads the analysis cannot attribute (dynamic dispatch, cross-function
attribute flows) contribute nothing — the standard unsound-by-design
trade (analysis/project.py docstring).
"""

from __future__ import annotations

import ast
from typing import (
    Callable,
    Dict,
    Iterable,
    List,
    Optional,
    Sequence,
    Set,
    Tuple,
)

from .. import absint
from ..core import Finding, register
from ..project import FunctionInfo, Project, ProjectRule


def _call_key(
    call: ast.Call, fn: FunctionInfo
) -> Optional[Tuple[str, str, str]]:
    """Donor-lookup key for a call expression: ("attr", class, name) for
    `self.name(...)`, ("name", rel, name) for bare `name(...)`."""
    func = call.func
    if (
        isinstance(func, ast.Attribute)
        and isinstance(func.value, ast.Name)
        and func.value.id == "self"
        and fn.class_name is not None
    ):
        return ("attr", fn.class_name, func.attr)
    if isinstance(func, ast.Name):
        return ("name", fn.rel, func.id)
    return None


def _result_targets(call: ast.Call) -> Set[str]:
    """Chains the statement containing `call` assigns the call's result to
    (through subscripts like `self._step(...)[0]` and tuple unpacking)."""
    node: ast.AST = call
    parent = getattr(node, "parent", None)
    while isinstance(parent, (ast.Subscript, ast.Starred)):
        node, parent = parent, getattr(parent, "parent", None)
    if isinstance(parent, ast.Assign):
        return absint.assigned_chains(parent)
    if isinstance(parent, (ast.AugAssign, ast.AnnAssign)):
        return absint.assigned_chains(parent)
    return set()


def _enclosing_loop(
    src_parents: Iterable[ast.AST], fn_node: ast.AST
) -> Optional[ast.AST]:
    for anc in src_parents:
        if anc is fn_node:
            return None
        if isinstance(anc, (ast.For, ast.AsyncFor, ast.While)):
            return anc
    return None


def _within(node: ast.AST, container: ast.AST) -> bool:
    cur: Optional[ast.AST] = node
    while cur is not None:
        if cur is container:
            return True
        cur = getattr(cur, "parent", None)
    return False


@register
class DonationSafetyRule(ProjectRule):
    name = "donation-safety"
    description = (
        "a buffer passed at a donated position of a jitted program is read "
        "(directly, via an alias, or on a later loop iteration) after the "
        "dispatch, or a donated engine attribute is not rebound by its own "
        "statement — donated buffers are deleted/reused by XLA and every "
        "later read is a crash or garbage"
    )

    def __init__(
        self, watch_prefixes: Sequence[str] = (absint.ENGINE_PREFIX,)
    ):
        self.watch_prefixes = tuple(watch_prefixes)

    def check_project(self, project: Project) -> List[Finding]:
        donors: Dict[Tuple[str, str, str], Tuple[int, ...]] = {}
        for site in absint.scan_jit_sites(project, self.watch_prefixes):
            if not site.donate_argnums or not site.attr:
                continue
            if site.is_self_attr:
                donors[("attr", site.owner, site.attr)] = site.donate_argnums
            else:
                donors[("name", site.rel, site.attr)] = site.donate_argnums
        if not donors:
            return []
        findings: List[Finding] = []
        seen: Set[Tuple[str, int, str]] = set()

        def report(fn: FunctionInfo, node: ast.AST, msg: str) -> None:
            key = (fn.rel, getattr(node, "lineno", 0), msg)
            if key not in seen:
                seen.add(key)
                findings.append(Finding(
                    rule=self.name, path=fn.rel,
                    line=getattr(node, "lineno", 0), message=msg,
                ))

        for fn in project.functions_in(self.watch_prefixes):
            self._check_function(fn, donors, report)
        return findings

    # ------------------------------------------------------------------

    def _check_function(
        self,
        fn: FunctionInfo,
        donors: Dict[Tuple[str, str, str], Tuple[int, ...]],
        report: Callable[[FunctionInfo, ast.AST, str], None],
    ) -> None:
        fn_node = fn.node
        calls: List[Tuple[ast.Call, Tuple[int, ...]]] = []
        for node in ast.walk(fn_node):
            if isinstance(node, ast.Call):
                key = _call_key(node, fn)
                if key is not None and key in donors:
                    calls.append((node, donors[key]))
        if not calls:
            return
        # All loads/assignments in the function, with their order chains.
        loads: List[Tuple[str, ast.AST, List]] = []
        assigns: List[Tuple[Set[str], ast.AST, List]] = []
        for node in ast.walk(fn_node):
            if isinstance(node, (ast.Name, ast.Attribute)) and isinstance(
                getattr(node, "ctx", None), ast.Load
            ):
                chain = absint.chain_str(node)
                if chain is not None:
                    loads.append(
                        (chain, node, absint.stmt_chain(node, fn_node))
                    )
            chains = absint.assigned_chains(node)
            if chains:
                assigns.append(
                    (chains, node, absint.stmt_chain(node, fn_node))
                )

        for call, positions in calls:
            call_chain = absint.stmt_chain(call, fn_node)
            rebinds = _result_targets(call)
            for pos in positions:
                if pos >= len(call.args):
                    continue
                arg = call.args[pos]
                donated = absint.chain_str(arg)
                if donated is None:
                    continue
                self._check_one_donation(
                    fn, fn_node, call, call_chain, rebinds, donated,
                    loads, assigns, report,
                )

    def _check_one_donation(
        self,
        fn: FunctionInfo,
        fn_node: ast.AST,
        call: ast.Call,
        call_chain: List[Tuple[int, str, int]],
        rebinds: Set[str],
        donated: str,
        loads: List[Tuple[str, ast.AST, List[Tuple[int, str, int]]]],
        assigns: List[Tuple[Set[str], ast.AST, List[Tuple[int, str, int]]]],
        report: Callable[[FunctionInfo, ast.AST, str], None],
    ) -> None:
        rebound_here = donated in rebinds

        # unbound-attr-donate: self.<attr> escapes the function scope.
        if donated.startswith("self.") and not rebound_here:
            report(fn, call, (
                f"donated attribute `{donated}` is not rebound by this "
                "statement — the attribute outlives the call and the next "
                "dispatch reads deleted buffers; write "
                f"`{donated} = <program>(...)` in one statement (see "
                "PagedEngine.reset's failure note)"
            ))
            return

        # loop-no-rebind: iteration 2 re-reads the donated name.
        loop = _enclosing_loop(
            fn.src.parents(call) if hasattr(fn, "src") else [], fn_node
        )
        if loop is not None and not rebound_here:
            rebound_in_loop = any(
                donated in chains and _within(node, loop)
                for chains, node, _ in assigns
            )
            if not rebound_in_loop:
                report(fn, call, (
                    f"`{donated}` is donated inside a loop and never "
                    "rebound in the loop body — the next iteration "
                    "dispatches a deleted buffer"
                ))
                return

        # read-after-donate (+ alias-read): any Load of the donated chain
        # (or an alias of it) ordered after the call, with no rebinding
        # ordered between. When the dispatch statement itself rebinds the
        # donated name, later reads of THAT name see the program's result
        # (fine) — but a pre-existing alias still points at the donated
        # buffer, so aliases stay checked.
        aliases = {donated}
        for chains, node, chain in assigns:
            if isinstance(node, ast.Assign) and absint.chain_str(
                node.value
            ) == donated:
                before = absint.execution_order(chain, call_chain)
                if before:
                    aliases.update(chains)
        if rebound_here:
            aliases.discard(donated)
            if not aliases:
                return
        for name, node, chain in loads:
            hit = any(
                name == a or name.startswith(a + ".") for a in aliases
            )
            if not hit or _within(node, call):
                continue
            after = absint.execution_order(call_chain, chain)
            if not after:
                continue
            killed = False
            for chains, anode, achain in assigns:
                if not any(
                    a in chains for a in aliases
                    if name == a or name.startswith(a + ".")
                ):
                    continue
                if absint.execution_order(call_chain, achain) and (
                    absint.execution_order(achain, chain) is not False
                ):
                    killed = True
                    break
            if killed:
                continue
            direct = name == donated or name.startswith(donated + ".")
            which = "" if direct else f" (alias of `{donated}`)"
            report(fn, node, (
                f"`{name}`{which} is read after being donated to a jitted "
                f"program at line {call.lineno} — the buffer is deleted or "
                "reused by then; read results from the program's RETURN "
                "value, or drop the donation"
            ))
