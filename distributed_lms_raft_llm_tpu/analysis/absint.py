"""dlrl-absint: abstract interpretation over the engine's jit-reachable code.

PR 4's project model answers *reachability* questions (who can call whom);
the engine bug classes that remain — spelling-consistent but
semantically-divergent shardings, use-after-donate, silent dtype
promotion, warmup that no longer covers the compiled-program set — are
questions about *values*. This module adds the value half: a small
abstract interpreter over the AST that propagates abstract facts
(PartitionSpec meaning, dtype, donation status) through the functions the
jit entry points reach, reusing `analysis/project.py`'s symbol table and
call graph for the interprocedural steps.

Everything here is still pure AST — nothing imports jax or the engine —
so it shares the project model's trade: **missing resolution loses
findings, never invents them.** An expression the evaluator cannot see
through becomes UNKNOWN and contributes nothing; the rules built on top
(pspec-flow, donation-safety, dtype-flow, program-inventory) only report
on facts that were positively derived.

Pieces, each consumed by one or more rules in `analysis/rules/`:

- `scan_jit_sites`: every `jax.jit(...)` call in a module set, with its
  bound attribute (`self._step = jax.jit(...)`), the wrapped program
  function (resolved through `functools.partial`), and literal
  `donate_argnums` / `static_argnums` — the static mirror of the runtime
  program caches that `utils/guards.compile_count_guard` counts.
- `SpecEval` + `collect_plane_puts` + `collect_plane_tables`: evaluates
  PartitionSpec expressions to a canonical *meaning* (trailing Nones
  dropped, helper functions like `paged._plane_spec` resolved through
  their returns, call-site argument binding for nested helpers such as
  `_canon_state.put`, literal plane-name strings flowed into spec-table
  subscripts like `partition.PAGED_PLANE_SPECS[name]`), and collects
  every `jax.device_put` of a named state plane with the spec it lands
  under plus every module-level literal plane->spec table.
- `DtypeWalker`: forward dtype propagation through a function body
  (constructors, `.astype`, project-local calls, arithmetic promotion),
  with hooks that fire on int8->float upcasts and weak-type promotions.
- statement-order utilities (`stmt_chain`, `execution_order`,
  `assigned_chains`, `chain_str`): branch-aware "does this read happen
  after that dispatch" queries for the donation-safety rule.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .project import FunctionInfo, ModuleInfo, Project, _dotted

ENGINE_PREFIX = "distributed_lms_raft_llm_tpu/engine/"
# The bucketed reference generator (`TutoringEngine`): the tests hold the
# served engine's answers equal to it and nothing serves it, so its jit
# sites make no warm-up claim and stay out of the program inventory.
REFERENCE_REL = ENGINE_PREFIX + "engine.py"


class _Unknown:
    """Bottom of every abstract domain: no fact derived, no finding."""

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return "<unknown>"


UNKNOWN = _Unknown()

_MAX_DEPTH = 8  # interprocedural evaluation depth bound (cycles included)


# --------------------------------------------------------------- utilities


def chain_str(node: ast.expr) -> Optional[str]:
    """'self.state.active' for pure Name/Attribute chains, else None."""
    out = _dotted(node)
    return out or None


def enclosing_function(src_parents: Iterable[ast.AST]) -> Optional[ast.AST]:
    for anc in src_parents:
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return anc
    return None


def enclosing_class_name(src_parents: Iterable[ast.AST]) -> Optional[str]:
    for anc in src_parents:
        if isinstance(anc, ast.ClassDef):
            return anc.name
    return None


def function_infos_by_node(project: Project, rel: str) -> Dict[int, FunctionInfo]:
    return {
        id(fn.node): fn
        for fn in project.functions.values()
        if fn.rel == rel
    }


_BLOCK_FIELDS = ("body", "orelse", "finalbody", "handlers")


def stmt_chain(node: ast.AST, stop: ast.AST) -> List[Tuple[int, str, int]]:
    """The enclosing-statement path of `node` up to (not including) `stop`,
    outermost first: [(id(owner), block_field, index), ...]. Two nodes'
    chains decide execution order (see `execution_order`)."""
    chain: List[Tuple[int, str, int]] = []
    cur: Optional[ast.AST] = node
    while cur is not None and cur is not stop:
        par = getattr(cur, "parent", None)
        if par is None:
            break
        for field in _BLOCK_FIELDS:
            seq = getattr(par, field, None)
            if isinstance(seq, list):
                for i, item in enumerate(seq):
                    if item is cur:
                        chain.append((id(par), field, i))
                        break
                else:
                    continue
                break
        cur = par
    chain.reverse()
    return chain


def execution_order(
    a: Sequence[Tuple[int, str, int]], b: Sequence[Tuple[int, str, int]]
) -> Optional[bool]:
    """True when chain `a` executes strictly before chain `b` on every path,
    False when strictly after, None when unordered (sibling branches of one
    `if`/`try`, or the same statement)."""
    for ea, eb in zip(a, b):
        if ea == eb:
            continue
        oa, fa, ia = ea
        ob, fb, ib = eb
        if oa == ob and fa == fb:
            return ia < ib
        # Same owner, different block (if-body vs orelse, try vs handler):
        # the two only run on different paths — unordered.
        return None
    return None  # one contains the other / same statement


def assigned_chains(stmt: ast.AST) -> Set[str]:
    """Dotted chains a statement (re)binds: Assign/AugAssign/AnnAssign
    targets, for-targets, with-as names; tuple targets flattened."""
    targets: List[ast.expr] = []
    if isinstance(stmt, ast.Assign):
        targets.extend(stmt.targets)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        targets.append(stmt.target)
    elif isinstance(stmt, (ast.For, ast.AsyncFor)):
        targets.append(stmt.target)
    elif isinstance(stmt, (ast.With, ast.AsyncWith)):
        targets.extend(
            item.optional_vars for item in stmt.items
            if item.optional_vars is not None
        )
    out: Set[str] = set()
    stack = list(targets)
    while stack:
        t = stack.pop()
        if isinstance(t, (ast.Tuple, ast.List)):
            stack.extend(t.elts)
        elif isinstance(t, ast.Starred):
            stack.append(t.value)
        else:
            chain = chain_str(t)
            if chain:
                out.add(chain)
    return out


# ------------------------------------------------------------ jit entry scan


@dataclasses.dataclass(frozen=True)
class JitSite:
    """One `jax.jit(...)` call: where it is, what it wraps, how it binds."""

    rel: str
    line: int
    owner: str                      # enclosing class name; "" at module level
    attr: str                       # bound name ("_step"); "" if unbound
    is_self_attr: bool              # bound via `self.<attr> = jax.jit(...)`
    target: str                     # wrapped function as written ("bert.embed")
    target_qname: Optional[str]     # resolved project qname, when visible
    donate_argnums: Tuple[int, ...]
    static_argnums: Tuple[int, ...]

    @property
    def key(self) -> Tuple[str, str, str]:
        return (self.owner, self.attr, self.target)


def _is_jit_func(func: ast.expr) -> bool:
    if isinstance(func, ast.Attribute):
        return (
            func.attr == "jit"
            and isinstance(func.value, ast.Name)
            and func.value.id == "jax"
        )
    return isinstance(func, ast.Name) and func.id == "jit"


def _argnums(call: ast.Call, name: str) -> Tuple[int, ...]:
    for kw in call.keywords:
        if kw.arg != name:
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return (v.value,)
        if isinstance(v, (ast.Tuple, ast.List)):
            out = []
            for e in v.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    out.append(e.value)
            return tuple(out)
    return ()


def _unwrap_partial(expr: ast.expr) -> ast.expr:
    """partial(fn, ...) / functools.partial(fn, ...) / the engine's
    `named_partial(fn, ...)` (engine/spans.py) -> fn; factories
    (`make_step(...)`) unwrap to the factory reference."""
    if isinstance(expr, ast.Call):
        func = expr.func
        name = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else ""
        )
        if name in ("partial", "named_partial") and expr.args:
            return _unwrap_partial(expr.args[0])
        return func
    return expr


def scan_jit_sites(
    project: Project, prefixes: Sequence[str] = (ENGINE_PREFIX,),
    *, exclude_rels: Sequence[str] = (),
) -> List[JitSite]:
    sites: List[JitSite] = []
    for rel, mod in sorted(project.modules.items()):
        if not any(rel.startswith(p) for p in prefixes):
            continue
        if rel in exclude_rels:
            continue
        infos = function_infos_by_node(project, rel)
        for node in ast.walk(mod.src.tree):
            if not isinstance(node, ast.Call) or not _is_jit_func(node.func):
                continue
            if not node.args:
                continue
            target_expr = _unwrap_partial(node.args[0])
            target = _dotted(target_expr) or "<expr>"
            owner = enclosing_class_name(mod.src.parents(node)) or ""
            fn_node = enclosing_function(mod.src.parents(node))
            enclosing = infos.get(id(fn_node)) if fn_node is not None else None
            qname: Optional[str] = None
            if isinstance(target_expr, (ast.Name, ast.Attribute)):
                resolved = project.resolve_call(
                    mod, target_expr,
                    enclosing.class_name if enclosing else None, enclosing,
                )
                qname = resolved.qname if resolved is not None else None
            attr, is_self = "", False
            parent = getattr(node, "parent", None)
            if isinstance(parent, ast.Assign) and len(parent.targets) == 1:
                t = parent.targets[0]
                if (
                    isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"
                ):
                    attr, is_self = t.attr, True
                elif isinstance(t, ast.Name):
                    attr = t.id
            sites.append(JitSite(
                rel=rel, line=node.lineno, owner=owner, attr=attr,
                is_self_attr=is_self, target=target, target_qname=qname,
                donate_argnums=_argnums(node, "donate_argnums"),
                static_argnums=_argnums(node, "static_argnums"),
            ))
    return sites


# --------------------------------------------------- PartitionSpec meaning


def canonical_pspec(call: ast.Call) -> object:
    """The canonical MEANING of a literal P(...)/PartitionSpec(...) call:
    trailing Nones dropped, remaining args unparsed. `P()`, `P(None)` and
    `P(None, None)` all evaluate to "P()" — the semantic identity the
    spelling-level `canonical-pspec` rule cannot see."""
    if any(isinstance(a, ast.Starred) for a in call.args) or call.keywords:
        return UNKNOWN
    kept = list(call.args)
    while kept and isinstance(kept[-1], ast.Constant) and kept[-1].value is None:
        kept.pop()
    try:
        inner = ", ".join(ast.unparse(a) for a in kept)
    except Exception:  # pragma: no cover - unparse is best-effort detail
        return UNKNOWN
    return f"P({inner})"


def _is_pspec_call(call: ast.Call) -> bool:
    func = call.func
    if isinstance(func, ast.Name):
        return func.id in ("P", "PartitionSpec")
    return isinstance(func, ast.Attribute) and func.attr == "PartitionSpec"


def _is_named_sharding_call(expr: ast.expr) -> bool:
    if not isinstance(expr, ast.Call):
        return False
    func = expr.func
    name = func.attr if isinstance(func, ast.Attribute) else (
        func.id if isinstance(func, ast.Name) else ""
    )
    return name == "NamedSharding"


def _trailing_name(expr: ast.expr) -> Optional[str]:
    """'PAGED_PLANE_SPECS' from either the bare Name or a module-qualified
    `partition.PAGED_PLANE_SPECS` attribute access."""
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute):
        return expr.attr
    return None


def collect_plane_tables(project: Project) -> Dict[str, Dict[str, object]]:
    """Every module-level literal spec table in the project: an (optionally
    annotated) assignment of a Name to a dict whose keys are ALL string
    constants and whose values are ALL literal P(...)/PartitionSpec(...)
    calls, each evaluated to its canonical meaning. A dict failing either
    shape test is not a spec table and is skipped whole — partial tables
    would let a half-literal dict masquerade as policy. Keyed by the bare
    table name (`PAGED_PLANE_SPECS`), which is how producer modules
    subscript it whether imported bare or module-qualified."""
    tables: Dict[str, Dict[str, object]] = {}
    for rel, mod in sorted(project.modules.items()):
        for node in mod.src.tree.body:
            if isinstance(node, ast.AnnAssign):
                target, value = node.target, node.value
            elif isinstance(node, ast.Assign) and len(node.targets) == 1:
                target, value = node.targets[0], node.value
            else:
                continue
            if not (isinstance(target, ast.Name)
                    and isinstance(value, ast.Dict) and value.keys):
                continue
            entries: Dict[str, object] = {}
            for k, v in zip(value.keys, value.values):
                if not (
                    isinstance(k, ast.Constant) and isinstance(k.value, str)
                    and isinstance(v, ast.Call) and _is_pspec_call(v)
                ):
                    entries = {}
                    break
                spec = canonical_pspec(v)
                if isinstance(spec, _Unknown):
                    entries = {}
                    break
                entries[k.value] = spec
            if entries:
                tables[target.id] = entries
    return tables


def plane_tables(project: Project) -> Dict[str, Dict[str, object]]:
    """Memoized collect_plane_tables — SpecEval consults it per Subscript
    and the pspec-flow rule per project, so scan the module set once."""
    cached = getattr(project, "_plane_table_cache", None)
    if cached is None:
        cached = collect_plane_tables(project)
        try:
            project._plane_table_cache = cached
        except Exception:  # pragma: no cover - frozen project models
            pass
    return cached


@dataclasses.dataclass
class Frame:
    """One evaluation scope: explicit bindings (call-site arguments) over
    lazily-resolved local assignments of `fn_node`."""

    bindings: Dict[str, object]
    fn_node: Optional[ast.AST]
    parent: Optional["Frame"] = None


class SpecEval:
    """Evaluate a PartitionSpec-valued expression to its canonical meaning
    (a "P(...)" string), known-None, or UNKNOWN."""

    def __init__(self, project: Project, mod: ModuleInfo):
        self.project = project
        self.mod = mod
        self.infos = function_infos_by_node(project, mod.rel)

    def eval(self, expr: ast.expr, frame: Frame, depth: int = 0) -> object:
        if depth > _MAX_DEPTH:
            return UNKNOWN
        if isinstance(expr, ast.Constant):
            # Strings flow too: plane NAMES key the spec table
            # (`partition.PAGED_PLANE_SPECS[name]`), so a literal plane
            # name bound at a put call site must survive to the Subscript
            # evaluation below. Everything else non-None stays UNKNOWN.
            if expr.value is None:
                return None
            return expr.value if isinstance(expr.value, str) else UNKNOWN
        if isinstance(expr, ast.Name):
            return self._eval_name(expr.id, frame, depth)
        if isinstance(expr, ast.Subscript):
            # `TABLE[name]` against a literal plane-spec table: when the
            # key evaluates to a known string and the subscripted name
            # resolves to a collected table (see collect_plane_tables),
            # the entry's canonical spec IS the value. Anything else —
            # unknown key, unknown table, missing entry — is UNKNOWN
            # (missing resolution loses findings, never invents them).
            key = self.eval(expr.slice, frame, depth + 1)
            if isinstance(key, str):
                tname = _trailing_name(expr.value)
                if tname is not None:
                    table = plane_tables(self.project).get(tname)
                    if table is not None:
                        return table.get(key, UNKNOWN)
            return UNKNOWN
        if isinstance(expr, ast.IfExp):
            test = self._eval_test(expr.test, frame, depth)
            if test is True:
                return self.eval(expr.body, frame, depth + 1)
            if test is False:
                return self.eval(expr.orelse, frame, depth + 1)
            a = self.eval(expr.body, frame, depth + 1)
            b = self.eval(expr.orelse, frame, depth + 1)
            return a if a == b and not isinstance(a, _Unknown) else UNKNOWN
        if isinstance(expr, ast.Call):
            if _is_pspec_call(expr):
                return canonical_pspec(expr)
            if _is_named_sharding_call(expr):
                if len(expr.args) >= 2:
                    return self.eval(expr.args[1], frame, depth + 1)
                return UNKNOWN
            return self._eval_project_call(expr, frame, depth)
        return UNKNOWN

    # Helpers ------------------------------------------------------------

    def _eval_name(self, name: str, frame: Frame, depth: int) -> object:
        cur: Optional[Frame] = frame
        while cur is not None:
            if name in cur.bindings:
                return cur.bindings[name]
            if cur.fn_node is not None:
                assign = self._single_assignment(cur.fn_node, name)
                if assign is not None:
                    return self.eval(assign, cur, depth + 1)
            cur = cur.parent
        return UNKNOWN

    @staticmethod
    def _single_assignment(fn_node: ast.AST, name: str) -> Optional[ast.expr]:
        found: List[ast.expr] = []
        for node in ast.walk(fn_node):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == name:
                        found.append(node.value)
        return found[0] if len(found) == 1 else None

    def _eval_test(self, test: ast.expr, frame: Frame, depth: int) -> object:
        """Decide `x is None` / `x is not None` when x's value is known."""
        if not (
            isinstance(test, ast.Compare)
            and len(test.ops) == 1
            and isinstance(test.ops[0], (ast.Is, ast.IsNot))
            and isinstance(test.comparators[0], ast.Constant)
            and test.comparators[0].value is None
        ):
            return UNKNOWN
        left = self.eval(test.left, frame, depth + 1)
        if isinstance(left, _Unknown):
            return UNKNOWN
        is_none = left is None
        return is_none if isinstance(test.ops[0], ast.Is) else not is_none

    def _eval_project_call(
        self, call: ast.Call, frame: Frame, depth: int
    ) -> object:
        fn_node = enclosing_function(self.mod.src.parents(call))
        enclosing = self.infos.get(id(fn_node)) if fn_node is not None else None
        resolved = self.project.resolve_call(
            self.mod, call.func,
            enclosing.class_name if enclosing else None, enclosing,
        )
        if resolved is None:
            return UNKNOWN
        bindings = bind_call_args(resolved.node, call)
        if bindings is None:
            return UNKNOWN
        callee_frame = Frame(
            bindings={
                k: (self.eval(v, frame, depth + 1)
                    if isinstance(v, ast.expr) else v)
                for k, v in bindings.items()
            },
            fn_node=resolved.node,
        )
        returns = [
            n.value for n in ast.walk(resolved.node)
            if isinstance(n, ast.Return) and n.value is not None
        ]
        values = {
            v for v in (
                self.eval(r, callee_frame, depth + 1) for r in returns
            ) if not isinstance(v, _Unknown)
        }
        return values.pop() if len(values) == 1 else UNKNOWN


def bind_call_args(
    fn_node: ast.AST, call: ast.Call
) -> Optional[Dict[str, object]]:
    """Map a call's argument expressions onto the callee's parameter names
    (positional + keyword + defaults). None when the shapes don't line up
    (starargs, **kwargs, too many positionals)."""
    args = getattr(fn_node, "args", None)
    if args is None:
        return None
    if any(isinstance(a, ast.Starred) for a in call.args):
        return None
    if any(kw.arg is None for kw in call.keywords):
        return None
    params = [a.arg for a in args.args]
    if params and params[0] == "self":
        params = params[1:]
    out: Dict[str, object] = {}
    if len(call.args) > len(params):
        return None
    for name, expr in zip(params, call.args):
        out[name] = expr
    for kw in call.keywords:
        if kw.arg in params:
            out[kw.arg] = kw.value
    # Defaults for parameters the call leaves unset.
    defaults = args.defaults or []
    for param_ast, default in zip(args.args[-len(defaults):], defaults):
        name = param_ast.arg
        if name != "self" and name not in out:
            out[name] = default
    for name in params:
        out.setdefault(name, UNKNOWN)
    return out


@dataclasses.dataclass(frozen=True)
class PlanePut:
    """One `jax.device_put` of a named state plane under a resolved spec."""

    rel: str
    line: int
    plane: str      # trailing attribute chain: "tok", "cache.length"
    spec: object    # "P(...)" | UNKNOWN


def _plane_key(expr: ast.expr) -> Optional[str]:
    """'cache.length' from `state.cache.length`: the plane identity is the
    attribute chain past the root binding (which is just a local name)."""
    chain = chain_str(expr)
    if chain is None or "." not in chain:
        return None
    root, rest = chain.split(".", 1)
    if root == "self" and "." in rest:
        # self.state.tok -> plane past the attribute root.
        rest = rest.split(".", 1)[1]
    return rest or None


def _is_device_put(call: ast.Call) -> bool:
    func = call.func
    name = func.attr if isinstance(func, ast.Attribute) else (
        func.id if isinstance(func, ast.Name) else ""
    )
    return name == "device_put"


def collect_plane_puts(
    project: Project, prefixes: Sequence[str] = (ENGINE_PREFIX,)
) -> List[PlanePut]:
    """Every device_put of a named plane in the watched modules, with the
    spec it lands under — one level of nested-helper indirection resolved
    by binding the helper's parameters at each of its call sites (the
    `paged._canon_state.put(state.tok)` shape)."""
    puts: List[PlanePut] = []
    for rel, mod in sorted(project.modules.items()):
        if not any(rel.startswith(p) for p in prefixes):
            continue
        ev = SpecEval(project, mod)
        for node in ast.walk(mod.src.tree):
            if not isinstance(node, ast.Call) or not _is_device_put(node):
                continue
            if len(node.args) < 2:
                continue
            value_expr, spec_expr = node.args[0], node.args[1]
            fn_node = enclosing_function(mod.src.parents(node))
            if fn_node is None:
                continue
            if isinstance(value_expr, ast.Attribute):
                plane = _plane_key(value_expr)
                if plane is None:
                    continue
                frame = Frame(bindings={}, fn_node=fn_node)
                puts.append(PlanePut(
                    rel=rel, line=node.lineno, plane=plane,
                    spec=ev.eval(spec_expr, frame),
                ))
                continue
            if not isinstance(value_expr, ast.Name):
                continue
            # `device_put(x, ...)` where x is a parameter of a nested
            # helper: bind each call site's actuals and evaluate there.
            params = {
                a.arg for a in getattr(fn_node, "args", ast.arguments(
                    args=[], posonlyargs=[], kwonlyargs=[], kw_defaults=[],
                    defaults=[],
                )).args
            }
            parent_fn = enclosing_function(mod.src.parents(fn_node))
            if value_expr.id not in params or parent_fn is None:
                continue
            helper_name = getattr(fn_node, "name", None)
            for site in ast.walk(parent_fn):
                if not isinstance(site, ast.Call):
                    continue
                if not (
                    isinstance(site.func, ast.Name)
                    and site.func.id == helper_name
                ):
                    continue
                bindings = bind_call_args(fn_node, site)
                if bindings is None:
                    continue
                actual = bindings.get(value_expr.id)
                if not isinstance(actual, ast.expr):
                    continue
                plane = _plane_key(actual)
                if plane is None:
                    continue
                outer = Frame(bindings={}, fn_node=parent_fn)
                frame = Frame(
                    bindings={
                        k: (ev.eval(v, outer)
                            if isinstance(v, ast.expr) else v)
                        for k, v in bindings.items()
                    },
                    fn_node=fn_node, parent=outer,
                )
                puts.append(PlanePut(
                    rel=rel, line=site.lineno, plane=plane,
                    spec=ev.eval(spec_expr, frame),
                ))
    return puts


# ------------------------------------------------------------- dtype flow


_FLOAT_DTYPES = {"float16", "float32", "float64", "bfloat16"}
_INT_DTYPES = {"int8", "int16", "int32", "int64", "uint8", "uint32"}
_DTYPE_NAMES = _FLOAT_DTYPES | _INT_DTYPES | {"bool_", "bool"}
WEAK_INT = "weak_int"
WEAK_FLOAT = "weak_float"


def dtype_of_node(node: ast.expr) -> Optional[str]:
    """'int8' for `jnp.int8` / `np.int8` / `"int8"`; None otherwise."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value if node.value in _DTYPE_NAMES else None
    if isinstance(node, ast.Attribute) and node.attr in _DTYPE_NAMES:
        return node.attr
    if isinstance(node, ast.Name) and node.id in _DTYPE_NAMES:
        return node.id
    return None


# jnp constructors: name -> index of the positional dtype argument.
_CTOR_DTYPE_POS = {
    "zeros": 1, "ones": 1, "empty": 1, "full": 2, "asarray": 1, "array": 1,
}


class DtypeWalker:
    """Forward dtype propagation through one function body.

    `on_upcast(node, src_dtype, dst_dtype)` fires on `.astype()` from int8
    to a float dtype; `on_weak_promotion(node, dtype)` fires when a
    known-int-dtype array meets a bare float literal (jax weak-type
    promotion silently widens the array to the default float dtype).
    Functions whose name mentions dequantization are exempt from the
    upcast hook — converting back to compute precision is their job.
    """

    def __init__(
        self,
        project: Project,
        on_upcast: Callable[[ast.AST, str, str], None],
        on_weak_promotion: Callable[[ast.AST, str], None],
    ):
        self.project = project
        self.on_upcast = on_upcast
        self.on_weak_promotion = on_weak_promotion
        self._return_cache: Dict[str, Optional[str]] = {}
        self._in_progress: Set[str] = set()
        self._last_inferred: Dict[int, Optional[str]] = {}
        # >0 while evaluating a CALLEE for its return dtype: the callee is
        # (or will be) walked directly under its own module, so findings
        # made during the quiet pass would be mis-attributed — drop them.
        self._quiet = 0

    # -- public entry ----------------------------------------------------

    def run(self, fn: FunctionInfo) -> None:
        allow_upcast = "dequant" in fn.name.lower()
        env: Dict[str, str] = {}
        for stmt in getattr(fn.node, "body", []):
            self._stmt(stmt, env, fn, allow_upcast)

    # -- statements ------------------------------------------------------

    def _stmt(
        self, stmt: ast.AST, env: Dict[str, str], fn: FunctionInfo,
        allow_upcast: bool,
    ) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return  # nested defs run via their own FunctionInfo
        if isinstance(stmt, ast.Assign):
            val = self._infer(stmt.value, env, fn, allow_upcast)
            self._bind_targets(stmt.targets, stmt.value, val, env)
            return
        if isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            val = self._infer(stmt.value, env, fn, allow_upcast)
            self._bind_targets([stmt.target], stmt.value, val, env)
            return
        if isinstance(stmt, ast.AugAssign):
            self._infer(stmt.value, env, fn, allow_upcast)
            chain = chain_str(stmt.target)
            if chain is not None:
                env.pop(chain, None)
            return
        if isinstance(stmt, (ast.Return, ast.Expr)):
            if getattr(stmt, "value", None) is not None:
                self._infer(stmt.value, env, fn, allow_upcast)
            return
        # Compound statement: guard expressions see the pre-branch env...
        for field in ("test", "iter", "items"):
            sub = getattr(stmt, field, None)
            if isinstance(sub, ast.expr):
                self._infer(sub, env, fn, allow_upcast)
        # ...and each block runs on its OWN copy — bindings made inside a
        # branch must not leak into a mutually-exclusive sibling (an
        # if-body's `x = int8` would otherwise invent findings on the
        # else-path's float `x`) nor survive past a block that may not
        # execute (if-without-else, zero-iteration loops). The pristine
        # env joins the merge as the "no block ran" path, so only
        # bindings NO branch touched survive — maximally conservative:
        # facts are lost, never invented.
        branch_envs = [dict(env)]
        for field in _BLOCK_FIELDS:
            seq = getattr(stmt, field, []) or []
            if not seq:
                continue
            benv = dict(branch_envs[0])
            for child in seq:
                self._stmt(child, benv, fn, allow_upcast)
            branch_envs.append(benv)
        env.clear()
        env.update({
            k: v for k, v in branch_envs[0].items()
            if all(b.get(k) == v for b in branch_envs[1:])
        })

    def _bind_targets(
        self, targets: List[ast.expr], value: ast.expr,
        val: Optional[str], env: Dict[str, str],
    ) -> None:
        for t in targets:
            if isinstance(t, (ast.Tuple, ast.List)):
                if isinstance(value, (ast.Tuple, ast.List)) and len(
                    value.elts
                ) == len(t.elts):
                    for sub_t, sub_v in zip(t.elts, value.elts):
                        self._bind_targets(
                            [sub_t], sub_v, self._last_inferred.get(
                                id(sub_v)
                            ), env,
                        )
                else:
                    for sub_t in t.elts:
                        chain = chain_str(sub_t)
                        if chain is not None:
                            env.pop(chain, None)
                continue
            chain = chain_str(t)
            if chain is None:
                continue
            if val is None:
                env.pop(chain, None)
            else:
                env[chain] = val

    # -- expressions -----------------------------------------------------

    def _infer(
        self, expr: ast.expr, env: Dict[str, str], fn: FunctionInfo,
        allow_upcast: bool, depth: int = 0,
    ) -> Optional[str]:
        out = self._infer_inner(expr, env, fn, allow_upcast, depth)
        self._last_inferred[id(expr)] = out
        return out

    def _infer_inner(
        self, expr: ast.expr, env: Dict[str, str], fn: FunctionInfo,
        allow_upcast: bool, depth: int,
    ) -> Optional[str]:
        if depth > _MAX_DEPTH:
            return None
        if isinstance(expr, ast.Constant):
            if isinstance(expr.value, bool):
                return "bool"
            if isinstance(expr.value, int):
                return WEAK_INT
            if isinstance(expr.value, float):
                return WEAK_FLOAT
            return None
        if isinstance(expr, (ast.Name, ast.Attribute)):
            chain = chain_str(expr)
            return env.get(chain) if chain is not None else None
        if isinstance(expr, ast.UnaryOp):
            return self._infer(expr.operand, env, fn, allow_upcast, depth + 1)
        if isinstance(expr, ast.Subscript):
            return self._infer(expr.value, env, fn, allow_upcast, depth + 1)
        if isinstance(expr, ast.IfExp):
            a = self._infer(expr.body, env, fn, allow_upcast, depth + 1)
            b = self._infer(expr.orelse, env, fn, allow_upcast, depth + 1)
            return a if a == b else None
        if isinstance(expr, ast.BinOp):
            return self._infer_binop(expr, env, fn, allow_upcast, depth)
        if isinstance(expr, ast.Call):
            return self._infer_call(expr, env, fn, allow_upcast, depth)
        # Anything else: walk children for side-effect findings.
        for child in ast.iter_child_nodes(expr):
            if isinstance(child, ast.expr):
                self._infer(child, env, fn, allow_upcast, depth + 1)
        return None

    def _infer_binop(
        self, expr: ast.BinOp, env: Dict[str, str], fn: FunctionInfo,
        allow_upcast: bool, depth: int,
    ) -> Optional[str]:
        left = self._infer(expr.left, env, fn, allow_upcast, depth + 1)
        right = self._infer(expr.right, env, fn, allow_upcast, depth + 1)
        for strong, weak in ((left, right), (right, left)):
            if strong in _INT_DTYPES and weak == WEAK_FLOAT:
                if not self._quiet:
                    self.on_weak_promotion(expr, strong)
                return "float32"
        if left == right:
            return left
        if {left, right} <= (_INT_DTYPES | {WEAK_INT}):
            known = [d for d in (left, right) if d in _INT_DTYPES]
            return known[0] if len(known) == 1 else None
        if isinstance(expr.op, ast.Div):
            return None  # true division promotes to float; dtype unclear
        return None

    def _infer_call(
        self, expr: ast.Call, env: Dict[str, str], fn: FunctionInfo,
        allow_upcast: bool, depth: int,
    ) -> Optional[str]:
        for a in expr.args:
            self._infer(a, env, fn, allow_upcast, depth + 1)
        for kw in expr.keywords:
            self._infer(kw.value, env, fn, allow_upcast, depth + 1)
        func = expr.func
        if isinstance(func, ast.Attribute):
            if func.attr == "astype":
                base = self._infer(
                    func.value, env, fn, allow_upcast, depth + 1
                )
                dst: Optional[str] = None
                if expr.args:
                    dst = dtype_of_node(expr.args[0])
                for kw in expr.keywords:
                    if kw.arg == "dtype":
                        dst = dtype_of_node(kw.value)
                if (
                    base == "int8" and dst in _FLOAT_DTYPES
                    and not allow_upcast and not self._quiet
                ):
                    self.on_upcast(expr, base, dst)
                return dst
            ns = func.value
            if isinstance(ns, ast.Name) and ns.id in ("jnp", "np", "numpy"):
                name = func.attr
                if name.endswith("_like") and expr.args:
                    return self._infer(
                        expr.args[0], env, fn, allow_upcast, depth + 1
                    )
                if name == "where" and len(expr.args) == 3:
                    a = self._infer(
                        expr.args[1], env, fn, allow_upcast, depth + 1
                    )
                    b = self._infer(
                        expr.args[2], env, fn, allow_upcast, depth + 1
                    )
                    return a if a == b else None
                if name in _CTOR_DTYPE_POS:
                    for kw in expr.keywords:
                        if kw.arg == "dtype":
                            return dtype_of_node(kw.value)
                    pos = _CTOR_DTYPE_POS[name]
                    if len(expr.args) > pos:
                        return dtype_of_node(expr.args[pos])
                    if name in ("asarray", "array") and expr.args:
                        return self._infer(
                            expr.args[0], env, fn, allow_upcast, depth + 1
                        )
                return None
        # Project-local call: memoized return dtype (context-insensitive).
        mod = self.project.modules.get(fn.rel)
        if mod is None:
            return None
        resolved = self.project.resolve_call(mod, func, fn.class_name, fn)
        if resolved is None:
            return None
        return self._return_dtype(resolved, depth)

    def _return_dtype(self, fn: FunctionInfo, depth: int) -> Optional[str]:
        if fn.qname in self._return_cache:
            return self._return_cache[fn.qname]
        if fn.qname in self._in_progress or depth > _MAX_DEPTH:
            return None
        self._in_progress.add(fn.qname)
        self._quiet += 1
        try:
            env: Dict[str, str] = {}
            allow = "dequant" in fn.name.lower()
            values: Set[Optional[str]] = set()
            for stmt in getattr(fn.node, "body", []):
                self._stmt(stmt, env, fn, allow)
            for node in ast.walk(fn.node):
                if isinstance(node, ast.Return) and node.value is not None:
                    values.add(
                        self._infer(node.value, env, fn, allow, depth + 1)
                    )
            out = values.pop() if len(values) == 1 else None
        finally:
            self._in_progress.discard(fn.qname)
            self._quiet -= 1
        self._return_cache[fn.qname] = out
        return out
