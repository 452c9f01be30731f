"""program-inventory: the checked-in manifest of jit entry points matches
the tree, and warmup covers it.

`engine/program_inventory.py` is generated from the static jit scan and
cross-validated at runtime by `compile_count_guard(
expected_from_inventory(engine))`. This rule closes the static side of
the loop on every lint run:

- **uninventoried**: a `jax.jit(...)` entry point in the engine modules
  with no matching manifest entry — a new program shipped unclassified
  (no warmup claim, no guard coverage).
- **stale**: a manifest entry no jit site matches — the engine moved on
  and the manifest (plus whatever dashboards/guards trust it) lies.
- **drift**: entry and site agree on identity but disagree on
  `donate_argnums`/`static_argnums` — the donation contract the
  donation-safety rule enforces is keyed off the manifest's claim.
- **warmup-miss**: an entry with `coverage="warmup"` whose owning class
  has a `warmup` method from which no call to that program is reachable
  (call-graph closure, so coverage through helpers like
  `PagedEngine.warmup -> _warm_score` counts). Deleting one warmup
  step fails here before the runtime guard ever runs.

`absint.REFERENCE_REL` (the tests' reference generator) is not scanned:
it serves nothing, so it has no program to inventory.

Matching keys on (engine, attr, target) — line numbers drift with
unrelated edits and are deliberately not part of the manifest.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .. import absint
from ..core import Finding, register
from ..project import FunctionInfo, Project, ProjectRule

DEFAULT_MANIFEST = "distributed_lms_raft_llm_tpu/engine/program_inventory.py"


class ManifestEntry:
    def __init__(self, line: int, fields: Dict[str, object]):
        self.line = line
        self.engine = str(fields.get("engine", ""))
        self.attr = str(fields.get("attr", ""))
        self.target = str(fields.get("target", ""))
        self.donate_argnums = tuple(fields.get("donate_argnums", ()) or ())
        self.static_argnums = tuple(fields.get("static_argnums", ()) or ())
        self.domain = str(fields.get("domain", ""))
        self.coverage = str(fields.get("coverage", ""))

    @property
    def key(self) -> Tuple[str, str, str]:
        return (self.engine, self.attr, self.target)


def _literal(node: ast.expr) -> object:
    try:
        return ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None


def parse_manifest(tree: ast.AST) -> List[ManifestEntry]:
    """The ProgramEntry(...) literals of the INVENTORY assignment."""
    entries: List[ManifestEntry] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.Assign, ast.AnnAssign)):
            continue
        targets = node.targets if isinstance(node, ast.Assign) else (
            [node.target]
        )
        if not any(
            isinstance(t, ast.Name) and t.id == "INVENTORY" for t in targets
        ):
            continue
        value = node.value
        if not isinstance(value, (ast.Tuple, ast.List)):
            continue
        for elt in value.elts:
            if not (
                isinstance(elt, ast.Call)
                and (
                    (isinstance(elt.func, ast.Name)
                     and elt.func.id == "ProgramEntry")
                    or (isinstance(elt.func, ast.Attribute)
                        and elt.func.attr == "ProgramEntry")
                )
            ):
                continue
            fields = {
                kw.arg: _literal(kw.value)
                for kw in elt.keywords if kw.arg is not None
            }
            entries.append(ManifestEntry(elt.lineno, fields))
    return entries


@register
class ProgramInventoryRule(ProjectRule):
    name = "program-inventory"
    description = (
        "the engine's jit entry points and the generated manifest "
        "(engine/program_inventory.py) must match, and every "
        "warmup-covered inventoried program must be reachable from its "
        "engine's warmup() — uncovered programs stall the first live "
        "request with an XLA compile (the PR-2 class)"
    )

    # Absence claims ("no site matches") need the whole tree.
    full_project_only = True

    def __init__(
        self,
        scan_prefixes: Sequence[str] = (absint.ENGINE_PREFIX,),
        manifest_rel: str = DEFAULT_MANIFEST,
    ):
        self.scan_prefixes = tuple(scan_prefixes)
        self.manifest_rel = manifest_rel

    def check_project(self, project: Project) -> List[Finding]:
        manifest_src = project.sources.get(self.manifest_rel)
        findings: List[Finding] = []
        if manifest_src is None:
            # Report on every scanned jit site: the manifest is missing
            # entirely (deleted, or the fixture forgot it).
            for site in self._sites(project):
                findings.append(Finding(
                    rule=self.name, path=site.rel, line=site.line,
                    message=(
                        f"jit entry point `{site.owner or site.rel}."
                        f"{site.attr or site.target}` has no program "
                        f"manifest ({self.manifest_rel} not found); "
                        "generate one (scripts/gen_program_inventory.py)"
                    ),
                ))
            return findings
        entries = parse_manifest(manifest_src.tree)
        sites = self._sites(project)
        by_key: Dict[Tuple[str, str, str], List[ManifestEntry]] = {}
        for e in entries:
            by_key.setdefault(e.key, []).append(e)

        matched: Set[int] = set()
        for site in sites:
            candidates = by_key.get(site.key, [])
            if not candidates:
                label = f"{site.owner}.{site.attr}" if site.owner else (
                    site.attr or site.target
                )
                findings.append(Finding(
                    rule=self.name, path=site.rel, line=site.line,
                    message=(
                        f"uninventoried jit entry point `{label}` (wraps "
                        f"`{site.target}`): every compiled program must be "
                        "classified in engine/program_inventory.py — "
                        "regenerate (scripts/gen_program_inventory.py "
                        "--write) and pick its coverage class"
                    ),
                ))
                continue
            entry = candidates[0]
            matched.add(id(entry))
            if (
                entry.donate_argnums != site.donate_argnums
                or entry.static_argnums != site.static_argnums
            ):
                findings.append(Finding(
                    rule=self.name, path=site.rel, line=site.line,
                    message=(
                        f"inventory drift for `{site.owner}.{site.attr}`: "
                        f"site has donate={site.donate_argnums} "
                        f"static={site.static_argnums}, manifest says "
                        f"donate={entry.donate_argnums} "
                        f"static={entry.static_argnums} — regenerate the "
                        "manifest so the donation contract stays true"
                    ),
                ))
        for entry in entries:
            if id(entry) not in matched:
                findings.append(Finding(
                    rule=self.name, path=self.manifest_rel, line=entry.line,
                    message=(
                        f"stale inventory entry `{entry.engine}."
                        f"{entry.attr}` (wraps `{entry.target}`): no jit "
                        "site in the engine matches — regenerate the "
                        "manifest (scripts/gen_program_inventory.py --write)"
                    ),
                ))

        findings.extend(self._check_warmup_coverage(project, entries, sites))
        return findings

    # ------------------------------------------------------------------

    def _sites(self, project: Project) -> List[absint.JitSite]:
        return [
            s for s in absint.scan_jit_sites(
                project, self.scan_prefixes,
                exclude_rels=(self.manifest_rel, absint.REFERENCE_REL),
            )
            if s.attr  # unbound jit expressions have no program identity
        ]

    def _check_warmup_coverage(
        self, project: Project, entries: List[ManifestEntry],
        sites: List[absint.JitSite],
    ) -> List[Finding]:
        findings: List[Finding] = []
        site_rel = {s.key: s.rel for s in sites}
        covered_classes: Dict[str, Optional[FunctionInfo]] = {}
        seen: Set[Tuple[str, str]] = set()
        for entry in entries:
            if entry.coverage != "warmup" or not entry.engine:
                continue
            if entry.key not in site_rel:
                continue  # already reported as stale
            if (entry.engine, entry.attr) in seen:
                continue  # one finding per program, not per wrapped variant
            seen.add((entry.engine, entry.attr))
            if entry.engine not in covered_classes:
                covered_classes[entry.engine] = self._warmup_fn(
                    project, entry.engine
                )
            warmup = covered_classes[entry.engine]
            if warmup is None:
                findings.append(Finding(
                    rule=self.name, path=site_rel[entry.key], line=1,
                    message=(
                        f"inventory marks `{entry.engine}.{entry.attr}` as "
                        "warmup-covered but the class has no warmup() "
                        "method — add one or reclassify the entry as "
                        "on-demand"
                    ),
                ))
                continue
            if not self._reaches_attr_call(project, warmup, entry.attr):
                findings.append(Finding(
                    rule=self.name, path=warmup.rel, line=warmup.node.lineno,
                    message=(
                        f"warmup no longer covers inventoried program "
                        f"`{entry.engine}.{entry.attr}`: no call to "
                        f"`self.{entry.attr}(...)` is reachable from "
                        "warmup() — the first live request would pay its "
                        "XLA compile (restore the warmup step or "
                        "reclassify the entry)"
                    ),
                ))
        return findings

    @staticmethod
    def _warmup_fn(
        project: Project, engine: str
    ) -> Optional[FunctionInfo]:
        for fn in project.functions.values():
            if fn.class_name == engine and fn.name == "warmup":
                return fn
        return None

    @staticmethod
    def _reaches_attr_call(
        project: Project, warmup: FunctionInfo, attr: str
    ) -> bool:
        reachable = project.reachable([warmup.qname])
        for qname in reachable:
            fn = project.functions.get(qname)
            if fn is None:
                continue
            for node in ast.walk(fn.node):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == attr
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "self"
                ):
                    return True
        return False
