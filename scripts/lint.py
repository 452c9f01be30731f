#!/usr/bin/env python
"""dlrl-lint CLI: run the repo-native static-analysis suite.

    python scripts/lint.py                 # whole tree (package+scripts+tests)
    python scripts/lint.py --json          # machine-readable findings
    python scripts/lint.py --sarif         # SARIF 2.1.0 (CI/editor annotations)
    python scripts/lint.py --rules guarded-by,deadline-flow engine/
    python scripts/lint.py --rules lock-order,atomicity-across-await
    python scripts/lint.py --changed       # only git-changed files (pre-commit)
    python scripts/lint.py --baseline lint-baseline.json   # fail on NEW only
    python scripts/lint.py --types         # + the mypy strict-subset gate
    python scripts/lint.py --list-rules    # the catalog

Exit status: 0 when clean, 1 when any (non-baselined) finding remains or
the type gate fails, 2 on usage errors. `tests/test_lint_clean.py` runs
the same `run_lint()` entry point in tier-1, so CI and this CLI can never
disagree about "clean".

## JSON schema (stable; additive changes only)

`--json` emits one document:

    {
      "schema": "dlrl-lint/1",
      "clean": bool,                  // no live findings (after baseline)
      "rules": [str, ...],            // rule names that ran
      "findings": [                   // live findings, sorted
        {"rule": str, "path": str, "line": int, "message": str}, ...
      ],
      "baselined": int,               // findings suppressed by --baseline
      "stale_baseline": [             // baseline entries nothing matched
        {"rule": str, "path": str, "message": str}, ...
      ]
    }

## Baselines (incremental adoption)

`--write-baseline f.json` records today's findings; `--baseline f.json`
then suppresses exactly those (matched on rule+path+message — line
numbers drift with unrelated edits) so a tree that predates a rule can
gate on NEW findings immediately and burn the baseline down over time.
Stale entries are reported so a shrinking baseline stays honest.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path
from typing import Dict, List, Tuple

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from distributed_lms_raft_llm_tpu.analysis import (  # noqa: E402
    all_rules,
    default_paths,
    run_lint,
)

# The mypy strict-subset gate (--types): these modules carry full
# annotations; pyproject.toml holds the per-module strictness flags.
TYPED_SUBSET = [
    "distributed_lms_raft_llm_tpu/raft/core.py",
    "distributed_lms_raft_llm_tpu/lms/state.py",
    "distributed_lms_raft_llm_tpu/utils/resilience.py",
    "distributed_lms_raft_llm_tpu/utils/guards.py",
    "distributed_lms_raft_llm_tpu/utils/metrics_registry.py",
    "distributed_lms_raft_llm_tpu/utils/locks.py",
    "distributed_lms_raft_llm_tpu/analysis",
]

_BaselineKey = Tuple[str, str, str]


def changed_paths() -> List[Path]:
    """Lintable files the checkout touched: `git status --porcelain`
    covers staged, unstaged, AND untracked in one listing (renames report
    the new name). Deleted files and non-Python artifacts are dropped."""
    proc = subprocess.run(
        # -uall: report untracked files individually — the default
        # collapses a new directory to one "dir/" entry and every .py
        # under it would silently skip the run.
        ["git", "status", "--porcelain", "--no-renames", "-uall"],
        cwd=str(REPO), capture_output=True, text=True, check=True,
    )
    out: List[Path] = []
    for line in proc.stdout.splitlines():
        status, rel = line[:2], line[3:]
        if status == "!!" or status.strip() == "D":
            continue
        if rel.startswith('"') and rel.endswith('"'):
            # git C-quotes names with spaces/non-ASCII (octal escapes);
            # undo it or the file silently drops out of the run.
            rel = (
                rel[1:-1].encode("ascii", "backslashreplace")
                .decode("unicode_escape").encode("latin-1").decode("utf-8")
            )
        path = REPO / rel
        if path.suffix != ".py" or not path.is_file():
            continue
        # Only files the full gate covers: a repo-root stray
        # would otherwise make --changed and the tier-1 clean run disagree
        # about what "clean" means.
        if any(path.resolve().is_relative_to(base.resolve())
               for base in default_paths(REPO)):
            out.append(path)
    return sorted(out)


def _baseline_key(f: Dict[str, object]) -> _BaselineKey:
    return (str(f["rule"]), str(f["path"]), str(f["message"]))


def _load_baseline(path: Path) -> List[_BaselineKey]:
    """Accepts a --write-baseline file or any --json output document."""
    doc = json.loads(path.read_text())
    entries = doc["findings"] if isinstance(doc, dict) else doc
    return [_baseline_key(e) for e in entries]


def to_sarif(findings, rules) -> Dict[str, object]:
    """Render the stable dlrl-lint/1 finding set as SARIF 2.1.0 — the
    interchange shape GitHub code scanning and editors consume, so lint
    findings surface as PR annotations instead of a CI log to scroll.
    Mapping: rule -> reportingDescriptor, finding -> result (level
    "error"; this linter has no warning tier), path/line ->
    physicalLocation with a repo-relative artifact URI."""
    by_name = {r.name: r for r in rules}
    return {
        "version": "2.1.0",
        "$schema": ("https://raw.githubusercontent.com/oasis-tcs/"
                    "sarif-spec/master/Schemata/sarif-schema-2.1.0.json"),
        "runs": [{
            "tool": {"driver": {
                "name": "dlrl-lint",
                "rules": [
                    {
                        "id": name,
                        "shortDescription": {
                            "text": by_name[name].description or name
                        },
                    }
                    for name in sorted(by_name)
                ],
            }},
            "results": [
                {
                    "ruleId": f.rule,
                    "level": "error",
                    "message": {"text": f.message},
                    "locations": [{
                        "physicalLocation": {
                            "artifactLocation": {
                                "uri": f.path,
                                "uriBaseId": "%SRCROOT%",
                            },
                            "region": {"startLine": f.line},
                        }
                    }],
                }
                for f in findings
            ],
        }],
    }


def run_type_gate() -> int:
    """The mypy strict-on-subset gate; returns an exit code.

    The container may not ship mypy (the runtime stack doesn't need it);
    in that case the gate reports itself skipped and passes — the lint
    rules still run everywhere, and CI images with mypy enforce types.
    """
    try:
        import mypy  # noqa: F401
    except ImportError:
        print("types: mypy not installed; skipping the type gate "
              "(pip install mypy to enable)", file=sys.stderr)
        return 0
    proc = subprocess.run(
        [sys.executable, "-m", "mypy", "--config-file", "pyproject.toml",
         *TYPED_SUBSET],
        cwd=str(REPO), capture_output=True, text=True,
    )
    out = (proc.stdout or "") + (proc.stderr or "")
    if proc.returncode != 0:
        sys.stderr.write(out)
        print("types: FAILED", file=sys.stderr)
        return 1
    print(f"types ok ({len(TYPED_SUBSET)} targets)")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("paths", nargs="*",
                        help="files/directories to lint (default: the "
                             "package, scripts/ and tests/)")
    parser.add_argument("--changed", action="store_true",
                        help="lint only files git reports as changed "
                             "(staged, unstaged, or untracked) — the "
                             "pre-commit loop; project rules still analyze "
                             "the full tree but report only into changed "
                             "paths")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="emit the dlrl-lint/1 JSON document")
    parser.add_argument("--sarif", action="store_true", dest="as_sarif",
                        help="emit SARIF 2.1.0 (for CI upload / editor "
                             "annotations); exit status still reflects "
                             "findings")
    parser.add_argument("--rule", "--rules", action="append", default=None,
                        dest="rules", metavar="RULES",
                        help="run only these rules (comma-separated; "
                             "repeatable)")
    parser.add_argument("--baseline", type=Path, default=None,
                        help="JSON baseline of known findings to suppress; "
                             "only NEW findings fail the run")
    parser.add_argument("--write-baseline", type=Path, default=None,
                        metavar="PATH",
                        help="write the current findings as a baseline "
                             "file and exit 0")
    parser.add_argument("--types", action="store_true",
                        help="also run the mypy strict-subset gate "
                             "(skipped with a note when mypy is not "
                             "installed)")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule catalog and exit")
    args = parser.parse_args(argv)

    rules = all_rules()
    if args.list_rules:
        for rule in sorted(rules, key=lambda r: r.name):
            print(f"{rule.name}: {rule.description}")
        return 0
    if args.rules:
        wanted = {
            name.strip()
            for chunk in args.rules
            for name in chunk.split(",")
            if name.strip()
        }
        known = {r.name for r in rules}
        unknown = wanted - known
        if unknown:
            print(f"unknown rule(s): {sorted(unknown)} "
                  f"(known: {sorted(known)})", file=sys.stderr)
            return 2
        rules = [r for r in rules if r.name in wanted]

    if args.as_json and args.as_sarif:
        print("--json and --sarif are mutually exclusive", file=sys.stderr)
        return 2
    paths = [Path(p) for p in args.paths] or None
    nothing_changed = False
    if args.changed:
        if paths is not None:
            print("--changed and explicit paths are mutually exclusive",
                  file=sys.stderr)
            return 2
        try:
            paths = changed_paths()
        except (OSError, subprocess.CalledProcessError) as e:
            print(f"--changed needs a git checkout: {e}", file=sys.stderr)
            return 2
        # An empty changed set is trivially clean — but fall through to
        # the normal output stage so --json still emits the dlrl-lint/1
        # document and --write-baseline still writes a (empty) baseline.
        nothing_changed = not paths
    findings = [] if nothing_changed else run_lint(
        paths=paths, rules=rules, root=REPO
    )

    if args.write_baseline is not None:
        args.write_baseline.write_text(json.dumps({
            "schema": "dlrl-lint/1",
            "findings": [f.to_json() for f in findings],
        }, indent=2) + "\n")
        print(f"wrote {len(findings)} finding(s) to {args.write_baseline}")
        return 0

    baselined = 0
    stale: List[_BaselineKey] = []
    if args.baseline is not None:
        try:
            known_keys = set(_load_baseline(args.baseline))
        except (OSError, ValueError, KeyError) as e:
            print(f"cannot read baseline {args.baseline}: {e}",
                  file=sys.stderr)
            return 2
        live = []
        matched = set()
        for f in findings:
            key = _baseline_key(f.to_json())
            if key in known_keys:
                baselined += 1
                matched.add(key)
            else:
                live.append(f)
        stale = sorted(known_keys - matched)
        findings = live

    if args.as_sarif:
        print(json.dumps(to_sarif(findings, rules), indent=2))
    elif args.as_json:
        print(json.dumps({
            "schema": "dlrl-lint/1",
            "clean": not findings,
            "rules": sorted(r.name for r in rules),
            "findings": [f.to_json() for f in findings],
            "baselined": baselined,
            "stale_baseline": [
                {"rule": r, "path": p, "message": m} for r, p, m in stale
            ],
        }, indent=2))
    else:
        for f in findings:
            print(f.format(), file=sys.stderr)
        if findings:
            print(f"\n{len(findings)} finding(s) across "
                  f"{len({f.path for f in findings})} file(s); suppress "
                  "intentional cases with `# lint: disable=<rule>` "
                  "(see README)", file=sys.stderr)
        else:
            note = f" ({baselined} baselined)" if baselined else ""
            print(f"lint ok ({len(rules)} rules){note}")
        if stale:
            print(f"note: {len(stale)} stale baseline entr"
                  f"{'y' if len(stale) == 1 else 'ies'} (fixed findings) — "
                  "regenerate with --write-baseline", file=sys.stderr)

    rc = 1 if findings else 0
    if args.types and run_type_gate() != 0:
        rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
