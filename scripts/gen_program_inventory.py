#!/usr/bin/env python
"""Generate the compiled-program inventory and its README table.

Two artifacts, one source of truth (the static jit scan +
`engine/program_inventory.static_paged_domain`):

- the `INVENTORY` block in `distributed_lms_raft_llm_tpu/engine/
  program_inventory.py` (between the ``inventory:begin/end`` markers):
  every `jax.jit(...)` entry point in the engine modules, with its
  donation/static contract and coverage class;
- the README "program inventory" block (between the
  ``<!-- program-inventory:begin/end -->`` markers): the entry table plus
  the derived static-argument domain — prompt buckets x cache widths x
  spec_tokens — for config.py defaults and every shipped `configs/*.toml`.

    python scripts/gen_program_inventory.py            # print both blocks
    python scripts/gen_program_inventory.py --check    # exit 1 on drift
    python scripts/gen_program_inventory.py --write    # rewrite in place

The `program-inventory` lint rule re-derives the entry list on every lint
run (pure AST, no imports), and `tests/test_program_inventory.py` runs
the --check logic in tier-1 — manifest or README drift fails the suite;
`utils/guards.expected_from_inventory` cross-validates the same manifest
against the live program caches at runtime.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path
from typing import Dict, List

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from distributed_lms_raft_llm_tpu.analysis import absint  # noqa: E402
from distributed_lms_raft_llm_tpu.analysis.core import (  # noqa: E402
    iter_sources,
)
from distributed_lms_raft_llm_tpu.analysis.project import Project  # noqa: E402

MANIFEST = REPO / "distributed_lms_raft_llm_tpu/engine/program_inventory.py"
README = REPO / "README.md"
INV_BEGIN = "# --- inventory:begin --- " \
    "(generated by scripts/gen_program_inventory.py)"
INV_END = "# --- inventory:end ---"
MD_BEGIN = "<!-- program-inventory:begin -->"
MD_END = "<!-- program-inventory:end -->"

# Domain/coverage classification per (engine, attr). The scan discovers
# entry points; what their static-argument domain IS (and whether warmup
# promises to cover it) is a design fact recorded here. A NEW jit site
# lands as ("shapes", "on-demand") — shipping it warmup-covered is a
# reviewed decision, not a default.
CLASSIFICATION = {
    ("PagedEngine", "_megastep"): ("width-k-pairs", "warmup"),
    ("PagedEngine", "_grow"): ("width-pairs", "warmup"),
    ("PagedEngine", "_stage"): ("stage-pairs", "warmup"),
    # Shared-prefix cache (engine/prefix_cache.py): domains are empty
    # when [tutoring] prefix_cache is off, so the warmup claim holds in
    # both modes (see static_paged_domain).
    ("PagedEngine", "_stage_block"): ("stage-widths", "warmup"),
    ("PagedEngine", "_export_block"): ("export-widths", "warmup"),
    # A stored run of the prefix tree (engine/program_inventory.py
    # STORED_RUN_BLOCKS): cut out of the slot at every width a run fits
    # where some bucket's prompts are a run long, none elsewhere.
    ("PagedEngine", "_export_run"): ("run-export-widths", "warmup"),
    # A recurrent family's state snapshots (engine/paged.py
    # `_restore_state_program`, `_export_state_program`): one program a
    # width, and none for a family without a recurrent state or with the
    # prefix cache off.
    ("PagedEngine", "_restore_state"): ("state-widths", "warmup"),
    ("PagedEngine", "_export_state"): ("state-widths", "warmup"),
    # Bulk-scoring tenant (engine/scoring.py): the score program's
    # (batch bucket x length bucket) domain is empty when
    # EngineConfig.scoring is off, so the warmup claim holds in both
    # modes (see static_score_domain).
    ("PagedEngine", "_score"): ("score-pairs", "warmup"),
}
DEFAULT_CLASS = ("shapes", "on-demand")


def scanned_entries() -> List[dict]:
    project = Project(iter_sources(None, root=REPO), root=REPO)
    manifest_rel = MANIFEST.relative_to(REPO).as_posix()
    entries = []
    for site in absint.scan_jit_sites(
        project, exclude_rels=(manifest_rel, absint.REFERENCE_REL)
    ):
        if not site.attr:
            continue
        domain, coverage = CLASSIFICATION.get(
            (site.owner, site.attr), DEFAULT_CLASS
        )
        entries.append(dict(
            engine=site.owner, attr=site.attr, target=site.target,
            donate_argnums=site.donate_argnums,
            static_argnums=site.static_argnums,
            domain=domain, coverage=coverage,
        ))
    entries.sort(key=lambda e: (e["engine"], e["attr"], e["target"]))
    return entries


def render_inventory_block(entries: List[dict]) -> str:
    lines = [INV_BEGIN, "INVENTORY: Tuple[ProgramEntry, ...] = ("]
    for e in entries:
        lines += [
            "    ProgramEntry(",
            f"        engine=\"{e['engine']}\", attr=\"{e['attr']}\", "
            f"target=\"{e['target']}\",",
            f"        donate_argnums={e['donate_argnums']!r}, "
            f"static_argnums={e['static_argnums']!r},",
            f"        domain=\"{e['domain']}\", "
            f"coverage=\"{e['coverage']}\",",
            "    ),",
        ]
    lines += [")", INV_END]
    return "\n".join(lines)


def shipped_domains() -> List[Dict[str, object]]:
    """The static-argument domain for config.py defaults and each
    configs/*.toml (requires the runtime imports; generation and tests
    only)."""
    from distributed_lms_raft_llm_tpu.config import (
        AppConfig,
        engine_config,
        load_config,
    )
    from distributed_lms_raft_llm_tpu.engine.program_inventory import (
        effective_megastep_max,
        static_paged_domain,
        static_score_domain,
    )
    from distributed_lms_raft_llm_tpu.models import registry

    named = [("defaults", AppConfig(), None)]
    for path in sorted((REPO / "configs").glob("*.toml")):
        named.append((
            f"configs/{path.name}", load_config(str(path)), path,
        ))
    rows: List[Dict[str, object]] = []
    for name, app, _ in named:
        ec = engine_config(app)
        _, cfg = registry.resolve(ec.model, ec.dtype, ec.param_dtype)
        t = app.tutoring
        dom = static_paged_domain(
            cfg.max_position_embeddings,
            ec.sampling.max_new_tokens,
            tuple(ec.length_buckets),
            ec.spec_tokens,
            megastep_max=effective_megastep_max(t.megastep,
                                                t.megastep_max),
            prefix_cache=t.prefix_cache,
            tp=ec.tp, ep=ec.ep,
        )
        sdom = static_score_domain(
            tuple(ec.length_buckets), tuple(ec.batch_buckets),
            cfg.max_position_embeddings,
            enabled=app.scoring.enabled,
        )
        total = (
            dom["stage_pairs"] + dom["megastep_pairs"] + dom["grow_pairs"]
            + dom["stage_block_widths"] + dom["export_widths"]
            + dom["run_export_widths"]
            + dom_score_pairs(sdom)
        )
        rows.append(dict(
            name=name, model=ec.model, mesh=dom["mesh"],
            spec_tokens=ec.spec_tokens,
            max_new=ec.sampling.max_new_tokens,
            buckets=dom["buckets"], widths=dom["widths"],
            k_ladder=dom["k_ladder"], prefix=t.prefix_cache,
            score_pairs=dom_score_pairs(sdom), total=total,
        ))
    return rows


def dom_score_pairs(sdom: Dict[str, object]) -> int:
    return int(sdom["score_pairs"])  # type: ignore[arg-type]


def render_readme_block(entries: List[dict]) -> str:
    lines = [MD_BEGIN]
    lines.append(
        "| engine | program | wrapped fn | donates | static | domain "
        "| coverage |"
    )
    lines.append("|---|---|---|---|---|---|---|")
    for e in entries:
        lines.append(
            f"| `{e['engine']}` | `{e['attr']}` | `{e['target']}` "
            f"| `{e['donate_argnums'] or '—'}` "
            f"| `{e['static_argnums'] or '—'}` "
            f"| {e['domain']} | {e['coverage']} |"
        )
    lines.append("")
    lines.append(
        "Derived static-argument domains (prompt buckets × cache "
        "widths × megastep ladder; `total` is the full warmup-compiled "
        "program count `expected_from_inventory` asserts, per mesh — a "
        "tp=2 engine's programs are different XLA programs than a tp=1 "
        "engine's, and the runtime guard cross-checks the mesh key):"
    )
    lines.append("")
    lines.append(
        "| config | model | mesh | spec_tokens | max_new "
        "| prompt buckets | cache widths | megastep ladder "
        "| prefix cache | score programs "
        "| total warmup programs |"
    )
    lines.append("|---|---|---|---|---|---|---|---|---|---|---|")
    for row in shipped_domains():
        mesh = row["mesh"]
        lines.append(
            f"| {row['name']} | `{row['model']}` "
            f"| tp={mesh['tp']} ep={mesh['ep']} | {row['spec_tokens']} "
            f"| {row['max_new']} "
            f"| {', '.join(str(b) for b in row['buckets'])} "
            f"| {', '.join(str(w) for w in row['widths'])} "
            f"| {', '.join(str(k) for k in row['k_ladder'])} "
            f"| {'on' if row['prefix'] else 'off'} "
            f"| {row['score_pairs']} "
            f"| {row['total']} |"
        )
    lines.append(MD_END)
    return "\n".join(lines)


def _splice(text: str, begin: str, end: str, block: str) -> str:
    start = text.find(begin)
    stop = text.find(end)
    if start == -1 or stop == -1 or stop < start:
        raise SystemExit(f"markers {begin!r}/{end!r} not found")
    return text[:start] + block + text[stop + len(end):]


def _current(text: str, begin: str, end: str) -> str:
    start = text.find(begin)
    stop = text.find(end)
    if start == -1 or stop == -1 or stop < start:
        raise SystemExit(f"markers {begin!r}/{end!r} not found")
    return text[start: stop + len(end)]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    mode = parser.add_mutually_exclusive_group()
    mode.add_argument("--check", action="store_true",
                      help="exit 1 when the manifest or README drifted "
                           "from the static scan")
    mode.add_argument("--write", action="store_true",
                      help="rewrite the manifest and README blocks in "
                           "place")
    args = parser.parse_args(argv)

    entries = scanned_entries()
    inv_block = render_inventory_block(entries)
    md_block = render_readme_block(entries)
    if not (args.check or args.write):
        print(inv_block)
        print()
        print(md_block)
        return 0

    stale = []
    manifest_text = MANIFEST.read_text()
    if _current(manifest_text, INV_BEGIN, INV_END) != inv_block:
        stale.append(str(MANIFEST.relative_to(REPO)))
    readme_text = README.read_text()
    if _current(readme_text, MD_BEGIN, MD_END) != md_block:
        stale.append("README.md")

    if args.check:
        if stale:
            print(
                f"program inventory drifted in: {', '.join(stale)}; run "
                "`python scripts/gen_program_inventory.py --write`",
                file=sys.stderr,
            )
            return 1
        print(f"program inventory up to date ({len(entries)} entries)")
        return 0

    if stale:
        MANIFEST.write_text(
            _splice(manifest_text, INV_BEGIN, INV_END, inv_block)
        )
        README.write_text(_splice(readme_text, MD_BEGIN, MD_END, md_block))
        print(f"rewrote: {', '.join(stale)}")
    else:
        print("program inventory already up to date")
    return 0


if __name__ == "__main__":
    sys.exit(main())
