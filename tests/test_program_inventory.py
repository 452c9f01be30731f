"""The compiled-program inventory: static manifest <-> runtime caches.

Three claims, each pinned:

- the checked-in manifest and README table match what the generator
  derives from the tree (drift fails tier-1, same scheme as the metrics
  table);
- after `warmup()`, a live paged session under
  `compile_count_guard(expected_from_inventory(eng))` compiles nothing
  and every inventoried program's cache size EQUALS the manifest's
  expectation — the acceptance path;
- both drift directions raise: skipping warmup (uncovered programs
  compile live) and a stale expectation (manifest counts the engine
  doesn't have).
"""

import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    SamplingParams,
    TutoringEngine,
)
from distributed_lms_raft_llm_tpu.engine import program_inventory as inv
from distributed_lms_raft_llm_tpu.utils.guards import (
    InventoryMismatchError,
    RecompileError,
    compile_count_guard,
    expected_from_inventory,
)

REPO = Path(__file__).resolve().parent.parent


def make_engine(**kw):
    kw.setdefault("length_buckets", (4, 16))
    return PagedEngine(
        EngineConfig(
            model="tiny",
            sampling=SamplingParams.greedy(max_new_tokens=8),
            batch_buckets=(1, 2),
            dtype=jnp.float32,
            **kw,
        ),
        slots=2, chunk=2,
    )


# ----------------------------------------------------- generated artifacts


def test_manifest_and_readme_match_static_scan():
    """scripts/gen_program_inventory.py --check: the INVENTORY block and
    the README program-inventory table are regenerated and compared."""
    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "gen_program_inventory.py"),
         "--check"],
        capture_output=True, text=True, cwd=str(REPO), timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr


def test_manifest_covers_the_paged_program_set():
    attrs = {e.attr for e in inv.entries_for("PagedEngine")}
    assert attrs == {"_megastep", "_grow", "_export_block",
                     "_stage", "_stage_block", "_score",
                     "_restore_state", "_export_state", "_export_run"}
    assert all(
        e.coverage == "warmup" for e in inv.entries_for("PagedEngine")
    ), "the paged engine's whole program set is a warmup promise"
    # The bulk-scoring program is a warmup promise (domain empty when
    # EngineConfig.scoring is off), bound once: the reference generator
    # serves nothing and has no rows.
    score = [e for e in inv.entries_for("PagedEngine")
             if e.attr == "_score"]
    assert score and score[0].domain == "score-pairs"
    assert inv.entries_for("TutoringEngine") == []


def test_static_domain_math_is_engine_math():
    """static_paged_domain mirrors PagedEngine.__init__'s derivation for
    representative configs (incl. spec-mode overhang and bucket capping)."""
    for spec_tokens, buckets in ((0, (4, 16)), (3, (4, 8, 16)), (2, (16,))):
        eng = make_engine(length_buckets=buckets, spec_tokens=spec_tokens)
        dom = inv.static_paged_domain(
            eng.cfg.max_position_embeddings,
            eng.config.sampling.max_new_tokens,
            buckets, spec_tokens,
        )
        assert dom["widths"] == list(eng.widths)
        assert max(dom["buckets"]) <= eng.bucket
    # The shared-prefix domain: zero with the cache off, and where no
    # bucket can hold a block; a program a width with it on.
    off = inv.static_paged_domain(64, 8, (8, 16), 0)
    assert off["stage_block_widths"] == off["export_widths"] == 0
    assert off["stage_pairs"] == 3    # (8,16) (8,24) (16,24)
    on = inv.static_paged_domain(64, 8, (8, 16), 0, prefix_cache=True,
                                 prefix_block_tokens=4)
    assert on["export_widths"] == 2       # a block leaves either width
    assert on["stage_block_widths"] == 2  # and is spliced at either
    none = inv.static_paged_domain(64, 8, (8, 16), 0, prefix_cache=True,
                                   prefix_block_tokens=32)
    assert none["stage_block_widths"] == none["export_widths"] == 0
    # One megastep program per width and rung, rung 1 included.
    assert inv.static_paged_domain(
        64, 8, (8, 16), 0, megastep_max=4)["megastep_pairs"] == 2 * 3


@pytest.mark.parametrize("run_blocks,want", [
    # Nothing is a run long: the programs of the tree before stored runs.
    (0, (2, 2, 0)), (128, (2, 2, 0)), (7, (2, 2, 0)),
    # The 24 bucket's prompts are a run of 6 blocks (24 tokens) long: the
    # 32-wide cache holds one and exports, splices and cuts it; the 16-wide
    # cannot be handed one, and gets its blocks cut out of it.
    (6, (3, 3, 1)),
    # A run of 3 blocks fits either width.
    (3, (4, 3, 2)),
], ids=["off", "shipped_length", "a_block_too_long", "widest_only",
        "every_width"])
def test_stored_runs_are_counted_where_a_width_holds_one(run_blocks, want):
    """The stored run's splice (`_stage_block`, one more program), its
    export (`_export_run`) and the cut of a block out of it
    (`_export_block`, one program whatever the width) are in the domain
    for every width that holds a run, where some bucket is a run long."""
    dom = inv.static_paged_domain(64, 8, (8, 24), 0, prefix_cache=True,
                                  prefix_block_tokens=4,
                                  stored_run_blocks=run_blocks)
    assert dom["widths"] == [16, 32]
    assert (dom["stage_block_widths"], dom["export_widths"],
            dom["run_export_widths"]) == want
    assert inv.static_paged_domain(
        64, 8, (8, 24), 0, stored_run_blocks=run_blocks)[
            "run_export_widths"] == 0          # no tree, no program
    assert inv.width_holds_stored_run(32, 4, 6)
    assert not inv.width_holds_stored_run(16, 4, 6)
    assert not inv.width_holds_stored_run(4096, 16, 0)
    assert inv.bucket_holds_stored_run(24, 4, 6)
    assert not inv.bucket_holds_stored_run(23, 4, 6)


def test_the_shipped_cells_count_their_stored_run_programs():
    """`minicpm-sala.reader-herd`'s widths (768 and 33,536): three programs
    more than before stored runs, all at the wide cache; a notes cell's
    2,560 bucket is a run long too; `gpt2-xl.deadline-herd` has the
    programs it had."""
    sala = inv.static_paged_domain(33536 + 512, 512, (256, 33024), 0,
                                   megastep_max=8, prefix_cache=True,
                                   recurrent_state=True)
    assert sala["widths"] == [768, 33536]
    assert (sala["stage_block_widths"], sala["export_widths"],
            sala["run_export_widths"]) == (5, 3, 1)
    before = inv.static_paged_domain(33536 + 512, 512, (256, 33024), 0,
                                     megastep_max=8, prefix_cache=True,
                                     recurrent_state=True,
                                     stored_run_blocks=0)
    assert (before["stage_block_widths"], before["export_widths"],
            before["run_export_widths"]) == (4, 2, 0)
    xl = inv.static_paged_domain(1024, 128, (256,), 0, prefix_cache=True)
    assert xl == inv.static_paged_domain(1024, 128, (256,), 0,
                                         prefix_cache=True,
                                         stored_run_blocks=0)
    assert inv.stage_runs(3, 2096, start=2048) == [(0, 3)]
    assert inv.stage_runs(20, 40, start=17) == [(0, 16), (7, 13)]
    assert inv.stage_runs(3, 18, start=8) == [(0, 1), (1, 1), (2, 1)]


@pytest.mark.parametrize("preset,recurrent", [
    ("axk1-tiny", False), ("nemotronh-tiny", True),
    ("kimilinear-tiny", True), ("lfm2-tiny", True)])
def test_a_family_adds_no_program_of_its_own(preset, recurrent):
    """Whatever planes a family's cache declares (a latent plane, state
    planes, both: `kimi_linear`; a window plane alone: `lfm2_moe`), its
    engine's programs are the
    inventory's: the snapshot programs count a width each for a recurrent
    family and zero for the others, and nothing else differs."""
    from distributed_lms_raft_llm_tpu.models import registry

    family, _ = registry.PRESETS[preset]
    assert family.recurrent_state == recurrent
    both = inv.static_paged_domain(64, 8, (8, 16), 0, prefix_cache=True,
                                   prefix_block_tokens=4,
                                   recurrent_state=family.recurrent_state)
    plain = inv.static_paged_domain(64, 8, (8, 16), 0, prefix_cache=True,
                                    prefix_block_tokens=4)
    assert both["state_widths"] == (2 if recurrent else 0)
    assert {k: v for k, v in both.items() if k != "state_widths"} == {
        k: v for k, v in plain.items() if k != "state_widths"}
    if preset == "kimilinear-tiny":
        assert family.latent_cache and family.recurrent_state


# ------------------------------------------------- runtime cross-validation


def test_warmed_paged_session_passes_inventory_guard():
    """The acceptance path: warmup compiles exactly the inventoried
    domain, then a live session (two widths, slot churn) adds nothing."""
    eng = make_engine()
    eng.warmup()
    expectation = expected_from_inventory(eng)
    # The static counts ARE the live caches post-warmup...
    assert expectation.mismatches() == {}
    # ...and stay so through a live session.
    with compile_count_guard(expectation) as guard:
        eng.submit("k v")
        eng.step()
        eng.submit("a longer question about raft elections and logs")
        eng.drain()
    assert guard.new_compiles() == 0


def test_missing_warmup_fails_the_inventory_guard():
    """Removing warmup coverage the static rule can't see (warmup still
    REACHES every program, it just compiles fewer shapes) is the runtime
    guard's half: an unwarmed engine compiles live and the guard raises."""
    eng = make_engine()  # no warmup()
    with pytest.raises(RecompileError):
        with compile_count_guard(expected_from_inventory(eng)):
            eng.submit("hello")
            eng.drain()


def test_stale_inventory_expectation_fails_the_guard():
    """The other drift direction: the manifest expecting MORE programs
    than the engine compiles (a stale entry/domain) fails at guard exit."""
    eng = make_engine()
    eng.warmup()
    expectation = expected_from_inventory(eng)
    expectation.expected["_megastep"] += 1  # simulate a stale manifest claim
    with pytest.raises(InventoryMismatchError, match="stale"):
        with compile_count_guard(expectation):
            pass


def test_inventory_guard_rejects_unlisted_engines():
    """expected_from_inventory only makes sense for engines whose warmup
    promises full coverage; the bucketed engine compiles per live shape
    by design and must be rejected loudly, not guarded wrongly."""
    eng = TutoringEngine(EngineConfig(
        model="tiny", sampling=SamplingParams.greedy(max_new_tokens=4),
        length_buckets=(8,), batch_buckets=(1,), dtype=jnp.float32,
    ))
    with pytest.raises(InventoryMismatchError, match="warmup-covered"):
        expected_from_inventory(eng)
