"""CPU rehearsal of chip_smoke.py: the same launcher, servers, client calls
and checks as the chip run, at tiny models with the servers held to the
CPU — so a wrong path, argument or control flow is found here, not on the
chip. Also pins what the script promises about itself: the parent stays
off JAX, every child is reaped, nothing lands in the checkout, and without
a TPU the real command says `"ok": false`."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The production config's phases at sizes a CPU compiles in seconds: tiny
# models, a short answer budget (tiny's position table holds 64), a short
# megastep ladder and no scoring-tenant warm-up inventory.
REHEARSAL = """
import json, sys
import chip_smoke
device = chip_smoke.served_path(
    jax_platform="cpu", bpe_vocab=False, start_timeout_s=240,
    overrides={
        "tutoring": {"model": "tiny", "megastep": 2, "megastep_max": 2,
                     "slots": 4},
        "gate": {"model": "tiny"},
        "sampling": {"max_new_tokens": 16},
        "scoring": {"enabled": False},
    },
)
print(json.dumps({"device": device, "jax_imported": "jax" in sys.modules}))
"""


def _tree():
    """Files of the checkout, minus what any Python run or the compile
    cache may add."""
    skip = {".git", ".jax_cache", "__pycache__", ".pytest_cache",
            "chiprun_out"}
    out = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip]
        out.update(os.path.join(root, f) for f in files)
    return out


def _lines(stdout):
    return [json.loads(line) for line in stdout.splitlines()
            if line.startswith("{")]


def test_rehearsal_serves_checks_and_cleans_up():
    before = _tree()
    env = dict(os.environ, PYTHONPATH=REPO)
    run = subprocess.run(
        [sys.executable, "-c", REHEARSAL], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert run.returncode == 0, run.stdout[-3000:] + run.stderr[-3000:]
    lines = _lines(run.stdout)
    last = lines[-1]
    assert last["jax_imported"] is False
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    by_phase = {doc["smoke"]: doc for doc in lines if "smoke" in doc}
    assert by_phase["tutoring_start"]["engine"] == "PagedEngine"
    assert by_phase["tutoring_start"]["programs_compiled"] > 0
    assert by_phase["requests"]["unary"] == 6
    assert by_phase["requests"]["streamed"] == 2
    assert by_phase["tutoring_after"]["tutoring_requests"] >= 8
    procs = by_phase["processes"]
    assert procs["parent_imported_jax"] is False
    assert procs["parent_loaded_libtpu"] is False
    assert procs["libtpu_loaded_by"] == []  # CPU servers never load it
    assert sorted(procs["pids"]) == ["lms1", "lms2", "lms3", "tutoring"]
    for name, pid in procs["pids"].items():
        assert not os.path.exists(f"/proc/{pid}"), f"{name} ({pid}) lives on"
    assert _tree() == before, sorted(_tree() ^ before)


def test_without_a_tpu_the_real_command_fails(tmp_path):
    """`python chip_smoke.py` as the driver runs it: here JAX finds no
    accelerator, so the tutoring server refuses to start and the verdict is
    `"ok": false` — never an answer from the CPU."""
    before = _tree()
    run = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=240,
    )
    assert run.returncode != 0
    last = json.loads(run.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"] is None
    assert "no TPU" in last["error"]
    assert not any(doc.get("ok") for doc in _lines(run.stdout))
    assert _tree() == before


def test_alone_in_a_directory_the_command_fails(tmp_path):
    """chip_smoke.py is no stand-alone program: without the repo around it
    there is nothing to start, and it says so."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    run = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=str(tmp_path), env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert run.returncode != 0
    last = json.loads(run.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
