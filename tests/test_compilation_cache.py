"""Where the persistent compile cache lives (utils/compilation.py): the
environment's directory if it names one — and then no other path is set in
code — else one fixed directory inside the checkout, the same in every
process."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = """
import json, os
import jax
from distributed_lms_raft_llm_tpu.utils import compilation
path = compilation.enable_compilation_cache()
print(json.dumps({
    "returned": path,
    "env": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
    "jax": jax.config.jax_compilation_cache_dir,
    "stats": compilation.cache_stats(),
    "again": compilation.enable_compilation_cache(),
}))
"""


def _probe(tmp_path, cache_env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run(
        [sys.executable, "-c", PROBE], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=120, check=True,
    ).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_env_var_wins_and_is_not_overwritten(tmp_path):
    want = str(tmp_path / "from_env")
    got = _probe(tmp_path, want)
    assert got["returned"] == got["again"] == want
    assert got["env"] == want, "the program must not rewrite the variable"
    assert got["jax"] == want, "JAX must cache where the environment says"
    assert got["stats"]["dir"] == want
    assert os.path.isdir(want)


def test_unset_gives_one_fixed_in_checkout_path_in_every_process(tmp_path):
    fixed = os.path.join(REPO, ".jax_cache")
    other = tmp_path / "elsewhere"
    other.mkdir()
    a, b = _probe(tmp_path, None), _probe(other, None)
    for got in (a, b):
        assert got["returned"] == got["jax"] == got["again"] == fixed
        assert got["env"] is None
    assert "~" not in fixed and str(os.getpid()) not in fixed


def test_gitignore_covers_the_in_checkout_cache():
    with open(os.path.join(REPO, ".gitignore")) as fh:
        ignored = {line.strip() for line in fh}
    assert ".jax_cache/" in ignored
    assert "chiprun_out/" in ignored


def test_the_test_run_itself_caches_outside_the_checkout():
    """tests/conftest.py points this run's cache away from the tree."""
    path = os.path.realpath(os.environ["JAX_COMPILATION_CACHE_DIR"])
    assert not path.startswith(os.path.realpath(REPO) + os.sep)
