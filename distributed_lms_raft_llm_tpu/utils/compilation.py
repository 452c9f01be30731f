"""Persistent XLA compilation cache setup.

A cold start compiles the whole warm-up inventory of the serving programs.
JAX can persist compiled executables keyed by HLO fingerprint; enabling it
once per process makes every warm restart skip straight to execution. The
reference has no analogue (PyTorch eager), so this is pure TPU-platform
work.

Where the cache lives: `JAX_COMPILATION_CACHE_DIR` if the environment names
one (JAX reads it itself; this module sets no other path then), else ONE
fixed directory inside the checkout, resolved from this package's own
location. The path is part of what makes a later start hit, so it never
depends on the home directory, the backend, a pid, a temp name or the time.
The in-checkout directory is git-ignored and must be empty when the tree is
copied to another machine: entries compiled on one host's CPU do not load
on another's. Test runs keep theirs outside the checkout (tests/conftest.py).
"""

from __future__ import annotations

import logging
import os
from typing import Dict

log = logging.getLogger(__name__)

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_REQUESTS_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_HITS_EVENT = "/jax/compilation_cache/cache_hits"

_enabled = False
_counts = {_REQUESTS_EVENT: 0, _HITS_EVENT: 0}


def _count_event(event: str, **_kwargs) -> None:
    if event in _counts:
        _counts[event] += 1


def cache_dir() -> str:
    """The directory `enable_compilation_cache` uses (no side effects)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def enable_compilation_cache() -> str:
    """Idempotently turn on JAX's on-disk compilation cache at
    `cache_dir()` and start counting its requests and hits."""
    global _enabled
    path = cache_dir()
    if _enabled:
        return path
    import jax

    os.makedirs(path, exist_ok=True)
    if path == DEFAULT_CACHE_DIR:  # else JAX has read the variable itself
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program that took non-trivial compile time; the decode
    # program is the one that matters and always clears this bar.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.monitoring.register_event_listener(_count_event)
    _enabled = True
    log.info("XLA compilation cache at %s", path)
    return path


def cache_stats() -> Dict[str, object]:
    """Compilations that consulted the persistent cache since it was
    enabled, and how many of them it answered (served on /healthz so a
    JAX-free parent can tell a cold start from a warm one)."""
    return {
        "dir": cache_dir(),
        "requests": _counts[_REQUESTS_EVENT],
        "hits": _counts[_HITS_EVENT],
    }
