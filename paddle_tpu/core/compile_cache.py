"""Persistent XLA compile cache, placed from outside.

A full-width program pays tens of seconds of XLA compile in every
process; JAX's persistent compilation cache pays it once per directory.
The rule for where that directory is, in this one place:

  * `JAX_COMPILATION_CACHE_DIR` set — JAX's own reading of it stands.
    Nothing here (or anywhere in the repo) sets a directory in code.
  * not set — the library leaves the cache off. The two chip entry
    points, `chip_smoke.py` and `benchmark/run.py`, call
    `enable_compile_cache()`,
    which turns it on at `CHECKOUT_CACHE_DIR`: one fixed directory inside
    the checkout, ignored by git. Never under `$HOME`, `$TMPDIR`, a pid
    or a time — the path is part of the cache key, so a directory that
    moves never hits.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
from jax.experimental.compilation_cache import compilation_cache as _jcc

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its
    directory. Call before the first compile of the process.

    Thresholds are zeroed so EVERY program qualifies: compiles span
    0.1 s to a minute, and a min-compile-time gate would silently keep
    the small ones out of warm starts."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _jcc.set_cache_dir(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax latches the cache state at the first compile of the process;
    # anything compiled before this call would leave it latched off
    _jcc.reset_cache()
    return active_cache_dir()


def active_cache_dir() -> Optional[str]:
    """The directory JAX's persistent cache writes to (None = off)."""
    return jax.config.jax_compilation_cache_dir or None


def cache_entry_count(path: Optional[str] = None) -> int:
    """Number of persisted executables in the cache dir (0 when off or
    not yet created): no new entries after a compile means it was warm."""
    path = path if path is not None else active_cache_dir()
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
