"""Persistent XLA compilation cache: the ONE place this repository
points JAX at a cache directory.

A relaunched worker (preemption, scale-up, the next chip-tool call on a
machine that kept its disk) compiles each byte-identical program once
and restores it from disk thereafter.

Where the directory comes from, in order:

- ``$JAX_COMPILATION_CACHE_DIR`` — JAX's own variable. JAX reads it at
  import (``jax.config.jax_compilation_cache_dir`` shows it), it is what
  an operator or the chip machine sets, and when it is set nothing in
  the program names another directory.
- otherwise ``DEFAULT_DIR``, a fixed git-ignored path inside the
  checkout. Fixed because a cache that moves between runs never hits;
  never a temporary name, a pid or a time.

Every entry point that wants the cache (``Trainer.train``, the fleet
replica process, the load generator, ``bench.py``, ``chip_smoke.py``)
calls ``enable()``. ``distributed.elastic.supervise`` hands its
children the directory through the same standard variable
(``child_env``), so the supervisor itself never imports jax.

The key of an entry covers the program's op metadata too (named
scopes, source lines): ``enable()`` turns on
``jax_compilation_cache_include_metadata_in_key`` unless
``$JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY`` is set, in which case
the operator's value stands. jax's default leaves metadata out, and a
program whose ``jax.named_scope`` names alone changed is then restored
from disk with its OLD names: a profiler trace read by scope
(``obs.TICK_SCOPES``, docs/OBSERVABILITY.md section 7) would put the
device's time under names the tree no longer has. The price is that the
same program traced from another call site is another entry; the test
suite, which wants exactly that sharing and reads no names off a
compiled program, sets the variable to false (tests/conftest.py).

``entries()`` lists the cache's program keys (the ``*-cache`` payload
files, not the ``-atime`` access-time markers) so tests and tools can
assert "the second startup hit the cache" by set equality on keys —
population, not wall time.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

__all__ = ["ENV_VAR", "META_ENV_VAR", "DEFAULT_DIR", "enable", "active_dir",
           "resolve_dir", "entries", "child_env"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
META_ENV_VAR = "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_lock = threading.Lock()


def resolve_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    in-checkout ``DEFAULT_DIR``."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def enable(min_compile_time_s: Optional[float] = None) -> str:
    """Turn the persistent cache on at ``resolve_dir()`` and return that
    directory. Idempotent and cheap; safe to call every ``train()``.
    ``min_compile_time_s`` gates trivial programs out of the cache;
    None keeps jax's own setting (1.0s unless
    ``$JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise — a
    train step or a serving tick is far above it, per-op jits mostly
    below). Op metadata goes into the cache key unless
    ``$JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY`` says otherwise
    (module docstring)."""
    cache_dir = resolve_dir()
    import jax
    with _lock:
        os.makedirs(cache_dir, exist_ok=True)
        if jax.config.jax_compilation_cache_dir != cache_dir:
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        if min_compile_time_s is not None:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs",
                float(min_compile_time_s))
        if META_ENV_VAR not in os.environ:
            jax.config.update(
                "jax_compilation_cache_include_metadata_in_key", True)
        _reset_latched_cache(cache_dir)
    return cache_dir


def _reset_latched_cache(cache_dir: str) -> None:
    """jax builds its cache object AT MOST ONCE, on the first compile
    that finds a directory configured. If that object points somewhere
    other than ``cache_dir`` (the directory changed after a compile),
    reset it so the next compile re-initializes against ``cache_dir``."""
    # the _src module, not the jax.experimental re-export: the latter's
    # module-level ints/bools are frozen at its import
    from jax._src import compilation_cache as cc
    if cc._cache_initialized and \
            str(getattr(cc._cache, "_path", None)) != cache_dir:
        cc.reset_cache()


def active_dir() -> Optional[str]:
    """The directory jax is currently caching into (None if disabled)."""
    import jax
    return jax.config.jax_compilation_cache_dir or None


def entries(cache_dir: Optional[str] = None) -> List[str]:
    """Sorted program keys currently in ``cache_dir`` (default: the
    active directory); payload files only — ``-atime`` access markers
    are bookkeeping, not programs."""
    d = cache_dir or active_dir()
    if not d or not os.path.isdir(d):
        return []
    return sorted(f for f in os.listdir(d) if not f.endswith("-atime"))


def child_env(cache_dir: Optional[str] = None,
              base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Environment for a worker process. The cache directory rides
    JAX's own variable, which the child's jax reads at import: the
    parent's ``$JAX_COMPILATION_CACHE_DIR`` when set (it wins over the
    argument, as everywhere), else ``cache_dir`` when given, else
    nothing — the child's own ``enable()`` then resolves the default."""
    env = dict(os.environ if base is None else base)
    d = os.environ.get(ENV_VAR) or cache_dir
    if d:
        env[ENV_VAR] = d
    return env
