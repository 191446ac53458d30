"""One place for the JAX API surface the codebase leans on.

Every ``shard_map`` / ``cost_analysis`` / persistent-cache / profiler-state
call site goes through these helpers instead of probing ``jax.<attr>``
itself, so a JAX upgrade changes exactly one file.

* :data:`shard_map` — ``jax.shard_map``.
* :func:`profiler_active` — whether a ``jax.profiler`` session is
  recording (``TraceMe.is_enabled()``).
* :func:`pvary` — mark a value device-varying over mesh axes inside
  ``shard_map`` (``jax.lax.pcast(..., to="varying")``).
* :func:`cost_analysis_dict` — ``Compiled.cost_analysis()`` as one flat
  dict (``{}`` where the backend reports nothing).
* :func:`enable_persistent_compile_cache` — wire up JAX's on-disk XLA
  compilation cache so a fresh process re-running an already-seen sweep
  skips the cold compiles. The directory is ``JAX_COMPILATION_CACHE_DIR``
  when that is set, else ``<checkout>/artifacts/xla_cache``
  (:func:`default_cache_dir`); :func:`persistent_cache_stats` counts its
  hits and misses through JAX's monitoring events.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax
from jax._src.lib import _profiler

shard_map = jax.shard_map
_TraceMe = _profiler.TraceMe

# <checkout>/src/repro/utils/jax_compat.py -> <checkout>
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def profiler_active() -> bool:
    """True while a ``jax.profiler`` session records (on any backend)."""
    return _TraceMe.is_enabled()


def pvary(x, axis_names):
    """Mark ``x`` device-varying over ``axis_names`` inside shard_map."""
    return jax.lax.pcast(x, axis_names, to="varying")


_PCACHE_STATS = {"hits": 0, "misses": 0}
_PCACHE_DIR: Optional[str] = None


def _pcache_event(event: str, **kwargs) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _PCACHE_STATS["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _PCACHE_STATS["misses"] += 1


def default_cache_dir() -> str:
    """Where :func:`enable_persistent_compile_cache` keeps executables
    when the caller names no directory: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``artifacts/xla_cache`` under the checkout. The
    fallback is anchored at this file, not the working directory, so
    every process of one checkout shares one cache (the directory is
    part of the cache key: a path that moves never hits)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return os.path.abspath(env)
    return os.path.join(_CHECKOUT, "artifacts", "xla_cache")


def enable_persistent_compile_cache(cache_dir: Optional[str] = None) -> str:
    """Persist XLA executables to ``cache_dir`` across processes
    (default :func:`default_cache_dir`).

    A second process running the same sweep (same shapes, configs, XLA
    flags) then loads each executable from disk instead of re-paying
    the cold compile — on the emulator scan that is seconds per
    compile-key group. Every entry-size / compile-time threshold is
    zeroed so the emulator's scan executables always qualify.

    Call it at process entry: JAX latches its cache-enabled decision at
    the first compilation, and the ``reset_cache()`` below re-opens it.
    Safe to call repeatedly (e.g. to move the directory). Returns the
    absolute cache dir."""
    global _PCACHE_DIR
    cache_dir = os.path.abspath(cache_dir or default_cache_dir())
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if _PCACHE_DIR is None:  # register the hit/miss listener once
        from jax._src import monitoring
        monitoring.register_event_listener(_pcache_event)
    from jax._src import compilation_cache
    compilation_cache.reset_cache()
    _PCACHE_DIR = cache_dir
    return cache_dir


def persistent_cache_stats() -> Dict[str, Any]:
    """{'hits': n, 'misses': n, 'dir': path-or-None} for the on-disk
    XLA compilation cache (all-zero/None until
    :func:`enable_persistent_compile_cache` ran). A hit means an XLA
    compile was skipped by loading the executable from disk."""
    return {**_PCACHE_STATS, "dir": _PCACHE_DIR}


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as one flat {metric: value} dict."""
    return dict(compiled.cost_analysis() or {})
