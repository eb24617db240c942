"""JAX's persistent compilation cache, placed the same way by every entry point.

``enable_compile_cache()`` is called by ``chip_smoke.py``,
``repro.launch.enumerate`` and ``repro.launch.serve`` before their first
compile:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it; that
  directory is used and no other is configured;
* otherwise the cache lives at ``<repo>/.jax_cache`` (listed in
  ``.gitignore``). The path is fixed — never derived from a temporary name, a
  pid or the time — because it is part of the cache's key: a directory that
  moves never hits.

Every entry is kept, however quick its compile, so a second run of the same
program compiles nothing. ``compile_stats()`` reports cache hits and misses
and the seconds spent tracing, lowering and compiling, from JAX's own
monitoring events.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict

import jax
from jax import monitoring

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_stats = {"cache_hits": 0, "cache_misses": 0, "compile_s": 0.0}
_listening = False


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _stats["cache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _stats["cache_misses"] += 1


def _on_duration(event: str, secs: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _stats["compile_s"] += secs


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    global _listening
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not _listening:
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return path


def compile_stats() -> Dict[str, float]:
    """Cache hits, misses and compile seconds since the cache was enabled."""
    return dict(_stats)
