"""JAX's persistent compilation cache, placed where it can be found again,
plus a running count of what compilation cost this process.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets no other path.  Otherwise the cache lives in ``.jax_cache/`` at the root
of the checkout: a fixed path, because the path is part of what makes a
later run hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_stats = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}
_listening = False


def _on_duration(event: str, seconds: float, **_) -> None:
    # Emitted once per XLA compilation; on a persistent-cache hit it spans
    # only the retrieval.
    if event == "/jax/core/compile/backend_compile_duration":
        _stats["compiles"] += 1
        _stats["compile_s"] += seconds


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _stats["cache_hits"] += 1


def enable_compile_cache() -> str:
    """Turn the persistent cache on (every program, however fast it
    compiles) and start counting compilations.  Returns the cache path."""
    global _listening
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path


def compile_stats() -> dict:
    """Compilations so far: count, seconds, and how many hit the cache."""
    return dict(_stats)
