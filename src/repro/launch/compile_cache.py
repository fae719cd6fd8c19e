"""JAX's persistent compilation cache, placed from outside, and a count
of what the compiler did.

``enable_compile_cache()`` is the one place that turns the cache on, for
every entry point that compiles at deployment size (``chip_smoke.py``,
``examples/ppo_atari.py``):

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
    module sets no other directory;
  * unset: ``<repo>/.jax_cache``.  The path is fixed (never a temporary
    name, a process id or a time) because a cache that moves never hits.

``CompileCounter`` listens to JAX's monitoring events for as long as it
is entered and counts persistent-cache hits and misses and the seconds
spent getting executables (compiling them, or loading them on a hit), so
a warm second run can be told from a cold first one.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    jax.config.update("jax_enable_compilation_cache", True)
    return jax.config.jax_compilation_cache_dir


class CompileCounter:
    """``with CompileCounter() as cc:`` counts ``cc.hits``/``cc.misses``
    of the persistent cache and sums ``cc.compile_s``, the seconds spent
    compiling or loading executables, over the block."""

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        self.compile_s = 0.0

    def _event(self, event: str, **kwargs) -> None:
        if event == _CACHE_HIT:
            self.hits += 1
        elif event == _CACHE_MISS:
            self.misses += 1

    def _duration(self, event: str, duration_secs: float, **kwargs) -> None:
        if event == _BACKEND_COMPILE:
            self.compile_s += duration_secs

    def __enter__(self) -> "CompileCounter":
        from jax import monitoring

        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc) -> None:
        from jax import monitoring

        monitoring.unregister_event_listener(self._event)
        monitoring.unregister_event_duration_listener(self._duration)

    def summary(self) -> dict:
        return {"compile_s": self.compile_s, "cache_hits": self.hits,
                "cache_misses": self.misses}


__all__ = ["CompileCounter", "REPO_CACHE_DIR", "enable_compile_cache"]
