"""JAX's persistent compilation cache, and compile accounting.

Every program the engine runs is the whole model, compiled once per shape
class, so a cold start is mostly compile time.  :func:`enable` turns on
the persistent cache before the first compile:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
  is set here;
* otherwise the cache lives at a fixed path in the checkout,
  ``<checkout>/.jax_cache`` (git-ignored).  The path is part of each
  entry's key, so it is never built from a temp name, a pid or the time.

:class:`CompileStats` counts backend compiles (a persistent-cache hit is
one too, only fast) and their seconds, from ``jax.monitoring`` events.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (this file is ``src/repro/launch/...``)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


class CompileStats:
    """Running totals of backend compiles seen by this process (one
    instance per process: :func:`stats`)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.count, self.seconds, self.cache_hits

    def since(self, snap: tuple) -> str:
        n, s, h = self.count - snap[0], self.seconds - snap[1], self.cache_hits - snap[2]
        return f"{n} compiles in {s:.1f} s ({h} served by the persistent cache)"


_STATS: CompileStats | None = None


def stats() -> CompileStats:
    """The process-wide :class:`CompileStats`, listening from first call."""
    global _STATS
    if _STATS is None:
        _STATS = CompileStats()
    return _STATS
