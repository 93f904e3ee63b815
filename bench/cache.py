"""Compile cache and compile counter of the benchmark.

The persistent compilation cache lives at ``.bench_cache/jax`` under the
root of the checkout the benchmark runs from.  The path is fixed because it
is part of every entry's key: a directory that moved would never hit.  It
is set before JAX is imported, and replaces any ``JAX_COMPILATION_CACHE_DIR``
the environment gives, so that two checkouts on one machine share nothing.
Every program is cached, however quick its compile, so that a second run of
a cell compiles nothing.

``CompileCounter`` listens to ``jax.monitoring``: a backend compile, or a
program read back from the persistent cache, is one count each.  Either
means a program was not in the process's own cache when it was called.
"""
from __future__ import annotations

import os
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".bench_cache" / "jax"

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at the checkout; call before any JAX
    computation.  Returns the directory."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileCounter:
    """Counts backend compiles and persistent-cache reads."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_reads = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            self.cache_reads += 1

    @property
    def programs(self) -> int:
        return self.compiles + self.cache_reads
