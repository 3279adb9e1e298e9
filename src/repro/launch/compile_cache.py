"""Persistent compilation cache for the launchers, placed from outside.

``JAX_COMPILATION_CACHE_DIR``, where set, is read by JAX itself and this
module sets no directory.  Otherwise the cache goes to
``<checkout>/.jax_cache``: a fixed path, because the directory is part of
what a later run must find again (a temporary or per-process path would
never hit).

Either way the cache key holds each program's metadata: its named scopes
and source lines.  JAX leaves them out by default, and a program loaded from
the cache then carries the metadata of whichever compile stored it, so a
profile would name its operations by another version's scopes.  Source
files are named relative to the checkout, so that the key does not depend
on where the checkout lies.

:class:`CompileStats` counts, while it is entered, the cache hits and
misses and the seconds spent in the backend compiler, so a launcher can
say whether a second run compiled anything.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax
from jax import monitoring

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_COMPILE = "/jax/core/compile/backend_compile_duration"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex", "^" + re.escape(f"{CHECKOUT}{os.sep}"))
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


class CompileStats:
    """Context manager counting compile-cache hits/misses and compile seconds."""

    def __init__(self):
        self.hits = 0
        self.misses = 0
        self.compile_s = 0.0

    def _on_event(self, event: str, **_kw) -> None:
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == _COMPILE:
            self.compile_s += seconds

    def __enter__(self) -> "CompileStats":
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc) -> None:
        monitoring.unregister_event_listener(self._on_event)
        monitoring.unregister_event_duration_listener(self._on_duration)

    def summary(self) -> str:
        return (
            f"compile {self.compile_s:.2f}s, cache {self.hits} hits / "
            f"{self.misses} misses"
        )
