"""Persistent XLA compilation cache for the entry-point scripts.

``chip_smoke.py``, ``benchmarks/run.py`` and the examples call
:func:`enable` once, before their first compile, so a second run on the
same machine loads its executables instead of compiling them again.
Importing this module changes nothing: the library and the tests never
turn the cache on.

Where the cache lives:

  * ``JAX_COMPILATION_CACHE_DIR`` set -> JAX already reads it; that
    directory is used and no other is set here;
  * otherwise -> ``<checkout>/.jax_cache``.  The path is part of each
    entry's key, so it is fixed (never a temp name, PID or time).
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_hits = 0


def _count_hit(event: str, **_) -> None:
    global _hits
    if event == _HIT_EVENT:
        _hits += 1


def enable() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.monitoring.register_event_listener(_count_hit)
    return path


def hits() -> int:
    """Executables loaded from the cache since :func:`enable`."""
    return _hits
