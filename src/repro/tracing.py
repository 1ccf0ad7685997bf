"""Program spans on the profiler's clock.

``span(name, **args)`` is a context manager over
``jax.profiler.TraceAnnotation``: while a profiler session runs
(``jax.profiler.start_trace``), the span lands in the same ``.xplane.pb``
as the device ops, on the same clock, so a device-idle gap can be set
against what the host was doing.  Every name starts with ``detlsh.``.

Compile work and garbage collection are charged to the innermost open
span of the thread that did them, and written as the span's args when it
closes:

  traces, trace_ms   jaxpr traces of a jitted function
  lower_ms           lowering of jaxprs to MLIR modules
  compiles, compile_ms
                     backend compiles, loads from the persistent compile
                     cache included (JAX times both as one compile)
  cache_hits         of those compiles, the loads from the cache
  gc_ms              time inside the garbage collector

With no profiler session a span pushes and pops its entry of the
thread's stack and does nothing else; events inside it are dropped.

A profile names each device op by the scope path (``tf_op``) that the
executable which ran it was compiled with, while the persistent compile
cache keys a program with its debug info, scopes included, stripped.  So
the cache key is salted with ``SCOPES``: an executable compiled from
source without these scopes (another checkout sharing the cache) is never
loaded under them.  Change it when a scope is added, moved or renamed.
"""

from __future__ import annotations

import gc
import threading
import time

import jax
from jax._src import cache_key as _cache_key
from jax.profiler import TraceAnnotation

PREFIX = "detlsh."

# monitoring event -> (count arg, milliseconds arg)
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": ("traces", "trace_ms"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": (None, "lower_ms"),
    "/jax/core/compile/backend_compile_duration": ("compiles", "compile_ms"),
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
ARGS = ("traces", "trace_ms", "lower_ms", "compiles", "compile_ms",
        "cache_hits", "gc_ms")

# every jax.named_scope of the program whose device time is read by name
SCOPES = "detlsh scopes 1: fold (core/query.py), assemble (core/detree.py)"

_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _innermost():
    """Counts of the innermost open span, or None (no span, or one opened
    with no profiler session)."""
    stack = getattr(_local, "stack", None)
    return stack[-1].args if stack else None


class span:
    """``with span("detlsh.search.dispatch", batch=64) as s: ...``;
    ``s.set(**args)`` adds args known only inside the span."""

    __slots__ = ("name", "args", "_ann")

    def __init__(self, name: str, **args):
        if not name.startswith(PREFIX):
            raise ValueError(f"span name {name!r} must start with {PREFIX!r}")
        self.name = name
        self.args = args

    def __enter__(self) -> "span":
        if TraceAnnotation.is_enabled():
            self.args.update(dict.fromkeys(ARGS, 0))
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        else:
            self.args = None
        _stack().append(self)
        return self

    def set(self, **args) -> None:
        if self.args is not None:
            self.args.update(args)

    def __exit__(self, *exc) -> None:
        _stack().pop()
        if self.args is not None:
            self._ann.set_metadata(**{
                k: round(v, 3) if isinstance(v, float) else v
                for k, v in self.args.items()})
            self._ann.__exit__(*exc)


def _on_duration(event: str, duration: float, **_) -> None:
    keys = _DURATIONS.get(event)
    counts = _innermost() if keys else None
    if counts is not None:
        count, ms = keys
        if count:
            counts[count] += 1
        counts[ms] += duration * 1e3


def _on_event(event: str, **_) -> None:
    counts = _innermost() if event == _CACHE_HIT else None
    if counts is not None:
        counts["cache_hits"] += 1


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _local.gc_start = (time.perf_counter()
                           if _innermost() is not None else None)
        return
    start = getattr(_local, "gc_start", None)
    counts = _innermost()
    if start is not None and counts is not None:
        counts["gc_ms"] += (time.perf_counter() - start) * 1e3


def _salted_cache_key(previous=_cache_key.custom_hook) -> str:
    return previous() + SCOPES


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
gc.callbacks.append(_on_gc)
_cache_key.custom_hook = _salted_cache_key
