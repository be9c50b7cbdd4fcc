"""The benchmark's own listener on jax's monitoring events, by EXACT name.

``photon_ml_tpu/utils/compile_cache.py`` sums every duration event whose name
contains "compile" -- trace, lowering, backend compile and even
``compile_time_saved_sec`` (time saved, added as time spent) -- so its total
cannot tell a cold compile from a re-trace answered by the persistent cache.
Here each event is counted under its own name and phase:

- a *re-trace* is one ``/jax/core/compile/jaxpr_trace_duration`` event: the
  host traced a function again (a fresh closure, a new shape);
- a *compile* is a ``/jax/core/compile/backend_compile_duration`` event that
  was NOT answered by ``/jax/compilation_cache/cache_hits``: XLA built a new
  program. jax emits the backend event around ``compile_or_get_cached``, so a
  persistent-cache hit fires both and counts as a re-trace, not a compile.
"""

from __future__ import annotations

import collections
from typing import Dict

TRACE = "/jax/core/compile/jaxpr_trace_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileListener:
    """Counts and seconds per (phase, event). ``phase`` is whatever the
    harness last set: "setup", "warm" (the second warm-up fit), "window"."""

    def __init__(self) -> None:
        self.phase = "setup"
        self.counts: Dict[tuple, int] = collections.Counter()
        self.seconds: Dict[tuple, float] = collections.Counter()

    def install(self) -> "CompileListener":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def on_duration(self, event: str, duration: float, **_kw) -> None:
        if event in (TRACE, BACKEND, RETRIEVAL):
            self.counts[self.phase, event] += 1
            self.seconds[self.phase, event] += float(duration)

    def on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT:
            self.counts[self.phase, event] += 1

    def retraces(self, phase: str) -> int:
        return self.counts[phase, TRACE]

    def compiles(self, phase: str) -> int:
        """Backend compiles of NEW programs: not answered by the cache."""
        return self.counts[phase, BACKEND] - self.counts[phase, CACHE_HIT]

    def backend_seconds(self, phase: str) -> float:
        """Backend compile seconds, cache retrieval included (jax times the
        backend event around the cache lookup)."""
        return self.seconds[phase, BACKEND]
