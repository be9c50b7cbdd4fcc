"""Persistent XLA compilation cache.

GLMix cold starts are compile-bound: CD iteration 0 pays one fresh
LBFGS/TRON compile per (K, S) entity-block bucket plus the fixed-effect
solves, and the serving engine one per ladder rung. The JAX persistent
compilation cache carries those executables across processes.

Placement is decided outside the program: when ``JAX_COMPILATION_CACHE_DIR``
is set jax reads it by itself and nothing here touches the directory;
otherwise the cache lives at one fixed path inside the checkout,
``<repo>/.xla_cache`` (the path is part of the cache key, so it must not
move between runs).
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger("photon_ml_tpu")

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".xla_cache")


def enable_persistent_compilation_cache() -> str:
    """Make sure jax persists compiled programs; returns the directory in
    force. Called by every entry point (CLI drivers, the benchmark, examples,
    chip_smoke) before the first compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # persist every program, not only those past jax's 1 s default: in the
    # chip smoke on a v5e (PR 21) 118 of 124 programs compiled in under 1 s
    # and were 21 of the 47 compile seconds; at 0 the second run loaded all
    # of them and spent 1.1 s
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


_compile_hook_installed = False


def install_compile_metrics_hook() -> bool:
    """Best-effort: register a jax monitoring listener that feeds jax's
    compile events into the obs layer. Spans are fed by EXACT event name:
    one ``jaxpr_trace_duration`` is one re-trace (span ``retraces`` /
    ``retrace_s``), ``backend_compile_duration`` alone is compile time
    (span ``compile_s``); the ``photon_jax_compile_*`` registry series keep
    every event whose name contains "compile", each under its own name, so
    no sum ever mixes time spent with ``compile_time_saved_sec``.
    ``RunTelemetry.register_listener`` calls this with a run's first
    listener. Idempotent; returns True when the hook is (already)
    installed."""
    global _compile_hook_installed
    if _compile_hook_installed:
        return True
    try:
        from jax._src import monitoring
    except Exception as e:  # private API: degrade to no compile attribution
        from .. import obs

        obs.swallowed_error("compile_cache.monitoring_import")
        logger.info("jax monitoring hook unavailable: %s", e)
        return False

    from .. import obs

    def _on_duration(event: str, duration: float, **kwargs) -> None:
        if "compile" not in event:
            return
        if event == obs.tracing.JAXPR_TRACE_EVENT:
            obs.add_retrace_seconds(duration)
        elif event == obs.tracing.BACKEND_COMPILE_EVENT:
            obs.add_compile_seconds(duration)
        reg = obs.current_run().registry
        reg.counter(
            "photon_jax_compile_total", "XLA compile events by jax event name"
        ).labels(event=event).inc()
        reg.summary(
            "photon_jax_compile_seconds", "XLA compile seconds by jax event name"
        ).labels(event=event).observe(duration)

    try:
        monitoring.register_event_duration_secs_listener(_on_duration)
    except Exception as e:
        obs.swallowed_error("compile_cache.monitoring_register")
        logger.info("jax monitoring hook registration failed: %s", e)
        return False
    _compile_hook_installed = True
    return True
