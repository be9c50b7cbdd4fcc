"""Hierarchical span tracing with JAX-aware annotations.

Spans nest through a contextvar (so the tree survives generators and is
isolated per thread / async context), carry wall time and the ``root_id``
of their tree (what all spans of one ``GameEstimator.fit`` share), and pick
up two kinds of annotation:

- re-trace and compile accounting, fed by the jax monitoring hook
  (``utils.compile_cache.install_compile_metrics_hook``, installed when a
  run gets its first listener) by EXACT event name: a span whose body made
  jax trace a function again reports ``retraces`` / ``retrace_s``, one
  whose body reached the backend compiler (or its persistent cache) reports
  ``compile_s`` — host cost apart from execute cost;
- device-transfer byte counters (``add_device_fetch_bytes`` /
  ``add_device_put_bytes``), called at the known host<->device crossing
  points (tracker aggregation, streamed staging/collection), and the
  seconds of each blocking fetch (``record_device_fetch``).

Device work is dispatched asynchronously, so a host span around a device
phase times the enqueue. ``Span.sync(*arrays)`` fences the phase when a
sink is attached: the span then covers the phase's host AND device time
(``attrs["device"]``, split into ``enqueue_s`` before the fence and
``wait_s`` inside it), which is why a traced run is a per-layer run and
never an end-to-end timing. With no sink it does nothing.

Span exit emits a ``SpanEvent`` through the current run's EventEmitter, so a
raising sink cannot fail the traced code path; with no sinks the span is
pure host bookkeeping (a perf_counter pair and a dict).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import itertools
import threading
import time
from typing import Dict, Optional

from ..utils.events import Event
from . import run as _run

_ctx: contextvars.ContextVar = contextvars.ContextVar("photon_obs_span", default=None)
_ids = itertools.count(1)

# The two jax monitoring events spans account for, by exact name (every
# other "compile" event is trace/lowering detail or, for
# /jax/compilation_cache/compile_time_saved_sec, time SAVED and not spent).
JAXPR_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# process-wide (retraces, retrace seconds, backend compile seconds), fed by
# the jax monitoring hook; spans snapshot it on entry and take the delta
_compile_lock = threading.Lock()
_compile_totals = (0, 0.0, 0.0)

# Lane identity for multi-process timelines. obs must stay importable without
# jax, so the process index is pushed in from outside (cli.train stamps it
# from parallel.multihost after distributed init); single-process runs keep 0.
_process_index = 0

# Serving-fleet identity: which replica of an N-replica fleet this process
# is. Orthogonal to the jax process index (training shards, serving
# replicates); ``cli serve --replica-id`` pushes it in, spans and JSONL
# lines stamp it, and the fleet aggregator keys per-replica gauges on it.
_replica_id: Optional[str] = None


def set_process_index(index: int) -> None:
    global _process_index
    # set once at startup (cli drivers stamp identity BEFORE any sink,
    # server or recorder thread exists); after that it is read-only, and
    # CPython reference assignment is atomic — a late reader sees the old
    # or the new index, never a torn value
    # photon: thread-confined
    _process_index = int(index)


def get_process_index() -> int:
    return _process_index


def set_replica_id(replica: Optional[str]) -> None:
    global _replica_id
    # same set-once-at-startup discipline as set_process_index above
    # photon: thread-confined
    _replica_id = None if replica is None else str(replica)


def get_replica_id() -> Optional[str]:
    return _replica_id


def add_retrace_seconds(seconds: float) -> None:
    """One jaxpr trace: the host traced a function again."""
    global _compile_totals
    with _compile_lock:
        n, trace_s, compile_s = _compile_totals
        _compile_totals = (n + 1, trace_s + float(seconds), compile_s)


def add_compile_seconds(seconds: float) -> None:
    """One backend compile (jax times it around the persistent-cache lookup,
    so a cache hit lands here too, as its retrieval time)."""
    global _compile_totals
    with _compile_lock:
        n, trace_s, compile_s = _compile_totals
        _compile_totals = (n, trace_s, compile_s + float(seconds))


def compile_seconds_total() -> float:
    return _compile_snapshot()[2]


def _compile_snapshot():
    with _compile_lock:
        return _compile_totals


@dataclasses.dataclass
class Span:
    name: str
    span_id: str
    parent_id: Optional[str]
    start_unix: float
    attrs: Dict[str, object]
    duration_s: Optional[float] = None
    # lane identity: which OS thread and which jax process ran this span
    thread_id: int = 0
    thread_name: str = ""
    process_index: int = 0
    # monotonic start (same clock as duration_s) — what the timeline
    # profiler aligns intervals on; start_unix is for humans and merging
    start_perf: float = 0.0
    # span_id of the tree's root, inherited through the parent: what all
    # spans of one fit (one request, ...) share. Also stamped into attrs on
    # close, for consumers that keep only name, times and attrs.
    root_id: str = ""

    def sync(self, *arrays) -> None:
        """Fence a device phase: with a sink attached, wait for ``arrays``
        so the span's duration covers the device work it dispatched, mark it
        ``device`` and split it into ``enqueue_s`` (span start to the first
        fence) and ``wait_s`` (inside fences). A wait, not a transfer —
        legal under the sweep's transfer guard. With no sink: nothing (the
        untraced program keeps its async dispatch)."""
        if not _run.current_run().has_listeners():
            return
        import jax

        fenced = time.perf_counter()
        jax.block_until_ready(arrays)
        waited = time.perf_counter() - fenced
        # the span's two halves: the host's own work up to its first fence
        # (cuts, tracing-cache look-ups, dispatch), and the seconds it then
        # stood waiting (summed if the span fences twice). A fenced phase
        # starts on a drained device, so wait_s is the device time the
        # enqueue did not cover
        self.attrs.setdefault("enqueue_s", fenced - self.start_perf)
        self.attrs["wait_s"] = self.attrs.get("wait_s", 0.0) + waited
        self.attrs["device"] = True


@dataclasses.dataclass(frozen=True)
class SpanEvent(Event):
    span: Span


def current_span() -> Optional[Span]:
    return _ctx.get()


def _root_id(parent: Optional[Span], span_id: str) -> str:
    if parent is None:
        return span_id
    return parent.root_id or parent.span_id  # hand-built parents carry none


@contextlib.contextmanager
def span(name: str, parent: Optional[Span] = None, **attrs):
    """Open a span named ``name``; nests under the current span if any.

    ``parent`` overrides the contextvar nesting — spans opened on worker
    threads (pipeline staging/eval lanes) have no ancestry there, so the
    lane owner passes the anchor span explicitly to keep the tree rooted
    under the sweep it serves."""
    if parent is None:
        parent = _ctx.get()
    span_id = f"s{next(_ids)}"
    s = Span(
        name=name,
        span_id=span_id,
        parent_id=parent.span_id if parent is not None else None,
        start_unix=time.time(),
        attrs=dict(attrs),
        thread_id=threading.get_ident(),
        thread_name=threading.current_thread().name,
        process_index=_process_index,
        root_id=_root_id(parent, span_id),
    )
    token = _ctx.set(s)
    retraces0, retrace0, compile0 = _compile_snapshot()
    t0 = time.perf_counter()
    s.start_perf = t0
    try:
        yield s
    finally:
        s.duration_s = time.perf_counter() - t0
        retraces1, retrace1, compile1 = _compile_snapshot()
        if retraces1 > retraces0:
            s.attrs["retraces"] = retraces1 - retraces0
            s.attrs["retrace_s"] = retrace1 - retrace0
        if compile1 > compile0:
            s.attrs["compile_s"] = compile1 - compile0
        s.attrs["root_id"] = s.root_id
        if _replica_id is not None:
            s.attrs.setdefault("replica", _replica_id)
        _ctx.reset(token)
        run = _run.current_run()
        if run.has_listeners():
            run.send_event(SpanEvent(span=s))


def record_span(
    name: str,
    start_perf: float,
    end_perf: float,
    parent: Optional[Span] = None,
    **attrs,
) -> Optional[Span]:
    """Emit an already-closed span from explicit ``perf_counter`` stamps.

    The serving microbatcher measures per-request stages across threads
    (enqueue on the caller, drain + score on the worker), so no context
    manager can bracket them; the worker reconstructs the stage intervals
    from the cross-thread stamps and emits them here, parented under the
    request's root span. Free when no sink is listening. ``start_unix`` is
    back-derived from the wall clock so stitched fleet timelines align."""
    run = _run.current_run()
    if not run.has_listeners():
        return None
    now_perf = time.perf_counter()
    span_id = f"s{next(_ids)}"
    s = Span(
        name=name,
        span_id=span_id,
        parent_id=parent.span_id if parent is not None else None,
        start_unix=time.time() - (now_perf - start_perf),
        attrs=dict(attrs),
        duration_s=max(0.0, float(end_perf) - float(start_perf)),
        thread_id=threading.get_ident(),
        thread_name=threading.current_thread().name,
        process_index=_process_index,
        start_perf=float(start_perf),
        root_id=_root_id(parent, span_id),
    )
    s.attrs["root_id"] = s.root_id
    if _replica_id is not None:
        s.attrs.setdefault("replica", _replica_id)
    run.send_event(SpanEvent(span=s))
    return s


def _add_transfer_bytes(direction: str, site: str, nbytes: int) -> None:
    nbytes = int(nbytes)
    _run.current_run().registry.counter(
        f"photon_device_{direction}_bytes_total",
        f"bytes transferred at instrumented device-{direction} sites",
    ).labels(site=site).inc(nbytes)
    s = _ctx.get()
    if s is not None:
        key = f"{direction}_bytes"
        s.attrs[key] = int(s.attrs.get(key, 0)) + nbytes


def add_device_fetch_bytes(site: str, nbytes: int) -> None:
    """Count a device->host fetch (nbytes is host-known: no extra sync)."""
    _add_transfer_bytes("fetch", site, nbytes)


def record_device_fetch(
    site: str, nbytes: int, start_perf: float, end_perf: float
) -> None:
    """Count a blocking device->host fetch: its bytes and the seconds it
    blocked. With no fence in the way (no sink), the seconds summed over
    sites are what the host stood waiting for the device. With a sink a
    fetch inside a span is also a closed ``fetch`` leaf under it (outside
    any span there is no tree to place the wait in: the serving worker's
    per-batch fetch stays a counter)."""
    add_device_fetch_bytes(site, nbytes)
    _run.current_run().registry.counter(
        "photon_device_fetch_seconds_total",
        "host seconds inside blocking device-fetch calls at instrumented sites",
    ).labels(site=site).inc(end_perf - start_perf)
    parent = _ctx.get()
    if parent is not None:
        record_span("fetch", start_perf, end_perf, parent=parent, site=site, bytes=int(nbytes))


def add_device_put_bytes(site: str, nbytes: int) -> None:
    """Count a host->device transfer."""
    _add_transfer_bytes("put", site, nbytes)
