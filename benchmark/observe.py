"""What a traced run hands to the per-layer metric readers.

A reader (``layer_metrics/<name>.py``) is ``read(obs) -> float | None``: it
takes its number from the spans, the counters, the compile listener, the
device trace or the job's shapes, and returns None when there is nothing to
read (the harness then leaves the metric out of the line).
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass
class SpanRecord:
    name: str
    start: float  # perf_counter seconds
    end: float
    attrs: dict


class SpanCollector:
    """An in-memory sink for the program's ``obs.span`` events."""

    def __init__(self) -> None:
        self.spans: List[SpanRecord] = []

    def handle(self, event) -> None:
        span = getattr(event, "span", None)
        if span is not None and span.duration_s is not None:
            self.spans.append(
                SpanRecord(span.name, span.start_perf, span.start_perf + span.duration_s,
                           dict(span.attrs))
            )

    def close(self) -> None:
        pass


@dataclasses.dataclass
class Observations:
    fit_windows: List[Tuple[float, float]]  # (start, end) of each traced fit, perf_counter
    spans: List[SpanRecord]
    counters: List[dict]  # registry snapshot of the traced part (fresh registry)
    listener: object  # CompileListener
    setup_spans: Dict[str, float]
    job: object
    peak: dict  # this device's entry of peaks.json
    chips: int
    memory_peak_bytes: int
    trace: Optional[object] = None  # trace.DeviceTrace on the perf_counter clock

    # -- helpers the readers share -------------------------------------------

    @property
    def n_fits(self) -> int:
        return len(self.fit_windows)

    @property
    def traced_window(self) -> Tuple[float, float]:
        """From the start of the first traced fit to the end of the last."""
        return (self.fit_windows[0][0], self.fit_windows[-1][1])

    def kernel_roofline(self, kernel: str, bytes_fn, flops_fn) -> Optional[float]:
        """Roofline share (%) of the fixed-effect kernel whose events contain
        ``kernel``: per-chip bytes and flops of one call (rows are sharded over
        the chips) against the kernel's mean device time per call."""
        if self.trace is None or not self.fit_windows:
            return None
        from . import shapes, trace

        seconds, calls = trace.kernel_seconds(self.trace, kernel, self.traced_window)
        if not calls:
            return None
        x = self.job.datasets[self.job.config["fixed_effect"]["name"]].batch.features.dense
        n, d = x.shape[0] // self.chips, x.shape[1]
        return shapes.roofline_share(
            bytes_fn(n, d, x.dtype.itemsize), flops_fn(n, d), seconds / calls, self.peak
        )["share"]

    def spans_named(self, name: str, **attrs) -> List[SpanRecord]:
        return [
            s for s in self.spans
            if s.name == name and all(s.attrs.get(k) == v for k, v in attrs.items())
        ]

    def median_span_s(self, name: str, **attrs) -> Optional[float]:
        found = self.spans_named(name, **attrs)
        return statistics.median(s.end - s.start for s in found) if found else None

    def counter_total(self, name: str, **labels) -> float:
        return sum(
            m.get("value", 0.0) for m in self.counters
            if m["name"] == name and all(m["labels"].get(k) == v for k, v in labels.items())
        )

    def summary_mean(self, name: str, **labels) -> Optional[float]:
        for m in self.counters:
            if m["name"] == name and all(m["labels"].get(k) == v for k, v in labels.items()):
                return m["stat"]["mean"] if m["stat"]["count"] else None
        return None
