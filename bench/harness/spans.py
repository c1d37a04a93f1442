"""Host spans the benchmark records around its calls into the program.
With tracing on, each span is also a ``jax.profiler.TraceAnnotation``
in the profiler's trace, on the same clock as the device's operations,
so idle gaps can be attributed."""
from __future__ import annotations

import contextlib
import statistics
import time
from collections import defaultdict


class Spans:
    """Durations per span name, kept in memory and read once the run
    ends."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.durations: dict[str, list[float]] = defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.durations[name].append(time.perf_counter() - t0)


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics (numpy's default rule), over every value given."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    if pos == lo:
        return xs[lo]
    return xs[lo] + (xs[lo + 1] - xs[lo]) * (pos - lo)


def occupancy(record: dict) -> dict:
    """Slots busy and pages live at each decode tick of a run: their
    50th and 95th percentiles and their maximum."""
    return {k: [percentile(v, 50), percentile(v, 95), max(v)]
            for k in ("tick_active", "tick_pages") if (v := record.get(k))}


def span_ms_p50(record: dict, name: str):
    """The metric the ``*_ms_p50.*`` readers give: the median of one
    span's durations in ms, with the count of calls; None without any."""
    d = record["spans"].get(name) or []
    if not d:
        return None
    return {"value": statistics.median(d) * 1e3, "unit": "ms",
            "calls": len(d)}
