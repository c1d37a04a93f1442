"""Metrics read from the serving engine's own spans: the recorder
(``repro.obs``) that ``ServeEngine`` keeps under its name, ``"serve"``
unless it is given another. The recorder outlives the engine, so a
reader finds what the measured stream recorded after the run. A program
without such a recorder gives nothing, and the readers return None.

Series (docs/serving.md, §observability): ``serve.tick`` and
``serve.admit``, each with the children ``serve.stage``,
``serve.dispatch``, ``serve.wait`` and ``serve.emit``
(``"serve.tick/serve.wait"``), and ``serve.gap``, the host gaps.

In a ``--trace 1`` run the benchmark's ``jax.profiler.start_trace``
(tens of ms) and ``stop_trace`` (seconds) run between engine calls with
slots active, so each lies inside one ``serve.gap``: there the gap
fields ``mean_ms``, ``max_ms`` and ``gap_share.none`` are mostly the
profiler's, and a p99 over few gaps may be. The median hardly moves."""
from __future__ import annotations

from bench.harness.spans import percentile

ENGINE = "serve"


def engine_recorder():
    """The engine's recorder, or None where the program has none."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.find(ENGINE)


def span_ms(rec: dict, series: str, q: float, *, gaps: bool = False):
    """The ``q``-th percentile of one series' durations in ms, with the
    count of values and the builds and GC pauses charged in the stream;
    ``gaps`` adds each span's share of the summed host gaps
    (``gap_share.<series>``; ``gap_share.none``: covered by no engine
    span), their mean and their longest. With a trace, ``trace_idle_ms_per_tick`` puts
    the device's idle time per traced tick beside it."""
    r = engine_recorder()
    if r is None:
        return None
    d = r.durations(series)
    if not d:
        return None
    out = {"value": percentile(d, q) * 1e3, "unit": "ms", "count": len(d)}
    out.update(r.charges())
    if gaps:
        out["mean_ms"] = sum(d) / len(d) * 1e3
        out["max_ms"] = max(d) * 1e3
        parts = r.gap_parts()
        total = sum(parts.values())
        for key, s in sorted(parts.items()):
            out[f"gap_share.{key}"] = s / total if total > 0 else 0.0
    red, calls = rec.get("trace"), rec.get("traced_calls")
    if red is not None and calls and calls["ticks"]:
        out["trace_idle_ms_per_tick"] = (
            (red.window_s - red.busy_s) * 1e3 / len(calls["ticks"]))
    return out
