"""Run one cell: find its items by name, drive the program through the
cell's driver, reduce what was recorded to the cell's metrics, and build
the result line.

``bench/run.py`` calls :func:`run_cell` after it has checked the
device; the control script and the tests call it directly.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import sys

from bench.harness import registry
from bench.harness.spans import Spans


@dataclasses.dataclass
class Context:
    """What a driver is given."""

    cell: dict
    conf: dict            # bench/configs/<config>.json
    mix: dict             # bench/traffic/<mix>.json
    check: dict           # bench/checks/<cell>.json
    seed: int
    seconds: float
    trace: bool
    root: pathlib.Path    # the checkout; traces are written under it
    spans: Spans
    t_start: float        # perf_counter at process start
    bench_dir: pathlib.Path = registry.BENCH  # where the items were found
    plan_overrides: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Outcome:
    """What a driver returns. ``record`` is what the metric readers
    read; ``checks`` maps each compared number to (value, limit)."""

    attempted: int
    failed: int
    record: dict
    checks: dict
    memory_peak_bytes: int
    problems: list = dataclasses.field(default_factory=list)

    @property
    def correct(self) -> bool:
        return (not self.problems and self.attempted > 0
                and all(v <= lim for v, lim in self.checks.values()))


def peaks_for(kind: str, bench_dir: pathlib.Path = registry.BENCH):
    """This device kind's published peaks, or None where the table has
    no entry (the harness refuses such a device before any run)."""
    table = registry.load_json(bench_dir / "harness" / "peaks.json")
    return table.get(kind)


def _value(v: float) -> float:
    if not math.isfinite(v):
        raise ValueError(f"metric value {v} is not finite")
    return float(v)


def make_context(name: str, *, seed: int, seconds: float, trace: bool,
                 t_start: float, root: pathlib.Path = registry.ROOT,
                 bench: dict | None = None,
                 plan_overrides: dict | None = None,
                 bench_dir: pathlib.Path = registry.BENCH):
    """(context, driver module) of one cell, its items found by name."""
    bench = bench if bench is not None else registry.benchmark(root)
    cell = registry.cell(bench, name)
    conf = registry.item("configs", cell["config"], bench_dir=bench_dir)
    mix = registry.item("traffic", cell["traffic"], bench_dir=bench_dir)
    check = registry.item("checks", name, bench_dir=bench_dir)
    driver = registry.module("drivers", mix["driver"], bench_dir=bench_dir)
    ctx = Context(
        cell=cell, conf=conf, mix=mix, check=check, seed=seed,
        seconds=seconds, trace=trace, root=root,
        spans=Spans(annotate=trace), t_start=t_start, bench_dir=bench_dir,
        plan_overrides=dict(plan_overrides or {}),
    )
    return ctx, driver


def run_cell(name: str, *, seed: int, seconds: float, trace: bool,
             t_start: float, root: pathlib.Path = registry.ROOT,
             bench: dict | None = None, plan_overrides: dict | None = None,
             bench_dir: pathlib.Path = registry.BENCH) -> tuple[dict, Outcome]:
    import jax

    bench = bench if bench is not None else registry.benchmark(root)
    ctx, driver = make_context(
        name, seed=seed, seconds=seconds, trace=trace, t_start=t_start,
        root=root, bench=bench, plan_overrides=plan_overrides,
        bench_dir=bench_dir)
    cell = ctx.cell
    outcome = driver.run(ctx)

    devices = jax.devices()
    dev = devices[0]
    peaks = peaks_for(dev.device_kind, bench_dir)
    rec = dict(outcome.record, peaks=peaks, devices=cell["chips"])
    wanted = (registry.per_layer(bench, name) if trace
              else registry.end_to_end(bench, name))
    metrics = {}
    for m in wanted:
        reader = registry.module("metrics", m["name"], bench_dir=bench_dir)
        got = reader.read(rec)
        if got is None:
            continue
        if got["unit"] != m["unit"]:
            raise ValueError(f"metric {m['name']}: reader gives unit "
                             f"{got['unit']!r}, BENCHMARK.json says "
                             f"{m['unit']!r}")
        metrics[m["name"]] = dict(got, value=_value(got["value"]))
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(outcome.memory_peak_bytes),
    }
    result = {
        "correct": outcome.correct,
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": metrics,
        "device": device,
    }
    red = outcome.record.get("trace")
    if trace and red is not None:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in outcome.checks.items()}
    return result, outcome


def report_checks(result: dict, outcome: Outcome, stream=None) -> None:
    """The compared numbers beside their limits, as the last lines on
    standard error."""
    stream = stream or sys.stderr
    for p in outcome.problems:
        print(f"problem: {p}", file=stream)
    for k, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} {verdict}",
              file=stream)
    print(f"correct: {result['correct']}", file=stream, flush=True)


def result_line(result: dict) -> str:
    return json.dumps(result, separators=(",", ":"))
