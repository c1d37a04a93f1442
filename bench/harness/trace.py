"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

The trace holds, per TPU, the operations the device ran (the ``XLA Ops``
line of each ``/device:TPU:<n>`` plane) and, on the host plane, the
benchmark's own spans (``TraceAnnotation``). Both are on one clock. The
traced window is the host span named ``WINDOW``; device events are
clipped to it.

Reduced here, per device and then averaged over the devices used:

- busy time: the union of the intervals in which an operation ran;
- time by op: summed durations per HLO instruction name (a Pallas
  kernel keeps its function's name there), control-flow ops that span
  their body's ops (``while``) left out;
- exposed collective time: collective intervals not covered by any
  other operation on that device;
- idle gaps: the complement of busy time in the window, each attributed
  to the benchmark span that overlaps it most.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench_window"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|\bsend\b|\brecv\b|all_gather|all_reduce|reduce_scatter",
    re.IGNORECASE,
)
_SUFFIX = re.compile(r"(\.\d+)+$")
CONTAINERS = {"while", "conditional", "call"}


@dataclasses.dataclass
class Trace:
    """``ops``: device name -> list of (name, start_ns, end_ns);
    ``spans``: host spans as (name, start_ns, end_ns)."""

    ops: dict
    spans: list


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, span_names=None) -> Trace:
    """Read one ``.xplane.pb``. ``span_names`` limits the host spans
    kept (the benchmark's own; None keeps every host event)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if span_names is None or e.name in span_names:
                        spans.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        )
    return Trace(ops, spans)


def op_kind(name: str) -> str:
    """The HLO instruction's name without its numeric suffixes. The TPU
    trace names an op by its whole HLO line (``%fusion.12 = f32[...]
    fusion(...)``); ``fusion.12`` and ``%fusion.12 = ...`` both give
    ``fusion``."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].strip().lstrip("%"))


def is_container(kind: str) -> bool:
    """Control-flow ops whose event spans the ops of their body."""
    return kind in CONTAINERS


def union(intervals):
    """Merge (start, end) intervals; returns a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def _subtract(intervals, cover):
    """Parts of merged ``intervals`` not covered by merged ``cover``."""
    out = []
    j = 0
    for s, e in intervals:
        cur = s
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            cs, ce = cover[k]
            if cs > cur:
                out.append((cur, cs))
            cur = max(cur, ce)
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window_bounds(trace: Trace):
    """(start_ns, end_ns) of the benchmark's window span."""
    wins = [(s, e) for n, s, e in trace.spans if n == WINDOW]
    if not wins:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    return min(s for s, _ in wins), max(e for _, e in wins)


@dataclasses.dataclass
class Reduction:
    window_s: float
    devices: int
    busy_s: float                  # mean over devices
    op_s: dict                     # op kind -> seconds, mean over devices
    exposed_collective_s: float    # mean over devices
    idle_gaps: list                # [(host span, seconds)], longest first

    def kernel_s(self, pattern: str) -> float:
        """Device seconds of the ops whose name contains ``pattern``."""
        return sum(t for k, t in self.op_s.items() if pattern in k)

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return {
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in self.idle_gaps[:n]],
        }


def idle_share(rec: dict):
    """The metric every cell's ``idle_share.*`` reader gives: the share
    of the traced window in which no operation ran on the device
    (1 - busy union / window), or None without a trace."""
    red = rec.get("trace")
    if red is None or red.window_s <= 0:
        return None
    return {"value": 100 * (1 - red.busy_s / red.window_s), "unit": "%",
            "window_s": red.window_s}


def reduce(trace: Trace, window=None) -> Reduction:
    lo, hi = window if window is not None else window_bounds(trace)
    if not trace.ops:
        raise ValueError("trace holds no TPU operations")
    n_dev = len(trace.ops)
    busy_total = 0.0
    exposed_total = 0.0
    op_s: dict[str, float] = {}
    gaps = []
    host = sorted((s, e, n) for n, s, e in trace.spans if n != WINDOW)
    for events in trace.ops.values():
        ev = _clip(events, lo, hi)
        merged = union((s, e) for _, s, e in ev)
        busy_total += _length(merged)
        kinds = [op_kind(n) for n, _, _ in ev]
        for k, (_, s, e) in zip(kinds, ev):
            if not is_container(k):
                op_s[k] = op_s.get(k, 0.0) + (e - s) / 1e9
        coll = union((s, e) for k, (_, s, e) in zip(kinds, ev)
                     if COLLECTIVE.search(k))
        other = union((s, e) for k, (_, s, e) in zip(kinds, ev)
                      if not COLLECTIVE.search(k) and not is_container(k))
        exposed_total += _length(_subtract(coll, other))
        for gs, ge in _subtract([(lo, hi)], merged):
            best, best_ov = "no benchmark span", 0
            for hs, he, hn in host:
                if hs >= ge:
                    break
                ov = min(he, ge) - max(hs, gs)
                if ov > best_ov:
                    best, best_ov = hn, ov
            gaps.append((best, (ge - gs) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Reduction(
        window_s=(hi - lo) / 1e9,
        devices=n_dev,
        busy_s=busy_total / n_dev / 1e9,
        op_s={k: v / n_dev for k, v in op_s.items()},
        exposed_collective_s=exposed_total / n_dev / 1e9,
        idle_gaps=gaps,
    )
