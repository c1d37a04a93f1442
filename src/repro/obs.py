"""Spans and counters of the program, on the profiler's clock.

``Recorder.span(name)`` times a block on the host
(``time.perf_counter_ns``) and enters ``jax.profiler.TraceAnnotation``
for the same stretch, so under a profile every span lies on the trace's
host plane, on the clock of the device's operations. A span's series is
its name under the enclosing span's (``"serve.tick/serve.wait"``); the
enclosing span is the innermost one open in the caller's context. Each
series keeps the (start, end) of its last ``CAP`` spans in a ring, so a
process that runs for days holds a fixed amount.

Recorders live in a process-wide index keyed by name
(:func:`recorder`, :func:`find`): an engine is given a name, and its
recorder outlives the engine, which is what an operator or a benchmark
reads after the fact. Recording is always on.

Two process-wide hooks, installed with the first recorder, count what
stalls the host inside a span: a ``jax.monitoring`` listener counts
each program build (a jit cache miss: trace, lowering, then a compile or
a persistent-cache load) with its seconds, and a ``gc.callbacks`` hook
counts each Python GC pause. Each is charged to the innermost span open
in the calling thread's context; one outside every span is not charged.

Host gaps (:meth:`Recorder.gap_from` / :meth:`Recorder.end_gap`): the
stretch from when a device program's results reached the host, with
work left for the device, to the dispatch of the next device program.
In it the device has nothing of this recorder's owner queued. The gaps
are the series :data:`GAP`; :meth:`Recorder.gap_parts` splits their time
over the spans that covered them when it is read.
"""
from __future__ import annotations

import collections
import contextvars
import gc
import threading
import time

import jax
import numpy as np

__all__ = ["CAP", "GAP", "Recorder", "Span", "find", "recorder"]

CAP = 65_536  # spans kept per series
GAP = "serve.gap"  # the series of host gaps
NO_SPAN = "none"  # gap time no span of the recorder covered

# one build = one backend compile or persistent-cache load; its seconds
# add the trace and lowering that led to it
_BUILD = "/jax/core/compile/backend_compile_duration"
_BUILD_PARTS = {
    _BUILD,
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
}

_open: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "repro_obs_open", default=()
)
_index: dict[str, Recorder] = {}
_index_lock = threading.Lock()
_hooks = {"installed": False, "gc_t0": 0}


class _Ring:
    """The last ``cap`` (start_ns, end_ns) pairs, oldest first."""

    __slots__ = ("buf", "n")

    def __init__(self, cap: int):
        self.buf = np.zeros((cap, 2), np.int64)
        self.n = 0

    def push(self, start: int, end: int) -> None:
        i = self.n % len(self.buf)
        self.buf[i, 0] = start
        self.buf[i, 1] = end
        self.n += 1

    def pairs(self) -> np.ndarray:
        cap = len(self.buf)
        if self.n <= cap:
            return self.buf[:self.n].copy()
        i = self.n % cap
        return np.concatenate([self.buf[i:], self.buf[:i]])


class Span:
    """One timed block; ``start`` and ``end`` are ``perf_counter_ns``
    (``end`` is set once the block has left)."""

    __slots__ = ("rec", "name", "key", "start", "end", "_ann", "_token")

    def __init__(self, rec: Recorder, name: str):
        self.rec = rec
        self.name = name
        self.start = self.end = 0

    def __enter__(self) -> Span:
        stack = _open.get()
        self.key = f"{stack[-1].key}/{self.name}" if stack else self.name
        self._token = _open.set(stack + (self,))
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _open.reset(self._token)
        self.rec.add(self.key, self.start, self.end)


class Recorder:
    """Spans, charges and host gaps of one owner (see the module
    docstring). Read with :meth:`pairs`, :meth:`durations`,
    :meth:`charges` and :meth:`gap_parts`; :meth:`clear` empties it."""

    def __init__(self, name: str):
        self.name = name
        self.clear()

    def clear(self) -> None:
        self._series: dict[str, _Ring] = {}
        # (series, kind, seconds), kind "build", "build_part" or "gc";
        # appended from any thread, so a deque and no read-modify-write
        self.charged: collections.deque = collections.deque(maxlen=CAP)
        self._gap_t0: int | None = None

    # -- recording ----------------------------------------------------------
    def span(self, name: str) -> Span:
        return Span(self, name)

    def add(self, series: str, start_ns: int, end_ns: int) -> None:
        ring = self._series.get(series)
        if ring is None:
            ring = self._series.setdefault(series, _Ring(CAP))
        ring.push(start_ns, end_ns)

    def gap_from(self, t_ns: int | None) -> None:
        """A host gap runs from ``t_ns``, when a program's results
        reached the host; None: the device is left with no work, so
        what follows is no host gap."""
        self._gap_t0 = t_ns

    def end_gap(self, t_ns: int) -> None:
        """A device program is dispatched at ``t_ns``: the running gap,
        if any, ends there and is recorded under :data:`GAP`."""
        if self._gap_t0 is not None:
            self.add(GAP, self._gap_t0, t_ns)
            self._gap_t0 = None

    # -- reading ------------------------------------------------------------
    def series(self) -> list[str]:
        return sorted(self._series)

    def pairs(self, series: str) -> np.ndarray:
        """(n, 2) int64 (start_ns, end_ns) of the series, oldest first."""
        ring = self._series.get(series)
        return ring.pairs() if ring is not None else np.zeros((0, 2), np.int64)

    def durations(self, series: str) -> list[float]:
        """The series' durations in seconds, oldest first."""
        p = self.pairs(series)
        return ((p[:, 1] - p[:, 0]) / 1e9).tolist()

    def charges(self) -> dict:
        """Builds and GC pauses charged to any span: ``builds``,
        ``build_s``, ``gc_pauses``, ``gc_s`` and the longest pause,
        ``gc_max_s``. :attr:`charged` holds each with its series."""
        out = {"builds": 0, "build_s": 0.0, "gc_pauses": 0, "gc_s": 0.0,
               "gc_max_s": 0.0}
        for _, kind, sec in list(self.charged):
            if kind == "build":
                out["builds"] += 1
                out["build_s"] += sec
            elif kind == "build_part":
                out["build_s"] += sec
            else:
                out["gc_pauses"] += 1
                out["gc_s"] += sec
                out["gc_max_s"] = max(out["gc_max_s"], sec)
        return out

    def gap_parts(self) -> dict[str, float]:
        """Seconds of the recorded gaps by the span that covered them:
        a child series for its own time, a parent for its time outside
        its children, :data:`NO_SPAN` for time no span covered."""
        gaps = self.pairs(GAP)
        cover = {k: _overlap(gaps, r.pairs()) for k, r in self._series.items()
                 if k != GAP}
        cover = {k: ns for k, ns in cover.items() if ns > 0}
        parts = {}
        for key, ns in cover.items():
            kids = sum(v for k, v in cover.items()
                       if k.rpartition("/")[0] == key)
            parts[key] = (ns - kids) / 1e9
        top = sum(ns for k, ns in cover.items() if "/" not in k)
        parts[NO_SPAN] = (int(np.sum(gaps[:, 1] - gaps[:, 0])) - top) / 1e9
        return parts


def _overlap(a: np.ndarray, b: np.ndarray) -> int:
    """Nanoseconds in both of two sets of (start, end) intervals, each
    disjoint and in order."""
    if not len(a) or not len(b):
        return 0
    starts, lens = a[:, 0], a[:, 1] - a[:, 0]
    before = np.concatenate([[0], np.cumsum(lens)])

    def upto(t):  # time of ``a`` before each t
        i = np.searchsorted(starts, t, side="right")
        j = np.maximum(i - 1, 0)
        return np.where(i > 0, before[j] + np.minimum(t - starts[j], lens[j]),
                        0)

    return int(np.sum(upto(b[:, 1]) - upto(b[:, 0])))


def recorder(name: str) -> Recorder:
    """The recorder named ``name``, made on first use."""
    with _index_lock:
        rec = _index.get(name)
        if rec is None:
            _install_hooks()
            rec = _index[name] = Recorder(name)
        return rec


def find(name: str) -> Recorder | None:
    """The recorder named ``name``, or None where none was made."""
    return _index.get(name)


def _innermost() -> Span | None:
    stack = _open.get()
    return stack[-1] if stack else None


def _on_duration(event: str, seconds: float, **_) -> None:
    if event in _BUILD_PARTS:
        s = _innermost()
        if s is not None:
            s.rec.charged.append(
                (s.key, "build" if event == _BUILD else "build_part", seconds))


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _hooks["gc_t0"] = time.perf_counter_ns()
        return
    s = _innermost()
    if s is not None:
        s.rec.charged.append(
            (s.key, "gc", (time.perf_counter_ns() - _hooks["gc_t0"]) / 1e9))


def _install_hooks() -> None:
    if not _hooks["installed"]:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        gc.callbacks.append(_on_gc)
        _hooks["installed"] = True
