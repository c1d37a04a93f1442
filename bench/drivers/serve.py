"""Serving driver: the program's paged continuous-batching engine,
``ServeEngine(paged=True)``, driven through its streaming surface
(``begin_stream`` / ``can_admit`` / ``admit`` / ``decode_tick`` /
``take_completed``) from one host thread.

Set-up makes the weights from the seed on the device in one jitted
call (the configuration's reference makes them in the published
layout, its converter turns them into the program's tree), builds the
engine with the mix's plan, slots and pages, and warms up every
program the mix will use (one prefill per prompt bucket, the
page insert of each bucket, the decode step and the samplers). Then the
window runs for ``--seconds``:

- ``"offline"`` arrivals: a queue that never runs dry; the window opens
  once the engine admits no further request (every slot busy, or too
  few free pages).
- ``"poisson"`` arrivals: an open loop; each request is due at its
  planned time and is handed to the engine's queue when the loop next
  looks (how late that is, is recorded). After the window the loop
  runs on, taking no new arrivals, until every request due in the
  window has its first token.

The non-speculative engine emits one token per active request per
``decode_tick``, so a request's token times are its admission and each
tick it took part in; that count is checked against what the engine
returns.

Correctness: once the window has closed and the engine is freed, the
reference (``bench/configs/<reference>.py``) reads a sample of the
finished requests, drawn from the seed with the longest among them. For
each served token it gives the gap by which the token's logit lies below
the reference's best, and the reference's own top-two margin there; the
gap per near tie (:func:`gap_numbers`) is held against the cell's limit.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import gc
import os
import pathlib
import shutil
import sys
import time

import numpy as np

from bench.harness import registry, trace as tracemod, traffic
from bench.harness.cell import Outcome

TRACE_DIR = ".bench_trace"
SPAN_NAMES = {"admit", "decode_tick", "gen_wait", tracemod.WINDOW}


@functools.lru_cache(maxsize=None)
def _module(bench_dir: pathlib.Path, name: str):
    return registry.module("configs", name, bench_dir=bench_dir)


def reference_module(ctx):
    """The configuration's plain reference (``bench/configs/<name>.py``)."""
    return _module(ctx.bench_dir, ctx.conf["reference"]["module"])


def converter(ctx):
    """The configuration's weight conversion into the program's tree
    (``bench/configs/<program.convert>.py``)."""
    return _module(ctx.bench_dir, ctx.conf["program"]["convert"])


def counts(ctx, s: dict) -> dict:
    """The reference's counts the metric readers use, of its sizes ``s``."""
    ref, planes = reference_module(ctx), ctx.conf["weight_planes"]
    return {"matmul_params": ref.matmul_params(s),
            "plane_elements": ref.plane_elements(
                s, planes["leaves"], planes["min_elements"]),
            "attention_layers": ref.attention_layers(s)}


def make_engine(ctx, cfg, s: dict):
    """Weights on the device from the seed, in one jitted call, and the
    engine over them."""
    import jax
    from repro.dist.spec import SINGLE, build_spec_tree, tree_to_storage
    from repro.models.init import param_shapes
    from repro.plan import PrecisionPlan
    from repro.serve.engine import ServeEngine

    ref, conv = reference_module(ctx), converter(ctx)
    shapes, metas = param_shapes(cfg)
    build = jax.jit(lambda k: conv.to_program(
        ctx.conf, s, cfg, ref.make_weights(ctx.conf, k)))
    params = build(ref.weight_key(ctx.seed))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), shapes)
    if got != want:
        raise ValueError(f"the weights converted by {ctx.conf['name']}'s "
                         f"{ctx.conf['program']['convert']!r} do not match "
                         "the program's parameter tree")
    spec_tree = build_spec_tree(shapes, metas, SINGLE)
    storage = tree_to_storage(params, spec_tree, SINGLE)
    del params
    p = dict(ctx.mix["plan"], **ctx.plan_overrides)
    plan = PrecisionPlan.build(
        cfg.num_groups + 1, round_to=p["round_to"], mode=p["mode"],
        act_round_to=p["act_round_to"], dtype=p.get("dtype", ctx.conf["dtype"]),
    )
    e = ctx.mix["engine"]
    cap = (traffic.max_length(ctx.mix["prompt_len"])
           + traffic.max_length(ctx.mix["output_len"]))
    engine = ServeEngine(
        cfg, SINGLE, None, spec_tree, storage, plan=plan,
        max_slots=e["max_slots"], cache_capacity=cap, paged=True,
        page_size=e["page_size"], num_pages=e["num_pages"],
    )
    return engine


def warm_up(ctx, engine, cfg) -> None:
    """Every program the window will use, once: a request of each
    prompt bucket through admission and two decode ticks."""
    from repro.serve.api import Request

    page = ctx.mix["engine"]["page_size"]
    dist = ctx.mix["prompt_len"]
    buckets = sorted({-(-int(n) // page) * page for n in
                      traffic.lengths(dist, ctx.mix.get("block", 64))})
    r = traffic.rng(ctx.seed, 2)
    engine.begin_stream()
    for i, n in enumerate(buckets):
        prompt = tuple(int(t) for t in r.integers(0, cfg.vocab_size, n))
        engine.admit(Request(rid=-1 - i, prompt_ids=prompt, max_new=3))
        engine.decode_tick()
    while engine.has_work:
        engine.decode_tick()
    engine.take_completed()
    engine.finish()


@dataclasses.dataclass
class Flight:
    """One request as the benchmark saw it."""

    planned: traffic.Planned
    due: float | None = None        # host clock
    offered: float | None = None    # handed to the engine's queue
    times: list = dataclasses.field(default_factory=list)  # token times
    tokens: list | None = None      # what the engine returned


class _Tracer:
    """The profiled sub-window of a ``--trace 1`` run."""

    def __init__(self, ctx, w0: float):
        t = ctx.mix["trace"]
        self.start = w0 + min(t["start_s"], ctx.seconds / 3)
        self.stop = self.start + t["seconds"]
        self.dir = str(ctx.root / TRACE_DIR / ctx.cell["name"])
        self.state = "before"
        self._ann = None
        self.calls = {"ticks": [], "admits": []}

    @property
    def on(self) -> bool:
        return self.state == "on"

    def poll(self, now: float) -> None:
        import jax

        if self.state == "before" and now >= self.start:
            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
            jax.profiler.start_trace(self.dir)
            self._ann = jax.profiler.TraceAnnotation(tracemod.WINDOW)
            self._ann.__enter__()
            self.state = "on"
        elif self.state == "on" and now >= self.stop:
            self.finish()

    def finish(self) -> None:
        import jax

        if self.state == "on":
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

    def reduce(self):
        """The trace's reduction; the trace itself is then deleted."""
        if self.state != "done":
            return None
        try:
            t = tracemod.load(tracemod.find_xplane(self.dir), SPAN_NAMES)
            return tracemod.reduce(t)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class Window:
    """What one measured window saw."""

    flights: dict
    completed: list
    problems: list
    record: dict
    attempted: int
    failed: int
    backlog: int


def setup(ctx):
    """(program config, engine) with every program of the mix warm."""
    s = reference_module(ctx).sizes(ctx.conf)
    cfg = converter(ctx).program_config(ctx.conf, s)
    engine = make_engine(ctx, cfg, s)
    warm_up(ctx, engine, cfg)
    return cfg, engine


def measure(ctx, engine, cfg) -> Window:
    """One window of ``ctx.seconds`` over a fresh stream."""
    from repro.serve.api import Request

    mix, spans = ctx.mix, ctx.spans
    offline = mix["arrivals"]["kind"] == "offline"
    gen = traffic.Generator(mix, ctx.seed, cfg.vocab_size)
    flights: dict[int, Flight] = {}
    queue: collections.deque = collections.deque()
    inflight: set = set()
    completed: list = []
    problems: list = []
    tick_active: list = []
    tick_pages: list = []
    admit_tokens: list = []
    tracer = None

    def admit_ready(now_fn) -> None:
        while queue and engine.can_admit(queue[0])[0]:
            req = queue.popleft()
            with spans.span("admit"):
                engine.admit(req)
            t = now_fn()
            admit_tokens.append(len(req.prompt_ids))
            flights[req.rid].times.append(t)
            inflight.add(req.rid)
            if tracer is not None and tracer.on:
                tracer.calls["admits"].append(len(req.prompt_ids))
            collect()

    def collect() -> None:
        for rid, res in engine.take_completed().items():
            f = flights[rid]
            f.tokens = list(res.tokens)
            inflight.discard(rid)
            completed.append(rid)
            if len(f.tokens) != len(f.times):
                problems.append(f"request {rid}: engine returned "
                                f"{len(f.tokens)} tokens, the benchmark saw "
                                f"{len(f.times)} emitted")

    def tick(now_fn) -> None:
        active = sorted(inflight)
        if tracer is not None and tracer.on:
            tracer.calls["ticks"].append([
                len(flights[r].planned.prompt) + len(flights[r].times)
                for r in active])
        with spans.span("decode_tick"):
            engine.decode_tick()
        t = now_fn()
        for rid in active:
            flights[rid].times.append(t)
        tick_active.append(len(active))
        tick_pages.append(engine.pages.live_pages)
        collect()

    def offer(p: traffic.Planned, due, now) -> None:
        flights[p.index] = Flight(p, due=due, offered=now)
        queue.append(Request(rid=p.index, prompt_ids=p.prompt,
                             max_new=p.max_new))

    clock = time.perf_counter
    engine.begin_stream()
    nxt = next(gen)
    if offline:
        # fill the engine; the window opens when it takes no more
        while True:
            while len(queue) < 2:
                offer(nxt, None, clock())
                nxt = next(gen)
            n_before = len(inflight)
            admit_ready(clock)
            if len(inflight) == n_before:
                break
    w0 = clock()
    w_end = w0 + ctx.seconds
    if ctx.trace:
        tracer = _Tracer(ctx, w0)
    t_last = w0
    while True:
        now = clock()
        if now >= w_end:
            break
        if tracer is not None:
            tracer.poll(now)
        if offline:
            while len(queue) < 2:
                offer(nxt, None, now)
                nxt = next(gen)
        else:
            while w0 + nxt.due <= now:
                offer(nxt, w0 + nxt.due, now)
                nxt = next(gen)
        admit_ready(clock)
        if inflight:
            tick(clock)
            t_last = clock()
        elif not offline:
            wait = min(w0 + nxt.due, w_end) - clock()
            if wait > 0:
                with spans.span("gen_wait"):
                    time.sleep(wait)
    if tracer is not None:
        tracer.finish()
    w1 = t_last if offline else max(t_last, w_end)
    due_in_window = [f for f in flights.values()
                     if f.due is not None and f.due < w_end]
    backlog = len(queue)
    if not offline:
        # late answers are late, not wrong: finish the first tokens of
        # every request due in the window, taking no new arrivals
        give_up = clock() + 60.0
        while any(not f.times for f in due_in_window) and clock() < give_up:
            admit_ready(clock)
            if inflight:
                tick(clock)

    sizes = reference_module(ctx).sizes(ctx.conf)
    emitted = [t for f in flights.values() for t in f.times if w0 <= t <= w1]
    itl = []
    for f in flights.values():
        ts = f.times
        itl.extend(b - a for a, b in zip(ts, ts[1:]) if a >= w0 and b <= w1)
    record = {
        "setup_s": w0 - ctx.t_start,
        "window_s": w1 - w0,
        "tokens": len(emitted),
        "itl_s": itl,
        "ttft_s": [(f.times[0] - f.due) if f.times else float("inf")
                   for f in due_in_window],
        "gen_late_s": [f.offered - f.due for f in due_in_window],
        "spans": dict(spans.durations),
        "tick_active": tick_active,
        "tick_pages": tick_pages,
        "decode_tokens": sum(tick_active),
        "admit_tokens": admit_tokens,
        "kv_itemsize": np.dtype(engine.plan.compute_dtype).itemsize,
        "sizes": sizes,
        "counts": counts(ctx, sizes),
        "conf": ctx.conf,
        "mix": mix,
        "traced_calls": tracer.calls if tracer is not None else None,
        "trace": tracer.reduce() if tracer is not None else None,
    }
    attempted = sum(
        1 for f in flights.values()
        if (f.due is not None and f.due < w_end)
        or any(w0 <= t <= w1 for t in f.times))
    failed = sum(1 for f in due_in_window if not f.times)
    return Window(flights, completed, problems, record, attempted, failed,
                  backlog)


def run(ctx) -> Outcome:
    import jax

    cfg, engine = setup(ctx)
    win = measure(ctx, engine, cfg)
    memory_peak = int((jax.devices()[0].memory_stats() or {})
                      .get("peak_bytes_in_use", 0))
    # correctness, once the program's state is freed
    finished = [win.flights[r] for r in win.completed
                if win.flights[r].tokens]
    del engine
    gc.collect()
    checks = {}
    if finished:
        c = ctx.check["numbers"]["gap_per_near_tie"]
        stats = gap_numbers(*served_gaps(ctx, finished), tau=c["tau"],
                            min_near_ties=c["min_near_ties"])
        win.record["served_gaps"] = stats
        print("served gaps: " + ", ".join(f"{k} {v!r}" for k, v in
                                          stats.items()), file=sys.stderr)
        for name, c in ctx.check["numbers"].items():
            checks[name] = (stats[name], c["limit"])
    else:
        win.problems.append("no request finished")
    return Outcome(attempted=win.attempted, failed=win.failed,
                   record=win.record, checks=checks,
                   memory_peak_bytes=memory_peak, problems=win.problems)


def sample(ctx, finished: list) -> list:
    """The longest finished request, then others in an order drawn from
    the seed, while the sample holds fewer than ``max_tokens`` served
    tokens and ``max_requests`` requests."""
    c = ctx.check["sample"]
    order = sorted(finished, key=lambda f: (-len(f.tokens), f.planned.index))
    picked, rest = [order[0]], order[1:]
    n = len(order[0].tokens)
    for i in traffic.rng(ctx.seed, 3).permutation(len(rest)):
        f = rest[int(i)]
        if n + len(f.tokens) > c["max_tokens"] or len(picked) >= c["max_requests"]:
            break
        picked.append(f)
        n += len(f.tokens)
    return picked


def gap_numbers(gaps: np.ndarray, margins: np.ndarray, tau: float,
                min_near_ties: int) -> dict:
    """Numbers from every sampled served token's gap and the reference's
    margin there (its best minus its second-best logit).

    ``gap_per_near_tie``, the number the serving cells compare: the
    summed gap over the count of positions where the reference's margin
    is under ``tau``. Rounding moves a served token off the reference's
    first choice only where the reference is nearly undecided, so
    dividing by the count of such near ties takes out how often a
    seed's weights happen to leave the reference undecided. A count
    under ``min_near_ties`` gives no steady rate, so the count divided
    by is at least that. The others are printed beside it."""
    near = int(np.sum(margins < tau))
    return {
        "gap_per_near_tie": float(np.sum(gaps)) / max(near, min_near_ties),
        "near_ties": near,
        "max_served_gap": float(np.max(gaps)),
        "mean_served_gap": float(np.mean(gaps)),
        "not_first_share": float(np.mean(gaps > 0)),
        "served_tokens_compared": int(gaps.size),
    }


def served_gaps(ctx, finished: list):
    """(gaps, margins) of every sampled served token (see
    ``Reference.served_gaps``)."""
    import jax

    ref = reference_module(ctx)
    p = dict(ctx.mix["plan"], **ctx.plan_overrides)
    weights = jax.jit(lambda k: ref.as_served(
        ref.make_weights(ctx.conf, k), ctx.conf["weight_planes"],
        p["round_to"], p["mode"]))(ref.weight_key(ctx.seed))
    model = ref.Reference(ctx.conf)
    picked = sample(ctx, finished)
    longest = max(len(f.planned.prompt) + len(f.tokens) for f in picked)
    pad_to = -(-longest // model.block) * model.block
    got = [model.served_gaps(weights, f.planned.prompt, f.tokens,
                             pad_to=pad_to) for f in picked]
    return (np.concatenate([g for g, _ in got]),
            np.concatenate([m for _, m in got]))
