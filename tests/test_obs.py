"""Spans and counters inside the serving engine (``repro.obs``): the
span tree of a tick and of an admission, the recorder's lifetime and
bounds, builds and GC pauses charged to the span they stall, host gaps
only while the device has work, and the spans on the profiler's clock."""
import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.registry import get_config, reduced
from repro.dist.spec import MeshCfg, build_spec_tree, tree_to_storage
from repro.models.init import init_params
from repro.plan import PrecisionPlan
from repro.serve.engine import Request, ServeEngine
from repro.serve.spec import DraftBundle
from repro.transport import CompressionPolicy

CHILDREN = ("serve.stage", "serve.dispatch", "serve.wait", "serve.emit")


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("qwen3-1.7b"))
    mesh_cfg = MeshCfg(tp=1, dp=1, compress_min_size=4096)
    params, metas = init_params(cfg, jax.random.PRNGKey(0), tp=1)
    spec_tree = build_spec_tree(params, metas, mesh_cfg)
    storage = tree_to_storage(params, spec_tree, mesh_cfg)
    plan = PrecisionPlan(
        weights=(CompressionPolicy(round_to=2),) * (cfg.num_groups + 1),
        host_device=CompressionPolicy(round_to=2),
    )
    return cfg, mesh_cfg, spec_tree, storage, plan


@pytest.fixture(scope="module")
def engine(model):
    cfg, mesh_cfg, spec_tree, storage, plan = model
    eng = ServeEngine(cfg, mesh_cfg, None, spec_tree, storage, plan=plan,
                      max_slots=2, cache_capacity=32, paged=True,
                      page_size=8, name="test-obs")
    # every program the tests use, built before any test looks
    eng.run([_request(cfg, 0, 8, 3)])
    return eng


def _request(cfg, rid, n, max_new):
    rng = np.random.default_rng(rid)
    return Request(rid=rid, max_new=max_new, prompt_ids=tuple(
        int(t) for t in rng.integers(0, cfg.vocab_size, n)))


def _nested_in_order(rec, parent):
    """Each ``parent`` span holds its four children once each, in order."""
    tops = rec.pairs(parent)
    kids = [rec.pairs(f"{parent}/{c}") for c in CHILDREN]
    assert len(tops) > 0
    for k in kids:
        assert len(k) == len(tops)
    for i, (t0, t1) in enumerate(tops):
        edges = [t0]
        for k in kids:
            edges += [k[i, 0], k[i, 1]]
        edges.append(t1)
        assert edges == sorted(edges), (parent, i, edges)


def test_each_tick_and_admission_is_one_span_with_four_children(engine):
    engine.begin_stream()
    cfg = engine.cfg
    engine.admit(_request(cfg, 1, 8, 4))
    engine.admit(_request(cfg, 2, 16, 6))
    ticks = 0
    while engine.has_work:
        engine.decode_tick()
        ticks += 1
    engine.decode_tick()  # no slot active: no tick span
    rec = obs.find("test-obs")
    assert rec is engine.obs
    assert len(rec.pairs("serve.tick")) == ticks == 5
    assert len(rec.pairs("serve.admit")) == 2
    _nested_in_order(rec, "serve.tick")
    _nested_in_order(rec, "serve.admit")
    assert {s.split("/")[0] for s in rec.series()} == {
        "serve.tick", "serve.admit", "serve.gap"}


def test_begin_stream_clears_and_a_series_stops_at_its_cap(engine,
                                                           monkeypatch):
    engine.begin_stream()
    engine.admit(_request(engine.cfg, 3, 8, 2))
    engine.decode_tick()
    assert engine.obs.series()
    engine.begin_stream()
    assert engine.obs.series() == []
    assert engine.obs.charges() == {"builds": 0, "build_s": 0.0,
                                    "gc_pauses": 0, "gc_s": 0.0,
                                    "gc_max_s": 0.0}
    monkeypatch.setattr(obs, "CAP", 4)
    ring = obs.Recorder("test-obs-cap")
    for i in range(10):
        ring.add("s", i, i + 1 + i)
    assert ring.pairs("s").tolist() == [[i, 2 * i + 1] for i in range(6, 10)]
    assert ring.durations("s") == [(i + 1) / 1e9 for i in range(6, 10)]


def test_build_and_gc_pause_are_charged_to_the_open_span():
    rec = obs.recorder("test-obs-charges")
    x = jnp.arange(7.0)
    was_on = gc.isenabled()
    gc.disable()  # no collection but the one asked for
    try:
        with rec.span("outer"):
            with rec.span("build"):
                jax.jit(lambda a: a * 3.0 + 1.0)(x).block_until_ready()
            with rec.span("gc"):
                gc.collect()
    finally:
        if was_on:
            gc.enable()
    kinds = [(key, kind) for key, kind, _ in rec.charged]
    assert kinds.count(("outer/build", "build")) == 1
    assert kinds.count(("outer/gc", "gc")) == 1
    assert {key for key, _ in kinds} == {"outer/build", "outer/gc"}
    got = rec.charges()
    assert got["builds"] == 1 and got["build_s"] > 0
    assert got["gc_pauses"] == 1 and got["gc_s"] == got["gc_max_s"] > 0
    # outside every span nothing is charged
    rec.clear()
    jax.jit(lambda a: a - 2.0)(x).block_until_ready()
    gc.collect()
    assert rec.charges()["builds"] == 0 and rec.charges()["gc_pauses"] == 0


def test_gaps_only_while_a_slot_is_active(engine):
    engine.begin_stream()
    rec = engine.obs
    cfg = engine.cfg
    # admitted to an idle engine: no gap before its prefill; each of the
    # 3 ticks after it dispatches with the slot active since the last
    # wait; the last tick retires it
    engine.admit(_request(cfg, 4, 8, 4))
    while engine.has_work:
        engine.decode_tick()
    assert len(rec.pairs("serve.gap")) == 3
    engine.admit(_request(cfg, 5, 16, 2))  # idle engine again
    assert len(rec.pairs("serve.gap")) == 3
    engine.admit(_request(cfg, 6, 8, 3))  # the slot of rid 5 is active
    assert len(rec.pairs("serve.gap")) == 4
    while engine.has_work:
        engine.decode_tick()
    gaps = rec.pairs("serve.gap")
    # each gap ends where a step program's dispatch starts
    starts = set(rec.pairs("serve.tick/serve.dispatch")[:, 0].tolist()
                 + rec.pairs("serve.admit/serve.dispatch")[:, 0].tolist())
    assert set(gaps[:, 1].tolist()) <= starts
    parts = rec.gap_parts()
    total = float(np.sum(gaps[:, 1] - gaps[:, 0])) / 1e9
    assert sum(parts.values()) == pytest.approx(total, rel=1e-9, abs=1e-9)
    assert parts["serve.tick/serve.emit"] > 0
    assert parts["serve.tick/serve.stage"] > 0
    assert min(parts.values()) >= 0


def test_speculative_gaps_end_where_the_draft_starts(model):
    cfg, mesh_cfg, spec_tree, storage, plan = model
    eng = ServeEngine(cfg, mesh_cfg, None, spec_tree, storage, plan=plan,
                      max_slots=2, cache_capacity=32, paged=True,
                      page_size=8, name="test-obs-spec",
                      draft=DraftBundle(cfg, spec_tree, storage), spec_k=2)
    eng.run([_request(cfg, 0, 8, 3)])  # build every program first
    eng.begin_stream()
    eng.admit(_request(cfg, 1, 8, 7))
    while eng.has_work:
        eng.decode_tick()
    rec = eng.obs
    drafts = rec.pairs("serve.tick/serve.draft")
    gaps = rec.pairs("serve.gap")
    assert len(drafts) == len(rec.pairs("serve.tick")) >= 2
    # the admission's wait opens a gap that the first draft closes; each
    # tick but the last, which retires the slot, opens the next
    assert len(gaps) == len(drafts)
    assert gaps[:, 1].tolist() == drafts[:, 0].tolist()


def test_spans_are_on_the_profiler_clock(engine, tmp_path):
    engine.begin_stream()
    cfg = engine.cfg
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test_obs_outer"):
            engine.admit(_request(cfg, 7, 8, 3))
            while engine.has_work:
                engine.decode_tick()
    path = sorted(glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
    outer = [(s, e) for n, s, e in events if n == "test_obs_outer"]
    assert len(outer) == 1
    lo, hi = outer[0]
    names = [n for n, _, _ in events if n.startswith("serve.")]
    assert names.count("serve.admit") == 1 and names.count("serve.tick") == 2
    for c in CHILDREN:
        assert names.count(c) == 3
    ticks = [(s, e) for n, s, e in events if n == "serve.tick"]
    for n, s, e in events:
        if n.startswith("serve."):
            assert lo <= s <= e <= hi, n
        if n == "serve.wait" and s > ticks[0][0]:
            assert any(ts <= s <= e <= te for ts, te in ticks)


def test_gap_parts_by_hand():
    rec = obs.Recorder("test-obs-parts")
    for start, end in [(10, 20), (30, 40)]:
        rec.gap_from(start)
        rec.end_gap(end)
    rec.end_gap(60)  # no gap open: nothing recorded
    rec.add("a", 0, 15)
    rec.add("a/b", 12, 14)
    rec.add("c", 35, 50)
    rec.add("d", 20, 30)  # between the gaps
    assert rec.pairs(obs.GAP).tolist() == [[10, 20], [30, 40]]
    assert rec.gap_parts() == pytest.approx(
        {"a": 3e-9, "a/b": 2e-9, "c": 5e-9, obs.NO_SPAN: 10e-9})
