"""Continuous-batching serve engine: scheduler invariants, determinism,
bit-exactness vs static batching, and host<->device wire accounting.

The contracts pinned here (see docs/serving.md):

  * no KV-slot leaks across admit/evict cycles (``SlotManager.audit``);
  * per-request token streams are a pure function of the prompt —
    identical regardless of arrival order or batch companions;
  * continuous batching is BIT-EXACT vs the static one-shot reference
    (``generate_static``) for identical request sets, mixed prompt
    lengths included, fp32 and int8-KV alike;
  * the engine's measured ``host_device`` staged bytes equal the
    analytic roofline serve model
    (``repro.roofline.analysis.serve_host_device_bytes``) — the serving
    twin of ``test_collective_wire_bytes``'s no-drift rule.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import load_plan, load_storage, save_checkpoint
from repro.configs.registry import get_config, reduced
from repro.dist.spec import MeshCfg, build_spec_tree, tree_to_storage
from repro.models.init import init_params
from repro.plan import PrecisionPlan
from repro.roofline.analysis import serve_host_device_bytes
from repro.serve.engine import (
    AllocatorError,
    CapacityError,
    GenResult,
    InvariantError,
    Request,
    ServeEngine,
    SlotManager,
    generate_static,
)
from repro.transport import CompressionPolicy
from repro.transport.hostdev import (
    pack_tokens,
    pack_tokens_host,
    unpack_tokens,
    unpack_tokens_host,
)

CAPACITY = 24
SLOTS = 2


@pytest.fixture(scope="module")
def setup():
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


def _requests(cfg, spec=((16, 6), (12, 8), (16, 4), (8, 8), (12, 5))):
    rng = np.random.default_rng(7)
    return [
        Request(
            rid=i,
            prompt_ids=tuple(int(t) for t in rng.integers(0, cfg.vocab_size, S)),
            max_new=gen,
        )
        for i, (S, gen) in enumerate(spec)
    ]


@pytest.fixture(scope="module")
def engine(setup):
    cfg, mesh_cfg, spec_tree, storage, plan = setup
    return ServeEngine(
        cfg, mesh_cfg, None, spec_tree, storage, plan=plan,
        max_slots=SLOTS, cache_capacity=CAPACITY,
    )


@pytest.fixture(scope="module")
def static_streams(setup):
    cfg, mesh_cfg, spec_tree, storage, plan = setup
    return generate_static(
        cfg, mesh_cfg, None, spec_tree, storage, _requests(cfg), plan=plan
    )


# ---------------------------------------------------------------------------
# slot manager invariants (pure python)
# ---------------------------------------------------------------------------


def test_slot_manager_alloc_release_audit():
    sm = SlotManager(3)
    a = sm.alloc(10)
    b = sm.alloc(11)
    assert (a, b) == (0, 1)  # lowest free slot first
    sm.audit()
    sm.release(a)
    c = sm.alloc(12)
    assert c == a  # freed slot is reused
    sm.release(b)
    sm.release(c)
    audit = sm.audit()
    assert audit == {"free": 3, "active": 0, "allocs": 3, "releases": 3}


def test_slot_manager_rejects_double_free_and_exhaustion():
    sm = SlotManager(1)
    s = sm.alloc(1)
    with pytest.raises(CapacityError):
        sm.alloc(2)
    sm.release(s)
    with pytest.raises(AllocatorError):
        sm.release(s)


def test_slot_manager_audit_catches_leak():
    sm = SlotManager(2)
    sm.alloc(1)
    sm._owner.pop(0)  # simulate a lost slot (neither free nor owned)
    with pytest.raises(InvariantError):
        sm.audit()


# ---------------------------------------------------------------------------
# token staging (host<->device byte planes)
# ---------------------------------------------------------------------------


def test_token_planes_lossless_and_host_device_parity():
    ids = np.array([0, 1, 255, 256, 65535, 99999, 151935], np.int32)
    for width in (1, 2, 3, 4):
        sub = ids[ids < 2 ** (8 * width)]
        host = pack_tokens_host(sub, width)
        dev = np.asarray(pack_tokens(jnp.asarray(sub), width))
        assert host.dtype == np.uint8 and host.shape == (width,) + sub.shape
        np.testing.assert_array_equal(host, dev)
        np.testing.assert_array_equal(unpack_tokens_host(host), sub)
        np.testing.assert_array_equal(
            np.asarray(unpack_tokens(jnp.asarray(host))), sub
        )


def test_token_wire_width_adapts_to_vocab():
    # compressing policies stage the lossless floor, never narrower
    assert CompressionPolicy(round_to=1).token_wire_width(256) == 1
    assert CompressionPolicy(round_to=1).token_wire_width(257) == 2
    assert CompressionPolicy(round_to=2).token_wire_width(151936) == 3
    assert CompressionPolicy(round_to=3).token_wire_width(512) == 3
    # uncompressed policy = raw int32 staging (the fp32-baseline analogue)
    assert CompressionPolicy(round_to=4).token_wire_width(512) == 4
    assert CompressionPolicy(round_to=2).token_host_bytes(10, 512) == 20


# ---------------------------------------------------------------------------
# scheduler end-to-end contracts
# ---------------------------------------------------------------------------


def test_continuous_matches_static_mixed_lengths(engine, setup, static_streams):
    cfg = setup[0]
    reqs = _requests(cfg)
    results = engine.run(reqs)
    assert set(results) == {r.rid for r in reqs}
    for r in reqs:
        assert isinstance(results[r.rid], GenResult)
        assert results[r.rid].tokens == static_streams[r.rid], r.rid
    # with 2 slots and 5 requests, admissions must have been staggered
    assert max(g.admitted_step for g in results.values()) > 0


def test_no_slot_leaks_across_admit_evict_cycles(engine, setup):
    cfg = setup[0]
    reqs = _requests(cfg)
    engine.run(reqs)
    audit = engine.slots.audit()
    assert audit["active"] == 0 and audit["free"] == SLOTS
    assert audit["allocs"] == audit["releases"]
    assert audit["allocs"] >= len(reqs)  # every request got a slot


def test_deterministic_streams_regardless_of_arrival_order(engine, setup):
    cfg = setup[0]
    reqs = _requests(cfg)
    a = engine.run(reqs)
    b = engine.run(list(reversed(reqs)))
    for r in reqs:
        assert a[r.rid].tokens == b[r.rid].tokens, r.rid


def test_wire_log_pins_analytic_serve_model(engine, setup):
    cfg, _, _, _, plan = setup
    reqs = _requests(cfg)
    engine.run(reqs)
    measured = engine.wire_summary()
    analytic = serve_host_device_bytes(
        plan, cfg.vocab_size, n_slots=SLOTS,
        prompt_lens=[len(r.prompt_ids) for r in reqs],
        decode_steps=measured["decode_steps"],
    )
    assert measured["host_device"] == analytic["total"]
    assert measured["token_width"] == analytic["token_width"]
    # per-step: admissions stage prompt+first token, decode the full batch
    w = measured["token_width"]
    by_rid = {r.rid: len(r.prompt_ids) for r in reqs}
    admit_order = [r.rid for r in reqs]  # engine admits in list order
    i = 0
    for rec in engine.step_log:
        expect = 0
        for _ in range(rec["admitted"]):
            expect += w * (by_rid[admit_order[i]] + 1)
            i += 1
        if rec["decoded"]:
            expect += 2 * w * SLOTS
        assert rec["host_device"] == expect, rec


def test_stop_on_eos_truncates_and_matches_static(engine, setup):
    cfg, mesh_cfg, spec_tree, storage, plan = setup
    base = _requests(cfg)[:2]
    free_run = engine.run(base)
    # pick an id the longer stream actually emits mid-way as the eos
    target = free_run[1].tokens[2]
    reqs = [
        base[0],
        Request(rid=1, prompt_ids=base[1].prompt_ids,
                max_new=base[1].max_new, eos_id=target),
    ]
    results = engine.run(reqs)
    want = free_run[1].tokens[: free_run[1].tokens.index(target) + 1]
    assert results[1].tokens == want
    assert results[1].tokens[-1] == target
    ref = generate_static(
        cfg, mesh_cfg, None, spec_tree, storage, reqs, plan=plan
    )
    for r in reqs:
        assert results[r.rid].tokens == ref[r.rid]


def test_int8_kv_continuous_matches_static(setup):
    cfg, mesh_cfg, spec_tree, storage, plan = setup
    import dataclasses

    plan8 = dataclasses.replace(plan, int8_kv=True)
    reqs = _requests(cfg, spec=((12, 5), (8, 6), (12, 4)))
    engine = ServeEngine(
        cfg, mesh_cfg, None, spec_tree, storage, plan=plan8,
        max_slots=2, cache_capacity=CAPACITY,
    )
    results = engine.run(reqs)
    ref = generate_static(
        cfg, mesh_cfg, None, spec_tree, storage, reqs, plan=plan8
    )
    for r in reqs:
        assert results[r.rid].tokens == ref[r.rid], r.rid


def test_engine_restores_from_checkpoint(tmp_path, setup, engine, static_streams):
    cfg, mesh_cfg, spec_tree, storage, plan = setup
    path = str(tmp_path / "served")
    momentum = jax.tree_util.tree_map(jnp.zeros_like, storage)
    save_checkpoint(path, storage, momentum, None, 3, plan=plan)
    restored_plan = load_plan(path)
    assert restored_plan == plan.broadcast(len(plan.weights))
    like = jax.tree_util.tree_map(jnp.zeros_like, storage)
    restored, step = load_storage(path, like)  # weights-only serve restore
    assert step == 3
    eng = ServeEngine(
        cfg, mesh_cfg, None, spec_tree, restored, plan=restored_plan,
        max_slots=SLOTS, cache_capacity=CAPACITY,
    )
    results = eng.run(_requests(cfg)[:2])
    for rid in (0, 1):
        assert results[rid].tokens == static_streams[rid]


def test_windowed_ring_decode_matches_static(setup):
    # capacity == window -> the cache rings; prompt+gen exceed capacity
    # so the ring genuinely wraps, and the masked linear cache of the
    # static reference must still agree token for token
    cfg, mesh_cfg, spec_tree, storage, plan = setup
    window = 12
    reqs = _requests(cfg, spec=((16, 8), (10, 8)))
    engine = ServeEngine(
        cfg, mesh_cfg, None, spec_tree, storage, plan=plan,
        max_slots=2, cache_capacity=window, window=window,
    )
    results = engine.run(reqs)
    ref = generate_static(
        cfg, mesh_cfg, None, spec_tree, storage, reqs, plan=plan,
        window=window,
    )
    for r in reqs:
        assert results[r.rid].tokens == ref[r.rid], r.rid


def test_non_ring_window_capacity_is_rejected(setup):
    # window set but capacity > window: the cache stays linear (mha only
    # rings when C <= window), so an overflowing request must be refused
    # up front instead of silently dropping its KV writes
    cfg, mesh_cfg, spec_tree, storage, plan = setup
    engine = ServeEngine(
        cfg, mesh_cfg, None, spec_tree, storage, plan=plan,
        max_slots=1, cache_capacity=20, window=12,
    )
    with pytest.raises(ValueError, match="does not ring"):
        engine.run([Request(rid=0, prompt_ids=(1,) * 16, max_new=8)])
    # ring narrower than the window: wrapping would evict tokens the
    # attention mask still wants — refused rather than silently diverging
    narrow = ServeEngine(
        cfg, mesh_cfg, None, spec_tree, storage, plan=plan,
        max_slots=1, cache_capacity=10, window=16,
    )
    with pytest.raises(ValueError, match="live tokens would be evicted"):
        narrow.run([Request(rid=0, prompt_ids=(1,) * 8, max_new=8)])
    # ...but a narrow ring the request never wraps is fine
    narrow.run([Request(rid=1, prompt_ids=(1, 2, 3), max_new=2)])


def test_moe_engine_matches_per_request_static():
    # MoE decode routes the slot batch through one capacity dispatch;
    # with max_slots * top_k <= 8 (the capacity floor) no token drops, so
    # streams stay companion-independent and match per-request (batch-of-
    # 1) static references — the comparison the launcher's --check-static
    # uses for MoE archs (grouped prefill would change capacity pressure)
    cfg = reduced(get_config("mixtral-8x7b"))
    assert cfg.num_experts and 2 * cfg.top_k <= 8
    mesh_cfg = MeshCfg(tp=1, dp=1, compress_min_size=4096)
    params, metas = init_params(cfg, jax.random.PRNGKey(0), tp=1)
    spec_tree = build_spec_tree(params, metas, mesh_cfg)
    storage = tree_to_storage(params, spec_tree, mesh_cfg)
    plan = PrecisionPlan(
        weights=(CompressionPolicy(round_to=2),) * (cfg.num_groups + 1),
        host_device=CompressionPolicy(round_to=2),
    )
    reqs = _requests(cfg, spec=((12, 4), (8, 5), (12, 3)))
    engine = ServeEngine(
        cfg, mesh_cfg, None, spec_tree, storage, plan=plan,
        max_slots=2, cache_capacity=CAPACITY,
    )
    results = engine.run(reqs)
    for r in reqs:
        ref = generate_static(
            cfg, mesh_cfg, None, spec_tree, storage, [r], plan=plan
        )
        assert results[r.rid].tokens == ref[r.rid], r.rid


def test_request_validation(engine):
    with pytest.raises(ValueError):
        Request(rid=0, prompt_ids=(), max_new=4)
    with pytest.raises(ValueError):
        Request(rid=0, prompt_ids=(1,), max_new=0)
    with pytest.raises(ValueError):  # prompt + gen beyond cache capacity
        engine.run([Request(rid=0, prompt_ids=(1,) * 20,
                            max_new=CAPACITY)])
    with pytest.raises(ValueError):  # duplicate rid
        engine.run([
            Request(rid=0, prompt_ids=(1, 2), max_new=1),
            Request(rid=0, prompt_ids=(3, 4), max_new=1),
        ])


# ---------------------------------------------------------------------------
# block-paged KV cache (page pool + page table + shared-prefix interning)
# ---------------------------------------------------------------------------

from repro.models.attention import init_cache
from repro.roofline.analysis import serve_paged_kv_bytes
from repro.serve.engine import PageAllocator

PAGE = 8


def _paged_engine(setup, plan=None, **kw):
    cfg, mesh_cfg, spec_tree, storage, default_plan = setup
    kw.setdefault("max_slots", SLOTS)
    kw.setdefault("cache_capacity", CAPACITY)
    kw.setdefault("page_size", PAGE)
    return ServeEngine(
        cfg, mesh_cfg, None, spec_tree, storage,
        plan=plan or default_plan, paged=True, **kw,
    )


def test_page_allocator_refcount_and_audit():
    pa = PageAllocator(4)
    a, b = pa.alloc(2)
    assert (a, b) == (0, 1)
    pa.retain(a)  # shared-prefix second holder
    assert pa.refcount(a) == 2
    assert not pa.release(a)  # still one holder -> not freed
    assert pa.release(a)  # last holder -> freed
    assert pa.release(b)
    audit = pa.audit()
    assert audit["live"] == 0 and audit["free"] == 4
    assert audit["allocs"] == audit["releases"] + audit["live"]
    with pytest.raises(AllocatorError):
        pa.release(a)  # double free
    with pytest.raises(CapacityError):
        pa.alloc(5)  # exhaustion
    pa._refs[9] = 1  # simulate a leaked page
    with pytest.raises(InvariantError):
        pa.audit()


def test_paged_matches_contiguous_and_static(engine, setup, static_streams):
    # mixed prompt lengths, slot reuse after release (5 requests, 2
    # slots), prompt bucketing active: streams must stay BIT-EXACT
    cfg = setup[0]
    reqs = _requests(cfg)
    paged = _paged_engine(setup)
    results = paged.run(reqs)
    cont = engine.run(reqs)
    for r in reqs:
        assert results[r.rid].tokens == static_streams[r.rid], r.rid
        assert results[r.rid].tokens == cont[r.rid].tokens, r.rid
    paged.slots.audit()
    audit = paged.pages.audit()
    assert audit["live"] == 0
    assert audit["allocs"] == audit["releases"] >= len(reqs)
    # bucketing: lens (16,12,16,8,12) at page 8 -> buckets {16, 8},
    # so 2 compiles serve all 5 admissions
    w = paged.wire_summary()
    assert w["prefill_misses"] == 2
    assert w["prefill_hits"] == 3


def test_paged_int8_kv_matches_static(setup):
    import dataclasses

    cfg, mesh_cfg, spec_tree, storage, plan = setup
    plan8 = dataclasses.replace(plan, int8_kv=True)
    reqs = _requests(cfg, spec=((12, 5), (8, 6), (12, 4)))
    results = _paged_engine(setup, plan=plan8).run(reqs)
    ref = generate_static(
        cfg, mesh_cfg, None, spec_tree, storage, reqs, plan=plan8
    )
    for r in reqs:
        assert results[r.rid].tokens == ref[r.rid], r.rid


def test_paged_shared_prefix_refcount_and_residency(setup):
    # 3 requests share a 2-page system prompt; all resident at once, so
    # the measured peak must equal the analytic page-granular model:
    # shared pages stored ONCE + per-request private tails
    cfg, mesh_cfg, spec_tree, storage, plan = setup
    rng = np.random.default_rng(3)
    shared = tuple(int(t) for t in rng.integers(0, cfg.vocab_size, 2 * PAGE))
    tails, gen = (4, 9, 12), 6
    reqs = [
        Request(rid=i, prompt_ids=shared + tuple(
            int(t) for t in rng.integers(0, cfg.vocab_size, t)),
            max_new=gen)
        for i, t in enumerate(tails)
    ]
    eng = _paged_engine(setup, max_slots=len(reqs), cache_capacity=40)
    results = eng.run(reqs)
    ref = generate_static(
        cfg, mesh_cfg, None, spec_tree, storage, reqs, plan=plan
    )
    for r in reqs:
        assert results[r.rid].tokens == ref[r.rid], r.rid
    analytic = serve_paged_kv_bytes(
        cfg, page_size=PAGE,
        requests=[(len(r.prompt_ids), gen) for r in reqs],
        shared_prefix_len=len(shared),
    )
    assert analytic["shared_pages"] == 2
    res = eng.kv_residency()
    assert res["bytes_per_page"] == analytic["bytes_per_page"]
    assert res["pages_peak"] == analytic["pages"]
    assert res["kv_bytes_peak"] == analytic["kv_bytes_resident"]
    # every retirement dropped its refcounts back to zero
    assert res["pages_live"] == 0 and res["kv_bytes_resident"] == 0
    audit = eng.pages.audit()
    assert audit["live"] == 0 and audit["allocs"] == audit["releases"]
    # sharing actually deduped: without it every request would intern
    # its own copy of the 2 shared pages
    no_share = sum(-(-(len(r.prompt_ids) + gen) // PAGE) for r in reqs)
    assert analytic["pages"] == no_share - 2 * (len(reqs) - 1) < no_share


def test_paged_wire_log_pins_analytic_serve_model(setup):
    cfg, _, _, _, plan = setup
    reqs = _requests(cfg)
    eng = _paged_engine(setup)
    eng.run(reqs)
    measured = eng.wire_summary()
    analytic = serve_host_device_bytes(
        plan, cfg.vocab_size, n_slots=SLOTS,
        prompt_lens=[len(r.prompt_ids) for r in reqs],
        decode_steps=measured["decode_steps"],
        page_table_entries=measured["page_table_entries"],
    )
    assert measured["host_device"] == analytic["total"]
    assert measured["page_table"] == analytic["page_table_h2d"]


def test_paged_attend_pages_counts_live_prefixes(setup):
    # 5 requests through 2 slots: retirements, slot reuse and, at the
    # end, a retired slot decoding as ballast beside a live one. A
    # request admitted at step a with an S-token prompt decodes in
    # steps a .. finished_step, at pos S + (t - a) before step t, so the
    # kernel walks ceil((pos + 1) / page) of its entries; retired and
    # empty slots walk none
    cfg = setup[0]
    reqs = _requests(cfg)
    eng = _paged_engine(setup)
    results = eng.run(reqs)
    ticks = [r for r in eng.step_log if r["decoded"]]
    assert [r["step"] for r in eng.step_log if "attend_pages" in r] == [
        r["step"] for r in ticks]
    for rec in ticks:
        t = rec["step"]
        want = sum(
            -(-(len(r.prompt_ids) + t - res.admitted_step + 1) // PAGE)
            for r in reqs
            for res in [results[r.rid]]
            if res.admitted_step <= t <= res.finished_step
        )
        assert rec["attend_pages"] == want, t
    w = eng.wire_summary()
    walked = sum(r["attend_pages"] for r in ticks)
    assert w["attend_pages"] == walked
    assert w["attend_walked_share"] == walked / (
        len(ticks) * SLOTS * -(-CAPACITY // PAGE))
    assert 0 < w["attend_walked_share"] < 1


def test_paged_rejects_windows_and_oversized_requests(setup):
    cfg, mesh_cfg, spec_tree, storage, plan = setup
    with pytest.raises(ValueError, match="contiguous"):
        _paged_engine(setup, window=12)
    eng = _paged_engine(setup, num_pages=2)
    with pytest.raises(ValueError, match="pages"):
        eng.run([Request(rid=0, prompt_ids=(1,) * 16, max_new=8)])


def test_cache_constructor_geometry_guard():
    # the admission-time window/capacity rules now live in the cache
    # constructors: a linear cache too small for its context, and a ring
    # narrower than its window, both fail at construction
    with pytest.raises(ValueError, match="does not ring"):
        init_cache(1, 20, 2, 8, jnp.float32, window=12, context=24)
    with pytest.raises(ValueError, match="live tokens would be evicted"):
        init_cache(1, 10, 2, 8, jnp.float32, window=16, context=18)
    with pytest.raises(ValueError, match="no sliding window"):
        init_cache(1, 16, 2, 8, jnp.float32, context=24)
    # capacity == window rings faithfully; fitting contexts are fine
    init_cache(1, 12, 2, 8, jnp.float32, window=12, context=24)
    init_cache(1, 24, 2, 8, jnp.float32, context=24)
