"""Continuous-batching serve engine (`repro.serve.engine`).

The production serving loop the ROADMAP left open: a request queue is
drained through a **slotted KV cache** — ``max_slots`` resident requests
decode together as one fixed-shape batch, and whenever a slot frees up
(stop condition hit) the scheduler admits the next queued prompt
*between decode steps* (prefill/decode interleave). Every slot carries
its own absolute position (``init_caches(per_slot=True)`` →
``(reps, slots)`` KV position vectors), so mixed prompt lengths and
staggered admissions coexist in one compiled decode program.

Data-motion story (the paper's host<->device boundary, finally exercised
by serving traffic): prompts enter and sampled ids leave through the
plan's ``host_device`` :class:`~repro.transport.CompressionPolicy` entry
— token ids are staged as lossless byte planes
(:mod:`repro.transport.hostdev`) at
:meth:`~repro.transport.CompressionPolicy.token_wire_width` bytes each,
and the engine logs the **measured** staged bytes per step
(:attr:`ServeEngine.step_log`, the serving twin of the trainer's
``StepRecord.wire_by_entry``). The analytic mirror lives in
:func:`repro.roofline.analysis.serve_host_device_bytes`; the two are
pinned equal by ``tests/test_serve_engine.py``.

Determinism contract: sampling is greedy and slots are independent, so
every request's token stream is a pure function of its prompt — byte
for byte the same regardless of arrival order, slot assignment, or what
else shares the batch, and bit-exact against the static one-shot
reference (:func:`generate_static`). Caveat for MoE archs: the capacity
dispatch ranks the *whole* batch's tokens per expert, so decode couples
slots once a single expert can be offered more than ``capacity`` tokens
— keep ``max_slots * top_k <= 8`` (the dispatch capacity floor) for a
drop-free, companion-independent decode, and note the batched static
reference prefills requests *together* while the engine prefills one at
a time, which changes MoE prefill capacity pressure: compare MoE archs
against per-request (batch-of-1) references. Vision cross-attention
archs are rejected (image payloads are not token-stageable; the static
launcher path still serves them).

Engine compilation surface: ONE decode program (fixed ``(slots, 1)``
shape) plus one prefill program per distinct prompt length — bucket
arrival lengths if that set is unbounded.

``paged=True`` swaps the contiguous slotted layout for the **block-paged
KV cache**: fixed-size pages in a slot-global pool, a host-side per-slot
page table staged each decode step, a refcounted :class:`PageAllocator`
(``SlotManager``'s page-granular twin), shared-prefix page interning
(a common system prompt is resident ONCE, copy-on-write), and prompt
bucketing to page granularity so one prefill program serves a whole
bucket. Streams stay bit-exact vs the contiguous engine and
``generate_static``; see docs/serving.md §paged for the layout and
lifecycle.
"""
from __future__ import annotations

import collections
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.dist.spec import MeshCfg
from repro.models import model as M
from repro.plan import PrecisionPlan, SamplingParams
from repro.serve.api import Request
from repro.serve.sampling import sample_tokens
from repro.serve.spec import (
    DraftBundle,
    DraftRunner,
    check_spec_arch,
    rollback_caches,
)
from repro.serve.step import (
    global_cache_shapes,
    make_decode_step,
    make_place_step,
    make_prefill_step,
    make_verify_step,
)
from repro.transport.hostdev import (
    pack_tokens,
    pack_tokens_host,
    stage,
    unpack_tokens,
    unpack_tokens_host,
)

__all__ = [
    "AllocatorError",
    "CapacityError",
    "CapacityWarning",
    "GenResult",
    "InvariantError",
    "Request",
    "SamplingParams",
    "ServeEngine",
    "generate_static",
]


# ---------------------------------------------------------------------------
# request / result types (Request itself lives in repro.serve.api — the
# unified submit surface — and is re-exported here for compatibility)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GenResult:
    """Completed generation: emitted ids in order (eos included if hit)."""

    rid: int
    prompt_len: int
    tokens: list[int]
    admitted_step: int
    finished_step: int


@dataclasses.dataclass
class _ReqState:
    req: Request
    slot: int
    admitted_step: int
    tokens: list[int] = dataclasses.field(default_factory=list)

    def emit(self, tok: int) -> bool:
        """Record one sampled id; True when the request just finished."""
        self.tokens.append(tok)
        if self.req.eos_id is not None and tok == self.req.eos_id:
            return True
        return len(self.tokens) >= self.req.max_new


# ---------------------------------------------------------------------------
# typed failures
# ---------------------------------------------------------------------------


class CapacityWarning(UserWarning):
    """A configuration exceeds a soft capacity floor (currently: MoE
    dispatch capacity at engine construction) — decode may couple slots
    and break the per-request determinism contract. Typed so callers
    and tests filter/assert it instead of string-matching."""


class CapacityError(RuntimeError):
    """A resource pool is exhausted: no free slot, not enough free
    pages, or the drain loop hit its step budget with requests still
    unfinished. Retryable in principle — the request, not the engine,
    is at fault."""


class AllocatorError(RuntimeError):
    """Allocator API misuse: double allocation, release of an unowned
    slot, retain/release of a dead page. The caller's bookkeeping is
    wrong; the pool itself is still consistent."""


class InvariantError(AssertionError):
    """An internal conservation audit failed (slot/page leak, counter
    imbalance): engine state is corrupt and the instance should be
    discarded. Subclasses :class:`AssertionError` because these are
    self-checks on the engine's own bookkeeping, not caller errors."""


# ---------------------------------------------------------------------------
# slot manager
# ---------------------------------------------------------------------------


class SlotManager:
    """KV-slot allocator with leak-audit counters.

    Slots are the unit of cache residency: ``alloc`` hands the lowest
    free slot to a request at admission, ``release`` returns it at
    retirement. :meth:`audit` asserts the conservation invariant (every
    slot is exactly free xor owned, allocs == releases + active) — the
    scheduler-invariant tests drive it after every admit/evict cycle.
    """

    def __init__(self, n_slots: int):
        if n_slots < 1:
            raise ValueError("need at least one slot")
        self.n_slots = n_slots
        self._free = list(range(n_slots - 1, -1, -1))  # pop() -> lowest first
        self._owner: dict[int, int] = {}  # slot -> rid
        self.alloc_count = 0
        self.release_count = 0

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def active(self) -> dict[int, int]:
        return dict(self._owner)

    def alloc(self, rid: int) -> int:
        if not self._free:
            raise CapacityError("no free slot")
        slot = self._free.pop()
        if slot in self._owner:
            raise AllocatorError(f"slot {slot} double-allocated")
        self._owner[slot] = rid
        self.alloc_count += 1
        return slot

    def release(self, slot: int) -> None:
        if slot not in self._owner:
            raise AllocatorError(f"release of unowned slot {slot}")
        del self._owner[slot]
        self._free.append(slot)
        self.release_count += 1

    def audit(self) -> dict:
        free, owned = set(self._free), set(self._owner)
        if free & owned:
            raise InvariantError(f"slots both free and owned: {free & owned}")
        if len(self._free) != len(free):
            raise InvariantError("duplicate entries in the free list")
        if free | owned != set(range(self.n_slots)):
            raise InvariantError("slot leak: free ∪ owned != all slots")
        if self.alloc_count != self.release_count + len(owned):
            raise InvariantError("alloc/release counters out of balance")
        return {
            "free": len(free),
            "active": len(owned),
            "allocs": self.alloc_count,
            "releases": self.release_count,
        }


class PageAllocator:
    """Free-page allocator with refcounts — the page-granular twin of
    :class:`SlotManager`, same leak-audit contract.

    Pages are the unit of KV residency in the paged layout: ``alloc``
    hands out physical pool rows at admission, ``retain`` adds a
    reference when a shared-prefix page is reused (CoW sharing: shared
    pages are immutable by construction — decode only ever writes a
    slot's private tail pages), ``release`` drops one reference and
    returns the page to the free list when the count hits zero.
    :meth:`audit` asserts conservation (free xor live, allocs ==
    releases + live)."""

    def __init__(self, num_pages: int):
        if num_pages < 1:
            raise ValueError("need at least one page")
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, -1, -1))  # pop() -> lowest
        self._refs: dict[int, int] = {}  # page -> refcount
        self.alloc_count = 0
        self.release_count = 0
        self.peak = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def live_pages(self) -> int:
        return len(self._refs)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise CapacityError(f"need {n} pages, {len(self._free)} free")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            if p in self._refs:
                raise AllocatorError(f"page {p} double-allocated")
            self._refs[p] = 1
        self.alloc_count += n
        self.peak = max(self.peak, len(self._refs))
        return pages

    def retain(self, page: int) -> None:
        if page not in self._refs:
            raise AllocatorError(f"retain of dead page {page}")
        self._refs[page] += 1

    def release(self, page: int) -> bool:
        """Drop one reference; True when the page was actually freed."""
        if page not in self._refs:
            raise AllocatorError(f"release of dead page {page}")
        self._refs[page] -= 1
        if self._refs[page] > 0:
            return False
        del self._refs[page]
        self._free.append(page)
        self.release_count += 1
        return True

    def audit(self) -> dict:
        free, live = set(self._free), set(self._refs)
        if free & live:
            raise InvariantError(f"pages both free and live: {free & live}")
        if len(self._free) != len(free):
            raise InvariantError("duplicate entries in the free page list")
        if free | live != set(range(self.num_pages)):
            raise InvariantError("page leak: free ∪ live != all pages")
        if any(c < 1 for c in self._refs.values()):
            raise InvariantError("live page with refcount < 1")
        if self.alloc_count != self.release_count + len(live):
            raise InvariantError("page alloc/release counters out of balance")
        return {
            "free": len(free),
            "live": len(live),
            "allocs": self.alloc_count,
            "releases": self.release_count,
            "peak": self.peak,
        }


def _page_pool_bytes(caches) -> int:
    """Global bytes ONE page occupies summed over every paged pool node
    (all groups x reps x K/V, plus int8 scale planes). Works on the
    ``global_cache_shapes`` tree (ShapeDtypeStructs) or live arrays."""
    per_page = 0
    for group in caches:
        for node in group.values():
            if isinstance(node, (M.PagedKVCache, M.PagedQuantKVCache)):
                leaves = [node.k, node.v]
                if isinstance(node, M.PagedQuantKVCache):
                    leaves += [node.k_scale, node.v_scale]
                for leaf in leaves:
                    P = leaf.shape[1]  # stacked (R, P, page, ...)
                    size = int(np.prod(leaf.shape))
                    per_page += size * jnp.dtype(leaf.dtype).itemsize // P
    return per_page


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class ServeEngine:
    """Continuous-batching driver over ``make_prefill_step`` /
    ``make_decode_step`` (see module docstring).

    Parameters mirror the step factories; ``storage`` is the sharded
    weight tree (``tree_to_storage``), ``plan`` the
    :class:`~repro.plan.PrecisionPlan` driving every precision choice
    including the ``host_device`` staging entry. ``cache_capacity`` caps
    ``prompt_len + max_new_tokens`` per request (validated at submit).
    ``name`` keys the engine's :class:`~repro.obs.Recorder` (``self.obs``)
    in the process-wide index; engines given one name share it.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        mesh_cfg: MeshCfg,
        mesh,
        spec_tree,
        storage,
        *,
        plan: PrecisionPlan,
        max_slots: int,
        cache_capacity: int,
        window: int | None = None,
        weight_stationary: bool = False,
        paged: bool = False,
        page_size: int = 64,
        num_pages: int | None = None,
        share_prefix: bool = True,
        draft: DraftBundle | None = None,
        spec_k: int | None = None,
        name: str = "serve",
    ):
        if not cfg.causal:
            raise ValueError(f"{cfg.name} is encoder-only: nothing to serve")
        if cfg.num_image_tokens or cfg.embed_is_input_stub:
            raise ValueError(
                f"{cfg.name}: the serve engine stages token payloads only "
                "(no image/feature requests)"
            )
        if cfg.num_experts and max_slots * cfg.top_k > 8:
            warnings.warn(
                f"{cfg.name}: max_slots={max_slots} x top_k={cfg.top_k} "
                "exceeds the MoE dispatch capacity floor (8) — congested "
                "experts may drop ranked decode tokens, coupling slots "
                "(see the determinism contract in repro.serve.engine)",
                CapacityWarning,
                stacklevel=2,
            )
        self.cfg = cfg
        self.mesh_cfg = mesh_cfg
        self.mesh = mesh
        self.spec_tree = spec_tree
        self.storage = storage
        self.plan = plan.broadcast(cfg.num_groups + 1)
        self.max_slots = int(max_slots)
        self.cache_capacity = int(cache_capacity)
        self.window = window
        self.paged = bool(paged)
        self.page_size = int(page_size)
        if self.paged:
            if self.page_size < 1:
                raise ValueError("page_size must be >= 1")
            if window is not None or cfg.sliding_window:
                raise ValueError(
                    f"{cfg.name}: paged serving keeps the full context "
                    "resident — sliding-window (ring) serving stays on the "
                    "contiguous layout"
                )
        self.spec_k = int(spec_k) if spec_k is not None else self.plan.spec_k
        if draft is not None:
            check_spec_arch(cfg, window=window)
            if self.spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            if draft.cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft.cfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size} — draft ids must be target ids"
                )
        # page-table width: capacity rounded up to whole pages; under
        # speculative decoding the verify block can write up to spec_k
        # positions past a finished stream, so widen the table enough
        # that clamped block writes land in trash entries, never a
        # live page
        spec_pad = self.spec_k if draft is not None else 0
        self._table_width = -(
            -(self.cache_capacity + spec_pad) // self.page_size
        )
        self.num_pages = (
            int(num_pages) if num_pages is not None
            else self.max_slots * self._table_width
        )
        # padded (page-bucketed) prompts are causal-safe only for pure-
        # attention patterns: MoE capacity dispatch ranks tokens across the
        # sequence and recurrent state absorbs pad positions
        self._bucket = (
            self.paged
            and not cfg.num_experts
            and all(k == "attn" for k in cfg.pattern)
        )
        # prefix pages are bit-shareable only when position i depends on
        # tokens <= i alone; the MoE dispatch breaks that per-position
        # causality (capacity ranking sees the whole sequence)
        self.share_prefix = (
            bool(share_prefix) and self.paged and not cfg.num_experts
        )
        self.host_policy = self.plan.host_device_policies()[0]
        self.token_width = self.host_policy.token_wire_width(cfg.vocab_size)
        self.slots = SlotManager(self.max_slots)
        self.pages = PageAllocator(self.num_pages) if self.paged else None
        self._intern: dict[tuple, int] = {}  # prompt-prefix key -> page
        self._page_key: dict[int, tuple] = {}  # page -> interned key
        self._slot_pages: dict[int, list[int]] = {}  # slot -> page row
        self.step_log: list[dict] = []
        # spans, host gaps, builds and GC pauses, by time (docs/serving.md
        # §observability); outlives the engine in the index under `name`
        self.obs = obs.recorder(name)

        B = self.max_slots
        self._shard_batch = (
            not self.paged  # the page pool has no batch dim to shard
            and mesh_cfg.dshards > 1 and B % mesh_cfg.dshards == 0
        )
        dshapes = {
            "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "pos": jax.ShapeDtypeStruct((B,), jnp.int32),
        }
        if self.paged:
            dshapes["page_table"] = jax.ShapeDtypeStruct(
                (B, self._table_width), jnp.int32
            )
        self._decode = make_decode_step(
            cfg, mesh_cfg, mesh, spec_tree, dshapes, plan=self.plan,
            shard_batch=self._shard_batch, window_override=window,
            weight_stationary=weight_stationary, slot_caches=True,
            paged=self.paged,
        )
        self._place = None
        if weight_stationary:
            self._place, _ = make_place_step(
                cfg, mesh_cfg, mesh, spec_tree, plan=self.plan
            )
        self._weights = (
            self._place(storage) if self._place is not None else storage
        )
        self._prefill_cache: dict[int, object] = {}
        self._cache_dtype = self.plan.compute_dtype
        self._unpack = jax.jit(unpack_tokens)
        vocab = cfg.vocab_size
        width = self.token_width

        def sample_pack(logits):
            tok = jnp.argmax(
                logits[:, -1, :vocab], axis=-1
            ).astype(jnp.int32)  # (B,)
            return tok, pack_tokens(tok, width)

        self._sample = jax.jit(sample_pack)

        def sample_rng_pack(logits, temp, top_p, top_k, seed, step):
            # per-row sampling (docs/serving.md §sampling); temp<=0 rows
            # reduce to the same argmax as sample_pack, so mixed batches
            # keep greedy requests token-identical to the fast path
            tok = sample_tokens(
                logits[:, -1], vocab, temp, top_p, top_k, seed, step
            )
            return tok, pack_tokens(tok, width)

        self._sample_rng = jax.jit(sample_rng_pack)

        def verify_sample_pack(logits, temp, top_p, top_k, seed, step0):
            # (B, T) target samples over the verify block: position j of
            # the block is the candidate emitted index step0 + j, keyed
            # accordingly — identical keys to T successive decode ticks
            T = logits.shape[1]

            def bt(a):
                return jnp.broadcast_to(a[:, None], (a.shape[0], T))

            steps = step0[:, None] + jnp.arange(T, dtype=jnp.int32)
            tok = sample_tokens(
                logits, vocab, bt(temp), bt(top_p), bt(top_k), bt(seed),
                steps,
            )
            return tok, pack_tokens(tok, width)

        self._verify_sample = jax.jit(verify_sample_pack)

        self.draft = None
        self._verify = None
        self._rollback = None
        if draft is not None:
            self._verify = make_verify_step(
                cfg, mesh_cfg, mesh, spec_tree, plan=self.plan,
                n_slots=B, block=self.spec_k + 1,
                shard_batch=self._shard_batch,
                weight_stationary=weight_stationary, paged=self.paged,
                table_width=self._table_width,
            )
            # the draft keeps contiguous per-slot caches with spec_k
            # spare positions (its last micro step absorbs the final
            # proposal before rollback)
            self.draft = DraftRunner(
                draft, mesh_cfg, mesh, plan=self.plan,
                max_slots=B, capacity=self.cache_capacity + self.spec_k,
                spec_k=self.spec_k, token_width=width,
            )
            self._rollback = jax.jit(rollback_caches, donate_argnums=(0,))

        def insert(big, small, slot):
            # prefill caches (batch of 1) -> slot `slot` of the engine
            # caches; the pos leaves are the one rank mismatch: (R,)
            # scalar-per-rep from prefill vs the engine's (R, B) vector
            def one(b, s):
                if b.ndim == s.ndim:
                    return b.at[:, slot].set(s[:, 0])
                return b.at[:, slot].set(s)

            return jax.tree_util.tree_map(one, big, small)

        self._insert = jax.jit(insert, donate_argnums=(0,))

        page = self.page_size

        def insert_paged(big, small, slot, phys, start, pos_val):
            # scatter the prompt's freshly computed KV pages into the pool
            # (shared-prefix hits are already resident and immutable —
            # skipped, so the first writer's bits stay authoritative) and
            # stamp the slot's position; non-paged nodes (recurrent state)
            # keep the contiguous slot insert
            n_new = phys.shape[0]

            def pool_write(b, s):
                # b (R, P, page, ...) pool; s (R, 1, cap_pre, ...) prefill
                seg = jax.lax.dynamic_slice_in_dim(
                    s[:, 0], start, n_new * page, axis=1
                )
                seg = seg.reshape(s.shape[0], n_new, page, *s.shape[3:])
                return b.at[:, phys].set(seg.astype(b.dtype))

            def one_node(bn, sn):
                if isinstance(bn, M.PagedQuantKVCache):
                    return M.PagedQuantKVCache(
                        pool_write(bn.k, sn.k), pool_write(bn.v, sn.v),
                        pool_write(bn.k_scale, sn.k_scale),
                        pool_write(bn.v_scale, sn.v_scale),
                        bn.pos.at[:, slot].set(pos_val),
                    )
                if isinstance(bn, M.PagedKVCache):
                    return M.PagedKVCache(
                        pool_write(bn.k, sn.k), pool_write(bn.v, sn.v),
                        bn.pos.at[:, slot].set(pos_val),
                    )

                def one(b, s):
                    if b.ndim == s.ndim:
                        return b.at[:, slot].set(s[:, 0])
                    return b.at[:, slot].set(s)

                return jax.tree_util.tree_map(one, bn, sn)

            return [
                {key: one_node(bn, sg[key]) for key, bn in bg.items()}
                for bg, sg in zip(big, small)
            ]

        self._insert_paged = jax.jit(insert_paged, donate_argnums=(0,))

        def install_pages(big, pages, slot, phys, pos_val):
            # migrated pool pages (already pool dtype, exported by a
            # prefill worker with the same slicing math as pool_write
            # above) scattered into place; position stamped exactly like
            # the local prefill insert
            def one_node(bn, pn):
                if isinstance(bn, M.PagedQuantKVCache):
                    return M.PagedQuantKVCache(
                        bn.k.at[:, phys].set(pn["k"]),
                        bn.v.at[:, phys].set(pn["v"]),
                        bn.k_scale.at[:, phys].set(pn["k_scale"]),
                        bn.v_scale.at[:, phys].set(pn["v_scale"]),
                        bn.pos.at[:, slot].set(pos_val),
                    )
                if isinstance(bn, M.PagedKVCache):
                    return M.PagedKVCache(
                        bn.k.at[:, phys].set(pn["k"]),
                        bn.v.at[:, phys].set(pn["v"]),
                        bn.pos.at[:, slot].set(pos_val),
                    )
                raise TypeError(
                    "migrated admission covers paged pools only "
                    f"(got {type(bn).__name__})"
                )

            return [
                {key: one_node(bn, pg[key]) for key, bn in bg.items()}
                for bg, pg in zip(big, pages)
            ]

        self._install_pages = (
            jax.jit(install_pages, donate_argnums=(0,)) if self.paged
            else None
        )
        self._page_bytes = (
            _page_pool_bytes(self._cache_shapes()) if self.paged else 0
        )
        # streaming state (populated by begin_stream; run() wraps it)
        self._caches = None
        self._next_tok = np.zeros((B,), np.int32)
        self._pos_host = np.zeros((B,), np.int32)
        self._active: dict[int, _ReqState] = {}
        self._results: dict[int, GenResult] = {}
        self._step = 0
        self._rec: dict | None = None
        self._reset_sampling_state()

    def _reset_sampling_state(self) -> None:
        """Per-slot SamplingParams mirrors fed to the jitted samplers.
        Ballast rows (free/retired slots) are greedy with seed 0 —
        their draws are discarded, and per-row sampling keeps them from
        touching live rows."""
        B = self.max_slots
        self._temp = np.zeros((B,), np.float32)
        self._top_p = np.ones((B,), np.float32)
        self._top_k = np.zeros((B,), np.int32)
        self._seed = np.zeros((B,), np.uint32)
        self._nemit = np.zeros((B,), np.int32)  # emitted-token counts

    def _set_sampling_slot(self, slot: int, s: SamplingParams) -> None:
        self._temp[slot] = s.temperature
        self._top_p[slot] = s.top_p
        self._top_k[slot] = s.top_k
        self._seed[slot] = s.seed
        self._nemit[slot] = 0

    def _clear_sampling_slot(self, slot: int) -> None:
        self._set_sampling_slot(slot, SamplingParams())

    # -- compiled-program plumbing ---------------------------------------
    def _prefill(self, prompt_len: int):
        """One compiled prefill per distinct prompt length (per distinct
        page-*bucket* length when prompt bucketing is on — the paged
        engine pads prompts to page multiples so arrivals share
        programs; padding happens device-side, staging stays at the true
        length)."""
        if prompt_len not in self._prefill_cache:
            plan = self.plan
            if self.paged:
                # batch["last"] (the true last-token gather for padded
                # prompts) needs the replicated layout
                plan = dataclasses.replace(plan, seq_parallel=False)
            elif plan.seq_parallel and prompt_len % max(self.mesh_cfg.tp, 1):
                # seq-parallel needs S % tp == 0; odd lengths fall back to
                # the psum layout (pinned bit-exact by scenario_seq_parallel)
                plan = dataclasses.replace(plan, seq_parallel=False)
            bshapes = {
                "tokens": jax.ShapeDtypeStruct((1, prompt_len), jnp.int32)
            }
            cap = self.cache_capacity
            if self.paged:
                # page-rounded so any padded bucket length fits; the extra
                # tail positions never reach the pool (insert slices whole
                # prompt pages only) and a bigger prefill cache does not
                # change the logits
                cap = self._table_width * self.page_size
                bshapes["last"] = jax.ShapeDtypeStruct((), jnp.int32)
            self._prefill_cache[prompt_len] = make_prefill_step(
                self.cfg, self.mesh_cfg, self.mesh, self.spec_tree, bshapes,
                plan=plan, cache_capacity=cap, shard_batch=False,
                window_override=self.window,
            )
        return self._prefill_cache[prompt_len]

    def _cache_shapes(self):
        return global_cache_shapes(
            self.cfg, self.mesh_cfg, self.max_slots, self.cache_capacity,
            self._cache_dtype, shard_batch=self._shard_batch, per_slot=True,
            int8_kv=self.plan.int8_kv,
            paged_pages=self.num_pages if self.paged else None,
            page_size=self.page_size,
        )

    def _init_caches(self):
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), self._cache_shapes(),
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )

    def _validate(self, req: Request):
        if max(req.prompt_ids) >= self.cfg.vocab_size or min(req.prompt_ids) < 0:
            raise ValueError(f"request {req.rid}: prompt id out of vocab")
        need = len(req.prompt_ids) + req.max_new
        # the geometry rules (linear cache must hold the request; rings
        # only when capacity <= window; narrow rings evict live tokens)
        # live with the cache constructors — same guard, same wording
        M.check_cache_geometry(
            self.cache_capacity, self.window, need,
            label=f"request {req.rid}: prompt+gen ",
        )
        if self.paged:
            need_pages = -(-need // self.page_size)
            if need_pages > self.num_pages:
                raise ValueError(
                    f"request {req.rid}: needs {need_pages} pages of "
                    f"{self.page_size}, the pool has {self.num_pages}"
                )

    def validate_request(self, req: Request) -> None:
        """Public admission-geometry validation (the fleet router's
        submit path — same checks :meth:`run` applies up front)."""
        self._validate(req)

    # -- the streaming surface (the fleet router drives these) ------------
    def begin_stream(self) -> None:
        """Reset allocators, caches and accounting for a fresh stream.

        An aborted previous stream (exception mid-decode) leaves its
        slots owned; every stream starts from a fresh allocator — the
        engine cache is rebuilt here, so stale residency means nothing.
        :meth:`run` calls this internally; the fleet router calls it
        once, then drives :meth:`admit` / :meth:`admit_pages` /
        :meth:`decode_tick` step by step.
        """
        self.slots = SlotManager(self.max_slots)
        B = self.max_slots
        if self.paged:
            self.pages = PageAllocator(self.num_pages)
            self._intern, self._page_key, self._slot_pages = {}, {}, {}
            # host-side page table; index num_pages = the pool's trash row
            # (unused entries and retired slots' ballast writes land there)
            self._table = np.full(
                (B, self._table_width), self.num_pages, np.int32
            )
        self._caches = self._init_caches()
        self._next_tok = np.zeros((B,), np.int32)  # per-slot feed tokens
        self._pos_host = np.zeros((B,), np.int32)  # absorbed-token counts
        self._reset_sampling_state()
        if self.draft is not None:
            self.draft.reset()
        self._active = {}
        self._results = {}
        self._step = 0
        self._rec = None
        self.step_log = []
        self.obs.clear()

    def _ensure_rec(self) -> dict:
        """The current step's record — admissions accumulate into it,
        :meth:`decode_tick` finalizes and appends it."""
        if self._rec is None:
            self._rec = {"step": self._step, "admitted": 0, "active": 0,
                         "decoded": 0, "host_device": 0}
            if self.paged:
                self._rec.update(page_table=0, prefill_hits=0,
                                 prefill_misses=0, kv_migration=0)
            if self.draft is not None:
                self._rec.update(spec_rounds=0, spec_proposed=0,
                                 spec_accepted=0, spec_emitted=0)
        return self._rec

    @property
    def has_work(self) -> bool:
        return bool(self._active)

    @property
    def active_slots(self) -> int:
        return len(self._active)

    @property
    def pending_record(self) -> bool:
        """True when admissions accumulated into a step record that no
        :meth:`decode_tick` has finalized yet."""
        return self._rec is not None

    def _prompt_hits(self, req: Request) -> list[int]:
        """Resident shared-prefix pages for this prompt (longest run of
        interned whole-prompt pages)."""
        hits: list[int] = []
        if self.paged and self.share_prefix:
            page = self.page_size
            for i in range(len(req.prompt_ids) // page):
                pid = self._intern.get(req.prompt_ids[:(i + 1) * page])
                if pid is None:
                    break
                hits.append(pid)
        return hits

    def can_admit(self, req: Request) -> tuple[bool, list[int]]:
        """Admission probe: a free slot and (paged) enough free pages
        once shared-prefix hits are discounted. Returns ``(ok, hits)``
        — the hit page ids let a fleet prefill worker skip resident
        prefix pages when building a migration parcel."""
        hits = self._prompt_hits(req)
        if not self.slots.free_slots:
            return False, hits
        if self.paged:
            need = -(-(len(req.prompt_ids) + req.max_new)
                     // self.page_size)
            if need - len(hits) > self.pages.free_pages:
                return False, hits
        return True, hits

    def _alloc_residency(self, req: Request, hits: list[int]):
        """Allocate the request's slot + page row, intern its new
        whole-prompt pages and stamp the page table. Shared logic
        between local and migrated admission."""
        S = len(req.prompt_ids)
        slot = self.slots.alloc(req.rid)
        row: list[int] = []
        if self.paged:
            page = self.page_size
            need = -(-(S + req.max_new) // page)
            full_pages = S // page  # whole-prompt pages, internable
            for pid in hits:
                self.pages.retain(pid)
            row = hits + self.pages.alloc(need - len(hits))
            for i in range(len(hits), full_pages):
                key = req.prompt_ids[:(i + 1) * page]
                self._intern[key] = row[i]
                self._page_key[row[i]] = key
            self._slot_pages[slot] = list(row)
            self._table[slot, :] = self.num_pages  # trash
            self._table[slot, :len(row)] = row
        return slot, row

    def _finish_admission(self, req: Request, slot: int, first: int,
                          rec: dict) -> None:
        st = _ReqState(req, slot, self._step)
        self._next_tok[slot] = first
        self._pos_host[slot] = len(req.prompt_ids)
        self._set_sampling_slot(slot, req.sampling)
        self._nemit[slot] = 1  # prefill's id is emitted index 0
        rec["admitted"] += 1
        if st.emit(first):
            self._results[req.rid] = self._retire(st, self._step)
        else:
            self._active[slot] = st

    def admit(self, req: Request) -> None:
        """Local-prefill admission of one request (between decode
        steps). Raises :class:`CapacityError` when :meth:`can_admit`
        says no — callers probe first."""
        with self.obs.span("serve.admit"):
            self._admit(req)

    def _admit(self, req: Request) -> None:
        ok, hits = self.can_admit(req)
        if not ok:
            raise CapacityError(
                f"request {req.rid}: no free slot/pages for admission"
            )
        self._validate(req)
        rec = self._ensure_rec()
        S, w, page = len(req.prompt_ids), self.token_width, self.page_size
        slot, row = self._alloc_residency(req, hits)
        o = self.obs
        with o.span("serve.stage"):
            planes = pack_tokens_host(
                np.asarray(req.prompt_ids, np.int32)[None, :], w
            )  # (w, 1, S) — h2d prompt staging (true length, no pads)
            rec["host_device"] += planes.nbytes
            staged = stage(planes)
        with o.span("serve.dispatch") as d:
            o.end_gap(d.start)
            tokens_dev = self._unpack(staged)
            if self.draft is not None:
                # draft mirrors the target's residency from the same
                # staged prompt — one priced h2d crossing covers both
                self.draft.prefill_insert(tokens_dev, slot)
            if self.paged:
                Spad = -(-S // page) * page if self._bucket else S
                rec["prefill_hits" if Spad in self._prefill_cache
                    else "prefill_misses"] += 1
                if Spad > S:
                    tokens_dev = jnp.pad(tokens_dev, ((0, 0), (0, Spad - S)))
                pbatch = {"tokens": tokens_dev,
                          "last": jnp.asarray(S - 1, jnp.int32)}
                logits, pcaches = self._prefill(Spad)(self.storage, pbatch)
                n_hits = len(hits)
                prompt_pages = -(-S // page)
                phys = jnp.asarray(row[n_hits:prompt_pages], jnp.int32)
                self._caches = self._insert_paged(
                    self._caches, pcaches, np.int32(slot), phys,
                    np.int32(n_hits * page), np.int32(S),
                )
            else:
                logits, pcaches = self._prefill(S)(
                    self.storage, {"tokens": tokens_dev}
                )
                self._caches = self._insert(
                    self._caches, pcaches, np.int32(slot)
                )
            s = req.sampling
            if s.greedy:
                _, tok_planes = self._sample(logits)  # byte-identical path
            else:
                _, tok_planes = self._sample_rng(
                    logits,
                    np.asarray([s.temperature], np.float32),
                    np.asarray([s.top_p], np.float32),
                    np.asarray([s.top_k], np.int32),
                    np.asarray([s.seed], np.uint32),
                    np.zeros((1,), np.int32),  # first token = emitted index 0
                )
        with o.span("serve.wait") as wt:
            tok_planes = np.asarray(tok_planes)  # (w, 1) — d2h first id
        o.gap_from(wt.end)
        with o.span("serve.emit"):
            rec["host_device"] += tok_planes.nbytes
            first = int(unpack_tokens_host(tok_planes)[0])
            self._finish_admission(req, slot, first, rec)
        if not self._active:
            o.gap_from(None)

    def admit_pages(self, req: Request, pages, *, n_hits: int,
                    first_tok: int, wire_bytes: int = 0) -> None:
        """Migration admission: install prefill-worker KV pages shipped
        through the fleet fabric instead of running a local prefill.

        ``pages`` is the unpacked parcel pytree — per group, per cache
        node, ``{"k", "v"(, "k_scale", "v_scale")}`` arrays shaped
        ``(R, n_new, page, ...)`` in pool dtype covering prompt pages
        ``[n_hits:prompt_pages)`` — and ``first_tok`` the worker's
        greedy first id (the worker runs the same compiled prefill, so
        both are bit-identical to what :meth:`admit` would produce).
        The parcel's wire size lands in the step record's
        ``kv_migration`` field, NOT ``host_device``: the serve staging
        pin covers token/table traffic only, and the fabric hop log is
        the measured side of the fleet migration pin.
        """
        if not self.paged:
            raise ValueError("admit_pages needs the paged engine "
                             "(paged=True)")
        with self.obs.span("serve.admit"):
            self._admit_pages(req, pages, n_hits, first_tok, wire_bytes)

    def _admit_pages(self, req: Request, pages, n_hits: int,
                     first_tok: int, wire_bytes: int) -> None:
        ok, hits = self.can_admit(req)
        if not ok:
            raise CapacityError(
                f"request {req.rid}: no free slot/pages for migration "
                "admission"
            )
        if len(hits) != int(n_hits):
            raise AllocatorError(
                f"request {req.rid}: parcel skipped {n_hits} prefix "
                f"pages but {len(hits)} are resident — probe and admit "
                "must see the same intern table"
            )
        self._validate(req)
        rec = self._ensure_rec()
        S, page = len(req.prompt_ids), self.page_size
        slot, row = self._alloc_residency(req, hits)
        prompt_pages = -(-S // page)
        o = self.obs
        with o.span("serve.stage"):
            phys = jnp.asarray(row[len(hits):prompt_pages], jnp.int32)
            staged = jax.tree_util.tree_map(stage, pages)
            rec["kv_migration"] += int(wire_bytes)
            dstaged = None
            if self.draft is not None:
                # migration ships target KV, not tokens: the draft must
                # prefill locally, so the prompt crosses h2d here (priced)
                dplanes = pack_tokens_host(
                    np.asarray(req.prompt_ids, np.int32)[None, :],
                    self.token_width,
                )
                rec["host_device"] += dplanes.nbytes
                dstaged = stage(dplanes)
        with o.span("serve.dispatch"):
            self._caches = self._install_pages(
                self._caches, staged, np.int32(slot), phys, np.int32(S)
            )
            if dstaged is not None:
                self.draft.prefill_insert(self._unpack(dstaged), slot)
        with o.span("serve.emit"):
            self._finish_admission(req, slot, int(first_tok), rec)

    def decode_tick(self) -> None:
        """One engine step: run one batched decode when any slot is
        active, then finalize the step record (idle steps append a
        zero-decode record, exactly like the drain loop)."""
        rec = self._ensure_rec()
        rec["active"] = len(self._active)
        if self._active:
            with self.obs.span("serve.tick"):
                if self.draft is not None:
                    self._spec_tick(rec)
                else:
                    self._tick(rec)
            if not self._active:
                self.obs.gap_from(None)
        self.step_log.append(rec)
        self._step += 1
        self._rec = None

    def _tick(self, rec: dict) -> None:
        """One batched decode over every slot (ballast rows included)."""
        w, o = self.token_width, self.obs
        with o.span("serve.stage"):
            feed_planes = pack_tokens_host(
                self._next_tok[:, None], w
            )  # (w, B, 1)
            rec["host_device"] += feed_planes.nbytes  # h2d token staging
            staged = {"tokens": stage(feed_planes),
                      "pos": stage(self._pos_host)}
            if self.paged:
                # the page table is scheduler state staged fresh each step
                # (retires/admissions edit the host copy between steps)
                rec["host_device"] += self._table.nbytes
                rec["page_table"] += self._table.nbytes
                rec["attend_pages"] = self._attend_pages()
                staged["page_table"] = stage(self._table)
        with o.span("serve.dispatch") as d:
            o.end_gap(d.start)
            batch = dict(staged, tokens=self._unpack(staged["tokens"]))
            logits, self._caches = self._decode(
                self._weights, self._caches, batch
            )
            if any(not st.req.sampling.greedy
                   for st in self._active.values()):
                _, out_planes = self._sample_rng(
                    logits, self._temp, self._top_p, self._top_k,
                    self._seed, self._nemit,
                )
            else:
                _, out_planes = self._sample(logits)  # byte-identical path
        with o.span("serve.wait") as wt:
            out_planes = np.asarray(out_planes)  # (w, B) — d2h sampled ids
        o.gap_from(wt.end)
        with o.span("serve.emit"):
            rec["host_device"] += out_planes.nbytes
            sampled = unpack_tokens_host(out_planes)
            self._pos_host += 1  # mirrors cache.pos + 1 (ballast too)
            rec["decoded"] = len(self._active)
            for slot, st in list(self._active.items()):
                tok = int(sampled[slot])
                self._next_tok[slot] = tok
                self._nemit[slot] += 1
                if st.emit(tok):
                    self._results[st.req.rid] = self._retire(st, self._step)
                    del self._active[slot]

    def _attend_pages(self) -> int:
        """Table entries the paged decode kernel walks this tick: each
        slot that owns its first page walks ``ceil((pos + 1) / page)``
        of them, at most the table's width; a retired or empty slot's row
        (all trash) reaches the kernel with length 0 and walks none."""
        owning = self._table[:, 0] != self.num_pages
        live = -(-(self._pos_host + 1) // self.page_size)
        return int(np.minimum(live, self._table_width)[owning].sum())

    def _spec_tick(self, rec: dict) -> None:
        """One speculative round: draft proposes ``spec_k`` ids per slot,
        the target verifies all ``k+1`` block positions in ONE batched
        decode, and the standard accept rule keeps the longest prefix the
        draft reproduced (plus the target's own sample at the first
        divergence). Every emitted id is the target's sample under its
        per-request key fold, so streams are token-identical to the
        non-speculative engine at the same seeds — speculation changes
        wall-clock shape and wire traffic, never content.

        Cache discipline: both target and draft advance ``pos`` by
        ``k+1`` inside the jitted steps; rejected suffix entries are
        rolled back by re-stamping ``pos`` downward (entries beyond pos
        are mask-invisible and get overwritten bit-identically next
        round). Ballast slots skip rollback entirely — their writes land
        in trash (clamped pages / dropped scatters) or are masked.
        """
        w, k, o = self.token_width, self.spec_k, self.obs
        T = k + 1
        with o.span("serve.draft") as d:
            # the draft's first program ends the host gap
            o.end_gap(d.start)
            drafts = self.draft.propose(
                self._next_tok, self._pos_host, self._nemit,
                self._temp, self._top_p, self._top_k, self._seed, rec,
            )  # (B, k) host int32
        with o.span("serve.stage"):
            feed = np.concatenate([self._next_tok[:, None], drafts], axis=1)
            feed_planes = pack_tokens_host(feed, w)  # (w, B, T)
            rec["host_device"] += feed_planes.nbytes  # h2d verify block
            staged = {"tokens": stage(feed_planes),
                      "pos": stage(self._pos_host)}
            if self.paged:
                rec["host_device"] += self._table.nbytes
                rec["page_table"] += self._table.nbytes
                staged["page_table"] = stage(self._table)
        with o.span("serve.dispatch"):
            batch = dict(staged, tokens=self._unpack(staged["tokens"]))
            logits, self._caches = self._verify(
                self._weights, self._caches, batch
            )
            _, t_planes = self._verify_sample(
                logits, self._temp, self._top_p, self._top_k,
                self._seed, self._nemit,
            )
        with o.span("serve.wait") as wt:
            t_planes = np.asarray(t_planes)  # (w, B, T) — d2h verified ids
        o.gap_from(wt.end)
        with o.span("serve.emit"):
            self._spec_emit(rec, t_planes, drafts)

    def _spec_emit(self, rec: dict, t_planes, drafts) -> None:
        """Accept rule, emission and rollback of one speculative round."""
        k = self.spec_k
        T = k + 1
        rec["host_device"] += t_planes.nbytes
        targets = unpack_tokens_host(t_planes)  # (B, T)
        self._pos_host += T  # mirrors the jitted pos += T (ballast too)
        rec["decoded"] = len(self._active)
        rec["spec_rounds"] += 1
        delta = np.zeros_like(self._pos_host)
        for slot, st in list(self._active.items()):
            accepted = considered = 0
            for j in range(T):
                tok = int(targets[slot, j])
                accepted += 1
                self._next_tok[slot] = tok
                self._nemit[slot] += 1
                rec["spec_emitted"] += 1
                if st.emit(tok):
                    self._results[st.req.rid] = self._retire(st, self._step)
                    del self._active[slot]
                    break
                if j < k:
                    # proposals past a finish are moot, not rejected —
                    # only *examined* ones count toward the acceptance
                    # rate (a perfect draft pins it at exactly 1.0)
                    considered += 1
                    if int(drafts[slot, j]) != tok:
                        break  # divergence: target's sample replaces it
            rec["spec_proposed"] += considered
            rec["spec_accepted"] += accepted - 1
            delta[slot] = T - accepted
        self._pos_host -= delta
        self._caches = self._rollback(self._caches, delta)
        self.draft.rollback(delta)

    def take_completed(self) -> dict[int, GenResult]:
        """Drain finished results (the router's stream-reassembly feed)."""
        out, self._results = self._results, {}
        return out

    def swap_weights(self, storage) -> None:
        """Hot-swap the weight tree between steps (the fleet's
        ``weight_publish`` install). The swap is unconditional at the
        engine level — in-flight slots continue decoding under the new
        weights. Fleet-level versioned-at-admission semantics (a
        replica swaps only while idle, so no in-flight request ever
        changes weights mid-stream) live in the router."""
        self.storage = storage
        self._weights = (
            self._place(storage) if self._place is not None else storage
        )

    def finish(self) -> dict[int, GenResult]:
        """End-of-stream conservation audits; returns completed results."""
        self.slots.audit()
        if self.paged:
            audit = self.pages.audit()
            if audit["live"] or self._intern or self._slot_pages:
                raise InvariantError("page leak after drain")
        return self._results

    # -- the serving loop -------------------------------------------------
    def run(self, requests, *, max_steps: int = 1_000_000) -> dict[int, GenResult]:
        """Drain ``requests`` (admission in list order) to completion.

        Returns ``{rid: GenResult}``. Appends one record per engine step
        to :attr:`step_log`:
        ``{"step", "admitted", "active", "decoded", "host_device"}`` —
        ``host_device`` is the *measured* staged byte count (sum of
        ``planes.nbytes`` over every boundary crossing that step).
        """
        requests = list(requests)
        if len({r.rid for r in requests}) != len(requests):
            raise ValueError("duplicate request ids")
        for r in requests:
            self._validate(r)
        self.begin_stream()
        queue = collections.deque(requests)
        while (queue or self._active) and self._step < max_steps:
            # admission: fill free slots between decode steps (FIFO —
            # the head of line waits for slots/pages to free)
            while queue:
                ok, _ = self.can_admit(queue[0])
                if not ok:
                    break
                self.admit(queue.popleft())
            self.decode_tick()
        if queue or self._active:
            raise CapacityError(f"engine stopped at max_steps={max_steps} "
                               f"with {len(queue) + len(self._active)} "
                               "unfinished")
        return self.finish()

    def _retire(self, st: _ReqState, step: int) -> GenResult:
        self.slots.release(st.slot)
        self._clear_sampling_slot(st.slot)
        if self.paged:
            for pid in self._slot_pages.pop(st.slot):
                if self.pages.release(pid):
                    # last holder gone: an interned prefix page dies with it
                    key = self._page_key.pop(pid, None)
                    if key is not None:
                        del self._intern[key]
            self._table[st.slot, :] = self.num_pages  # ballast -> trash
        return GenResult(
            rid=st.req.rid,
            prompt_len=len(st.req.prompt_ids),
            tokens=list(st.tokens),
            admitted_step=st.admitted_step,
            finished_step=step,
        )

    # -- accounting --------------------------------------------------------
    def wire_summary(self) -> dict:
        """Aggregate of :attr:`step_log` in the shape the analytic
        serve-wire model (:func:`repro.roofline.analysis.
        serve_host_device_bytes`) reproduces."""
        out = {
            "host_device": sum(r["host_device"] for r in self.step_log),
            "decode_steps": sum(1 for r in self.step_log if r["decoded"]),
            "admissions": sum(r["admitted"] for r in self.step_log),
            "steps": len(self.step_log),
            "token_width": self.token_width,
        }
        if self.paged:
            out["page_table"] = sum(
                r.get("page_table", 0) for r in self.step_log
            )
            out["page_table_entries"] = self.max_slots * self._table_width
            walks = [r["attend_pages"] for r in self.step_log
                     if "attend_pages" in r]
            if walks:
                # the kernel's walk as a share of every slot's whole row
                out["attend_pages"] = sum(walks)
                out["attend_walked_share"] = sum(walks) / (
                    len(walks) * out["page_table_entries"])
            out["prefill_hits"] = sum(
                r.get("prefill_hits", 0) for r in self.step_log
            )
            out["prefill_misses"] = sum(
                r.get("prefill_misses", 0) for r in self.step_log
            )
        if self.draft is not None:
            rounds = sum(r.get("spec_rounds", 0) for r in self.step_log)
            proposed = sum(r.get("spec_proposed", 0) for r in self.step_log)
            accepted = sum(r.get("spec_accepted", 0) for r in self.step_log)
            emitted = sum(r.get("spec_emitted", 0) for r in self.step_log)
            out["spec_rounds"] = rounds
            out["spec_proposed"] = proposed
            out["spec_accepted"] = accepted
            out["spec_emitted"] = emitted
            out["acceptance_rate"] = accepted / max(proposed, 1)
            out["tokens_per_target_step"] = emitted / max(rounds, 1)
            out["spec_k"] = self.spec_k
        return out

    def kv_residency(self) -> dict:
        """Measured page-granular KV residency — the counterpart of the
        analytic :func:`repro.roofline.analysis.serve_paged_kv_bytes`.
        ``bytes_per_page`` sums every paged pool's per-page footprint
        across layers (int8 KV includes the scale planes)."""
        if not self.paged:
            raise ValueError("kv_residency is defined for the paged "
                               "engine (paged=True)")
        live, peak = self.pages.live_pages, self.pages.peak
        return {
            "pages_live": live,
            "pages_peak": peak,
            "page_size": self.page_size,
            "bytes_per_page": self._page_bytes,
            "kv_bytes_resident": live * self._page_bytes,
            "kv_bytes_peak": peak * self._page_bytes,
        }


# ---------------------------------------------------------------------------
# static one-shot reference path
# ---------------------------------------------------------------------------


def generate_static(
    cfg: ModelConfig,
    mesh_cfg: MeshCfg,
    mesh,
    spec_tree,
    storage,
    requests,
    *,
    plan: PrecisionPlan,
    window: int | None = None,
    image_features=None,
) -> dict[int, list[int]]:
    """The pre-engine reference path: classic static batching. Requests
    are grouped by prompt length, each group runs one batched prefill and
    a scalar-``pos`` decode loop to the group's longest request; per-
    request stop conditions truncate the streams afterwards. The engine
    is pinned bit-exact against this for identical request sets.

    Sampling follows each request's :class:`SamplingParams`: all-greedy
    groups keep the historical argmax loop (byte-identical to pre-
    sampling releases), and any sampled request switches its group to
    the shared per-row sampler (:func:`repro.serve.sampling.
    sample_tokens`) under the key-fold contract, so sampled streams are
    bit-exact against the engine at the same per-request seeds.

    Vision features ride on ``Request.image_features``; the legacy
    ``image_features={rid: array}`` kwarg still works one release behind
    a :class:`DeprecationWarning`."""
    plan = plan.broadcast(cfg.num_groups + 1)
    if image_features is not None:
        warnings.warn(
            "generate_static(image_features=...) is deprecated — set "
            "Request.image_features per request instead",
            DeprecationWarning, stacklevel=2,
        )

    def _feats(r):
        if r.image_features is not None:
            return r.image_features
        return None if image_features is None else image_features.get(r.rid)

    if cfg.num_image_tokens and any(_feats(r) is None for r in requests):
        raise ValueError(
            f"{cfg.name} needs image_features per request "
            f"(Request.image_features, "
            f"({cfg.num_image_tokens}, {cfg.vision_dim}) array)"
        )
    groups: dict[int, list[Request]] = {}
    for r in requests:
        groups.setdefault(len(r.prompt_ids), []).append(r)
    out: dict[int, list[int]] = {}
    for S, reqs in groups.items():
        B = len(reqs)
        gen = max(r.max_new for r in reqs)
        cap = S + gen
        toks = jnp.asarray([r.prompt_ids for r in reqs], jnp.int32)
        bshapes = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        batch = {"tokens": toks}
        if cfg.num_image_tokens:
            batch["image_features"] = jnp.asarray(
                np.stack([_feats(r) for r in reqs]),
                jnp.float32,
            )
            bshapes["image_features"] = jax.ShapeDtypeStruct(
                batch["image_features"].shape, jnp.float32
            )
        gplan = plan
        if gplan.seq_parallel and S % max(mesh_cfg.tp, 1):
            gplan = dataclasses.replace(gplan, seq_parallel=False)
        shard_batch = mesh_cfg.dshards > 1 and B % mesh_cfg.dshards == 0
        prefill = make_prefill_step(
            cfg, mesh_cfg, mesh, spec_tree, bshapes, plan=gplan,
            cache_capacity=cap, shard_batch=shard_batch,
        )
        dshapes = {
            "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
        }
        decode = make_decode_step(
            cfg, mesh_cfg, mesh, spec_tree, dshapes, plan=gplan,
            shard_batch=shard_batch, window_override=window,
        )
        all_greedy = all(r.sampling.greedy for r in reqs)
        if not all_greedy:
            temp = np.asarray(
                [r.sampling.temperature for r in reqs], np.float32)
            topp = np.asarray([r.sampling.top_p for r in reqs], np.float32)
            topk = np.asarray([r.sampling.top_k for r in reqs], np.int32)
            seed = np.asarray([r.sampling.seed for r in reqs], np.uint32)

            @jax.jit
            def samp(lg, step, temp=temp, topp=topp, topk=topk, seed=seed):
                return sample_tokens(
                    lg[:, -1], cfg.vocab_size, temp, topp, topk, seed, step
                )[:, None]

        logits, caches = prefill(storage, batch)
        if all_greedy:
            tok = jnp.argmax(
                logits[:, -1, : cfg.vocab_size], -1
            )[:, None].astype(jnp.int32)
        else:
            tok = samp(logits, np.zeros((B,), np.int32))
        streams = [np.asarray(tok)[:, 0]]
        for i in range(gen - 1):
            logits, caches = decode(
                storage, caches,
                {"tokens": tok, "pos": jnp.asarray(S + i, jnp.int32)},
            )
            if all_greedy:
                tok = jnp.argmax(
                    logits[:, 0, : cfg.vocab_size], -1
                )[:, None].astype(jnp.int32)
            else:
                tok = samp(logits, np.full((B,), i + 1, np.int32))
            streams.append(np.asarray(tok)[:, 0])
        mat = np.stack(streams, axis=1)  # (B, gen)
        for b, r in enumerate(reqs):
            ids = mat[b].tolist()[: r.max_new]
            if r.eos_id is not None and r.eos_id in ids:
                ids = ids[: ids.index(r.eos_id) + 1]
            out[r.rid] = ids
    return out
