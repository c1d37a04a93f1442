"""GQA attention: flash-style tiled softmax, sliding windows, KV caches.

One implementation covers every assigned flavour:

  * causal / bidirectional (hubert) / cross (llama-vision),
  * GQA with kv-head replication when kv < TP degree,
  * qk-norm (qwen3), qkv-bias (qwen2.5), partial rotary (chatglm3),
  * sliding-window (mixtral SWA, recurrentgemma local, long_500k variant),
  * prefill (tiled, O(S·chunk) memory) and single-token decode with either a
    linear or ring-buffer KV cache.

The prefill path unrolls over q chunks with *exact* kv ranges (triangular /
banded), so HLO_FLOPs ≈ useful FLOPs — the masked-full-rectangle variant is
kept (``causal_skip=False``) as the §Perf baseline ablation.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.env import Env
from repro.models.layers import apply_rope, head_rms_norm

NEG_INF = -1e30


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KVCache:
    """Uniform-length KV cache. ``pos`` = number of tokens already absorbed.

    Capacity ``C = k.shape[1]``. When ``C < context`` the cache is used as a
    ring buffer (sliding-window decode)."""

    k: jnp.ndarray  # (B, C, Kv_local, head_dim)
    v: jnp.ndarray
    pos: jnp.ndarray  # () int32

    def tree_flatten(self):
        return (self.k, self.v, self.pos), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.k.shape[1]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantKVCache:
    """int8 KV cache with per-(slot, head) fp scales (beyond-paper §Perf:
    decode shapes are HBM-bound on cache reads; int8 quarters the traffic
    vs fp32, halves vs bf16)."""

    k: jnp.ndarray        # (B, C, Kv_local, head_dim) int8
    v: jnp.ndarray
    k_scale: jnp.ndarray  # (B, C, Kv_local) f32
    v_scale: jnp.ndarray
    pos: jnp.ndarray      # () int32

    def tree_flatten(self):
        return (self.k, self.v, self.k_scale, self.v_scale, self.pos), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.k.shape[1]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVCache:
    """Block-paged KV cache: a shared page *pool* instead of per-slot
    contiguous arrays. K/V for all slots live in ``(P, page, Kv_local,
    head_dim)`` pools; which pool rows a slot owns is decided by the
    host-side page table (``(B, n_pages)`` int32, staged into each decode
    step as ``batch["page_table"]`` — it is scheduler state, not cache
    state, so it does NOT travel in this pytree). The last pool row is
    the **trash page**: retired slots' ballast writes and unused table
    entries point there, so resident bytes track tokens actually written,
    not ``max_slots * capacity``.

    ``pos`` is the per-slot absorbed-token count, exactly as in the
    slotted :class:`KVCache` layout."""

    k: jnp.ndarray    # (P, page, Kv_local, head_dim) — row P-1 is trash
    v: jnp.ndarray
    pos: jnp.ndarray  # (B,) int32

    def tree_flatten(self):
        return (self.k, self.v, self.pos), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def page_size(self) -> int:
        return self.k.shape[1]

    @property
    def num_pages(self) -> int:
        """Pool rows including the trailing trash page."""
        return self.k.shape[0]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedQuantKVCache:
    """int8 variant of :class:`PagedKVCache`: codes pools plus per-(page
    row, offset, head) fp32 scale pools."""

    k: jnp.ndarray        # (P, page, Kv_local, head_dim) int8
    v: jnp.ndarray
    k_scale: jnp.ndarray  # (P, page, Kv_local) f32
    v_scale: jnp.ndarray
    pos: jnp.ndarray      # (B,) int32

    def tree_flatten(self):
        return (self.k, self.v, self.k_scale, self.v_scale, self.pos), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def page_size(self) -> int:
        return self.k.shape[1]

    @property
    def num_pages(self) -> int:
        return self.k.shape[0]


def _quantize_kv(x):
    """(B, S, Kv, hd) fp -> (int8 values, (B, S, Kv) scales)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def check_cache_geometry(capacity: int, window: Optional[int], context: int,
                         *, label: str = ""):
    """Guard against a KV cache that silently drops or evicts live tokens.

    ``mha``'s rule: a cache rings iff ``window is not None and capacity
    <= window``; a linear cache must hold the whole ``context``. Raised
    here (shared by ``init_cache``/``init_caches`` construction and the
    serve engine's per-request admission check) so the train-side
    windowed ring caches get the same guard as the serve path."""
    if context <= capacity:
        return
    ring = window is not None and capacity <= window
    if not ring:
        hint = (
            " (no sliding window)" if window is None else
            f" (window={window} does not ring: capacity "
            f"{capacity} > window — shrink the cache capacity to the "
            "window)"
        )
        raise ValueError(
            f"{label}context {context} exceeds cache capacity "
            f"{capacity}{hint}"
        )
    if capacity < window:
        # a wrapping ring narrower than the window evicts tokens the
        # attention mask still wants — streams would silently diverge
        raise ValueError(
            f"{label}context {context} wraps a ring cache of "
            f"{capacity} slots that is smaller than window={window}: "
            "live tokens would be evicted — set the cache capacity == "
            "window"
        )
    # capacity == window rings faithfully (wrapping IS window eviction)


def init_cache(batch: int, capacity: int, kv_heads: int, head_dim: int, dtype,
               per_slot: bool = False, *, window: Optional[int] = None,
               context: Optional[int] = None):
    """``per_slot=True`` gives the cache a ``(batch,)`` position vector —
    the serve engine's slotted layout where every request sits at its own
    sequence offset. Scalar ``pos`` (the default) keeps the historical
    uniform-batch semantics byte-for-byte.

    ``context`` (when known) is the number of tokens this cache will be
    asked to absorb: construction then runs :func:`check_cache_geometry`
    against ``window`` so a silently-evicting geometry fails loudly at
    build time instead of corrupting streams."""
    if context is not None:
        check_cache_geometry(capacity, window, context)
    pos = jnp.zeros((batch,) if per_slot else (), jnp.int32)
    if dtype == jnp.int8:
        z = jnp.zeros((batch, capacity, kv_heads, head_dim), jnp.int8)
        sc = jnp.zeros((batch, capacity, kv_heads), jnp.float32)
        return QuantKVCache(z, z, sc, sc, pos)
    zeros = jnp.zeros((batch, capacity, kv_heads, head_dim), dtype)
    return KVCache(zeros, zeros, pos)


def init_paged_cache(batch: int, num_pages: int, page_size: int,
                     kv_heads: int, head_dim: int, dtype):
    """Paged pool + per-slot positions. ``num_pages`` counts *allocatable*
    pages; one extra trash row (index ``num_pages``) is appended for
    ballast writes and unused page-table entries."""
    P = num_pages + 1
    pos = jnp.zeros((batch,), jnp.int32)
    if dtype == jnp.int8:
        z = jnp.zeros((P, page_size, kv_heads, head_dim), jnp.int8)
        sc = jnp.zeros((P, page_size, kv_heads), jnp.float32)
        return PagedQuantKVCache(z, z, sc, sc, pos)
    zeros = jnp.zeros((P, page_size, kv_heads, head_dim), dtype)
    return PagedKVCache(zeros, zeros, pos)


# ---------------------------------------------------------------------------
# core softmax-attention tiles
# ---------------------------------------------------------------------------


def _attend_tile(q, k, v, mask):
    """Dense tile: q (B,Kv,G,Sq,hd), k/v (B,Sk,Kv,hd), mask (Sq,Sk) or None.

    Returns (scores_max, sumexp, acc) suitable for online combination.
    Scores/softmax accumulate in fp32 regardless of compute dtype."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bkgqh,bskh->bkgqs", q, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum(
        "bkgqs,bskh->bkgqh", p, v, preferred_element_type=jnp.float32
    )
    return m, l, acc


def _combine(m1, l1, a1, m2, l2, a2):
    m = jnp.maximum(m1, m2)
    c1 = jnp.exp(m1 - m)
    c2 = jnp.exp(m2 - m)
    return m, l1 * c1 + l2 * c2, a1 * c1[..., None] + a2 * c2[..., None]


def attend_tiled(
    q: jnp.ndarray,  # (B, Sq, Kv, G, hd)
    k: jnp.ndarray,  # (B, Sk, Kv, hd)
    v: jnp.ndarray,
    *,
    causal: bool,
    window: Optional[int],
    q_offset: int = 0,
    chunk: int = 1024,
    causal_skip: bool = True,
) -> jnp.ndarray:
    """Flash-style tiled attention; returns (B, Sq, Kv, G, hd).

    ``q_offset``: absolute position of q[0] relative to k[0] (prefill
    continuation). q chunks are unrolled with exact kv ranges so that masked
    work is *not* lowered (unless causal_skip=False, the §Perf baseline)."""
    B, Sq, Kv, G, hd = q.shape
    Sk = k.shape[1]
    cq = min(chunk, Sq)
    if Sq % cq:
        raise ValueError(f"Sq={Sq} not divisible by chunk={cq}")
    # kv ranges are tiled in cq-sized blocks: pad kv up to a multiple and
    # mask the tail, otherwise a short kv (cross-attn image tokens with
    # Sk < cq, or Sk % cq != 0) is silently truncated to floor(Sk/cq)
    # whole blocks — zero attention output for Sk < cq
    sk_pad = ((Sk + cq - 1) // cq) * cq if Sk else 0
    if sk_pad != Sk:
        padw = [(0, 0)] * k.ndim
        padw[1] = (0, sk_pad - Sk)
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
    nq = Sq // cq
    outs = []
    for i in range(nq):
        q_i = q[:, i * cq : (i + 1) * cq].transpose(0, 2, 3, 1, 4)  # B,Kv,G,cq,hd
        q_pos_lo = q_offset + i * cq
        # exact kv range for this q chunk
        k_hi = min(Sk, q_pos_lo + cq) if (causal and causal_skip) else Sk
        k_lo = 0
        if window is not None and causal_skip:
            k_lo = max(0, q_pos_lo - window + 1)
        # align to chunk for tidy inner tiling
        k_lo = (k_lo // cq) * cq
        k_hi = min(sk_pad, ((k_hi + cq - 1) // cq) * cq)
        nk = (k_hi - k_lo) // cq if k_hi > k_lo else 0
        if nk == 0:
            outs.append(jnp.zeros((B, cq, Kv, G, hd), q.dtype))
            continue

        q_pos = q_pos_lo + jnp.arange(cq)

        def kv_block(j):
            lo = k_lo + j * cq
            kc = lax.dynamic_slice_in_dim(k, lo, cq, axis=1)
            vc = lax.dynamic_slice_in_dim(v, lo, cq, axis=1)
            k_pos = lo + jnp.arange(cq)
            mask = jnp.ones((cq, cq), bool)
            if sk_pad != Sk:
                mask &= k_pos[None, :] < Sk
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            return kc, vc, mask

        def body(carry, j):
            m, l, acc = carry
            kc, vc, mask = kv_block(j)
            m2, l2, a2 = _attend_tile(q_i, kc, vc, mask)
            return _combine(m, l, acc, m2, l2, a2), None

        m0 = jnp.full((B, Kv, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Kv, G, cq), jnp.float32)
        a0 = jnp.zeros((B, Kv, G, cq, hd), jnp.float32)
        (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(nk))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        outs.append(out.transpose(0, 3, 1, 2, 4))  # B,cq,Kv,G,hd
    return jnp.concatenate(outs, axis=1)


def _attend_decode_multi(q, cache, *, ring: bool, window: Optional[int]):
    """T-token block attention (the speculative-decoding verify step)
    over the already updated per-slot cache: block token j sits at
    absolute position ``pos - T + j`` and attends exactly its own
    prefix, including the block's earlier tokens. Every op reduces
    along the slot axis only, mirroring :func:`attend_decode`, so a
    T-block is bitwise the T successive single-token steps."""
    B, T, Kv, G, hd = q.shape
    if ring or window is not None or not jnp.ndim(cache.pos):
        raise ValueError(
            "multi-token decode (speculative verify) needs per-slot "
            "linear caches (no ring/window)"
        )
    C = cache.capacity
    slots = jnp.arange(C)
    tpos = (cache.pos - T)[:, None] + jnp.arange(T)  # (B, T) abs positions
    valid = slots[None, None, :] <= tpos[:, :, None]  # (B, T, C)
    vmask = valid[:, None, None]  # (B, 1, 1, T, C)
    scale = hd**-0.5
    quant = isinstance(cache, QuantKVCache)
    s = jnp.einsum(
        "btkgh,bskh->bkgts", q, cache.k, preferred_element_type=jnp.float32
    ) * scale
    if quant:
        s = s * cache.k_scale.transpose(0, 2, 1)[:, :, None, None, :]
    s = jnp.where(vmask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if quant:
        p = p * cache.v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    return jnp.einsum(
        "bkgts,bskh->btkgh", p, cache.v, preferred_element_type=jnp.float32
    ).astype(q.dtype)


def attend_decode(
    q: jnp.ndarray,  # (B, T, Kv, G, hd) — T=1 outside speculative verify
    cache,
    *,
    ring: bool,
    window: Optional[int],
) -> jnp.ndarray:
    """Single-token attention over the (already updated) cache; handles
    both fp (KVCache) and int8 (QuantKVCache) layouts. ``T > 1``
    (the speculative verify block) dispatches to
    :func:`_attend_decode_multi`; the T=1 path below is unchanged.

    ``cache.pos`` may be a scalar (uniform batch — the historical path,
    kept bit-for-bit) or a ``(B,)`` vector (per-slot positions from the
    continuous-batching serve engine): the validity mask then becomes
    per-request, so every slot attends exactly its own prefix."""
    B, T, Kv, G, hd = q.shape
    if T > 1:
        return _attend_decode_multi(q, cache, ring=ring, window=window)
    C = cache.capacity
    pos = cache.pos - 1  # absolute position of the current token
    slots = jnp.arange(C)
    if jnp.ndim(pos):
        pos_b = pos[:, None]  # (B, 1)
        if ring:
            slot_pos = pos_b - jnp.mod(pos_b - slots[None, :], C)
        else:
            slot_pos = jnp.broadcast_to(slots[None, :], (B, C))
        valid = (slot_pos >= 0) & (slot_pos <= pos_b)
        if window is not None:
            valid &= (pos_b - slot_pos) < window
        vmask = valid[:, None, None, :]  # (B, 1, 1, C)
    else:
        if ring:
            # slot j currently holds absolute position: pos - ((pos-j) mod C)
            slot_pos = pos - jnp.mod(pos - slots, C)
        else:
            slot_pos = slots
        valid = (slot_pos >= 0) & (slot_pos <= pos)
        if window is not None:
            valid &= (pos - slot_pos) < window
        vmask = valid[None, None, None, :]
    scale = hd**-0.5
    qh = q[:, 0]  # B,Kv,G,hd
    quant = isinstance(cache, QuantKVCache)
    s = jnp.einsum(
        "bkgh,bskh->bkgs", qh, cache.k, preferred_element_type=jnp.float32
    ) * scale
    if quant:
        # scores were computed against int8 codes: apply per-slot scales
        s = s * cache.k_scale.transpose(0, 2, 1)[:, :, None, :]
    s = jnp.where(vmask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if quant:
        p = p * cache.v_scale.transpose(0, 2, 1)[:, :, None, :]
    out = jnp.einsum(
        "bkgs,bskh->bkgh", p, cache.v, preferred_element_type=jnp.float32
    ).astype(q.dtype)
    return out[:, None]


def attend_decode_paged(
    q: jnp.ndarray,  # (B, 1, Kv, G, hd)
    cache,           # PagedKVCache | PagedQuantKVCache (already updated)
    page_table: jnp.ndarray,  # (B, n_pages) int32
    *,
    window: Optional[int] = None,
    impl: Optional[str] = None,
) -> jnp.ndarray:
    """Single-token attention over the paged pool.

    ``impl=None`` dispatches like ``kernels.bitpack.resolve_interpret``:
    the fused page-walking Pallas kernel on a real TPU (fp caches, no
    window), the dense reference elsewhere. ``impl="dense"`` gathers the
    slot's pages into a contiguous per-slot view and runs the *exact*
    ``attend_decode`` ops — positions past ``pos`` mask to ``NEG_INF``
    so their softmax weight is exactly 0.0, which keeps paged streams
    bit-identical to the contiguous engine layout."""
    quant = isinstance(cache, PagedQuantKVCache)
    if impl is None:
        impl = (
            "pallas"
            if jax.default_backend() == "tpu" and not quant
            and window is None and q.shape[1] == 1
            else "dense"
        )
    if impl == "pallas":
        from repro.kernels.paged_attention import paged_attend

        out = paged_attend(q[:, 0], cache.k, cache.v, page_table, cache.pos)
        return out[:, None]
    B = q.shape[0]
    n_pages = page_table.shape[1]
    cap = n_pages * cache.page_size
    gk = cache.k[page_table].reshape(B, cap, *cache.k.shape[2:])
    gv = cache.v[page_table].reshape(B, cap, *cache.v.shape[2:])
    if quant:
        gks = cache.k_scale[page_table].reshape(B, cap, -1)
        gvs = cache.v_scale[page_table].reshape(B, cap, -1)
        dense = QuantKVCache(gk, gv, gks, gvs, cache.pos)
    else:
        dense = KVCache(gk, gv, cache.pos)
    return attend_decode(q, dense, ring=False, window=window)


def _paged_write(cache, k, v, page_table):
    """Scatter the decoded token block into each slot's pages.

    ``k/v (B, T, Kv, hd)`` — T=1 is the ordinary decode step (path kept
    bit-for-bit), T=k+1 the speculative verify block. Logical page
    ``pos // page`` is clamped to the table width: retired-ballast
    slots (table all-trash, ``pos`` still advancing) then keep writing
    into the trash page, and under speculative decoding the engine
    widens the table so a verify block near end-of-capacity clamps
    into unallocated (trash) entries, never a live page."""
    B = page_table.shape[0]
    page = cache.page_size
    pos = cache.pos  # (B,) tokens absorbed BEFORE this block
    T = k.shape[1]
    if T == 1:
        pi = jnp.minimum(pos // page, page_table.shape[1] - 1)
        phys = page_table[jnp.arange(B), pi]  # (B,)
        off = jnp.mod(pos, page)
        if isinstance(cache, PagedQuantKVCache):
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
            return PagedQuantKVCache(
                cache.k.at[phys, off].set(kq[:, 0]),
                cache.v.at[phys, off].set(vq[:, 0]),
                cache.k_scale.at[phys, off].set(ks[:, 0]),
                cache.v_scale.at[phys, off].set(vs[:, 0]),
                pos + 1,
            )
        return PagedKVCache(
            cache.k.at[phys, off].set(k[:, 0].astype(cache.k.dtype)),
            cache.v.at[phys, off].set(v[:, 0].astype(cache.v.dtype)),
            pos + 1,
        )
    tpos = pos[:, None] + jnp.arange(T)  # (B, T) absolute positions
    pi = jnp.minimum(tpos // page, page_table.shape[1] - 1)
    phys = page_table[jnp.arange(B)[:, None], pi]  # (B, T)
    off = jnp.mod(tpos, page)
    if isinstance(cache, PagedQuantKVCache):
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        return PagedQuantKVCache(
            cache.k.at[phys, off].set(kq),
            cache.v.at[phys, off].set(vq),
            cache.k_scale.at[phys, off].set(ks),
            cache.v_scale.at[phys, off].set(vs),
            pos + T,
        )
    return PagedKVCache(
        cache.k.at[phys, off].set(k.astype(cache.k.dtype)),
        cache.v.at[phys, off].set(v.astype(cache.v.dtype)),
        pos + T,
    )


def _flash_prefill_viable(mode, causal, window, is_cross, pos_offset, qg, k):
    """The fused flash kernel handles the plain causal prefill shape on a
    real TPU; everything else (CPU tests — the bit-exactness pins — and
    windows/cross/per-slot offsets/untiled lengths) keeps ``attend_tiled``.
    Training keeps it too: the kernel has no VJP."""
    if mode != "prefill" or jax.default_backend() != "tpu":
        return False
    if not causal or window is not None or is_cross:
        return False
    if jnp.ndim(pos_offset):
        return False
    B, Sq, Kv, G, hd = qg.shape
    Sk = k.shape[1]
    if hd % 128:
        return False
    return Sq % 128 == 0 and Sk % 128 == 0


def _flash_prefill_call(qg, k, v, *, q_offset):
    """(B,S,Kv,G,hd) q / (B,Sk,Kv,hd) kv -> fused kernel layouts and back."""
    from repro.kernels.flash_prefill import flash_prefill

    B, Sq, Kv, G, hd = qg.shape
    qf = qg.transpose(0, 2, 3, 1, 4).reshape(B, Kv * G, Sq, hd)
    kf = k.transpose(0, 2, 1, 3)
    vf = v.transpose(0, 2, 1, 3)
    out = flash_prefill(qf, kf, vf, causal=True, q_offset=q_offset)
    return out.reshape(B, Kv, G, Sq, hd).transpose(0, 3, 1, 2, 4)


# ---------------------------------------------------------------------------
# full attention layer (projections + rope + cache plumbing)
# ---------------------------------------------------------------------------


def mha(
    x: jnp.ndarray,  # (B, S, d) — model-axis replicated
    w: dict,
    cfg,
    env: Env,
    *,
    mode: str = "train",  # train | prefill | decode
    cache: Optional[KVCache] = None,
    window: Optional[int] = None,
    kv_ext: Optional[jnp.ndarray] = None,  # cross-attn source (B, N, d)
    is_cross: bool = False,
    pos_offset=0,
    page_table: Optional[jnp.ndarray] = None,  # (B, n_pages) — paged decode
) -> tuple[jnp.ndarray, Optional[KVCache]]:
    """One attention layer. Returns (out (B,S,d), updated cache).

    Under ``env.seq_parallel`` the incoming ``x`` is a sequence shard;
    ``env.enter`` all-gathers it, so every shape below derives from the
    gathered ``xin`` (full sequence), and ``env.exit`` reduce-scatters
    the output back onto shards."""
    hd = cfg.head_dim
    # head counts from the (TP-local, possibly padded) weights themselves
    Hq_l = w["wq"].shape[1] // hd
    Kv_l = w["wk"].shape[1] // hd
    G = Hq_l // Kv_l
    is_cross = is_cross or (kv_ext is not None)

    xin = env.enter(x)
    B, S, _ = xin.shape
    q = xin @ w["wq"]
    if cfg.qkv_bias:
        q = q + w["bq"]
    q = q.reshape(B, S, Hq_l, hd)

    # image KV are replicated (never sequence-sharded): always the psum pair
    kv_src = env.psum_enter(kv_ext) if is_cross else xin
    if is_cross and mode == "decode":
        k = v = None  # cross KV live in the cache, computed at prefill
    else:
        k = kv_src @ w["wk"]
        v = kv_src @ w["wv"]
        if cfg.qkv_bias:
            k = k + w["bk"]
            v = v + w["bv"]
        Skv = kv_src.shape[1]
        k = k.reshape(B, Skv, Kv_l, hd)
        v = v.reshape(B, Skv, Kv_l, hd)

    if cfg.qk_norm:
        q = head_rms_norm(q, w["q_norm"], cfg.norm_eps)
        if k is not None:
            k = head_rms_norm(k, w["k_norm"], cfg.norm_eps)

    if not is_cross:
        if jnp.ndim(pos_offset):  # (B,) per-slot offsets (serve engine)
            q_pos = pos_offset[:, None] + jnp.arange(S)
            k_pos = pos_offset[:, None] + jnp.arange(k.shape[1])
        else:
            q_pos = pos_offset + jnp.arange(S)
            k_pos = pos_offset + jnp.arange(k.shape[1])
        q = apply_rope(q, q_pos, rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)
        k = apply_rope(k, k_pos, rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)

    qg = q.reshape(B, S, Kv_l, G, hd)
    new_cache = cache
    paged = isinstance(cache, (PagedKVCache, PagedQuantKVCache))

    if paged and mode != "decode":
        raise ValueError(
            "paged caches are decode-only: prefill runs on contiguous "
            "caches and the serve engine scatters them into pages"
        )
    if mode == "decode" and paged:
        if page_table is None:
            raise ValueError(
                "paged decode needs a page_table (S=1 ordinary decode, "
                "S=k+1 the speculative verify block)"
            )
        if window is not None:
            raise ValueError(
                "paged KV keeps the full context: sliding-window decode "
                "stays on the contiguous ring layout"
            )
        new_cache = _paged_write(cache, k, v, page_table)
        out = attend_decode_paged(qg, new_cache, page_table)
    elif mode == "decode" and not is_cross:
        if cache is None:
            raise ValueError("decode needs a KV cache")
        C = cache.capacity
        ring = window is not None and C <= window
        per_slot = jnp.ndim(cache.pos) > 0
        if S != 1 and (not per_slot or window is not None):
            raise ValueError(
                f"multi-token decode (S={S}) needs per-slot linear "
                "caches (no ring/window)"
            )
        idx = jnp.mod(cache.pos, C) if ring else cache.pos
        if per_slot and S > 1:
            # speculative verify block: scatter all S tokens at
            # (slot, pos + j); mode="drop" skips past-capacity writes
            # (ballast slots and block tails past the stop position,
            # both never attended)
            bi2 = jnp.arange(B)[:, None]
            idx2 = cache.pos[:, None] + jnp.arange(S)  # (B, S)
            if isinstance(cache, QuantKVCache):
                kq, ks = _quantize_kv(k)
                vq, vs = _quantize_kv(v)
                new_cache = QuantKVCache(
                    cache.k.at[bi2, idx2].set(kq, mode="drop"),
                    cache.v.at[bi2, idx2].set(vq, mode="drop"),
                    cache.k_scale.at[bi2, idx2].set(ks, mode="drop"),
                    cache.v_scale.at[bi2, idx2].set(vs, mode="drop"),
                    cache.pos + S,
                )
            else:
                new_cache = KVCache(
                    cache.k.at[bi2, idx2].set(
                        k.astype(cache.k.dtype), mode="drop"
                    ),
                    cache.v.at[bi2, idx2].set(
                        v.astype(cache.v.dtype), mode="drop"
                    ),
                    cache.pos + S,
                )
        elif per_slot:
            # per-request write positions (continuous batching): a batched
            # scatter at (slot, idx[slot]); mode="drop" silently skips
            # requests whose linear cache is already full (a retired slot
            # the engine keeps decoding as ballast)
            bi = jnp.arange(B)
            if isinstance(cache, QuantKVCache):
                kq, ks = _quantize_kv(k)
                vq, vs = _quantize_kv(v)
                kc = cache.k.at[bi, idx].set(kq[:, 0], mode="drop")
                vc = cache.v.at[bi, idx].set(vq[:, 0], mode="drop")
                ksc = cache.k_scale.at[bi, idx].set(ks[:, 0], mode="drop")
                vsc = cache.v_scale.at[bi, idx].set(vs[:, 0], mode="drop")
                new_cache = QuantKVCache(kc, vc, ksc, vsc, cache.pos + 1)
            else:
                kc = cache.k.at[bi, idx].set(
                    k[:, 0].astype(cache.k.dtype), mode="drop"
                )
                vc = cache.v.at[bi, idx].set(
                    v[:, 0].astype(cache.v.dtype), mode="drop"
                )
                new_cache = KVCache(kc, vc, cache.pos + 1)
        elif isinstance(cache, QuantKVCache):
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
            kc = lax.dynamic_update_slice(cache.k, kq, (0, idx, 0, 0))
            vc = lax.dynamic_update_slice(cache.v, vq, (0, idx, 0, 0))
            ksc = lax.dynamic_update_slice(cache.k_scale, ks, (0, idx, 0))
            vsc = lax.dynamic_update_slice(cache.v_scale, vs, (0, idx, 0))
            new_cache = QuantKVCache(kc, vc, ksc, vsc, cache.pos + 1)
        else:
            kc = lax.dynamic_update_slice(
                cache.k, k.astype(cache.k.dtype), (0, idx, 0, 0)
            )
            vc = lax.dynamic_update_slice(
                cache.v, v.astype(cache.v.dtype), (0, idx, 0, 0)
            )
            new_cache = KVCache(kc, vc, cache.pos + 1)
        out = attend_decode(qg, new_cache, ring=ring, window=window)
    elif mode == "decode" and is_cross:
        # cross-attention during decode: attend to static image KV
        out = _cross_decode(qg, cache)
        new_cache = cache
    else:
        causal = cfg.causal and not is_cross
        q_off = int(pos_offset) if isinstance(pos_offset, int) else 0
        if _flash_prefill_viable(
            mode, causal, window, is_cross, pos_offset, qg, k
        ):
            out = _flash_prefill_call(qg, k, v, q_offset=q_off)
        else:
            out = attend_tiled(
                qg, k, v,
                causal=causal,
                window=window,
                q_offset=q_off,
                chunk=min(env.attn_chunk, S),
                causal_skip=env.causal_skip,
            )
        if mode == "prefill":
            if is_cross:
                new_cache = KVCache(k, v, jnp.asarray(k.shape[1], jnp.int32))
            else:
                if cache is None:
                    raise ValueError("prefill needs a pre-allocated KV cache")
                C = cache.capacity
                pos = jnp.asarray(S, jnp.int32)
                # C < S keeps the trailing window, ROLLED so absolute
                # position p sits at slot p % C — the layout the ring
                # decode formula (attend_decode) and the ring write index
                # (idx = pos % C above) both assume
                if isinstance(cache, QuantKVCache):
                    ks, kv_sc = _quantize_kv(k if C >= S else k[:, S - C:])
                    vs, vv_sc = _quantize_kv(v if C >= S else v[:, S - C:])
                    if C >= S:
                        kc = lax.dynamic_update_slice(cache.k, ks, (0, 0, 0, 0))
                        vc = lax.dynamic_update_slice(cache.v, vs, (0, 0, 0, 0))
                        ksc = lax.dynamic_update_slice(cache.k_scale, kv_sc, (0, 0, 0))
                        vsc = lax.dynamic_update_slice(cache.v_scale, vv_sc, (0, 0, 0))
                    else:
                        r = S % C
                        kc = jnp.roll(ks, r, axis=1)
                        vc = jnp.roll(vs, r, axis=1)
                        ksc = jnp.roll(kv_sc, r, axis=1)
                        vsc = jnp.roll(vv_sc, r, axis=1)
                    new_cache = QuantKVCache(kc, vc, ksc, vsc, pos)
                else:
                    kc, vc = cache.k, cache.v
                    if C >= S:
                        kc = lax.dynamic_update_slice(kc, k.astype(kc.dtype), (0, 0, 0, 0))
                        vc = lax.dynamic_update_slice(vc, v.astype(vc.dtype), (0, 0, 0, 0))
                    else:
                        kc = jnp.roll(k[:, S - C :], S % C, axis=1).astype(kc.dtype)
                        vc = jnp.roll(v[:, S - C :], S % C, axis=1).astype(vc.dtype)
                    new_cache = KVCache(kc, vc, pos)

    out = out.reshape(B, S, Hq_l * hd)
    y = out @ w["wo"]
    if is_cross and "gate" in w:
        y = jnp.tanh(w["gate"]) * y
    return env.exit(y), new_cache


def _cross_decode(qg, cache: KVCache):
    """Decode-time gated cross attention over the static image KV."""
    B, S, Kv, G, hd = qg.shape
    scale = hd**-0.5
    s = jnp.einsum(
        "bqkgh,bskh->bkgqs", qg, cache.k, preferred_element_type=jnp.float32
    ) * scale
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkgqs,bskh->bkgqh", p, cache.v, preferred_element_type=jnp.float32
    ).astype(qg.dtype)
    return out.transpose(0, 3, 1, 2, 4)
