"""Pallas TPU kernel: paged decode attention over a block page table.

Decode-time attention for the block-paged KV cache: K/V live in a page
pool ``(P, page, Kv, hd)`` and each slot owns a row of the page table
``(B, n_pages)`` mapping logical page ``j`` to a physical pool row. The
kernel walks the table with a scalar-prefetch index map — the grid is
``(B, n_pages)`` and the K/V BlockSpec picks block ``table[b, j]`` —
and each slot walks only its live prefix, the ``ceil(lengths[b] /
page)`` pages that hold its tokens. A grid step past that prefix does no
compute, and its index map repeats the last live page's block index, so
the pipeline fetches nothing for it either: pages reserved for tokens
not yet written, and a retired slot's row (``lengths[b] == 0``), cost a
grid step's fixed overhead and no HBM traffic.

The online softmax ``(m, l, acc)`` carry persists in VMEM scratch
across the sequential ``j`` steps of one batch row (initialised at
``j == 0``, output written at the last ``j``), the same running-rescale
algebra as :mod:`repro.kernels.flash_prefill`. Skipping the dead pages
changes no bit of a row with ``lengths[b] >= 1``: its first page holds a
live token, so ``m`` is finite after it, and a fully masked page then
gives ``m_new = m``, ``p = 0`` and ``corr = 1``, leaving ``l`` and
``acc`` unchanged to the bit. A ``lengths[b] == 0`` row emits zeros
(``0 / max(0, 1e-30)``).

Bit-compatibility contract: :func:`paged_attend_ref` replays the exact
page walk through the shared :func:`_page_update` helper, so kernel and
oracle agree bitwise under ``interpret=True``. Dispatch mirrors
``kernels/bitpack.py``: ``resolve_interpret`` compiles on a real TPU
and interprets elsewhere. The serve engine's CPU path uses the dense
``attend_decode_paged`` reference in ``models/attention.py`` (bit-exact
vs the contiguous engine); this kernel is the TPU fast path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitpack import resolve_interpret

NEG_INF = -1e30  # matches models.attention: exp() underflows to exact 0.0


def _page_valid(j, page: int, length):
    """(page,) bool — which rows of logical page ``j`` hold live tokens.

    Shared kernel/oracle. 2D iota then squeeze: TPU requires >=2D iota.
    """
    offs = jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)[:, 0]
    return (j * page + offs) < length


def _page_update(q, k_pg, v_pg, valid, m, l, acc):
    """One page of the online-softmax walk.

    ``q (Kv, G, hd)``; ``k_pg/v_pg (page, Kv, hd)``; ``valid (page,)``
    bool; carry ``m/l (Kv, G)`` and ``acc (Kv, G, hd)`` in fp32. Shared
    VERBATIM by kernel body and oracle — bitwise parity by construction.
    """
    s = jnp.einsum(
        "kgh,pkh->kgp", q, k_pg, preferred_element_type=jnp.float32
    ) * (q.shape[-1] ** -0.5)
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "kgp,pkh->kgh", p, v_pg.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def _paged_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j * page < len_ref[b])  # page j holds a live token
    def _step():
        valid = _page_valid(j, page, len_ref[b])
        m, l, acc = _page_update(
            q_ref[0], k_ref[0], v_ref[0], valid,
            m_ref[...], l_ref[...], acc_ref[...],
        )
        m_ref[...] = m
        l_ref[...] = l
        acc_ref[...] = acc

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attend(
    q: jnp.ndarray,        # (B, Kv, G, hd) — one decode step of queries
    k_pool: jnp.ndarray,   # (P, page, Kv, hd) — shared page pool
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,  # (B, n_pages) int32 physical page ids
    lengths: jnp.ndarray,     # (B,) int32 live tokens per slot
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Paged decode attention; returns ``(B, Kv, G, hd)``.

    Every table entry must be a valid pool row (point unused entries at
    a ballast page). Slot ``b`` walks pages ``0 .. ceil(lengths[b] /
    page) - 1`` only; rows past ``lengths[b]`` within its last page are
    masked to ``NEG_INF`` so their softmax weight is exactly 0.0, and a
    ``lengths[b] == 0`` row returns zeros.
    """
    B, Kv, G, hd = q.shape
    P, page = k_pool.shape[0], k_pool.shape[1]
    n_pages = page_table.shape[1]

    def kv_block(b, j, table, lens):
        # past the live prefix, repeat the last live block: no new DMA
        # (lens - 1 clamped at 0 first, so nothing negative is divided)
        last = jnp.maximum(lens[b] - 1, 0) // page
        return (table[b, jnp.minimum(j, last)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, Kv, G, hd), lambda b, j, table, lens: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, Kv, hd), kv_block),
            pl.BlockSpec((1, page, Kv, hd), kv_block),
        ],
        out_specs=pl.BlockSpec(
            (1, Kv, G, hd), lambda b, j, table, lens: (b, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((Kv, G), jnp.float32),
            pltpu.VMEM((Kv, G), jnp.float32),
            pltpu.VMEM((Kv, G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, page=page),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, G, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pool, v_pool)


@jax.jit
def paged_attend_ref(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
) -> jnp.ndarray:
    """Pure-JAX oracle: replays the kernel's page walk through the shared
    :func:`_page_update` helper (bitwise-parity reference), over the same
    live prefix of ``ceil(lengths[b] / page)`` pages.

    As in ``flash_prefill_ref``, the walk is a jitted ``fori_loop`` with
    ``dynamic_slice`` page gathers so XLA compiles the per-page einsums
    in the same context as the interpreted kernel — an unrolled eager
    replay differs by ~1 ulp.
    """
    B, Kv, G, hd = q.shape
    page = k_pool.shape[1]
    n_pages = page_table.shape[1]
    out = []
    for b in range(B):
        q_b = q[b]
        m0 = jnp.full((Kv, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((Kv, G), jnp.float32)
        a0 = jnp.zeros((Kv, G, hd), jnp.float32)

        def body(j, carry, b=b, q_b=q_b):
            m, l, acc = carry
            pid = page_table[b, j]
            k_pg = jax.lax.dynamic_slice(
                k_pool, (pid, 0, 0, 0), (1, page, Kv, hd)
            )[0]
            v_pg = jax.lax.dynamic_slice(
                v_pool, (pid, 0, 0, 0), (1, page, Kv, hd)
            )[0]
            valid = _page_valid(j, page, lengths[b])
            return _page_update(q_b, k_pg, v_pg, valid, m, l, acc)

        n_live = jnp.minimum((lengths[b] + page - 1) // page, n_pages)
        m, l, acc = jax.lax.fori_loop(0, n_live, body, (m0, l0, a0))
        out.append(
            (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        )
    return jnp.stack(out, axis=0)
