"""Pallas TPU kernel: online-softmax flash prefill attention.

The fused form of :func:`repro.models.attention.attend_tiled`: each
``(batch, head, q-block)`` runs the ``(m, l, acc)`` running rescale over
its k-blocks *inside* the kernel, so the ``(Sq, Sk)`` score matrix never
round-trips through HBM — scores, softmax weights and the
weighted value sum live entirely in VMEM. That is the paper's thesis
applied to attention itself: the data motion (score traffic) shrinks,
the FLOPs stay identical.

Bit-compatibility contract (mirrors :mod:`repro.kernels.bitpack`):
:func:`flash_prefill_ref` is the pure-JAX oracle that replays the exact
tile schedule through the shared :func:`_flash_tile` update, so under
``interpret=True`` kernel and oracle agree *bitwise*
(``tests/test_kernels.py``). Dispatch follows ``resolve_interpret``:
compiled on a real TPU, interpreted elsewhere. The serving engine's CPU
reference path keeps using ``attend_tiled`` (the bit-exactness pin vs
``generate_static``); this kernel is the TPU fast path.

GQA layout: ``q (B, H, Sq, hd)`` attends ``k/v (B, Kv, Sk, hd)`` with
``G = H // Kv`` query heads sharing each kv head (the k/v BlockSpec
index map walks ``h // G``). The grid is ``(B, H, q-block, k-block)``;
the ``(m, l, acc)`` carry lives in VMEM scratch across the sequential
k-block steps (initialised at the first, written out at the last), as in
:mod:`repro.kernels.paged_attention`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitpack import resolve_interpret

NEG_INF = -1e30  # matches models.attention: exp() underflows to exact 0.0
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _flash_tile(q, k, v, mask, m, l, acc):
    """One (block_q, block_k) online-softmax tile update.

    ``q (bq, hd)``, ``k/v (bk, hd)``, ``mask (bq, bk)`` bool,
    carry ``m/l (bq,)`` and ``acc (bq, hd)`` in fp32 — the same
    max/rescale algebra as ``attention._attend_tile``/``_combine``,
    fused into a single update. Shared VERBATIM by the kernel body and
    the oracle: bitwise parity under interpret mode is by construction.
    """
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (q.shape[-1] ** -0.5)
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[:, None] + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def _tile_mask(q_pos, j, block_q, block_k, causal):
    """(bq, bk) validity mask for k-block ``j`` (shared kernel/oracle)."""
    k_pos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    if not causal:
        return jnp.ones((block_q, block_k), bool)
    return q_pos >= k_pos


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, q_offset: int):
    qi = pl.program_id(2)
    j = pl.program_id(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q_pos = q_offset + qi * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0
    )
    mask = _tile_mask(q_pos, j, bq, bk, causal)
    m, l, acc = _flash_tile(
        q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], mask,
        m_ref[...], l_ref[...], acc_ref[...],
    )
    m_ref[...] = m
    l_ref[...] = l
    acc_ref[...] = acc

    @pl.when(j == pl.num_programs(3) - 1)
    def _emit():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


def _resolve_blocks(Sq, Sk, block_q, block_k):
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"Sq={Sq}/Sk={Sk} must divide into blocks ({block_q}, {block_k})"
        )
    return block_q, block_k


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_offset", "block_q", "block_k", "interpret"),
)
def flash_prefill(
    q: jnp.ndarray,  # (B, H, Sq, hd)
    k: jnp.ndarray,  # (B, Kv, Sk, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused flash prefill attention; returns ``(B, H, Sq, hd)``.

    ``q_offset`` is the absolute position of ``q[..., 0, :]`` relative to
    ``k[..., 0, :]`` (prefill continuation), as in ``attend_tiled``. The
    k-blocks are the innermost grid axis, so K/V stream through VMEM one
    ``(block_k, hd)`` tile at a time and the working set does not grow
    with ``Sk``.
    """
    B, H, Sq, hd = q.shape
    Kv, Sk = k.shape[1], k.shape[2]
    if H % Kv:
        raise ValueError(f"H={H} not a multiple of Kv={Kv}")
    G = H // Kv
    block_q, block_k = _resolve_blocks(Sq, Sk, block_q, block_k)
    return pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, q_offset=q_offset),
        grid=(B, H, Sq // block_q, Sk // block_k),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd), lambda b, h, i, j: (b, h // G, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd), lambda b, h, i, j: (b, h // G, j, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)


@functools.partial(
    jax.jit, static_argnames=("causal", "q_offset", "block_q", "block_k")
)
def flash_prefill_ref(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
) -> jnp.ndarray:
    """Pure-JAX oracle: replays the kernel's exact tile schedule through
    the shared :func:`_flash_tile` update (bitwise-parity reference).

    The structure mirrors the kernel op-for-op — a ``fori_loop`` over
    k-blocks sliced with ``dynamic_slice``, under jit — because XLA's
    matmul accumulation order depends on that compilation context; an
    unrolled eager replay lands ~1 ulp away.
    """
    B, H, Sq, hd = q.shape
    Kv, Sk = k.shape[1], k.shape[2]
    G = H // Kv
    block_q, block_k = _resolve_blocks(Sq, Sk, block_q, block_k)
    out = jnp.zeros_like(q)
    for b in range(B):
        for h in range(H):
            k_head = jax.lax.dynamic_slice(k, (b, h // G, 0, 0), (1, 1, Sk, hd))[0, 0]
            v_head = jax.lax.dynamic_slice(v, (b, h // G, 0, 0), (1, 1, Sk, hd))[0, 0]
            for i in range(Sq // block_q):
                q_blk = jax.lax.dynamic_slice(
                    q, (b, h, i * block_q, 0), (1, 1, block_q, hd)
                )[0, 0]
                q_pos = q_offset + i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
                l0 = jnp.zeros((block_q,), jnp.float32)
                a0 = jnp.zeros((block_q, hd), jnp.float32)

                def body(j, carry, q_blk=q_blk, q_pos=q_pos,
                         k_head=k_head, v_head=v_head):
                    m, l, acc = carry
                    k_blk = jax.lax.dynamic_slice(
                        k_head, (j * block_k, 0), (block_k, hd)
                    )
                    v_blk = jax.lax.dynamic_slice(
                        v_head, (j * block_k, 0), (block_k, hd)
                    )
                    mask = _tile_mask(q_pos, j, block_q, block_k, causal)
                    return _flash_tile(q_blk, k_blk, v_blk, mask, m, l, acc)

                m, l, acc = jax.lax.fori_loop(
                    0, Sk // block_k, body, (m0, l0, a0)
                )
                o = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(q.dtype)
                out = jax.lax.dynamic_update_slice(
                    out, o[None, None], (b, h, i * block_q, 0)
                )
    return out
