"""The compression transport: pack -> collective -> unpack pipelines.

This module owns every compressed byte that crosses a mesh link:

  * :func:`all_gather` — weight path. fp32 shard -> byte planes (Pallas
    bitpack on TPU, oracle on CPU) -> plane all-gather over the FSDP axes
    -> bitunpack. Its custom VJP is a (optionally compressed)
    reduce-scatter, so training steps just call it and get the paper's
    weight/gradient motion for free.
  * :func:`reduce_scatter` — gradient path (beyond-paper): pack the chunk
    destined for each peer, ``all_to_all`` the planes, unpack and reduce
    locally in fp32. Handles arbitrary-rank leaves and any scatter axis
    (placed / stacked storage included); the reshape to per-peer plane
    blocks happens here, never at call sites.
  * :func:`seq_gather` / :func:`seq_scatter` — activation path (TP axis).
    The sequence-parallel conjugate pair: compressed all-gather along the
    sequence dim with a compressed reduce-scatter VJP, and vice versa.
    Dtype-preserving (bf16 activations round-trip through an exact fp32
    cast before packing).
  * :func:`all_reduce` — compressed all-reduce, decomposed into
    reduce-scatter + all-gather of packed planes along a divisible split
    axis. NOT differentiable by design: it is the forward/cotangent mover
    inside the TP-region custom VJPs (``core.collectives``), whose
    transposes must stay pinned to identity to avoid double-counting.
  * :func:`quantize` — single-device format truncation (pack∘unpack) with
    a straight-through VJP: what the compute side sees when there is no
    collective to ride on.

Kernel dispatch is backend-aware: ``CompressionPolicy.impl="auto"`` lowers
the Pallas kernels compiled on TPU and falls back to the pure-jnp oracle on
CPU (where the distributed steps want pure-HLO collectives); ``"pallas"``
forces the kernels, running them in interpret mode off-TPU. Both impls are
bit-exact by construction (same byte-plane semantics), which
``tests/test_transport.py`` locks in.

The chunked path (``policy.chunks > 1``) splits the gather into
independent pack -> all-gather -> unpack block pipelines so XLA's async
collectives can overlap block k's wire time with block k±1's pack/unpack
(double buffering), then re-interleaves the blocks to the exact layout of
the unchunked gather.

Wire formats per entry point (see docs/collectives.md for the plane
layout and a worked byte example): weight-path forwards move
``policy.round_to`` bytes/element, gradient/cotangent paths
``policy.grad_round_to``; ``seq_gather``/``seq_scatter`` forwards use the
policy's forward fields and their VJPs the grad fields, so one activation
policy describes both directions of the TP axis.
"""
from __future__ import annotations

import functools
from typing import Hashable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels import ref
from repro.kernels.bitpack import BLOCK_ROWS, LANES, bitpack_2d
from repro.kernels.bitunpack import bitunpack_2d
from repro.transport.policy import FP32_BYTES, CompressionPolicy, policy_for
from repro.utils.trees import round_up

AxisNames = Hashable | Sequence[Hashable]


# ---------------------------------------------------------------------------
# mesh-axis helpers
# ---------------------------------------------------------------------------


def axis_size(axis_names: AxisNames) -> int:
    """Static total size of one axis name or a tuple of axis names."""
    if isinstance(axis_names, (tuple, list)):
        total = 1
        for a in axis_names:
            total *= lax.axis_size(a)
        return total
    return lax.axis_size(axis_names)


def resolve_impl(impl: str, mode: str = "truncate") -> str:
    """auto -> pallas on TPU, ref on CPU. Rounding modes other than
    truncation need PRNG/word-level arithmetic and live in the ref path."""
    if mode != "truncate":
        return "ref"
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


# ---------------------------------------------------------------------------
# pack / unpack dispatch (exact-shape planes)
# ---------------------------------------------------------------------------


def pack_planes(
    w: jnp.ndarray,
    round_to: int,
    *,
    mode: str = "truncate",
    impl: str = "auto",
    key=None,
) -> jnp.ndarray:
    """fp32 array (any shape) -> uint8 byte planes ``(round_to, *w.shape)``.

    Plane 0 is the most significant byte. The Pallas path pads to the
    kernel's tile internally and slices back, so the planes returned are
    always exact-shape — safe to feed straight into a collective.
    """
    if resolve_impl(impl, mode) == "ref":
        return ref.bitpack_ref(w, round_to, mode=mode, key=key)
    flat = w.reshape(-1)
    n = flat.shape[0]
    tile = BLOCK_ROWS * LANES
    padded = round_up(max(n, 1), tile)
    flat = jnp.pad(flat, (0, padded - n))
    # interpret mode resolves inside the kernel wrapper (backend-aware)
    planes = bitpack_2d(flat.reshape(-1, LANES), round_to)
    return planes.reshape(round_to, padded)[:, :n].reshape(
        (round_to,) + w.shape
    )


def unpack_planes(planes: jnp.ndarray, *, impl: str = "auto") -> jnp.ndarray:
    """uint8 byte planes ``(round_to, *shape)`` -> fp32 ``shape``."""
    if resolve_impl(impl) == "ref":
        return ref.bitunpack_ref(planes)
    round_to = planes.shape[0]
    shape = planes.shape[1:]
    flat = planes.reshape(round_to, -1)
    n = flat.shape[1]
    tile = BLOCK_ROWS * LANES
    padded = round_up(max(n, 1), tile)
    flat = jnp.pad(flat, ((0, 0), (0, padded - n)))
    out = bitunpack_2d(flat.reshape(round_to, -1, LANES))
    return out.reshape(-1)[:n].reshape(shape)


# ---------------------------------------------------------------------------
# forward implementations
# ---------------------------------------------------------------------------


def _packed_all_gather(x, axis_names, round_to, mode, impl, axis: int,
                       key=None):
    """Compressed all-gather of an arbitrary-rank array along ``axis``.

    Dtype-preserving: non-fp32 inputs (bf16 activations) are cast to fp32
    — exactly — before packing and the unpacked result is cast back.
    ``key`` feeds stochastic rounding (required iff mode="stochastic").
    """
    axis = axis % x.ndim  # planes prepend a dim: negatives must resolve first
    out_dtype = x.dtype
    xf = x.astype(jnp.float32)
    planes = pack_planes(xf, round_to, mode=mode, impl=impl, key=key)
    # planes prepend the plane dim, so the data axis shifts by one
    planes_g = lax.all_gather(planes, axis_names, axis=axis + 1, tiled=True)
    return unpack_planes(planes_g, impl=impl).astype(out_dtype)


def _packed_reduce_scatter(g, axis_names, round_to, mode, impl, axis: int,
                           key=None):
    """Compressed reduce-scatter of an arbitrary-rank array along ``axis``.

    The scatter dim is split into per-peer plane blocks *here* — call
    sites never reshape. Each peer's block is packed, the planes ride one
    ``all_to_all`` (single- or multi-axis), and the unpacked
    contributions are accumulated locally in fp32 before casting back to
    the input dtype. Trailing dims are unconstrained; only the scatter
    dim must divide by the axis size (inherent to reduce-scatter).
    """
    axis = axis % g.ndim  # moveaxis target 0 below: resolve negatives first
    size = axis_size(axis_names)
    length = g.shape[axis]
    if length % size:
        raise ValueError(
            f"scatter dim {axis} of shape {g.shape} not divisible by "
            f"axis size {size}"
        )
    out_dtype = g.dtype
    gm = jnp.moveaxis(g.astype(jnp.float32), axis, 0)
    block = (length // size,) + gm.shape[1:]
    gm = gm.reshape((size,) + block)
    if gm[0].size % LANES == 0:
        # lay each peer's block out as (rows, 128): the TPU compiler takes
        # minutes over an all_to_all of u8 planes whose minor dims are
        # small or ragged (seconds per 10M elements), and under a second
        # over lane-shaped ones. Same bytes, same order, same values.
        gm = gm.reshape(size, -1, LANES)
    planes = pack_planes(gm, round_to, mode=mode, impl=impl, key=key)
    # (round_to, size, ...): exchange the `size` dim; after the
    # all_to_all the exchanged dim stays `size` (= one block per peer).
    planes_x = lax.all_to_all(
        planes, axis_names, split_axis=1, concat_axis=1, tiled=False
    )
    contribs = unpack_planes(planes_x, impl=impl)
    out = jnp.sum(contribs, axis=0).reshape(block)  # fp32 accumulation
    return jnp.moveaxis(out, 0, axis).astype(out_dtype)


def _all_gather_impl(w, axis_names, policy: CompressionPolicy, axis: int,
                     key=None):
    if not policy.compresses:
        return lax.all_gather(w, axis_names, axis=axis, tiled=True)
    if (
        policy.chunks > 1
        and axis == 0
        and w.ndim == 1
        and w.shape[0] % policy.chunks == 0
    ):
        return _chunked_all_gather(w, axis_names, policy, key)
    return _packed_all_gather(
        w, axis_names, policy.round_to, policy.mode, policy.impl, axis,
        key=key,
    )


def _chunked_all_gather(w, axis_names, policy: CompressionPolicy, key=None):
    """Double-buffered gather: independent per-block plane pipelines,
    re-interleaved to match the unchunked layout exactly."""
    n_chunks = policy.chunks
    loc = w.shape[0] // n_chunks
    gathered = []
    for c in range(n_chunks):
        piece = lax.slice_in_dim(w, c * loc, (c + 1) * loc)
        planes = pack_planes(
            piece, policy.round_to, mode=policy.mode, impl=policy.impl,
            key=None if key is None else jax.random.fold_in(key, c),
        )
        planes_g = lax.all_gather(planes, axis_names, axis=1, tiled=True)
        gathered.append(unpack_planes(planes_g, impl=policy.impl))
    # gathered[c] = concat_d shard_d[block c]; the full gather is
    # concat_d concat_c shard_d[block c] — transpose (chunk, device) out.
    n_dev = axis_size(axis_names)
    stacked = jnp.stack(gathered, 0).reshape(n_chunks, n_dev, loc)
    return jnp.transpose(stacked, (1, 0, 2)).reshape(-1)


def _reduce_scatter_impl(g, axis_names, policy: CompressionPolicy, axis: int,
                         key=None):
    if not policy.compresses_grads:
        return lax.psum_scatter(
            g, axis_names, scatter_dimension=axis, tiled=True
        )
    return _packed_reduce_scatter(
        g, axis_names, policy.grad_round_to, policy.grad_mode, policy.impl,
        axis, key=key,
    )


def _seq_gather_impl(x, axis_names, policy: CompressionPolicy, axis: int):
    if not policy.compresses:
        return lax.all_gather(x, axis_names, axis=axis, tiled=True)
    return _packed_all_gather(
        x, axis_names, policy.round_to, policy.mode, policy.impl, axis
    )


def _seq_scatter_impl(x, axis_names, policy: CompressionPolicy, axis: int):
    # forward activation path: the policy's *forward* format fields
    if not policy.compresses:
        return lax.psum_scatter(
            x, axis_names, scatter_dimension=axis, tiled=True
        )
    return _packed_reduce_scatter(
        x, axis_names, policy.round_to, policy.mode, policy.impl, axis
    )


def pick_split_axis(shape, size: int) -> int | None:
    """Rightmost dim divisible by ``size`` — the axis the compressed
    all-reduce decomposition splits along (rightmost so the per-peer
    blocks stay contiguous in the activation layout (B, S, d): feature
    dim first, then sequence, then batch). None = no divisible dim; the
    caller falls back to an uncompressed ``lax.psum``."""
    for a in reversed(range(len(shape))):
        if shape[a] >= size and shape[a] % size == 0:
            return a
    return None


def _all_reduce_impl(
    x, axis_names, policy: CompressionPolicy, use_grad_format: bool
):
    rt = policy.grad_round_to if use_grad_format else policy.round_to
    mode = policy.grad_mode if use_grad_format else policy.mode
    if rt >= FP32_BYTES:
        # same barrier as the uncompressed TP-region paths: keeps the
        # psum in the compute dtype (stops the CPU backend's
        # excess-precision pass from cancelling a bf16 down-cast)
        return lax.psum(lax.optimization_barrier(x), axis_names)
    size = axis_size(axis_names)
    axis = pick_split_axis(x.shape, size)
    if axis is None:
        return lax.psum(lax.optimization_barrier(x), axis_names)
    part = _packed_reduce_scatter(x, axis_names, rt, mode, policy.impl, axis)
    return _packed_all_gather(part, axis_names, rt, mode, policy.impl, axis)


def _quantize_impl(w, policy: CompressionPolicy, key=None):
    if not policy.compresses:
        # rt=4 keeps every byte: rounding is a no-op regardless of mode
        return w
    planes = pack_planes(
        w, policy.round_to, mode=policy.mode, impl=policy.impl, key=key
    )
    return unpack_planes(planes, impl=policy.impl)


def _key_cotangent(key):
    """Cotangent for an (integer) PRNG-key primal in a custom VJP: the
    zero of jax's float0 — integer inputs carry no tangent."""
    if key is None:
        return None
    return np.zeros(np.shape(key), jax.dtypes.float0)


# fold id of the backward (cotangent) pack. Deliberately outside the
# forward chunked gather's per-chunk fold range (0..chunks-1) so forward
# and backward stochastic-rounding noise never share a stream.
_BWD_FOLD = 0x62776421


# ---------------------------------------------------------------------------
# differentiable entry points
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def all_gather(
    w_local: jnp.ndarray,
    axis_names: AxisNames,
    policy: CompressionPolicy,
    axis: int = 0,
    key=None,
) -> jnp.ndarray:
    """Compressed all-gather with a reduce-scatter VJP.

    Forward moves ``policy.round_to`` of every fp32 byte over
    ``axis_names``; backward reduce-scatters the cotangent at
    ``policy.grad_round_to`` (4 = uncompressed, paper-faithful). The
    format itself is not differentiated — straight-through, like the
    paper's fp32 master-weight update.

    ``key`` is the stochastic-rounding PRNG key (a primal input so it
    can reach the backward pack: the forward uses it as-is — folded per
    chunk when chunked — and the cotangent reduce-scatter packs with a
    dedicated fold outside the chunk range). Required exactly when a
    used direction has ``mode="stochastic"``.
    """
    return _all_gather_impl(w_local, axis_names, policy, axis, key)


def _ag_fwd(w_local, axis_names, policy, axis, key):
    return _all_gather_impl(w_local, axis_names, policy, axis, key), key


def _ag_bwd(axis_names, policy, axis, key, g):
    gkey = None if key is None else jax.random.fold_in(key, _BWD_FOLD)
    return (
        _reduce_scatter_impl(g, axis_names, policy, axis, key=gkey),
        _key_cotangent(key),
    )


all_gather.defvjp(_ag_fwd, _ag_bwd)


def reduce_scatter(
    g: jnp.ndarray,
    axis_names: AxisNames,
    policy: CompressionPolicy,
    axis: int = 0,
    key=None,
) -> jnp.ndarray:
    """Compressed reduce-scatter along ``axis`` (default 0: the flat
    gradient path, ``(S,)`` -> ``(S_loc,)``).

    Any rank is accepted — stacked leaves scatter their flat dim at
    ``axis=1``, placed activations their sequence dim — with the reshape
    to per-peer plane blocks handled inside the transport. Wire format is
    ``policy.grad_round_to`` bytes; rounding defaults to *nearest* (not
    the paper's truncation) because gradient sums are bias-sensitive.
    ``grad_mode="stochastic"`` needs ``key``.
    """
    return _reduce_scatter_impl(g, axis_names, policy, axis, key=key)


# -- activation path (TP axis) ----------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def seq_gather(
    x: jnp.ndarray,
    axis_names: AxisNames,
    policy: CompressionPolicy,
    axis: int = 1,
) -> jnp.ndarray:
    """Sequence-parallel enter: compressed all-gather of activation
    shards along ``axis`` (1 = sequence), with a compressed
    reduce-scatter VJP.

    Forward moves ``policy.round_to`` of every fp32 byte; the cotangent
    rides the same packed-plane pipeline at ``policy.grad_round_to``.
    Dtype-preserving (bf16 activations cast exactly through fp32).
    """
    return _seq_gather_impl(x, axis_names, policy, axis)


def _sg_fwd(x, axis_names, policy, axis):
    return _seq_gather_impl(x, axis_names, policy, axis), None


def _sg_bwd(axis_names, policy, axis, _, g):
    return (_reduce_scatter_impl(g, axis_names, policy, axis),)


seq_gather.defvjp(_sg_fwd, _sg_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def seq_scatter(
    x: jnp.ndarray,
    axis_names: AxisNames,
    policy: CompressionPolicy,
    axis: int = 1,
) -> jnp.ndarray:
    """Sequence-parallel exit: compressed reduce-scatter of partial
    activations along ``axis``, with a compressed all-gather VJP.

    Forward packs each peer's block at ``policy.round_to`` bytes
    (contributions are summed in fp32 *after* unpacking — planes are
    never added); the cotangent all-gathers at ``policy.grad_round_to``.
    """
    return _seq_scatter_impl(x, axis_names, policy, axis)


def _ss_fwd(x, axis_names, policy, axis):
    return _seq_scatter_impl(x, axis_names, policy, axis), None


def _ss_bwd(axis_names, policy, axis, _, g):
    if not policy.compresses_grads:
        return (lax.all_gather(g, axis_names, axis=axis, tiled=True),)
    return (
        _packed_all_gather(
            g, axis_names, policy.grad_round_to, policy.grad_mode,
            policy.impl, axis,
        ),
    )


seq_scatter.defvjp(_ss_fwd, _ss_bwd)


def all_reduce(
    x: jnp.ndarray,
    axis_names: AxisNames,
    policy: CompressionPolicy,
    *,
    use_grad_format: bool = False,
) -> jnp.ndarray:
    """Compressed all-reduce: reduce-scatter + all-gather of packed
    planes along the rightmost divisible dim (``pick_split_axis``);
    uncompressed policies and shapes with no divisible dim fall back to
    ``lax.psum``.

    NOT differentiable on purpose: this is the data mover *inside* the
    TP-region custom VJPs (``core.collectives.tp_region_enter/exit``),
    whose transposes are pinned to identity — differentiating through
    the decomposition would re-introduce the replicated-operand
    double-count those VJPs exist to prevent. ``use_grad_format=True``
    selects the policy's grad fields (cotangent psums).
    """
    return _all_reduce_impl(x, axis_names, policy, use_grad_format)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def quantize(w: jnp.ndarray, policy: CompressionPolicy, key=None) -> jnp.ndarray:
    """Format truncation (pack∘unpack) with a straight-through VJP.
    ``key`` feeds stochastic rounding (trivial-mesh materialization)."""
    return _quantize_impl(w, policy, key)


def _q_fwd(w, policy, key):
    return _quantize_impl(w, policy, key), key


def _q_bwd(policy, key, g):
    return (g, _key_cotangent(key))


quantize.defvjp(_q_fwd, _q_bwd)


# ---------------------------------------------------------------------------
# object API
# ---------------------------------------------------------------------------


class Transport:
    """Pack -> collective -> unpack pipeline bound to a set of mesh axes.

    The functional forms above are what the custom-VJP machinery uses;
    this object is the ergonomic entry point for code that talks to one
    axis group repeatedly (steps, tests, benchmarks)::

        t = Transport(mesh_cfg.fsdp_axes)
        w_full = t.all_gather(w_shard, policy)        # differentiable
        g_shard = t.reduce_scatter(g_full, policy)    # any rank, axis=...

        tp = Transport(mesh_cfg.model_axis)           # activation path
        x_full = tp.seq_gather(x_shard, act_policy)   # compressed fwd+bwd
        y_shard = tp.seq_scatter(y_partial, act_policy)
        y = tp.all_reduce(y_partial, act_policy)      # inside TP VJPs only
    """

    def __init__(self, axis_names: AxisNames):
        if isinstance(axis_names, list):
            axis_names = tuple(axis_names)
        self.axis_names = axis_names

    def all_gather(self, w, policy, *, axis: int = 0, key=None):
        return all_gather(w, self.axis_names, policy_for(policy), axis, key)

    def reduce_scatter(self, g, policy, *, axis: int = 0, key=None):
        return reduce_scatter(
            g, self.axis_names, policy_for(policy), axis, key
        )

    def seq_gather(self, x, policy, *, axis: int = 1):
        return seq_gather(x, self.axis_names, policy_for(policy), axis)

    def seq_scatter(self, x, policy, *, axis: int = 1):
        return seq_scatter(x, self.axis_names, policy_for(policy), axis)

    def all_reduce(self, x, policy, *, use_grad_format: bool = False):
        return all_reduce(
            x, self.axis_names, policy_for(policy),
            use_grad_format=use_grad_format,
        )

    def quantize(self, w, policy, *, key=None):
        return quantize(w, policy_for(policy), key)

    def axis_size(self) -> int:
        return axis_size(self.axis_names)
