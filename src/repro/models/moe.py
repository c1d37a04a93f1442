"""Mixture-of-Experts channel mixer: top-k router + two sharding layouts.

* ``tp``  — every expert's d_ff is sharded over the model axis; dispatch is
  device-local and the only collective is the block-exit psum. Used when the
  expert count doesn't divide the TP degree (mixtral: 8e over 16 shards).
* ``ep``  — experts sharded over the model axis (arctic: 128e → 8/shard);
  tokens are split over the model axis, dispatched via ``all_to_all`` to
  their expert owners, processed, returned via the mirrored ``all_to_all``,
  and re-replicated with an all-gather. This is the paper-relevant pattern:
  the all-to-all wire bytes show up in the roofline's collective term.

Dispatch is sort-based with a static capacity (no (T,E,C) one-hot blow-up):
tokens are ranked within their expert via ``searchsorted`` over the sorted
expert ids and scattered into an (E, C, d) buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.env import Env
from repro.transport import axis_size
from repro.utils.trees import round_up


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _token_split(x, axis_name):
    """fwd: take this rank's token chunk; bwd: all-gather chunk cotangents."""
    m = lax.axis_index(axis_name)
    tloc = x.shape[0] // axis_size(axis_name)
    return lax.dynamic_slice_in_dim(x, m * tloc, tloc, axis=0)


def _tsplit_fwd(x, axis_name):
    return _token_split(x, axis_name), None


def _tsplit_bwd(axis_name, _, g):
    # lint: allow(RAW-COLLECTIVE): EP token-split transpose — lossless re-layout, raw dtype is the wire format (audited as relayout)
    return (lax.all_gather(g, axis_name, axis=0, tiled=True),)


_token_split.defvjp(_tsplit_fwd, _tsplit_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _token_merge(x_loc, axis_name):
    """fwd: all-gather token chunks; bwd: slice this rank's cotangent."""
    # lint: allow(RAW-COLLECTIVE): EP token-merge — lossless re-layout, raw dtype is the wire format (audited as relayout)
    return lax.all_gather(x_loc, axis_name, axis=0, tiled=True)


def _tmerge_fwd(x_loc, axis_name):
    return _token_merge(x_loc, axis_name), None


def _tmerge_bwd(axis_name, _, g):
    m = lax.axis_index(axis_name)
    tloc = g.shape[0] // axis_size(axis_name)
    return (lax.dynamic_slice_in_dim(g, m * tloc, tloc, axis=0),)


_token_merge.defvjp(_tmerge_fwd, _tmerge_bwd)


def _route(x, router_w, num_experts: int, top_k: int):
    """Top-k routing in fp32. Returns (probs (T,k), experts (T,k), aux)."""
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs_full = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs_full, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # renormalize
    # switch-style load-balance loss
    T = x.shape[0]
    me = jnp.mean(probs_full, axis=0)
    one_hot = jax.nn.one_hot(top_e[:, 0], num_experts, dtype=jnp.float32)
    ce = jnp.mean(one_hot, axis=0)
    aux = num_experts * jnp.sum(me * ce)
    return top_p, top_e, aux


def _dispatch_indices(top_e: jnp.ndarray, num_experts: int, capacity: int):
    """Sort-based capacity dispatch.

    Returns (src_token (N,), dest_slot (N,), keep (N,), probs_order (N,))
    where N = T*k and dest_slot indexes an (E*C,) buffer (dropped tokens
    point at slot E*C, which is sliced away)."""
    T, k = top_e.shape
    flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(num_experts))
    rank = jnp.arange(T * k) - starts[sorted_e]
    keep = rank < capacity
    dest = jnp.where(keep, sorted_e * capacity + rank, num_experts * capacity)
    src = order // k
    return src, dest, keep, order


def _expert_ffn(buf, w_gate, w_up, w_down):
    """(E, C, d) x per-expert SwiGLU -> (E, C, d)."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    return jnp.einsum("ecf,efd->ecd", g * u, w_down)


def moe_block(x: jnp.ndarray, w: dict, cfg, env: Env) -> tuple[jnp.ndarray, jnp.ndarray]:
    """MoE mixer on (B, S, d) -> (out, aux_loss). Dispatch per cfg.moe_impl.

    Under ``env.seq_parallel`` the incoming ``x`` is a sequence shard.
    The ``tp`` layout gathers it at the block boundary (``env.enter``,
    fwd all-gather) and reduce-scatters the partial outputs back
    (``env.exit``) — the same contract as the dense mixers. The ``ep``
    layout needs no boundary collective at all: the sequence shards
    *are* this rank's token split, so dispatch goes straight to the
    expert all_to_alls and the combined output already is the shard."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    impl = cfg.moe_impl if env.tp > 1 else "tp"
    sp = env.seq_parallel_active

    dense_y = None
    if cfg.moe_dense_ff and impl == "ep":
        # arctic's parallel dense residual: computed TP-style on the
        # replicated tokens (EP token-splitting below must not see it —
        # its weights are model-axis sharded and need the exit psum).
        # Boundary collectives run at (B, S, d) so the seq-parallel
        # gather/scatter land on the sequence axis.
        xr = env.enter(x).reshape(-1, d)
        g = jax.nn.silu(xr @ w["dense_gate"])
        u = xr @ w["dense_up"]
        dy = ((g * u) @ w["dense_down"]).reshape(B, -1, d)
        dense_y = env.exit(dy)

    # EP needs the token count to split evenly over the model axis; decode
    # steps have a handful of tokens, so they run "replicated EP": every
    # rank dispatches the full (tiny) token set and the all_to_all carries
    # M redundant copies — negligible at decode token counts.
    ep_split = impl == "ep" and (B * S) % env.tp == 0 and (B * S) >= env.tp

    if impl == "ep" and sp:
        # sequence shards are already a disjoint per-rank token split
        xf = x.reshape(B * S, d)
    elif impl == "ep" and ep_split:
        xf = _token_split(env.psum_enter(x.reshape(B * S, d)), env.model_axis)
    elif impl == "ep":
        xf = env.psum_enter(x.reshape(B * S, d))
    else:  # tp layout: boundary collectives at (B, S, d)
        xf = env.enter(x).reshape(-1, d)
    T = xf.shape[0]

    top_p, top_e, aux = _route(xf, w["router"], E, k)
    capacity = max(8, round_up(int(cfg.capacity_factor * T * k / E), 8))
    src, dest, keep, order = _dispatch_indices(top_e, E, capacity)

    buf = jnp.zeros((E * capacity + 1, d), xf.dtype)
    buf = buf.at[dest].add(xf[src] * keep[:, None].astype(xf.dtype))
    buf = buf[:-1].reshape(E, capacity, d)

    if impl == "ep":
        M = env.tp
        e_loc = E // M
        # (E, C, d) -> exchange expert dim: every rank keeps its e_loc experts
        # lint: allow(RAW-COLLECTIVE): EP expert exchange — a permutation of token buffers, lossless by definition (audited as relayout)
        sent = lax.all_to_all(
            buf, env.model_axis, split_axis=0, concat_axis=1, tiled=True
        )  # (e_loc, M*C, d)
        out_loc = _expert_ffn(sent, w["w_gate"], w["w_up"], w["w_down"])
        # lint: allow(RAW-COLLECTIVE): EP expert return exchange — same lossless permutation on the way back
        buf_out = lax.all_to_all(
            out_loc, env.model_axis, split_axis=1, concat_axis=0, tiled=True
        )  # (E, C, d)
    else:
        out_full = _expert_ffn(buf, w["w_gate"], w["w_up"], w["w_down"])
        buf_out = out_full  # psum applied at block exit

    flat_out = buf_out.reshape(E * capacity, d)
    flat_out = jnp.concatenate([flat_out, jnp.zeros((1, d), xf.dtype)], axis=0)
    gathered = flat_out[dest] * (top_p.reshape(-1)[order] * keep)[:, None].astype(
        xf.dtype
    )
    y = jnp.zeros((T, d), xf.dtype).at[src].add(gathered)

    if cfg.moe_dense_ff and impl != "ep":
        # dense residual in the TP layout shares the block-exit psum
        g = jax.nn.silu(xf @ w["dense_gate"])
        u = xf @ w["dense_up"]
        y = y + (g * u) @ w["dense_down"]

    if impl == "ep" and sp:
        # y is complete for this rank's tokens == the sequence shard
        # lint: allow(RAW-COLLECTIVE): scalar MoE aux-loss reduction — metrics traffic, audited as a scalar psum
        aux = lax.psum(aux, env.model_axis) / env.tp
        y = y.reshape(B, S, d)
    elif impl == "ep" and ep_split:
        y = _token_merge(y, env.model_axis).reshape(B, S, d)
        # lint: allow(RAW-COLLECTIVE): scalar MoE aux-loss reduction — metrics traffic, audited as a scalar psum
        aux = lax.psum(aux, env.model_axis) / env.tp
    elif impl == "ep":
        y = y.reshape(B, S, d)  # replicated EP: complete on every rank
    else:
        # (B, S_full, d) under seq_parallel: exit scatters back to shards
        y = env.exit(y.reshape(B, -1, d))
    if dense_y is not None:
        y = y + dense_y
    return y, aux
