"""Roofline sweep that picks the weight-gather chunk count for a plan.

The chunked gather (``CompressionPolicy.chunks > 1``, see
docs/transport.md §"Chunked double-buffered gather") splits a flat FSDP
shard into independent pack → all-gather → unpack block pipelines so the
wire time of block *k* overlaps the pack/unpack of block *k±1*. More
chunks buy more overlap but pay a per-collective launch latency, so
there is an interior optimum. This helper models the pipeline with the
peak rates of :data:`repro.roofline.analysis.PEAKS` for one device kind
and returns the argmin — the ``plan``-selected chunk count the launchers
use for ``--chunks auto``.
"""
from __future__ import annotations

from repro.roofline.analysis import DRYRUN_KIND, peaks_for
from repro.transport import CompressionPolicy, policy_for

COLLECTIVE_LATENCY = 5e-6   # s per collective launch (dispatch + sync)

CHUNK_CANDIDATES = (1, 2, 4, 8, 16)


def modeled_gather_time(
    s_loc: int, axis_size: int, policy: CompressionPolicy, chunks: int,
    device_kind: str = DRYRUN_KIND,
) -> float:
    """Modeled seconds for one chunked compressed all-gather of an
    ``s_loc``-element fp32 shard over ``axis_size`` devices.

    Per block: pack touches the fp32 read + plane write, unpack the
    gathered planes + fp32 write (HBM term); the plane all-gather pays
    the policy's ring wire bytes (ICI term) plus a launch latency.
    Blocks double-buffer: total ≈ first pack + (chunks-1) overlapped
    stages + last unpack.
    """
    peaks = peaks_for(device_kind)
    n = max(int(axis_size), 1)
    blk = s_loc / chunks
    pack_s = blk * (4 + policy.round_to) / peaks.hbm_bw
    unpack_s = n * blk * (policy.round_to + 4) / peaks.hbm_bw
    wire_s = (
        policy.all_gather_wire_bytes(max(int(blk), 1), n) / peaks.ici_bw
        + COLLECTIVE_LATENCY
    )
    # fill (first pack) + steady state (wire overlaps neighbouring
    # pack/unpack) + drain (last unpack); chunks=1 degenerates to the
    # unoverlapped pack + wire + unpack sum
    stage = max(pack_s + unpack_s, wire_s)
    return pack_s + stage * (chunks - 1) + wire_s + unpack_s


def sweep_chunks(
    s_loc: int,
    axis_size: int,
    policy=2,
    candidates=CHUNK_CANDIDATES,
    device_kind: str = DRYRUN_KIND,
) -> dict[int, float]:
    """Modeled gather time per candidate chunk count (only candidates
    that divide ``s_loc`` — the transport falls back to the unchunked
    pipeline otherwise, so a non-dividing pick would be a silent no-op)."""
    pol = policy_for(policy)
    out = {}
    for c in candidates:
        if c >= 1 and s_loc % c == 0:
            out[c] = modeled_gather_time(
                s_loc, axis_size, pol, c, device_kind
            )
    return out


def pick_chunks(
    s_loc: int,
    axis_size: int,
    policy=2,
    candidates=CHUNK_CANDIDATES,
    device_kind: str = DRYRUN_KIND,
) -> int:
    """The plan-selected chunk count: argmin of :func:`sweep_chunks`
    (1 when nothing divides, or when the gather is degenerate)."""
    if s_loc <= 0 or axis_size <= 1:
        return 1
    table = sweep_chunks(s_loc, axis_size, policy, candidates, device_kind)
    if not table:
        return 1
    return min(table, key=table.get)
