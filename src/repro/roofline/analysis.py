"""Three-term roofline model from compiled dry-run artifacts.

  compute term    = HLO_FLOPs / peak_FLOP/s            (per chip)
  memory term     = HLO_bytes_accessed / HBM_bw        (per chip)
  collective term = wire_bytes / link_bw               (per chip)

FLOPs/bytes come from ``compiled.cost_analysis()`` (per-device after SPMD
partitioning). Wire bytes are parsed from the compiled HLO text: every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
is charged its ring-algorithm wire traffic. Compressed-transport
collectives (uint8 byte planes — weight gathers, gradient reduce-scatters,
TP-axis activation pipelines) are charged at their true packed width and
reported separately as the plane-wire split (see
:mod:`repro.roofline.hlo_cost`).

Sequence-parallel steps (``Env.seq_parallel``) trade each block's
enter/exit psum pair for an ag + rs boundary pair
(``CompressionPolicy.seq_pair_wire_bytes`` — same ring volume at equal
width, docs/collectives.md): the activation all-reduce entries disappear
from these reports and reappear under all-gather / reduce-scatter /
all-to-all, packed-plane when an activation policy compresses.

The serving path has its own wire model:
:func:`serve_host_device_bytes` prices the continuous-batching engine's
host<->device token staging (the plan's ``host_device`` traffic class)
from the same ``CompressionPolicy`` formulas the engine's measured log
uses, so logged and analytic bytes are pinned equal.

Peak rates come from :data:`PEAKS`, keyed by ``device_kind`` as JAX
reports it; a kind missing from the table raises. Compile dry-runs model
:data:`DRYRUN_KIND`.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Any

from repro.transport import ring_wire_bytes


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peak rates."""

    flops: float   # bf16 FLOP/s
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s per link, one direction (what a ring hop pays)


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s of inter-chip interconnect per chip (4 links x 50 GB/s).
PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}
DRYRUN_KIND = "TPU v5 lite"


def peaks_for(device_kind: str) -> DevicePeaks:
    """Peak rates of ``device_kind`` (``jax.Device.device_kind``)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak rates for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        ) from None


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_COLL_KINDS = (
    "all-gather-start", "all-gather",
    "all-reduce-start", "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute-start", "collective-permute",
)


def shape_bytes(shape_str: str) -> int:
    m = _SHAPE_RE.match(shape_str)
    if not m:
        return 0
    dtype, dims = m.groups()
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    """Participant count per replica group from HLO text."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9, ]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    return 2  # conservative default


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    wire_bytes: dict
    total_wire_bytes: int

    def to_dict(self):
        return {
            "counts": self.counts,
            "wire_bytes": self.wire_bytes,
            "total_wire_bytes": self.total_wire_bytes,
        }


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Per-device wire bytes by collective kind (ring algorithm model)."""
    counts: dict[str, int] = {}
    wire: dict[str, int] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # result shape = first shape token; op kind after " = <shape> "
        m = re.match(r"%?[\w.\-]+ = ([\w\[\],{}\/ ]*?)(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)(-start)?\(", stripped)
        if not m:
            continue
        kind = m.group(2)
        out_match = _SHAPE_RE.search(stripped)
        out_bytes = shape_bytes(out_match.group(0)) if out_match else 0
        # operand shapes: inside the call parens
        paren = stripped[stripped.index("(") + 1 :]
        operand_bytes = sum(
            shape_bytes(sm.group(0)) for sm in _SHAPE_RE.finditer(paren)
        )
        n = _group_size(stripped)
        # ring model, shared with the transport policy accounting so the
        # analytical and measured byte counts cannot drift; all-gather and
        # all-to-all are charged on their output size per the formula's
        # contract (matches hlo_cost.py)
        payload = (
            out_bytes if kind in ("all-gather", "all-to-all") else operand_bytes
        )
        bytes_on_wire = int(ring_wire_bytes(kind, payload, n))
        counts[kind] = counts.get(kind, 0) + 1
        wire[kind] = wire.get(kind, 0) + bytes_on_wire
    return CollectiveStats(counts, wire, sum(wire.values()))


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    wire_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    useful_ratio: float
    collectives: dict

    def to_dict(self):
        return dataclasses.asdict(self)


def roofline_from_compiled(
    compiled, model_flops_per_device: float, act_bytes: int = 4,
    *, seq_parallel: bool = False, plan=None, plan_geometry: dict | None = None,
) -> Roofline:
    """While-trip-aware roofline (see repro.roofline.hlo_cost for why raw
    cost_analysis cannot be used with scanned layer stacks).

    ``act_bytes``: wire width of *uncompressed* activation all-reduces.
    The CPU emulation backend promotes every sub-f32 collective to f32
    and cancels the down-casts (excess-precision pass), so a bf16 compute
    dtype cannot be observed in the emulated HLO; on TPU these psums run
    natively in the compute dtype. All all-reduces in this framework's
    step functions are activation psums (weight grads go through
    reduce-scatter), so they are charged at ``act_bytes`` analytically
    when < 4.

    A compressing activation policy needs no parameter here: it replaces
    TP psums with packed-plane reduce-scatter + all-gather pipelines
    whose u8 wire bytes appear *exactly* in the HLO (the CPU backend
    cannot promote u8). The plane-wire split is always reported in
    ``collectives`` and can be checked against
    ``CompressionPolicy.all_reduce_wire_bytes``.

    ``seq_parallel``: the step was built with ``Env.seq_parallel`` — the
    block-boundary wire is then an ag + rs pair per TP region instead of
    the 2× all-reduce decomposition
    (``CompressionPolicy.seq_pair_wire_bytes``). Compressed boundaries
    are u8 planes and need no correction; *uncompressed* boundaries put
    raw-dtype all-gather / reduce-scatter legs on the wire, and the CPU
    backend promotes the reducing half to f32 exactly like psums, so the
    same analytical ``act_bytes`` correction is applied to the non-plane
    reduce-scatter residue. (Caveat: only pass ``seq_parallel=True`` for
    steps whose weight-gradient reduce-scatters are compressed — an
    uncompressed f32 grad reduce-scatter is indistinguishable from an
    activation one in HLO text and would be wrongly scaled.)

    ``plan`` + ``plan_geometry`` (``dist_elems_per_group``,
    ``gather_axis_size``, optional ``training``): break the wire down by
    :class:`~repro.plan.PrecisionPlan` traffic class — the per-entry
    numbers come from the plan's ``CompressionPolicy`` formulas and the
    measured packed-plane residue (see
    :func:`repro.roofline.hlo_cost.plan_wire_split`); the table lands in
    ``collectives["per_plan_entry"]``."""
    from repro.roofline.hlo_cost import analyze_hlo, plan_wire_split

    cost = compiled.cost_analysis()
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    c = analyze_hlo(compiled.as_text())
    if act_bytes < 4 and "all-reduce" in c.wire:
        # scales only the raw-dtype psums: a compressing act_policy turns
        # TP psums into u8 all_to_all + all-gather plane pipelines (never
        # a u8 all-reduce), which are already exact in the HLO — the
        # all-reduce entries remaining here are the uncompressed
        # residue (no divisible split axis, grad syncs, loss scalars)
        c.wire["all-reduce"] *= act_bytes / 4.0
    if seq_parallel and act_bytes < 4 and "reduce-scatter" in c.wire:
        # seq-parallel exits are psum_scatters: promoted to f32 on the
        # CPU backend like psums; plane (u8) scatters stay exact
        raw_rs = c.wire["reduce-scatter"] - c.plane_wire.get(
            "reduce-scatter", 0
        )
        c.wire["reduce-scatter"] -= raw_rs * (1.0 - act_bytes / 4.0)
    flops = max(c.flops, raw_flops)
    hbm = max(c.bytes, raw_bytes)
    peaks = peaks_for(DRYRUN_KIND)
    compute_s = flops / peaks.flops
    memory_s = hbm / peaks.hbm_bw
    coll_s = c.wire_total / peaks.ici_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dominant = max(terms, key=terms.get)
    useful = model_flops_per_device / flops if flops else 0.0
    per_plan_entry = None
    if plan is not None:
        per_plan_entry = plan_wire_split(c, plan, **(plan_geometry or {}))
    return Roofline(
        flops=flops,
        hbm_bytes=hbm,
        wire_bytes=float(c.wire_total),
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=coll_s,
        dominant=dominant,
        model_flops=model_flops_per_device,
        useful_ratio=useful,
        collectives={
            "counts": c.coll_counts,
            "wire_bytes": c.wire,
            # packed-plane (compressed transport) share of wire_bytes:
            # weight gathers, grad reduce-scatters, TP activation planes
            "plane_wire_bytes": c.plane_wire,
            "plane_wire_total": c.plane_wire_total,
            # wire bytes by PrecisionPlan traffic class (plan-driven runs)
            "per_plan_entry": per_plan_entry,
            "raw_cost_analysis": {"flops": raw_flops, "bytes": raw_bytes},
        },
    )


def serve_host_device_bytes(
    plan_or_policy,
    vocab_size: int,
    *,
    n_slots: int,
    prompt_lens,
    decode_steps: int,
    page_table_entries: int = 0,
) -> dict:
    """Analytic serve-wire model: host<->device staging bytes of one
    continuous-batching engine run (the serving twin of
    :meth:`~repro.plan.PrecisionPlan.wire_table`).

    Every term derives from
    :meth:`~repro.transport.CompressionPolicy.token_host_bytes` — the
    same formula the engine's measured ``step_log`` packing uses — so
    ``ServeEngine.wire_summary()["host_device"]`` must equal this
    table's ``total`` for the run's observed geometry
    (``tests/test_serve_engine.py`` pins it):

      * ``prompt_h2d``     — each admitted prompt (one ``prompt_lens``
        entry per admission) staged once, h2d;
      * ``first_token_d2h``— one sampled id per admission (the prefill
        logits' argmax) returning d2h;
      * ``decode_token_io``— per decode step the engine stages the full
        slot batch both ways (next-step feed h2d + sampled ids d2h),
        retired-slot ballast included — the honest cost of the
        fixed-shape batch;
      * ``page_table_h2d`` — paged engines re-stage the host page table
        (``page_table_entries`` = slots x table width, raw int32 — no
        token packing) every decode step; zero entries for the
        contiguous layout keeps the model backward compatible.
    """
    pol = plan_or_policy
    if hasattr(pol, "host_device_policies"):  # a PrecisionPlan
        pol = pol.host_device_policies()[0]
    prompt_lens = list(prompt_lens)
    admissions = len(prompt_lens)
    tok = pol.token_host_bytes
    table = {
        "prompt_h2d": tok(sum(prompt_lens), vocab_size),
        "first_token_d2h": tok(admissions, vocab_size),
        "decode_token_io": 2 * tok(n_slots, vocab_size) * int(decode_steps),
        "page_table_h2d": 4 * int(page_table_entries) * int(decode_steps),
        "token_width": pol.token_wire_width(vocab_size),
    }
    table["total"] = (
        table["prompt_h2d"] + table["first_token_d2h"]
        + table["decode_token_io"] + table["page_table_h2d"]
    )
    return table


def serve_spec_decode_bytes(
    plan_or_policy,
    vocab_size: int,
    *,
    n_slots: int,
    prompt_lens,
    spec_rounds: int,
    spec_k: int,
    page_table_entries: int = 0,
) -> dict:
    """Analytic serve-wire model for the **speculative** engine — the
    fourth measured==analytic pin (after the training collectives, the
    plain serve model, and the fleet migration fabric). Same
    ``token_host_bytes`` arithmetic as :func:`serve_host_device_bytes`,
    reshaped by the draft/verify protocol (``T = spec_k + 1``):

      * ``prompt_h2d``     — each admitted prompt staged once, h2d; the
        draft model prefills from the SAME staged device tokens on the
        local-admission path, so the prompt crosses the boundary once
        (migration admissions re-stage it for the draft — callers add
        one extra ``prompt_h2d``-shaped term per migrated prompt);
      * ``first_token_d2h``— one sampled id per admission, d2h;
      * ``draft_h2d``      — per round the draft runs ``T`` micro decode
        steps, each feeding the full slot batch one token h2d
        (``k`` sampled proposals + the absorb-only final step);
      * ``draft_d2h``      — per round ``k`` proposal batches return d2h
        (the absorb step samples nothing);
      * ``verify_token_io``— per round the target stages the ``(B, T)``
        verify block h2d and the ``T`` verified ids per slot d2h;
      * ``page_table_h2d`` — paged engines re-stage the (spec-widened)
        host table every verify step, raw int32.
    """
    pol = plan_or_policy
    if hasattr(pol, "host_device_policies"):  # a PrecisionPlan
        pol = pol.host_device_policies()[0]
    prompt_lens = list(prompt_lens)
    admissions = len(prompt_lens)
    tok = pol.token_host_bytes
    rounds, k = int(spec_rounds), int(spec_k)
    T = k + 1
    table = {
        "prompt_h2d": tok(sum(prompt_lens), vocab_size),
        "first_token_d2h": tok(admissions, vocab_size),
        "draft_h2d": rounds * tok(n_slots * T, vocab_size),
        "draft_d2h": rounds * tok(n_slots * k, vocab_size),
        "verify_token_io": 2 * rounds * tok(n_slots * T, vocab_size),
        "page_table_h2d": 4 * int(page_table_entries) * rounds,
        "token_width": pol.token_wire_width(vocab_size),
    }
    table["total"] = (
        table["prompt_h2d"] + table["first_token_d2h"]
        + table["draft_h2d"] + table["draft_d2h"]
        + table["verify_token_io"] + table["page_table_h2d"]
    )
    return table


def train_ingest_bytes(
    plan_or_policy,
    vocab_size: int,
    *,
    kind: str,
    batch: int,
    seq: int,
    steps: int,
    dim: int = 0,
    reader=None,
) -> dict:
    """Analytic training-ingest model: the byte cost of feeding ``steps``
    batches from the tiered shard pipeline (the training twin of
    :func:`serve_host_device_bytes`). Two terms, matching the measured
    per-step ``StepRecord.io_by_entry``:

      * ``shard_read`` — stored bytes the reader moves off disk. Pure
        manifest arithmetic (:meth:`~repro.data.shards.ShardReader.planned_bytes`
        from the reader's *current* position — order matters because
        per-record compressed plane sizes differ), so it prices the
        actual tier the reader's ``quality`` knob selects. 0 when no
        ``reader`` is passed (inline synthetic data reads no shards).
      * ``ingest_h2d`` — bytes staged across the host→device boundary at
        the plan's ``host_device``
        :class:`~repro.transport.CompressionPolicy`: integer ids packed
        to ``token_wire_width`` planes
        (:func:`~repro.data.prefetch.staged_ids_per_batch` ids per batch
        — LM stages the ``seq+1`` stream once, not tokens+labels
        separately) plus raw fp32 feature payloads
        (``batch·seq·dim·4``; lossy staging of training inputs would
        change the optimization problem).

    ``tests/scenarios/scenario_train_io.py`` pins both terms equal to
    the prefetcher's measured log."""
    from repro.data.prefetch import staged_ids_per_batch

    pol = plan_or_policy
    if pol is None:
        from repro.transport import CompressionPolicy

        pol = CompressionPolicy()
    elif hasattr(pol, "host_device_policies"):  # a PrecisionPlan
        pol = pol.host_device_policies()[0]
    steps = int(steps)
    ids = staged_ids_per_batch(kind, batch, seq) * steps
    float_bytes = 0
    if kind == "feature":
        float_bytes = 4 * batch * seq * int(dim) * steps
    table = {
        "shard_read": (
            reader.planned_bytes(batch * steps) if reader is not None else 0
        ),
        "ingest_h2d": pol.token_host_bytes(ids, vocab_size) + float_bytes,
        "token_width": pol.token_wire_width(vocab_size),
    }
    table["total"] = table["shard_read"] + table["ingest_h2d"]
    return table


def train_checkpoint_bytes(
    storage_like,
    opt_like=None,
    *,
    spec_tree=None,
    round_tos=None,
    residuals: bool = True,
) -> dict:
    """Analytic byte model of one width-aware sharded checkpoint — must
    equal :func:`repro.checkpoint.sharded.manifest_bytes` of the written
    directory (and the summed ``os.path.getsize`` of its ``.bin`` files;
    the train-I/O tests pin all three equal).

    Walks the same :func:`~repro.checkpoint.sharded.assign_widths` the
    writer uses: a compressible fp32 leaf in a group at ``round_to=rt``
    costs ``elems·rt`` wire bytes (+ ``elems·(4-rt)`` residual bytes
    when ``residuals``); every other storage leaf and the whole
    optimizer tree cost full width. No compression estimate is needed —
    checkpoint shards store raw planes, so the model is exact."""
    import numpy as np

    from repro.checkpoint.sharded import assign_widths, leaf_entries

    widths: dict[str, int] = {}
    if round_tos is not None and spec_tree is not None:
        widths = assign_widths(storage_like, spec_tree, round_tos)
    wire = residual = 0
    for tree, use_widths in ((storage_like, True), (opt_like, False)):
        if tree is None:
            continue
        for kpath, leaf in leaf_entries(tree):
            n = int(math.prod(leaf.shape)) if len(leaf.shape) else 1
            full = np.dtype(leaf.dtype).itemsize
            w = widths.get(kpath, full) if use_widths else full
            wire += n * w
            if residuals and w < full:
                residual += n * (full - w)
    return {"wire": wire, "residual": residual, "total": wire + residual}


def serve_paged_kv_bytes(
    cfg,
    *,
    page_size: int,
    requests,
    shared_prefix_len: int = 0,
    int8_kv: bool = False,
    dtype_bytes: int = 4,
) -> dict:
    """Analytic page-granular KV residency for the paged serve engine:
    the peak-resident byte model ``ServeEngine.kv_residency()`` must
    reproduce when every request is resident at once (the shared-prefix
    test pins measured == analytic).

    ``requests`` is an iterable of ``(prompt_len, max_new_tokens)``;
    ``shared_prefix_len`` tokens are common to ALL requests, so their
    whole pages (``shared_prefix_len // page_size``) are stored once and
    refcounted instead of per-request. Per page, every attention layer
    holds K + V — ``2 * page_size * num_kv_heads * head_dim`` elements
    at ``dtype_bytes`` (1 for int8 KV, which then adds two fp32 scale
    planes of ``page_size * num_kv_heads`` each).
    """
    reqs = list(requests)
    layers = cfg.num_groups * cfg.layers_per_group
    attn_frac = sum(1 for k in cfg.pattern if k == "attn") / len(cfg.pattern)
    attn_layers = int(layers * attn_frac)
    kv_elems = page_size * cfg.num_kv_heads * cfg.head_dim
    per_layer = 2 * kv_elems * (1 if int8_kv else dtype_bytes)
    if int8_kv:
        per_layer += 2 * page_size * cfg.num_kv_heads * 4  # fp32 scales
    bytes_per_page = per_layer * attn_layers
    shared_pages = shared_prefix_len // page_size
    private_pages = sum(
        -(-(s + g) // page_size) - shared_pages for s, g in reqs
    )
    pages = shared_pages + private_pages
    return {
        "bytes_per_page": bytes_per_page,
        "shared_pages": shared_pages,
        "private_pages": private_pages,
        "pages": pages,
        "kv_bytes_resident": pages * bytes_per_page,
    }


def fleet_migration_bytes(
    plan_or_policy,
    cfg,
    *,
    page_size: int,
    migrated_pages: int,
    int8_kv: bool = False,
    dtype_bytes: int = 4,
    publish_wire_bytes: int = 0,
    publish_installs: int = 0,
) -> dict:
    """Analytic fleet-fabric model: inter-replica parcel bytes of a
    disaggregated serving run — the third measured==analytic pin after
    the serve staging log and the checkpoint manifest. Must equal the
    :class:`~repro.transport.FabricChannel` hop log EXACTLY
    (``tests/scenarios/scenario_fleet.py`` pins both classes).

      * ``kv_migration`` — every migrated page ships each attention
        layer's K + V plane-packed at the ``kv_migration`` policy's
        :meth:`~repro.transport.CompressionPolicy.kv_wire_width` —
        the same :func:`serve_paged_kv_bytes` geometry, priced at wire
        width instead of resident width (int8 pools ship 1
        byte/element under a compressing policy, their fp32 scale
        planes always 4; an uncompressed policy pads everything to
        raw fp32 words). ``migrated_pages`` is the run's total new
        (non-shared-prefix) prompt pages — the router counts them.
      * ``weight_publish`` — each rolling-refresh install moves one
        checkpoint-tier parcel (``publish_wire_bytes``, already exact
        via :func:`train_checkpoint_bytes` /
        ``WeightParcel.manifest_meta``) across the fabric;
        ``publish_installs`` counts replica installs (join + refresh).
    """
    pol = plan_or_policy
    if hasattr(pol, "kv_migration_policy"):  # a PrecisionPlan
        pol = pol.kv_migration_policy()
    layers = cfg.num_groups * cfg.layers_per_group
    attn_frac = sum(1 for k in cfg.pattern if k == "attn") / len(cfg.pattern)
    attn_layers = int(layers * attn_frac)
    kv_elems = page_size * cfg.num_kv_heads * cfg.head_dim
    kv_width = pol.kv_wire_width(1 if int8_kv else dtype_bytes)
    per_layer = 2 * kv_elems * kv_width
    if int8_kv:
        # fp32 scale planes ride at full width under every policy
        per_layer += 2 * page_size * cfg.num_kv_heads * pol.kv_wire_width(4)
    page_wire_bytes = per_layer * attn_layers
    table = {
        "page_wire_bytes": page_wire_bytes,
        "kv_width": kv_width,
        "migrated_pages": int(migrated_pages),
        "kv_migration": page_wire_bytes * int(migrated_pages),
        "weight_publish": int(publish_wire_bytes) * int(publish_installs),
    }
    table["total"] = table["kv_migration"] + table["weight_publish"]
    return table


def model_flops_estimate(cfg, shape, chips: int) -> float:
    """6·N_active·D per device (decode: D = new tokens = batch)."""
    n_active = cfg.active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens / chips
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens / chips
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch / chips
