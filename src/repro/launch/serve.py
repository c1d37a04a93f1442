"""Production serving launcher: continuous batching over the slotted
KV cache (`repro.serve.engine`), with the pre-engine static one-shot
path kept as the bit-exact reference (``--static`` / ``--check-static``).

One :class:`~repro.plan.PrecisionPlan` drives the weight wire format,
activation compression, sequence-parallel prefill, chunked gathers, the
int8 KV cache AND the host<->device token staging (the plan's
``host_device`` entry): pass ``--plan plan.json``. ``--round-to`` /
``--act-round-to`` are plain plan-builder sugar (routed through
:meth:`PrecisionPlan.build`, ignored when a plan is loaded); the layout
flags (``--int8-kv``, ``--seq-parallel``, ``--chunks``,
``--weight-stationary``) stay first-class and override the loaded plan.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
      --prompt-lens 64,48,64,32 --gen 32 --max-slots 2 [--int8-kv] \
      [--plan plan.json] [--check-static] [--ckpt ckpt.npz]

``--paged`` switches the engine to the block-paged KV layout (page pool
+ per-slot page table, ``--page-size`` tokens per page); ``--shared-prefix
N`` prepends N common tokens to every prompt so the refcounted prefix-
page sharing is visible in the printed page stats. Streams stay
bit-exact vs ``--contiguous`` and the static reference either way.

``--temperature/--top-p/--top-k/--seed`` switch every request to seeded
per-request sampling (request i gets ``seed + i``) under the key-fold
contract of :mod:`repro.serve.sampling` — ``--check-static`` still
holds bit-exactly. ``--spec-decode --draft tiny --spec-k 4`` adds
speculative decoding (:mod:`repro.serve.spec`): token streams are
IDENTICAL to the non-speculative run at the same seeds; only the
acceptance rate and wire/step shape change.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
import warnings

import jax
import numpy as np

from repro.checkpoint.ckpt import load_plan, load_storage
from repro.configs.registry import ARCHS, get_config, reduced
from repro.dist.spec import build_spec_tree, tree_to_storage
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh_from_cfg
from repro.launch.train import _null, parse_mesh
from repro.models.init import init_params
from repro.plan import PrecisionPlan, SamplingParams
from repro.roofline.analysis import serve_spec_decode_bytes
from repro.serve.engine import Request, ServeEngine, generate_static
from repro.serve.spec import build_draft

def plan_from_args(args, nrt: int) -> PrecisionPlan:
    """Serve-launcher plan resolution: ``--plan`` (or the checkpointed
    plan) wins; the precision flags are plan-builder sugar routed
    through the same :meth:`PrecisionPlan.build` the train launcher
    uses; layout flags override either source."""
    plan = None
    if args.plan:
        plan = PrecisionPlan.from_file(args.plan).broadcast(nrt)
    elif args.ckpt:
        plan = load_plan(args.ckpt)
        if plan is not None:
            plan = plan.broadcast(nrt)
        else:
            warnings.warn(
                f"checkpoint {args.ckpt} carries no PrecisionPlan "
                "(pre-plan training run?): serving falls back to the "
                "flag-built plan — pass --plan to pin the formats the "
                "run actually used",
                stacklevel=2,
            )
    if plan is None:
        plan = PrecisionPlan.build(
            nrt,
            round_to=args.round_to if args.round_to is not None else 2,
            act_round_to=(
                args.act_round_to if args.act_round_to is not None else 4
            ),
        )
    # layout flags stay first-class and override the loaded plan
    overrides = {}
    if args.seq_parallel:
        overrides["seq_parallel"] = True
    if args.int8_kv:
        overrides["int8_kv"] = True
    if args.chunks is not None:
        overrides["chunks"] = args.chunks
    if overrides:
        plan = dataclasses.replace(plan, **overrides)
    return plan


def sampling_from_args(args, rid: int) -> SamplingParams:
    """Per-request SamplingParams from the launcher flags: one shared
    temperature/top-p/top-k knob, a DISTINCT seed per request
    (``--seed + rid``) so streams are independent yet reproducible."""
    if args.temperature <= 0:
        return SamplingParams()
    return SamplingParams(
        temperature=args.temperature, top_p=args.top_p,
        top_k=args.top_k, seed=args.seed + rid,
    )


def build_requests(args, cfg) -> list[Request]:
    if args.prompt_lens:
        lens = [int(s) for s in args.prompt_lens.split(",")]
    else:
        lens = [args.prompt_len] * args.requests
    rng = np.random.default_rng(0)
    shared = tuple(
        int(t) for t in rng.integers(0, cfg.vocab_size, args.shared_prefix)
    )
    return [
        Request(
            rid=i,
            prompt_ids=shared + tuple(
                int(t) for t in rng.integers(0, cfg.vocab_size, S)
            ),
            max_new=args.gen,
            sampling=sampling_from_args(args, i),
        )
        for i, S in enumerate(lens)
    ]


def run_static(cfg, mesh_cfg, mesh, spec_tree, storage, requests, plan,
               window, image_features=None):
    t0 = time.time()
    if cfg.num_experts:
        # MoE capacity dispatch ranks a whole batch's tokens per expert,
        # so a *grouped* static prefill is not a valid comparison target
        # for the engine's batch-of-1 prefills (see repro.serve.engine):
        # reference MoE archs per request. Each call builds fresh step
        # closures (one compile per request, not per distinct length) —
        # acceptable for a reference path.
        streams = {}
        for r in requests:
            streams.update(generate_static(
                cfg, mesh_cfg, mesh, spec_tree, storage, [r], plan=plan,
                window=window, image_features=image_features,
            ))
        kind = "per-request static"
    else:
        streams = generate_static(
            cfg, mesh_cfg, mesh, spec_tree, storage, requests, plan=plan,
            window=window, image_features=image_features,
        )
        kind = "static one-shot"
    print(f"{kind} reference: {len(requests)} requests in "
          f"{time.time()-t0:.2f}s (incl. compile)")
    return streams


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--prompt-lens", default="",
                    help="comma-separated per-request prompt lengths "
                         "(mixed-length continuous batching); overrides "
                         "--requests/--prompt-len")
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-slots", type=int, default=0,
                    help="KV slots resident in the engine (default: "
                         "min(4, requests))")
    ap.add_argument("--plan", default="",
                    help="PrecisionPlan JSON — the declarative source of "
                         "truth incl. the host_device staging entry")
    ap.add_argument("--ckpt", default="",
                    help="restore served weights (+ plan, unless --plan "
                         "overrides) from a training checkpoint")
    # precision sugar: builds the same plan --plan would declare
    ap.add_argument("--round-to", type=int, default=None,
                    help="ADT weight wire format (plan-builder sugar; "
                         "ignored when a plan is loaded)")
    ap.add_argument("--act-round-to", type=int, default=None,
                    help="activation wire format on the TP axis "
                         "(plan-builder sugar)")
    # layout flags: first-class, override a loaded plan
    ap.add_argument("--seq-parallel", action="store_true",
                    help="sequence-parallel prefill activations (decode is "
                         "single-token and keeps the psum layout)")
    ap.add_argument("--chunks", type=int, default=None,
                    help="weight-gather chunk count (double buffering)")
    ap.add_argument("--weight-stationary", action="store_true")
    ap.add_argument("--int8-kv", action="store_true")
    ap.add_argument("--window", type=int, default=0,
                    help="sliding-window decode override (long-context)")
    # per-request sampling (0 temperature = the greedy fast path)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy, the default; "
                         ">0 switches every request to seeded sampling)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus cutoff (with --temperature > 0)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k cutoff, 0 = all (with --temperature > 0)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed; request i uses seed + i")
    # speculative decoding
    ap.add_argument("--spec-decode", action="store_true",
                    help="speculative decoding: a draft model proposes "
                         "--spec-k tokens/slot, the target verifies them "
                         "in one batched step (streams stay identical)")
    ap.add_argument("--draft", default="tiny",
                    help="draft model: 'tiny' (auto-shrunk target, same "
                         "vocab) or a registry arch name (--spec-decode)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft proposals per round (--spec-decode)")
    layout = ap.add_mutually_exclusive_group()
    layout.add_argument("--paged", action="store_true",
                        help="block-paged KV layout: page pool + per-slot "
                             "page table, shared-prefix pages refcounted")
    layout.add_argument("--contiguous", action="store_true",
                        help="slotted contiguous KV layout (default)")
    ap.add_argument("--page-size", type=int, default=64,
                    help="tokens per KV page (--paged)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool size (default: slots x table width)")
    ap.add_argument("--no-share-prefix", action="store_true",
                    help="disable shared-prefix page interning (--paged)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many common tokens to every "
                         "prompt (demonstrates prefix-page sharing)")
    ap.add_argument("--static", action="store_true",
                    help="run ONLY the static one-shot reference path")
    ap.add_argument("--check-static", action="store_true",
                    help="run both paths and assert bit-exact token "
                         "streams (CI smoke)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if not cfg.causal:
        raise SystemExit(f"{args.arch} is encoder-only: no decode path")
    mesh_cfg = parse_mesh(args.mesh)
    mesh = make_mesh_from_cfg(mesh_cfg)

    params, metas = init_params(cfg, jax.random.PRNGKey(0), tp=mesh_cfg.tp)
    spec_tree = build_spec_tree(params, metas, mesh_cfg)
    storage = tree_to_storage(params, spec_tree, mesh_cfg)
    nrt = cfg.num_groups + 1
    plan = plan_from_args(args, nrt)
    if args.ckpt:
        storage, ckpt_step = load_storage(args.ckpt, storage)
        print(f"restored weights from {args.ckpt} (train step {ckpt_step}, "
              f"plan rts {plan.round_tos})")

    requests = build_requests(args, cfg)
    lens = [len(r.prompt_ids) for r in requests]
    window = args.window or None
    # windowed decode rings only when capacity <= window (the engine
    # validates this): cap at the window so long prompts wrap instead of
    # silently dropping writes past a too-small linear cache
    cap = max(lens) + args.gen if window is None else min(
        max(lens) + args.gen, window
    )
    slots = args.max_slots or min(4, len(requests))

    image_features = None
    if cfg.num_image_tokens:
        # vision cross-attn archs serve via the static path only: image
        # payloads are not token-stageable through the engine's boundary
        if not args.static:
            raise SystemExit(
                f"{args.arch} has image inputs: serve it with --static "
                "(the continuous-batching engine stages token payloads "
                "only)"
            )
        frng = np.random.default_rng(0)
        image_features = {
            r.rid: frng.normal(
                0, 1, (cfg.num_image_tokens, cfg.vision_dim)
            ).astype(np.float32)
            for r in requests
        }

    ctx = mesh if mesh is not None else _null()
    with ctx:
        static_streams = None
        if args.static or args.check_static:
            static_streams = run_static(
                cfg, mesh_cfg, mesh, spec_tree, storage, requests, plan,
                window, image_features,
            )
            if args.static:
                for r in requests[:4]:
                    print(f"  req{r.rid}: "
                          f"{static_streams[r.rid][:16]}")
                return

        draft = None
        if args.spec_decode:
            draft = build_draft(cfg, mesh_cfg, args.draft)
            print(f"speculative decoding: draft {draft.cfg.name}, "
                  f"k={args.spec_k}")
        engine = ServeEngine(
            cfg, mesh_cfg, mesh, spec_tree, storage, plan=plan,
            max_slots=slots, cache_capacity=cap, window=window,
            weight_stationary=args.weight_stationary,
            paged=args.paged, page_size=args.page_size,
            num_pages=args.num_pages or None,
            share_prefix=not args.no_share_prefix,
            draft=draft, spec_k=args.spec_k if draft is not None else None,
        )
        t0 = time.time()
        results = engine.run(requests)
        wall = time.time() - t0

    total_new = sum(len(r.tokens) for r in results.values())
    summary = engine.wire_summary()
    print(f"{cfg.name}: {len(requests)} requests, prompts {min(lens)}"
          f"..{max(lens)}, +{args.gen} tokens, {slots} slots")
    print(f"engine: {summary['steps']} steps "
          f"({summary['decode_steps']} decode) in {wall:.2f}s "
          f"({total_new/max(wall, 1e-9):.1f} tok/s incl. compile)")
    print(f"host_device wire: {summary['host_device']} B staged at "
          f"{summary['token_width']} B/token "
          f"({4/summary['token_width']:.1f}x vs raw int32)")
    if args.spec_decode:
        print(f"spec decode: {summary['spec_rounds']} rounds, "
              f"acceptance {summary['acceptance_rate']:.2f}, "
              f"{summary['tokens_per_target_step']:.2f} emitted "
              f"tokens/target step (k={summary['spec_k']})")
        analytic = serve_spec_decode_bytes(
            plan, cfg.vocab_size, n_slots=slots,
            prompt_lens=[len(r.prompt_ids) for r in requests],
            spec_rounds=summary["spec_rounds"], spec_k=args.spec_k,
            page_table_entries=(
                summary["page_table_entries"] if args.paged else 0
            ),
        )
        if summary["host_device"] != analytic["total"]:
            raise SystemExit(
                f"spec-decode wire DIVERGED from the analytic model: "
                f"measured {summary['host_device']} != analytic "
                f"{analytic['total']} ({analytic})"
            )
        print(f"wire == serve_spec_decode_bytes: {analytic['total']} B "
              f"at {analytic['token_width']} B/id — measured equals "
              "analytic")
    if args.paged:
        res = engine.kv_residency()
        audit = engine.pages.audit()
        print(f"paged KV: page_size={res['page_size']}, "
              f"{audit['allocs']} page allocs / {audit['releases']} "
              f"releases, peak {res['pages_peak']} pages resident "
              f"({res['kv_bytes_peak']} B at {res['bytes_per_page']} "
              "B/page)")
        print(f"paged prefill: {summary['prefill_misses']} compiles, "
              f"{summary['prefill_hits']} bucket cache hits; page-table "
              f"staging {summary['page_table']} B")
    for r in requests[:4]:
        print(f"  req{r.rid}: {results[r.rid].tokens[:16]}")

    if args.check_static:
        bad = [
            r.rid for r in requests
            if results[r.rid].tokens != static_streams[r.rid]
        ]
        if bad:
            raise SystemExit(
                f"continuous vs static token streams DIVERGED for "
                f"requests {bad}"
            )
        print(f"check-static: {len(requests)} streams bit-exact vs the "
              "static one-shot reference")


if __name__ == "__main__":
    main()
