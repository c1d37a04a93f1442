"""Disaggregated fleet serving launcher (`repro.fleet`): a request
router over N decode replicas with dedicated prefill workers, KV pages
migrating replica-to-replica as compressed fabric parcels, and an
optional mid-run live weight refresh.

One :class:`~repro.plan.PrecisionPlan` drives everything the serve
launcher's plan drives PLUS the two fleet traffic classes
(``kv_migration`` / ``weight_publish``): pass ``--plan plan.json``, or
use the same plan-builder sugar flags. Streams are bit-exact vs the
static one-shot reference under every fleet topology —
``--check-static`` asserts it per weight version, including across the
``--refresh-at`` boundary (pre-refresh requests check against the v0
static streams, post-refresh traffic against v1).

  PYTHONPATH=src python -m repro.launch.fleet --arch qwen3-1.7b --reduced \
      --replicas 2 --workers 1 --prompt-lens 16,12,16,8 --gen 8 \
      --page-size 8 [--int8-kv] [--refresh-at 2] [--check-static]

After the drain the launcher prints the fabric hop totals and asserts
them EQUAL to the analytic
:func:`repro.roofline.analysis.fleet_migration_bytes` model — the
fleet's measured==analytic pin, enforced on every run.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCHS, get_config, reduced
from repro.dist.spec import build_spec_tree, tree_to_storage
from repro.fleet import DecodeReplica, FleetRouter, PrefillWorker, WeightPublisher
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh_from_cfg
from repro.launch.train import _null, parse_mesh
from repro.models.init import init_params
from repro.launch.serve import sampling_from_args
from repro.plan import PrecisionPlan
from repro.roofline.analysis import fleet_migration_bytes
from repro.serve.engine import Request, ServeEngine, generate_static


def _plan_from_args(args, nrt: int) -> PrecisionPlan:
    if args.plan:
        plan = PrecisionPlan.from_file(args.plan).broadcast(nrt)
    else:
        plan = PrecisionPlan.build(
            nrt,
            round_to=args.round_to if args.round_to is not None else 2,
            act_round_to=(
                args.act_round_to if args.act_round_to is not None else 4
            ),
        )
    if args.int8_kv:
        plan = dataclasses.replace(plan, int8_kv=True)
    return plan


def _build_requests(args, cfg, *, rid_base: int, seed: int) -> list[Request]:
    if args.prompt_lens:
        lens = [int(s) for s in args.prompt_lens.split(",")]
    else:
        lens = [args.prompt_len] * args.requests
    rng = np.random.default_rng(seed)
    shared = tuple(
        int(t) for t in rng.integers(0, cfg.vocab_size, args.shared_prefix)
    )
    return [
        Request(
            rid=rid_base + i,
            prompt_ids=shared + tuple(
                int(t) for t in rng.integers(0, cfg.vocab_size, S)
            ),
            max_new=args.gen,
            sampling=sampling_from_args(args, rid_base + i),
        )
        for i, S in enumerate(lens)
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--replicas", type=int, default=2,
                    help="decode replicas (each one paged ServeEngine)")
    ap.add_argument("--workers", type=int, default=1,
                    help="dedicated prefill workers (round-robin)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--prompt-lens", default="",
                    help="comma-separated per-request prompt lengths; "
                         "overrides --requests/--prompt-len")
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--max-slots", type=int, default=2,
                    help="KV slots per replica")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many common tokens to every prompt "
                         "(prefix pages then migrate once per replica)")
    ap.add_argument("--plan", default="",
                    help="PrecisionPlan JSON incl. the kv_migration / "
                         "weight_publish fabric entries")
    ap.add_argument("--round-to", type=int, default=None,
                    help="ADT weight wire format (plan-builder sugar)")
    ap.add_argument("--act-round-to", type=int, default=None,
                    help="activation wire format (plan-builder sugar)")
    ap.add_argument("--int8-kv", action="store_true")
    # per-request sampling (same contract as repro.launch.serve: request
    # i samples under seed + i; 0 temperature = the greedy fast path)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus cutoff (with --temperature > 0)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k cutoff, 0 = all (with --temperature > 0)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed; request i uses seed + i")
    ap.add_argument("--refresh-at", type=int, default=0,
                    help="after this many completed requests, publish "
                         "refreshed weights (PRNGKey(1) init) and submit "
                         "a second request wave under the new version")
    ap.add_argument("--check-static", action="store_true",
                    help="assert router streams bit-exact vs the static "
                         "reference, per weight version (CI smoke)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh_cfg = parse_mesh(args.mesh)
    mesh = make_mesh_from_cfg(mesh_cfg)

    params, metas = init_params(cfg, jax.random.PRNGKey(0), tp=mesh_cfg.tp)
    spec_tree = build_spec_tree(params, metas, mesh_cfg)
    storage0 = tree_to_storage(params, spec_tree, mesh_cfg)
    nrt = cfg.num_groups + 1
    plan = _plan_from_args(args, nrt)

    wave_a = _build_requests(args, cfg, rid_base=0, seed=0)
    wave_b = []
    storage1 = None
    if args.refresh_at:
        params1, _ = init_params(cfg, jax.random.PRNGKey(1), tp=mesh_cfg.tp)
        storage1 = tree_to_storage(params1, spec_tree, mesh_cfg)
        wave_b = _build_requests(
            args, cfg, rid_base=len(wave_a), seed=1
        )
    lens = [len(r.prompt_ids) for r in wave_a]
    cap = max(lens) + args.gen

    ctx = mesh if mesh is not None else _null()
    with ctx:
        replicas = [
            DecodeReplica(f"r{i}", ServeEngine(
                cfg, mesh_cfg, mesh, spec_tree, storage0, plan=plan,
                max_slots=args.max_slots, cache_capacity=cap, paged=True,
                page_size=args.page_size,
            ))
            for i in range(args.replicas)
        ]
        workers = [
            PrefillWorker(f"w{i}", cfg, mesh_cfg, mesh, spec_tree,
                          plan=plan, cache_capacity=cap,
                          page_size=args.page_size)
            for i in range(args.workers)
        ]
        router = FleetRouter(replicas, workers)
        publisher = WeightPublisher(cfg, spec_tree, plan=plan)
        parcel0 = publisher.publish(storage0)
        router.publish(parcel0)

        refreshed = {"done": not args.refresh_at}

        def do_refresh(r):
            refreshed["done"] = True
            r.publish(publisher.publish(storage1, step=1))
            for req in wave_b:
                r.submit(req)
            print(f"tick {r.ticks}: published v1 and submitted "
                  f"{len(wave_b)} refresh-wave requests")

        def on_tick(r):
            if not refreshed["done"] and len(r.results) >= args.refresh_at:
                do_refresh(r)

        t0 = time.time()
        results = router.run(wave_a, on_tick=on_tick)
        if not refreshed["done"]:
            # wave A drained before the threshold tripped mid-tick
            # (small fleets finish whole waves in one tick) — refresh
            # now and drain the second wave
            do_refresh(router)
            results = router.run([])
        wall = time.time() - t0

        static0 = static1 = None
        if args.check_static:
            static0 = generate_static(
                cfg, mesh_cfg, mesh, spec_tree, storage0, wave_a, plan=plan
            )
            if wave_b:
                static1 = generate_static(
                    cfg, mesh_cfg, mesh, spec_tree, storage1, wave_b,
                    plan=plan,
                )

    n_req = len(wave_a) + len(wave_b)
    total_new = sum(len(r.tokens) for r in results.values())
    ws = router.wire_summary()
    print(f"{cfg.name}: {n_req} requests over {args.replicas} replicas / "
          f"{args.workers} workers, prompts {min(lens)}..{max(lens)}, "
          f"+{args.gen} tokens, page_size={args.page_size}"
          + (", int8 KV" if plan.int8_kv else ""))
    print(f"fleet: {ws['ticks']} ticks in {wall:.2f}s "
          f"({total_new/max(wall, 1e-9):.1f} tok/s incl. compile)")
    print(f"fabric: kv_migration {ws['kv_migration']} B over "
          f"{ws['hops']['kv_migration']} hops ({ws['migrated_pages']} "
          f"pages), weight_publish {ws['weight_publish']} B over "
          f"{ws['publish_installs']} installs")
    by_replica = {}
    for meta in router.placements.values():
        by_replica[meta["replica"]] = by_replica.get(meta["replica"], 0) + 1
    print(f"placement: {dict(sorted(by_replica.items()))}")

    dtype_bytes = jnp.dtype(plan.compute_dtype).itemsize
    analytic = fleet_migration_bytes(
        plan, cfg, page_size=args.page_size,
        migrated_pages=ws["migrated_pages"], int8_kv=plan.int8_kv,
        dtype_bytes=dtype_bytes, publish_wire_bytes=parcel0.nbytes,
        publish_installs=ws["publish_installs"],
    )
    for cls in ("kv_migration", "weight_publish"):
        if ws[cls] != analytic[cls]:
            raise SystemExit(
                f"fleet fabric DIVERGED from the analytic model on "
                f"{cls}: measured {ws[cls]} != analytic {analytic[cls]}"
            )
    print(f"fabric == fleet_migration_bytes: kv {analytic['kv_migration']} "
          f"B at {analytic['kv_width']} B/elem, publish "
          f"{analytic['weight_publish']} B — measured equals analytic")

    if args.check_static:
        bad = [r.rid for r in wave_a
               if results[r.rid].tokens != static0[r.rid]]
        bad += [r.rid for r in wave_b
                if results[r.rid].tokens != static1[r.rid]]
        if bad:
            raise SystemExit(
                f"fleet vs static token streams DIVERGED for requests "
                f"{bad}"
            )
        print(f"check-static: {n_req} streams bit-exact vs the static "
              "reference"
              + (" (v0 and v1 waves)" if wave_b else ""))
    for r in (wave_a + wave_b)[:4]:
        print(f"  req{r.rid}: {results[r.rid].tokens[:16]}")


if __name__ == "__main__":
    main()
