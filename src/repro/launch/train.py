"""Production training launcher.

Selects an assigned architecture (``--arch``), builds the FSDP×TP mesh,
and runs the A²DTWP loop (AWP controller + ADT-compressed gathers) on the
synthetic pipeline. On this CPU container use ``--reduced`` plus a small
``--mesh``; on a real pod run the full config on 16x16 or 2x16x16.

Every precision knob rides one :class:`~repro.plan.PrecisionPlan`:
``--plan plan.json`` loads a declarative plan (the single source of
truth — checkpointed next to the AWP state), and the individual flags
(``--grad-round-to``, ``--act-round-to``, ``--seq-parallel``, ``--bf16``,
``--chunks``, ``--grad-mode``, AWP options) are sugar that builds the
same plan. ``--chunks auto`` picks the double-buffered gather chunk
count from the roofline sweep (``repro.plan.pick_chunks``).

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --reduced \
      --mesh 2x4 --steps 100 --policy awp
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --reduced \
      --mesh 2x4 --steps 20 --plan plan.json
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import (
    AsyncCheckpointer, load_checkpoint, load_extra, save_checkpoint,
)
from repro.configs.registry import ARCHS, get_config, reduced
from repro.data.pipeline import synthetic_feature_batch, synthetic_lm_batch
from repro.data.prefetch import Prefetcher
from repro.data.shards import ShardReader, batches
from repro.dist.spec import (
    DIST, LeafSpec, MeshCfg, build_spec_tree, dist_elems_per_group,
    tree_to_storage,
)
from repro.roofline.analysis import train_ingest_bytes
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh_from_cfg
from repro.models.init import init_params
from repro.optim.sgd import SGDConfig, init_momentum
from repro.plan import PrecisionPlan, pick_chunks
from repro.train.loop import Trainer
from repro.train.step import make_train_step


def parse_mesh(spec: str) -> MeshCfg:
    """"1x1" | "<dp>x<tp>" | "<pods>x<dp>x<tp>"."""
    parts = [int(p) for p in spec.split("x")]
    if len(parts) == 2:
        return MeshCfg(tp=parts[1], dp=parts[0])
    if len(parts) == 3:
        return MeshCfg(tp=parts[2], dp=parts[1], pods=parts[0])
    raise SystemExit(f"bad --mesh {spec!r}")


def plan_from_args(args, nrt: int, spec_tree, mesh_cfg) -> PrecisionPlan:
    """CLI flags -> PrecisionPlan (``--plan`` wins outright)."""
    if args.plan:
        return PrecisionPlan.from_file(args.plan).broadcast(nrt)
    schedule = "awp"
    round_to = 4
    if args.policy == "baseline":
        schedule = "static"
    elif args.policy.startswith("oracle:"):
        schedule = "static"
        round_to = int(args.policy.split(":")[1])
    elif args.policy != "awp":
        raise SystemExit(f"bad --policy {args.policy!r}")
    if args.chunks == "auto":
        # representative shard: the largest per-group flat shard length
        s_loc = max(
            (s.s_loc for s in jax.tree_util.tree_leaves(
                spec_tree, is_leaf=lambda x: isinstance(x, LeafSpec)
            ) if isinstance(s, LeafSpec) and s.kind == DIST),
            default=0,
        )
        chunks = pick_chunks(
            s_loc, max(mesh_cfg.dshards, 1),
            round_to if schedule == "static" else 1,
            device_kind=jax.devices()[0].device_kind,
        )
        print(f"--chunks auto -> {chunks} (roofline sweep, s_loc={s_loc})")
    else:
        chunks = int(args.chunks)
    return PrecisionPlan.build(
        nrt,
        round_to=round_to,
        grad_round_to=args.grad_round_to,
        grad_mode=args.grad_mode,
        act_round_to=args.act_round_to,
        seq_parallel=args.seq_parallel,
        chunks=chunks,
        dtype="bf16" if args.bf16 else "f32",
        accum_steps=args.accum,
        schedule=schedule,
        awp_threshold=args.awp_threshold,
        awp_interval=args.awp_interval,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--plan", default="",
                    help="PrecisionPlan JSON: the declarative source of "
                         "truth for every precision knob (other precision "
                         "flags are ignored when set)")
    ap.add_argument("--policy", default="awp",
                    help="awp | baseline | oracle:<rt> (plan-builder sugar)")
    ap.add_argument("--awp-threshold", type=float, default=1e-3)
    ap.add_argument("--awp-interval", type=int, default=25)
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--grad-round-to", type=int, default=4)
    ap.add_argument("--grad-mode", default="nearest",
                    choices=["truncate", "nearest", "stochastic"],
                    help="rounding of the compressed gradient "
                         "reduce-scatter (stochastic plumbs a per-step "
                         "PRNG key through the step)")
    ap.add_argument("--act-round-to", type=int, default=4,
                    help="activation wire format on the TP axis (<4 routes "
                         "TP psums and seq collectives through packed planes)")
    ap.add_argument("--seq-parallel", action="store_true",
                    help="sequence-parallel activations: norms/residuals on "
                         "1/tp sequence shards, block boundaries become "
                         "seq_gather/seq_scatter (requires seq %% tp == 0)")
    ap.add_argument("--chunks", default="1",
                    help="weight-gather chunk count (int, or 'auto' to pick "
                         "from the roofline sweep)")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="also checkpoint every N steps (0 = only final); "
                         "each save stores the data-pipeline iterator "
                         "state so --resume replays the exact batch stream")
    ap.add_argument("--async-ckpt", action="store_true",
                    help="serialize checkpoints on a worker thread, "
                         "overlapped with the next train step")
    ap.add_argument("--resume", action="store_true",
                    help="restore storage/momentum/AWP/data state from "
                         "--ckpt and continue to --steps")
    ap.add_argument("--data-dir", default="",
                    help="ingest from a tiered shard dir (repro.data.write) "
                         "through the double-buffered prefetcher instead of "
                         "generating batches inline")
    ap.add_argument("--data-quality", type=int, default=4,
                    help="progressive-record tier: float payloads read only "
                         "their N most significant byte planes (ids are "
                         "always lossless)")
    ap.add_argument("--prefetch-depth", type=int, default=2)
    ap.add_argument("--losses-out", default="",
                    help="write the per-step loss stream as JSON (the "
                         "artifact --check compares against)")
    ap.add_argument("--check", default="",
                    help="reference losses JSON: verify this run's losses "
                         "are bit-exact on overlapping steps (resume "
                         "determinism) and exit nonzero otherwise")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh_cfg = parse_mesh(args.mesh)
    need, devices = mesh_cfg.tp * mesh_cfg.dshards, jax.devices()
    if need > len(devices):
        if devices[0].platform == "cpu":
            hint = "set XLA_FLAGS=--xla_force_host_platform_device_count=N"
        else:
            hint = (f"this host has {len(devices)} "
                    f"{devices[0].device_kind} chips")
        raise SystemExit(
            f"mesh {args.mesh} needs {need} devices, have {len(devices)} "
            f"({hint})"
        )
    mesh = make_mesh_from_cfg(mesh_cfg)

    params, metas = init_params(cfg, jax.random.PRNGKey(0), tp=mesh_cfg.tp)
    spec_tree = build_spec_tree(params, metas, mesh_cfg)
    storage = tree_to_storage(params, spec_tree, mesh_cfg)
    n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))
    nrt = cfg.num_groups + 1
    plan = plan_from_args(args, nrt, spec_tree, mesh_cfg)
    print(f"{cfg.name}: {n/1e6:.1f}M params, mesh {mesh_cfg.shape}, "
          f"schedule {plan.schedule.source}, rts {plan.round_tos}")

    B, S = args.batch, args.seq
    audio = cfg.embed_is_input_stub
    if audio:
        batch_shapes = {
            "features": jax.ShapeDtypeStruct((B, S, cfg.vision_dim), jnp.float32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
    else:
        batch_shapes = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
    if cfg.num_image_tokens:
        batch_shapes["image_features"] = jax.ShapeDtypeStruct(
            (B, cfg.num_image_tokens, cfg.vision_dim), jnp.float32
        )

    opt = SGDConfig(lr=args.lr, momentum=0.9, weight_decay=1e-4)

    def builder(round_tos):
        return make_train_step(
            cfg, mesh_cfg, mesh, spec_tree, opt, batch_shapes,
            plan=plan.with_round_tos(round_tos),
        )

    trainer = Trainer(
        builder, nrt, plan=plan,
        dist_elems_per_group=dist_elems_per_group(spec_tree, mesh_cfg, nrt),
        gather_axis_size=max(mesh_cfg.dshards, 1),
    )
    mom = init_momentum(storage)

    # -- resume: storage/momentum/AWP state + data iterator position ----
    start_step = 0
    data_state = None
    if args.resume:
        if not args.ckpt:
            raise SystemExit("--resume needs --ckpt")
        storage, mom, start_step = load_checkpoint(
            args.ckpt, storage, mom, trainer.controller
        )
        data_state = load_extra(args.ckpt).get("data_state")
        print(f"resumed {args.ckpt} at step {start_step}")
    if start_step >= args.steps:
        raise SystemExit(f"checkpoint step {start_step} >= --steps {args.steps}")

    # -- data source: tiered shards through the prefetcher, or inline ---
    reader = prefetcher = None
    ingest_plan = None
    if args.data_dir:
        reader = ShardReader(
            args.data_dir, quality=args.data_quality, seed=0
        )
        want_kind = "feature" if audio else "lm"
        if reader.kind != want_kind:
            raise SystemExit(
                f"--data-dir holds {reader.kind!r} shards, arch needs "
                f"{want_kind!r}"
            )
        for key, want in (("vocab", cfg.vocab_size), ("seq", S)):
            got = reader.meta.get(key)
            if got is not None and got != want:
                raise SystemExit(
                    f"--data-dir {key}={got} does not match run {key}={want}"
                )
        if data_state is not None:
            reader.load_state(data_state)
        # analytic ingest model from the reader's CURRENT position —
        # must be priced before the prefetcher starts reading ahead
        ingest_plan = train_ingest_bytes(
            plan, cfg.vocab_size, kind=reader.kind, batch=B, seq=S,
            steps=args.steps - start_step, dim=cfg.vision_dim,
            reader=reader,
        )
        prefetcher = Prefetcher(
            batches(reader, B), kind=reader.kind, vocab=cfg.vocab_size,
            plan=plan, depth=args.prefetch_depth,
        )

    async_ckpt = AsyncCheckpointer() if args.async_ckpt else None

    def checkpoint(step):
        save_checkpoint(
            args.ckpt, storage, mom, trainer.controller, step, plan=plan,
            spec_tree=spec_tree, round_tos=trainer.current_round_tos(),
            extra={"data_state": data_state} if data_state else None,
            async_ckpt=async_ckpt,
        )

    rngi = np.random.default_rng(0)
    ctx = mesh if mesh is not None else _null()
    t0 = time.time()
    done = 0
    with ctx:
        for step in range(start_step, args.steps):
            io_log = None
            if prefetcher is not None:
                batch, io_log = prefetcher.next()
                data_state = io_log["data_state"]
            elif audio:
                f, l = synthetic_feature_batch(
                    cfg.vision_dim, cfg.vocab_size, B, S, step
                )
                batch = {"features": f, "labels": l}
            else:
                t, l = synthetic_lm_batch(cfg.vocab_size, B, S, step)
                batch = {"tokens": t, "labels": l}
            if cfg.num_image_tokens and "image_features" not in batch:
                batch["image_features"] = jnp.asarray(
                    rngi.normal(0, 1, (B, cfg.num_image_tokens, cfg.vision_dim)),
                    jnp.float32,
                )
            extra = (
                (jax.random.PRNGKey(step),) if plan.needs_rng else ()
            )
            storage, mom, _ = trainer.run_step(
                storage, mom, batch, args.lr, *extra, io_log=io_log
            )
            done += 1
            if args.ckpt and args.ckpt_every and (
                (step + 1) % args.ckpt_every == 0 and step + 1 < args.steps
            ):
                checkpoint(step + 1)
            if done % 20 == 0:
                r = trainer.records[-1]
                print(f"step {step+1:4d}  loss {r.loss:.4f}  rts {r.round_tos}"
                      f"  wire {r.wire_bytes/1e6:.1f}MB"
                      f"  {(time.time()-t0)/done:.2f}s/step", flush=True)
    if prefetcher is not None:
        prefetcher.close()
        reader.close()
    s = trainer.summary()
    print(f"done: loss {s['final_loss']:.4f}  wire-reduction "
          f"{s['wire_reduction']*100:.1f}%  recompiles {s['recompiles']}")
    if "wire_by_entry" in s:
        entries = ", ".join(
            f"{k} {v/1e6:.1f}MB" for k, v in s["wire_by_entry"].items() if v
        )
        print(f"wire by plan entry: {entries}")
    if ingest_plan is not None and "io_by_entry" in s:
        io = s["io_by_entry"]
        measured = {
            "shard_read": io.get("shard_read", 0),
            "ingest_h2d": io.get("host_device", 0),
        }
        analytic = {k: ingest_plan[k] for k in measured}
        status = "OK" if measured == analytic else "MISMATCH"
        print(f"ingest bytes measured {measured} analytic {analytic} "
              f"[{status}]")
        if measured != analytic:
            raise SystemExit("measured ingest bytes != analytic model")
    print(f"AWP: {s['bits_history']}")
    if args.ckpt:
        checkpoint(args.steps)
        if async_ckpt is not None:
            async_ckpt.wait()
        print(f"checkpoint -> {args.ckpt} (plan + data state persisted)")

    losses = [r.loss for r in trainer.records]
    if args.losses_out:
        with open(args.losses_out, "w") as f:
            json.dump({"start_step": start_step, "losses": losses}, f)
        print(f"losses -> {args.losses_out}")
    if args.check:
        with open(args.check) as f:
            ref = json.load(f)
        mism = [
            (g, ref["losses"][g - ref["start_step"]], losses[g - start_step])
            for g in range(
                max(start_step, ref["start_step"]),
                min(start_step + len(losses),
                    ref["start_step"] + len(ref["losses"])),
            )
            if ref["losses"][g - ref["start_step"]] != losses[g - start_step]
        ]
        if mism:
            for g, a, b in mism[:5]:
                print(f"step {g}: ref {a!r} != run {b!r}")
            raise SystemExit(
                f"--check: {len(mism)} loss mismatches vs {args.check}"
            )
        print(f"--check OK: losses bit-exact vs {args.check}")


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
