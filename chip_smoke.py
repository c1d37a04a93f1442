"""End-to-end smoke run of qwen3-1.7b on TPU chips, in one process.

    python chip_smoke.py             # one chip: paged serving, then training
    python chip_smoke.py --chips 4   # four chips: FSDP training only

One chip. The serve phase runs the published qwen3-1.7b widths through
``ServeEngine(paged=True)`` (flash prefill and paged decode kernels),
compares its token streams with ``generate_static`` and checks the
logits of the first prefill and decode step against the dense XLA path
at ``highest`` matmul precision. The train phase takes a few steps of
``make_train_step`` + ``Trainer`` under an AWP plan at a depth cut that
fits the chip's memory.

Four chips. FSDP over ``dp=4``: at the train phase's depth, losses on
the mesh against the same model on one device; then all 28 layers with
weights and gradients crossing the wire as 2-byte planes, whose compiled
step must show u8 all-gathers and the bitpack/bitunpack kernels.

The script refuses to run without a TPU. Its last output line is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every
check passed; a failed check exits non-zero once the phases have run.
Weights are random, drawn from ``--seed``.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import gc
import json
import math
import pathlib
import re
import statistics
import sys
import time

ARCH = "qwen3-1.7b"
# serve: prompt lengths that tile the flash kernel's 128-row blocks
PROMPT_LENS = (128, 256, 512, 128, 256, 512, 128, 256)
GEN = 32
SLOTS = 4
PAGE = 64
# train: the fp32 master copy, momentum and gradients of the 622M
# embedding/head parameters alone take 7.5 GB; 8 of the 28 layers keep
# the compiled step near 10 GiB of the chip's 16 GiB
TRAIN_LAYERS = 8
TRAIN_BATCH, TRAIN_SEQ = 8, 256
TRAIN_STEPS = 4
DP4_STEPS = 3
LR = 0.05
# logits of the kernel path vs the dense path, both at highest matmul
# precision, as a share of the reference's largest |logit|: what is left
# is the kernels' own arithmetic. Dropping one cached token moved them
# by 3e-2 at reduced widths on the CPU.
LOGIT_TOL = 2e-2
# one device vs dp=4 at identical weights and global batch: only the
# order of the gradient and loss reductions differs
LOSS_RTOL = 1e-3

# the result type of an HLO all-gather (or the tuple of an async one)
_U8_GATHER = re.compile(r"= \(*u8\[[^=]* all-gather(?:-start)?\(")
_CUSTOM = 'custom_call_target="tpu_custom_call"'


def _custom_calls(hlo: str) -> int:
    return hlo.count(_CUSTOM)


def _kernel_calls(hlo: str, kernel: str) -> int:
    """Custom calls of the jitted Pallas wrapper ``kernel``: XLA names
    the instruction after it (``%bitpack_2d.3 = ... custom-call(...)``)."""
    return sum(
        1 for line in hlo.splitlines()
        if _CUSTOM in line and f"%{kernel}" in line.split("=", 1)[0]
    )


def _gib(n: int) -> str:
    return f"{n / 2**30:.2f} GiB"


def _peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def model_config(layers: int | None = None):
    """The published qwen3-1.7b widths; ``layers`` cuts depth only."""
    from repro.configs.registry import get_config

    cfg = get_config(ARCH)
    if layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    return cfg


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def _paged_pool_from_prefill(pool, pcaches, n_pages: int, S: int):
    """Engine slot 0 after admitting one S-token prompt: its prefill KV
    written to pool pages ``0..n_pages-1``, position S (the other slots
    stay empty, as ballast)."""
    import jax.numpy as jnp
    from repro.models.attention import PagedKVCache

    def node(bn, sn):
        def pages(x):  # (R, 1, cap, Kv, hd) -> (R, n_pages, PAGE, Kv, hd)
            seg = x[:, 0, : n_pages * PAGE]
            return seg.reshape(x.shape[0], n_pages, PAGE, *x.shape[3:])

        return PagedKVCache(
            bn.k.at[:, :n_pages].set(pages(sn.k)),
            bn.v.at[:, :n_pages].set(pages(sn.v)),
            bn.pos.at[:, 0].set(jnp.int32(S)),
        )

    return [
        {key: node(bn, sg[key]) for key, bn in bg.items()}
        for bg, sg in zip(pool, pcaches)
    ]


def _rel_err(x, ref) -> float:
    import numpy as np

    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(x - ref)) / np.max(np.abs(ref)))


def _precision(name):
    """``None`` is the default matmul precision the engine runs at."""
    import jax

    if name is None:
        return contextlib.nullcontext()
    return jax.default_matmul_precision(name)


def logits_check(cfg, spec_tree, storage, plan, prompt, first_tok,
                 table_width, num_pages, failed):
    """First prefill and first decode step of one request. The engine's
    kernel programs (flash prefill, paged decode), built as ServeEngine
    builds them, against the dense path: a prefill of the prompt, the
    first token and one pad, whose length is no multiple of 128, so it
    runs ``attend_tiled`` with no kernel. Both sides run at ``highest``
    matmul precision, which leaves the kernels' own arithmetic as the
    difference; the engine's default-precision programs and the dense
    path at default precision are printed beside them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.dist.spec import SINGLE
    from repro.serve.step import (
        global_cache_shapes, make_decode_step, make_prefill_step,
    )

    S = len(prompt)
    V = cfg.vocab_size
    sds = jax.ShapeDtypeStruct
    pplan = dataclasses.replace(plan, seq_parallel=False)
    pre_shapes = {"tokens": sds((1, S), jnp.int32), "last": sds((), jnp.int32)}
    dec_shapes = {
        "tokens": sds((SLOTS, 1), jnp.int32),
        "pos": sds((SLOTS,), jnp.int32),
        "page_table": sds((SLOTS, table_width), jnp.int32),
    }
    pool_shapes = global_cache_shapes(
        cfg, SINGLE, SLOTS, table_width * PAGE, plan.compute_dtype,
        shard_batch=False, per_slot=True, int8_kv=False,
        paged_pages=num_pages, page_size=PAGE,
    )
    # slot 0 owns the pages of its prompt and of the tokens it will add;
    # the other slots' entries point at the trash page
    n_pages = -(-S // PAGE)
    owned = -(-(S + GEN) // PAGE)
    table = np.full((SLOTS, table_width), num_pages, np.int32)
    table[0, :owned] = np.arange(owned)
    feed = np.zeros((SLOTS, 1), np.int32)
    feed[0, 0] = first_tok
    pos = np.zeros((SLOTS,), np.int32)
    pos[0] = S
    dec_batch = {"tokens": jnp.asarray(feed), "pos": jnp.asarray(pos),
                 "page_table": jnp.asarray(table)}
    L = S + 2
    ref_shapes = {"tokens": sds((1, L), jnp.int32), "last": sds((), jnp.int32)}
    ref_toks = jnp.asarray([list(prompt) + [first_tok, 0]], jnp.int32)

    logits = {}
    for precision in (None, "highest"):
        with _precision(precision):
            prefill = make_prefill_step(
                cfg, SINGLE, None, spec_tree, pre_shapes, plan=pplan,
                cache_capacity=table_width * PAGE, shard_batch=False,
            ).lower(storage, pre_shapes).compile()
            decode = make_decode_step(
                cfg, SINGLE, None, spec_tree, dec_shapes, plan=plan,
                shard_batch=False, slot_caches=True, paged=True,
            ).lower(storage, pool_shapes, dec_shapes).compile()
            dense = make_prefill_step(
                cfg, SINGLE, None, spec_tree, ref_shapes, plan=pplan,
                cache_capacity=L, shard_batch=False,
            ).lower(storage, ref_shapes).compile()
        if precision is None:
            for name, exe, kernel in (("prefill", prefill, "flash_prefill"),
                                      ("decode", decode, "paged_attend")):
                hlo = exe.as_text()
                k = _kernel_calls(hlo, kernel)
                print(f"serve {name} step HLO: {_custom_calls(hlo)} "
                      f"tpu_custom_call, {k} of them {kernel!r}")
                if not k:
                    failed.append(f"serve {name} step runs no compiled "
                                  f"{kernel} kernel")
        out, pcaches = prefill(
            storage, {"tokens": ref_toks[:, :S], "last": jnp.int32(S - 1)}
        )
        logits["prefill", "kernel", precision] = out
        pool = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), pool_shapes,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
        pool = _paged_pool_from_prefill(pool, pcaches, n_pages, S)
        logits["decode", "kernel", precision], _ = decode(
            storage, pool, dec_batch
        )
        for name, last in (("prefill", S - 1), ("decode", S)):
            logits[name, "dense", precision], _ = dense(
                storage, {"tokens": ref_toks, "last": jnp.int32(last)}
            )

    def row(key):
        return np.asarray(logits[key])[0, 0, :V]

    for name in ("prefill", "decode"):
        ref = row((name, "dense", "highest"))
        err = _rel_err(row((name, "kernel", "highest")), ref)
        err_engine = _rel_err(row((name, "kernel", None)), ref)
        err_dense = _rel_err(row((name, "dense", None)), ref)
        top = int(np.argmax(row((name, "kernel", None)))) == int(np.argmax(ref))
        print(f"logits {name} (max|err| / max|logit| {np.max(np.abs(ref)):.3f},"
              f" vs dense at highest): kernel path at highest {err:.3e} "
              f"(tolerance {LOGIT_TOL:g}); at default precision: kernel "
              f"path {err_engine:.3e}, dense {err_dense:.3e}; engine top-1 "
              f"{'agrees' if top else 'differs'}")
        if not err <= LOGIT_TOL:
            failed.append(f"logits {name}: kernel path off the dense path "
                          f"by {err:.3e} > {LOGIT_TOL:g}")


def serve_phase(seed: int, dev, failed: list) -> None:
    import jax
    import numpy as np
    from repro.dist.spec import build_spec_tree, tree_to_storage
    from repro.launch.mesh import make_mesh_from_cfg
    from repro.launch.train import parse_mesh
    from repro.models.init import init_params
    from repro.plan import PrecisionPlan
    from repro.serve.engine import Request, ServeEngine, generate_static

    cfg = model_config()
    mesh_cfg = parse_mesh("1x1")
    mesh = make_mesh_from_cfg(mesh_cfg)
    t0 = time.perf_counter()
    params, metas = init_params(cfg, jax.random.PRNGKey(seed), tp=1)
    spec_tree = build_spec_tree(params, metas, mesh_cfg)
    storage = tree_to_storage(params, spec_tree, mesh_cfg)
    del params
    jax.block_until_ready(storage)
    n = sum(x.size for x in jax.tree_util.tree_leaves(storage))
    print(f"serve: {cfg.name} {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}: {n / 1e9:.3f} B fp32 "
          f"params made in {time.perf_counter() - t0:.1f}s")
    # launch/serve.py's default plan: 2-byte weight planes, fp32 compute
    plan = PrecisionPlan.build(cfg.num_groups + 1, round_to=2, act_round_to=4)

    rng = np.random.default_rng(seed)
    requests = [
        Request(
            rid=i,
            prompt_ids=tuple(int(t) for t in rng.integers(0, cfg.vocab_size, S)),
            max_new=GEN,
        )
        for i, S in enumerate(PROMPT_LENS)
    ]
    engine = ServeEngine(
        cfg, mesh_cfg, mesh, spec_tree, storage, plan=plan,
        max_slots=SLOTS, cache_capacity=max(PROMPT_LENS) + GEN,
        paged=True, page_size=PAGE,
    )

    t0 = time.perf_counter()
    warm = engine.run(requests)
    warm_s = time.perf_counter() - t0

    # steady run over the streaming surface, timing each call; admit and
    # decode_tick return only after the sampled ids reach the host, so
    # each span covers its device program
    engine.begin_stream()
    queue = collections.deque(requests)
    admit_s, tick_s = [], []
    t0 = time.perf_counter()
    while queue or engine.has_work:
        while queue and engine.can_admit(queue[0])[0]:
            ta = time.perf_counter()
            engine.admit(queue.popleft())
            admit_s.append(time.perf_counter() - ta)
        td = time.perf_counter()
        engine.decode_tick()
        tick_s.append(time.perf_counter() - td)
    wall = time.perf_counter() - t0
    results = engine.finish()
    new_tokens = sum(len(r.tokens) for r in results.values())
    if any(results[r.rid].tokens != warm[r.rid].tokens for r in requests):
        failed.append("serve: the steady run's streams differ from the "
                      "warm-up run's")
    decode_ms = statistics.median(tick_s) * 1e3
    print(f"serve steady run (chip, this run): {len(requests)} requests, "
          f"{new_tokens} new tokens in {wall:.3f}s = "
          f"{new_tokens / wall:.1f} tok/s; decode step median "
          f"{decode_ms:.2f} ms over {len(tick_s)} steps ({SLOTS} slots), "
          f"prefill+admit median {statistics.median(admit_s) * 1e3:.2f} ms")
    print(f"serve warm-up run (the same requests, every program compiled "
          f"on first use): {warm_s:.3f}s, so compilation took about "
          f"{warm_s - wall:.1f}s")

    t0 = time.perf_counter()
    static = generate_static(
        cfg, mesh_cfg, mesh, spec_tree, storage, requests, plan=plan
    )
    agree = sum(
        a == b
        for r in requests
        for a, b in zip(results[r.rid].tokens, static[r.rid])
    )
    total = sum(len(static[r.rid]) for r in requests)
    same = sum(results[r.rid].tokens == static[r.rid] for r in requests)
    print(f"streams vs generate_static ({time.perf_counter() - t0:.1f}s): "
          f"{agree}/{total} tokens agree, {same}/{len(requests)} streams "
          "identical")

    r0 = requests[0]
    logits_check(
        cfg, spec_tree, storage, plan, r0.prompt_ids,
        results[r0.rid].tokens[0], -(-engine.cache_capacity // PAGE),
        engine.num_pages, failed,
    )
    print(f"serve peak device memory: {_gib(_peak_bytes(dev))}")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def _train(cfg, mesh_cfg, mesh, spec_tree, storage, plan, steps, label,
           failed):
    """``steps`` steps of ``make_train_step`` + ``Trainer`` on the
    synthetic pipeline, as ``launch/train.py`` drives them. Each step
    program is compiled ahead of time, and its compile time reported. Returns (losses, seconds of each step that compiled nothing,
    the compiled programs)."""
    import jax
    import jax.numpy as jnp
    from repro.data.pipeline import synthetic_lm_batch
    from repro.dist.spec import dist_elems_per_group
    from repro.optim.sgd import SGDConfig, init_momentum
    from repro.train.loop import Trainer
    from repro.train.step import make_train_step

    nrt = cfg.num_groups + 1
    B, S = TRAIN_BATCH, TRAIN_SEQ
    batch_shapes = {
        k: jax.ShapeDtypeStruct((B, S), jnp.int32) for k in ("tokens", "labels")
    }
    opt = SGDConfig(lr=LR, momentum=0.9, weight_decay=1e-4)
    mom = init_momentum(storage)
    compiled = {}

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
            tree,
        )

    def builder(round_tos):
        t0 = time.perf_counter()
        step = make_train_step(
            cfg, mesh_cfg, mesh, spec_tree, opt, batch_shapes,
            plan=plan.with_round_tos(round_tos),
        )
        compiled[round_tos] = step.lower(
            abstract(storage), abstract(mom), batch_shapes, LR
        ).compile()
        print(f"{label}: step for round_tos {round_tos} compiled in "
              f"{time.perf_counter() - t0:.2f}s")
        return compiled[round_tos]

    trainer = Trainer(
        builder, nrt, plan=plan,
        dist_elems_per_group=dist_elems_per_group(spec_tree, mesh_cfg, nrt),
        gather_axis_size=max(mesh_cfg.dshards, 1),
    )
    step_s = []
    with mesh if mesh is not None else contextlib.nullcontext():
        for step in range(steps):
            t, l = synthetic_lm_batch(cfg.vocab_size, B, S, step)
            n_compiled = len(compiled)
            t0 = time.perf_counter()
            storage, mom, _ = trainer.run_step(
                storage, mom, {"tokens": t, "labels": l}, LR
            )
            jax.block_until_ready((storage, mom))
            if len(compiled) == n_compiled:
                step_s.append(time.perf_counter() - t0)
    losses = [r.loss for r in trainer.records]
    print(f"{label}: losses {losses}")
    if not all(math.isfinite(x) for x in losses):
        failed.append(f"{label}: non-finite loss")
    return losses, step_s, compiled


def _init_storage(cfg, mesh_cfg, seed):
    """(spec tree, storage) of random weights from ``seed``."""
    import jax
    from repro.dist.spec import build_spec_tree, tree_to_storage
    from repro.models.init import init_params

    params, metas = init_params(cfg, jax.random.PRNGKey(seed), tp=1)
    spec_tree = build_spec_tree(params, metas, mesh_cfg)
    return spec_tree, tree_to_storage(params, spec_tree, mesh_cfg)


def _memory(exe) -> str:
    m = exe.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return f"{_gib(total)} per device (compiler's memory analysis)"


def train_phase(seed: int, dev, failed: list) -> None:
    from repro.dist.spec import SINGLE
    from repro.plan import PrecisionPlan

    cfg = model_config(TRAIN_LAYERS)
    spec_tree, storage = _init_storage(cfg, SINGLE, seed)
    # launch/train.py's default plan: AWP from 8-bit weight planes
    plan = PrecisionPlan.build(
        cfg.num_groups + 1, round_to=4, grad_round_to=4, schedule="awp",
        awp_threshold=1e-3, awp_interval=25,
    )
    label = f"train {cfg.num_layers}/{model_config().num_layers} layers"
    _, step_s, compiled = _train(
        cfg, SINGLE, None, spec_tree, storage, plan, TRAIN_STEPS, label,
        failed,
    )
    del storage
    exe = next(iter(compiled.values()))
    step_ms = statistics.median(step_s) * 1e3
    tokens = TRAIN_BATCH * TRAIN_SEQ
    print(f"{label} (chip, this run): batch {TRAIN_BATCH}x{TRAIN_SEQ}, "
          f"step median {step_ms:.2f} ms over {len(step_s)} steps = "
          f"{tokens / (step_ms / 1e3):.0f} tokens/s; {_memory(exe)}; "
          f"{_custom_calls(exe.as_text())} tpu_custom_call; process peak "
          f"device memory {_gib(_peak_bytes(dev))}")


# ---------------------------------------------------------------------------
# four chips: FSDP over dp=4
# ---------------------------------------------------------------------------


def dp4_phase(seed: int, failed: list) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.dist.spec import (
        SINGLE, build_spec_tree, leaf_to_storage, tree_partition_specs,
        tree_to_storage,
    )
    from repro.launch.mesh import make_mesh_from_cfg
    from repro.launch.train import parse_mesh
    from repro.models.init import init_params
    from repro.plan import PrecisionPlan

    mesh_cfg = parse_mesh("4x1")
    mesh = make_mesh_from_cfg(mesh_cfg)

    def sharded_storage(params, spec_tree):
        # leaf by leaf, so device 0 holds the weights and at most one
        # leaf's storage-layout copy besides its shards
        return jax.tree_util.tree_map(
            lambda x, s, p: jax.device_put(
                leaf_to_storage(x, s, mesh_cfg), NamedSharding(mesh, p)
            ),
            params, spec_tree, tree_partition_specs(spec_tree, mesh_cfg),
        )

    # 1. the depth cut, uncompressed: dp=4 against one device, same
    # weights and global batch
    cfg = model_config(TRAIN_LAYERS)
    plan = PrecisionPlan.build(cfg.num_groups + 1, round_to=4)
    params, metas = init_params(cfg, jax.random.PRNGKey(seed), tp=1)
    spec4 = build_spec_tree(params, metas, mesh_cfg)
    storage4 = sharded_storage(params, spec4)
    spec1 = build_spec_tree(params, metas, SINGLE)
    # own buffers: the one-device run donates them, and device_put may
    # have kept the params' buffers as device 0's shards of storage4
    storage1 = jax.tree_util.tree_map(
        jnp.copy, tree_to_storage(params, spec1, SINGLE)
    )
    del params
    one, _, _ = _train(cfg, SINGLE, None, spec1, storage1, plan, DP4_STEPS,
                       f"{cfg.num_layers} layers, one device", failed)
    del storage1
    four, _, _ = _train(cfg, mesh_cfg, mesh, spec4, storage4, plan,
                        DP4_STEPS, f"{cfg.num_layers} layers, dp=4", failed)
    del storage4
    worst = max(abs(a - b) / abs(a) for a, b in zip(one, four))
    print(f"dp=4 vs one device: worst relative loss gap {worst:.3e} "
          f"(tolerance {LOSS_RTOL:g})")
    if not worst <= LOSS_RTOL:
        failed.append(f"dp=4 losses off the one-device run's by {worst:.3e}")

    # 2. all 28 layers, weights and gradients as 2-byte planes
    cfg = model_config()
    plan = PrecisionPlan.build(cfg.num_groups + 1, round_to=2, grad_round_to=2)
    t0 = time.perf_counter()
    params, metas = init_params(cfg, jax.random.PRNGKey(seed), tp=1)
    spec = build_spec_tree(params, metas, mesh_cfg)
    storage = sharded_storage(params, spec)
    del params
    jax.block_until_ready(storage)
    print(f"{cfg.num_layers} layers: weights made and sharded in "
          f"{time.perf_counter() - t0:.1f}s")
    label = f"{cfg.num_layers} layers, dp=4, weights+grads round_to 2"
    _, step_s, compiled = _train(
        cfg, mesh_cfg, mesh, spec, storage, plan, DP4_STEPS, label, failed
    )
    del storage
    exe = next(iter(compiled.values()))
    hlo = exe.as_text()
    u8_gathers = len(_U8_GATHER.findall(hlo))
    pack = _kernel_calls(hlo, "bitpack_2d")
    unpack = _kernel_calls(hlo, "bitunpack_2d")
    step_ms = statistics.median(step_s) * 1e3
    print(f"{label} (chips, this run): step median {step_ms:.2f} ms over "
          f"{len(step_s)} steps; {_memory(exe)}; compiled step has "
          f"{u8_gathers} u8 all-gathers, {_custom_calls(hlo)} "
          f"tpu_custom_call ({pack} bitpack, {unpack} bitunpack)")
    if not (u8_gathers and pack and unpack):
        failed.append("the compressed step moves no u8 planes through "
                      "compiled bitpack/bitunpack kernels")


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve + train on one chip; 4: only the dp=4 "
                         "FSDP training comparisons")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform {dev.platform}, kind {dev.device_kind!r}, "
          f"count {len(devices)}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit("chip_smoke: no TPU found; this script measures "
                         "the chip and has no CPU fallback")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} chips, found {len(devices)}")

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    failed: list[str] = []
    if args.chips == 4:
        dp4_phase(args.seed, failed)
    else:
        serve_phase(args.seed, dev, failed)
        gc.collect()
        train_phase(args.seed, dev, failed)
    if failed:
        raise SystemExit("chip_smoke FAILED:\n  " + "\n  ".join(failed))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
