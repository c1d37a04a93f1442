"""Subprocess scenario: sequence-parallel activations (Env.seq_parallel)
on an 8-device host mesh.

Equivalence pins, per architecture family (attention, MoE-tp, mLSTM/sLSTM,
RG-LRU, audio encoder, vision cross-attn):

  * seq_parallel=True at round_to=4 (uncompressed seq pair) matches the
    psum-decomposition train step BIT-EXACTLY at tp=2 — norms, residuals
    and the embedding/logits entries on sequence shards reproduce the
    replicated layout's sums exactly (two-operand reductions have a
    single order).
  * seq_parallel + act_policy=rt2: every block boundary rides packed
    planes fwd AND bwd; loss stays inside the bf16-grade envelope and
    training keeps descending.
  * prefill under seq_parallel produces bit-close logits AND caches, and
    decode (which drops the flag — no sequence dim to shard) continues
    from those caches transparently.
"""
import dataclasses
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config, reduced
from repro.dist.spec import MeshCfg, build_spec_tree, tree_to_storage
from repro.launch.mesh import make_mesh_from_cfg
from repro.models.init import init_params
from repro.optim.sgd import SGDConfig, init_momentum
from repro.plan import PrecisionPlan
from repro.serve.step import make_decode_step, make_prefill_step
from repro.train.step import make_train_step
from repro.transport import CompressionPolicy

OPT = SGDConfig(lr=0.05, momentum=0.9, weight_decay=0.0)
B, S = 8, 32


def _plan(nrt, **kw):
    return PrecisionPlan.build(nrt, **kw)


def _batch(cfg, seed=0):
    rng = np.random.default_rng(seed)
    if cfg.embed_is_input_stub:
        b = {
            "features": jnp.asarray(
                rng.normal(0, 1, (B, S, cfg.vision_dim)), jnp.float32
            ),
            "labels": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32
            ),
        }
    else:
        b = {
            "tokens": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32
            ),
            "labels": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32
            ),
        }
    if cfg.num_image_tokens:
        b["image_features"] = jnp.asarray(
            rng.normal(0, 1, (B, cfg.num_image_tokens, cfg.vision_dim)),
            jnp.float32,
        )
    return b


def _fresh_storage(cfg, spec, mesh_cfg):
    # every step is donate_argnums=(0, 1): re-init per section
    params, _ = init_params(cfg, jax.random.PRNGKey(0), tp=mesh_cfg.tp)
    return tree_to_storage(params, spec, mesh_cfg)


def run_train_equivalence(arch, mesh_cfg, mesh):
    """seq_parallel rt=4 == psum layout, bit-exact at tp=2."""
    cfg = reduced(get_config(arch))
    batch = _batch(cfg)
    bs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}
    nrt = cfg.num_groups + 1
    params, metas = init_params(cfg, jax.random.PRNGKey(0), tp=mesh_cfg.tp)
    spec = build_spec_tree(params, metas, mesh_cfg)

    st = tree_to_storage(params, spec, mesh_cfg)
    step = make_train_step(cfg, mesh_cfg, mesh, spec, OPT, bs,
                           plan=_plan(nrt))
    s_a, m_a, met_a = step(st, init_momentum(st), batch, 0.05)

    st2 = _fresh_storage(cfg, spec, mesh_cfg)
    step_sp = make_train_step(
        cfg, mesh_cfg, mesh, spec, OPT, bs, plan=_plan(nrt, seq_parallel=True)
    )
    s_b, m_b, met_b = step_sp(st2, init_momentum(st2), batch, 0.05)

    la, lb = float(met_a["loss"]), float(met_b["loss"])
    assert la == lb, (arch, la, lb)
    np.testing.assert_array_equal(
        np.asarray(met_a["group_norms_sq"]), np.asarray(met_b["group_norms_sq"])
    )
    # a second step from the updated storage stays pinned
    _, _, met_a2 = step(s_a, m_a, _batch(cfg, seed=1), 0.05)
    _, _, met_b2 = step_sp(s_b, m_b, _batch(cfg, seed=1), 0.05)
    assert float(met_a2["loss"]) == float(met_b2["loss"]), arch
    print(f"  {arch}: seq-parallel == psum bit-exact ({la:.4f})")
    return spec


def run_compressed(cfg, spec, mesh_cfg, mesh):
    """seq_parallel + act rt2: planes on every boundary, loss in envelope."""
    batch = _batch(cfg)
    bs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}
    nrt = cfg.num_groups + 1
    act2 = CompressionPolicy(round_to=2, grad_round_to=2, mode="nearest")

    st = _fresh_storage(cfg, spec, mesh_cfg)
    step = make_train_step(cfg, mesh_cfg, mesh, spec, OPT, bs,
                           plan=_plan(nrt))
    _, _, met_ref = step(st, init_momentum(st), batch, 0.05)
    l_ref = float(met_ref["loss"])

    st2 = _fresh_storage(cfg, spec, mesh_cfg)
    plan_c = PrecisionPlan(
        weights=_plan(nrt).weights, activations=act2, seq_parallel=True
    )
    step_c = make_train_step(
        cfg, mesh_cfg, mesh, spec, OPT, bs, plan=plan_c,
    )
    s_c, m_c, met_c = step_c(st2, init_momentum(st2), batch, 0.05)
    l_c = float(met_c["loss"])
    assert abs(l_c - l_ref) < 0.05 + 0.05 * abs(l_ref), (l_ref, l_c)
    _, _, met_c2 = step_c(s_c, m_c, batch, 0.05)
    assert float(met_c2["loss"]) < l_c + 0.05, "seq-parallel rt2 diverged"
    print(f"  act-rt2 seq-parallel: {l_ref:.4f} -> {l_c:.4f} OK")


def run_serve(cfg, spec, mesh_cfg, mesh):
    """Prefill on shards == replicated prefill (logits AND caches), and
    decode continues from seq-parallel caches."""
    Sp = 16
    nrt = cfg.num_groups + 1
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (B, Sp)),
        jnp.int32,
    )}
    bshapes = {"tokens": jax.ShapeDtypeStruct((B, Sp), jnp.int32)}
    st = _fresh_storage(cfg, spec, mesh_cfg)

    pre = make_prefill_step(
        cfg, mesh_cfg, mesh, spec, bshapes, plan=_plan(nrt),
        cache_capacity=Sp + 2,
    )
    lg_a, caches_a = pre(st, batch)
    pre_sp = make_prefill_step(
        cfg, mesh_cfg, mesh, spec, bshapes,
        plan=_plan(nrt, seq_parallel=True), cache_capacity=Sp + 2,
    )
    lg_b, caches_b = pre_sp(st, batch)
    v = cfg.vocab_size
    np.testing.assert_allclose(
        np.asarray(lg_a[..., :v]), np.asarray(lg_b[..., :v]),
        rtol=1e-5, atol=1e-5,
    )
    for xa, xb in zip(
        jax.tree_util.tree_leaves(caches_a), jax.tree_util.tree_leaves(caches_b)
    ):
        np.testing.assert_allclose(
            np.asarray(xa), np.asarray(xb), rtol=1e-5, atol=1e-6
        )

    dshapes = {
        "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
    tok = {"tokens": jnp.ones((B, 1), jnp.int32),
           "pos": jnp.asarray(Sp, jnp.int32)}
    dstep = make_decode_step(cfg, mesh_cfg, mesh, spec, dshapes,
                             plan=_plan(nrt))
    dl_a, _ = dstep(st, caches_a, tok)
    dstep_sp = make_decode_step(
        cfg, mesh_cfg, mesh, spec, dshapes,
        plan=_plan(nrt, seq_parallel=True),
    )
    dl_b, _ = dstep_sp(st, caches_b, tok)
    np.testing.assert_allclose(
        np.asarray(dl_a[..., :v]), np.asarray(dl_b[..., :v]),
        rtol=1e-5, atol=1e-5,
    )
    print("  prefill/decode under seq-parallel OK")


def run_ep_moe(mesh_cfg, mesh):
    """Expert-parallel MoE: under seq_parallel the sequence shards ARE the
    EP token split (no boundary collective). The psum layout splits the
    flat token axis instead, so per-rank routing sets — and hence
    capacity drops — differ: statistical, not bit, equivalence. Also
    covers the ep_split path itself (_token_split/_token_merge)."""
    cfg = dataclasses.replace(
        reduced(get_config("mixtral-8x7b")), moe_impl="ep"
    )
    batch = _batch(cfg)
    bs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}
    nrt = cfg.num_groups + 1
    params, metas = init_params(cfg, jax.random.PRNGKey(0), tp=mesh_cfg.tp)
    spec = build_spec_tree(params, metas, mesh_cfg)

    st = tree_to_storage(params, spec, mesh_cfg)
    step = make_train_step(cfg, mesh_cfg, mesh, spec, OPT, bs,
                           plan=_plan(nrt))
    _, _, met_a = step(st, init_momentum(st), batch, 0.05)
    st2 = _fresh_storage(cfg, spec, mesh_cfg)
    step_sp = make_train_step(
        cfg, mesh_cfg, mesh, spec, OPT, bs, plan=_plan(nrt, seq_parallel=True)
    )
    s_b, m_b, met_b = step_sp(st2, init_momentum(st2), batch, 0.05)
    la, lb = float(met_a["loss"]), float(met_b["loss"])
    assert abs(la - lb) < 0.02 + 0.01 * abs(la), (la, lb)
    _, _, met_b2 = step_sp(s_b, m_b, batch, 0.05)
    assert float(met_b2["loss"]) < lb + 0.05, "EP seq-parallel diverged"
    print(f"  ep-moe: psum {la:.4f} vs seq-parallel {lb:.4f} OK")


def run_seq_divisibility_guard(cfg, spec, mesh_cfg, mesh):
    bad = {"tokens": jax.ShapeDtypeStruct((B, 33), jnp.int32),
           "labels": jax.ShapeDtypeStruct((B, 33), jnp.int32)}
    nrt = cfg.num_groups + 1
    try:
        make_train_step(
            cfg, mesh_cfg, mesh, spec, OPT, bad,
            plan=_plan(nrt, seq_parallel=True),
        )
    except ValueError as e:
        assert "seq_parallel" in str(e)
        print("  seq divisibility guard OK")
        return
    raise AssertionError("expected ValueError for seq % tp != 0")


def main():
    mesh_cfg = MeshCfg(tp=2, dp=4)
    mesh = make_mesh_from_cfg(mesh_cfg)
    with mesh:
        # one arch per family: attention/vocab-parallel, MoE (tp layout),
        # mLSTM+sLSTM (incl. the replicated-recurrence re-shard path),
        # RG-LRU, audio feature stub, vision cross-attention
        spec_q = run_train_equivalence("qwen3-1.7b", mesh_cfg, mesh)
        for arch in ("mixtral-8x7b", "xlstm-1.3b", "recurrentgemma-9b",
                     "hubert-xlarge", "llama-3.2-vision-90b"):
            run_train_equivalence(arch, mesh_cfg, mesh)
        run_ep_moe(mesh_cfg, mesh)
        cfg_q = reduced(get_config("qwen3-1.7b"))
        run_compressed(cfg_q, spec_q, mesh_cfg, mesh)
        run_serve(cfg_q, spec_q, mesh_cfg, mesh)
        run_seq_divisibility_guard(cfg_q, spec_q, mesh_cfg, mesh)
    print("scenario_seq_parallel OK")


if __name__ == "__main__":
    main()
