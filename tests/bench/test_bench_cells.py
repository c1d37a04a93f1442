"""A whole run of a tiny serving cell on the CPU, the chip check
skipped: the timed path as it is comes out correct; the control (the
program's bf16 compute path) and each fault the serving cells can have,
planted in the timed path, come out not correct."""
import pathlib
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(HERE)]

import bench_tiny  # noqa: E402
from bench.harness.cell import run_cell  # noqa: E402

SEED = 2**31 + 11


def _run(tmp_path, config, mix="tiny-offline", plan=None, seconds=1.0):
    bench_dir, bench, name = bench_tiny.make(tmp_path, config, mix)
    result, outcome = run_cell(
        name, seed=SEED, seconds=seconds, trace=False,
        t_start=time.perf_counter(), root=tmp_path, bench=bench,
        bench_dir=bench_dir, plan_overrides=plan)
    return result, outcome


CONFIGS = [bench_tiny.QWEN_LIKE, bench_tiny.GLM_LIKE]
IDS = ["qwen-like", "glm-like"]


@pytest.mark.parametrize("config", CONFIGS, ids=IDS)
@pytest.mark.parametrize("mix", ["tiny-offline", "tiny-poisson"])
def test_timed_path_is_correct(tmp_path, config, mix):
    result, outcome = _run(tmp_path, config, mix)
    assert result["correct"], (result["checks"], outcome.problems)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert {"setup_s", "itl_p95_ms"} <= set(result["metrics"])
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("config", CONFIGS, ids=IDS)
def test_control_is_not_correct(tmp_path, config):
    result, outcome = _run(tmp_path, config, "tiny-decode",
                           plan={"dtype": "bf16"}, seconds=3.0)
    assert outcome.record["served_gaps"]["served_tokens_compared"] >= 500
    assert not result["correct"], result["checks"]


def _wrap_decode(monkeypatch, change):
    import repro.serve.engine as eng

    make = eng.make_decode_step

    def broken(*args, **kwargs):
        step = make(*args, **kwargs)

        def call(weights, caches, batch):
            logits, new = step(weights, caches, batch)
            return change(logits, caches, new)
        return call

    monkeypatch.setattr(eng, "make_decode_step", broken)


def test_fault_state_unchanged(tmp_path, monkeypatch):
    # the decode step hands back the caches it was given
    _wrap_decode(monkeypatch, lambda logits, old, new: (logits, old))
    result, _ = _run(tmp_path, bench_tiny.QWEN_LIKE)
    assert not result["correct"], result["checks"]


def test_fault_half_the_batch_left_out(tmp_path, monkeypatch):
    # the second half of the slots gets the first half's logits
    def half(logits, old, new):
        h = logits.shape[0] // 2
        rest = logits.shape[0] - h
        return jnp.concatenate([logits[:h], logits[:rest]], axis=0), new
    _wrap_decode(monkeypatch, half)
    result, _ = _run(tmp_path, bench_tiny.QWEN_LIKE)
    assert not result["correct"], result["checks"]


def test_fault_token_altered_where_produced(tmp_path, monkeypatch):
    # every sampled id is off by one as it reaches the host
    import repro.serve.engine as eng

    unpack = eng.unpack_tokens_host
    vocab = bench_tiny.QWEN_LIKE["vocab_size"]
    monkeypatch.setattr(eng, "unpack_tokens_host",
                        lambda planes: (np.asarray(unpack(planes)) + 1) % vocab)
    result, _ = _run(tmp_path, bench_tiny.QWEN_LIKE)
    assert not result["correct"], result["checks"]
