"""A configuration of another architecture than the dense decoder goes
into the benchmark as new files only: a sparse-expert decoder on the
program's existing expert mixer, its configuration, plain reference and
weight conversion written into a copy of ``bench/`` whose drivers,
harness and metric readers are the repository's, byte for byte, runs
through ``run_cell`` to ``correct``, and its ``mfu.decode`` counts the
experts a token is routed to, not all of them."""
import pathlib
import sys
import time
import warnings

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(HERE)]

import bench_tiny  # noqa: E402
from bench.harness import registry  # noqa: E402
from bench.harness.cell import run_cell  # noqa: E402

SEED = 2**31 + 23
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
# tiny-mixtral by hand, per layer: attention q 64*64 + k 64*32 + v 64*32
# + o 64*64 = 12288; 2 of the 8 experts at 3 * 64*128 = 24576 each;
# router 64*8 = 512. Two layers and the head 64*2048.
ROUTED = 2 * (12288 + 2 * 24576 + 512) + 64 * 2048
EVERY_EXPERT = 2 * (12288 + 8 * 24576 + 512) + 64 * 2048


def _files(d: pathlib.Path) -> dict:
    return {p.relative_to(d): p.read_bytes() for p in sorted(d.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture
def expert_cell(tmp_path):
    bench_dir, bench, name = bench_tiny.make(tmp_path, bench_tiny.MOE_LIKE,
                                             "tiny-moe")
    for f in bench_tiny.MOE_FILES:
        (bench_dir / "configs" / f).write_text(
            (bench_tiny.FIXTURES / f).read_text())
    return bench_dir, bench, name


def test_only_new_files(expert_cell):
    bench_dir, _, name = expert_cell
    repo, copy = _files(ROOT / "bench"), _files(bench_dir)
    assert {k: v for k, v in copy.items() if k in repo} == repo
    assert {str(k) for k in set(copy) - set(repo)} == {
        "configs/tiny-mixtral.json", "configs/moe_decoder.py",
        "configs/moe_program.py", "traffic/tiny-moe.json",
        f"checks/{name}.json"}


def test_expert_cell_is_correct_and_counts_routed_experts(expert_cell):
    bench_dir, bench, name = expert_cell
    from repro.serve.engine import CapacityWarning

    with warnings.catch_warnings():
        # the mix's sizes keep the program's dispatch from dropping tokens
        warnings.simplefilter("error", CapacityWarning)
        result, outcome = run_cell(
            name, seed=SEED, seconds=2.0, trace=False,
            t_start=time.perf_counter(), root=bench_dir.parent, bench=bench,
            bench_dir=bench_dir)
    assert result["correct"], (result["checks"], outcome.problems)
    assert outcome.record["served_gaps"]["served_tokens_compared"] >= 100
    rec = outcome.record
    assert rec["counts"]["matmul_params"] == ROUTED < EVERY_EXPERT
    mfu = registry.module("metrics", "mfu.decode", bench_dir=bench_dir).read(
        dict(rec, peaks=PEAKS, devices=1))
    ticks = rec["spans"]["decode_tick"]
    assert mfu["value"] == pytest.approx(
        100 * 2 * ROUTED * rec["decode_tokens"] / sum(ticks) / 100.0)
