"""The per-layer metrics read from the serving engine's own spans
(``bench/harness/engine_spans.py``): each gives a finite value in ms
with its fields after a tiny cell's run, agrees with the benchmark's own
span around the same calls, computes its percentile as by hand, and
gives nothing for a program without the engine's recorder."""
import json
import math
import pathlib
import sys
import time
import types

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(HERE)]

import bench_tiny  # noqa: E402
from bench.harness import registry  # noqa: E402
from bench.harness.cell import run_cell  # noqa: E402
from bench.harness.spans import percentile  # noqa: E402

SEED = 2**31 + 13
READERS = {
    "host_gap_ms_p50.decode": "serve.gap",
    "tick_wait_ms_p50.decode": "serve.tick/serve.wait",
    "admit_wait_ms_p50.prefill": "serve.admit/serve.wait",
    "host_gap_ms_p99.prefill": "serve.gap",
}
GAP_READERS = {"host_gap_ms_p50.decode", "host_gap_ms_p99.prefill"}
CHARGES = {"builds", "build_s", "gc_pauses", "gc_s", "gc_max_s"}


def _reader(name):
    return registry.module("metrics", name)


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("engine_spans")
    bench_dir, bench, name = bench_tiny.make(tmp, bench_tiny.QWEN_LIKE,
                                             "tiny-poisson")
    result, outcome = run_cell(
        name, seed=SEED, seconds=1.5, trace=False,
        t_start=time.perf_counter(), root=tmp, bench=bench,
        bench_dir=bench_dir)
    got = {m: _reader(m).read(outcome.record) for m in READERS}
    return result, outcome, got


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_gives_a_finite_value_in_ms_with_its_fields(tiny_run, metric):
    result, outcome, got = tiny_run
    assert result["correct"], result["checks"]
    m = got[metric]
    assert m is not None
    assert m["unit"] == "ms" and math.isfinite(m["value"]) and m["value"] > 0
    assert m["count"] > 0 and CHARGES <= set(m)
    # every program was built in warm-up, before the measured stream
    assert m["builds"] == 0, m
    if metric in GAP_READERS:
        shares = {k: v for k, v in m.items() if k.startswith("gap_share.")}
        assert {"gap_share.none", "gap_share.serve.tick/serve.stage",
                "gap_share.serve.tick/serve.emit"} <= set(shares)
        assert sum(shares.values()) == pytest.approx(1.0)
        assert m["value"] <= m["max_ms"] and math.isfinite(m["mean_ms"])
    assert "trace_idle_ms_per_tick" not in m  # no trace in this run


def test_engine_tick_agrees_with_the_benchmark_span(tiny_run):
    from repro import obs

    _, outcome, _ = tiny_run
    bench_ms = percentile(outcome.record["spans"]["decode_tick"], 50) * 1e3
    engine_ms = percentile(obs.find("serve").durations("serve.tick"), 50) * 1e3
    assert len(obs.find("serve").durations("serve.tick")) == len(
        outcome.record["spans"]["decode_tick"])
    assert abs(engine_ms - bench_ms) < 0.5, (engine_ms, bench_ms)
    waits = obs.find("serve").durations("serve.tick/serve.wait")
    assert percentile(waits, 50) * 1e3 < bench_ms


def test_percentiles_of_a_hand_filled_recorder():
    from repro import obs

    rec = obs.recorder("serve")
    rec.clear()
    ms = [4.0, 1.0, 3.0, 2.0, 10.0]
    t = 1_000_000_000
    for x in ms:
        rec.add("serve.gap", t, t + int(x * 1e6))
        rec.add("serve.tick/serve.wait", t, t + int(2 * x * 1e6))
        rec.add("serve.admit/serve.wait", t, t + int(3 * x * 1e6))
        t += 50_000_000
    red = types.SimpleNamespace(window_s=1.0, busy_s=0.9)
    traced = {"ticks": [[1]] * 20, "admits": []}
    # sorted 1, 2, 3, 4, 10: median 3; p99 at position 3.96 -> 4 + 0.96 x 6
    p50 = _reader("host_gap_ms_p50.decode").read(
        {"trace": red, "traced_calls": traced})
    assert p50["value"] == pytest.approx(3.0)
    assert p50["mean_ms"] == pytest.approx(4.0)
    assert p50["max_ms"] == pytest.approx(10.0)
    assert p50["count"] == 5
    assert p50["trace_idle_ms_per_tick"] == pytest.approx(5.0)
    p99 = _reader("host_gap_ms_p99.prefill").read({})
    assert p99["value"] == pytest.approx(9.76)
    assert _reader("tick_wait_ms_p50.decode").read({})["value"] == (
        pytest.approx(6.0))
    assert _reader("admit_wait_ms_p50.prefill").read({})["value"] == (
        pytest.approx(9.0))
    rec.clear()
    assert all(_reader(m).read({}) is None for m in READERS)


def test_no_value_from_a_program_without_the_recorder(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import fails
    for m in READERS:
        assert _reader(m).read({}) is None


def test_benchmark_lists_the_four_span_metrics():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cells = {"decode": "qwen3-1.7b.decode-offline",
             "prefill": "chatglm3-6b.longprompt-poisson"}
    for name in READERS:
        m = by_name[name]
        assert m["source"] == "program_span" and m["unit"] == "ms"
        assert m["better"] == "lower"
        assert cells[name.rsplit(".", 1)[1]] in m["workloads"]
