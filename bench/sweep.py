"""Sweep of an open-loop cell's arrival rate, to find its knee once
(the highest rate with no growing backlog). One process: set-up once,
then one window per rate.

    python3 bench/sweep.py --workload <cell> --rates 1,2,3 --seconds 30 --seed 1

For each rate it prints one JSON line: requests due in the window, the
queue left at its end, the 50th and 95th percentile of time to first
token over the first and the second half of the window (a backlog that
grows shows as a second half far slower than the first), and output
tokens per second, and the slots busy and pages live at each decode tick.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.path.insert(0, str(ROOT / "bench"))
    import run as bench_run

    from bench.harness import registry
    from bench.harness.cell import make_context
    from bench.harness.spans import Spans, occupancy, percentile

    bench = registry.benchmark(ROOT)
    bench_run.check_device(registry.cell(bench, args.workload)["chips"])
    bench_run.enable_cache()
    ctx, driver = make_context(
        args.workload, seed=args.seed, seconds=args.seconds, trace=False,
        t_start=T_START, root=ROOT, bench=bench)
    cfg, engine = driver.setup(ctx)
    base = ctx.mix
    for rate in (float(r) for r in args.rates.split(",")):
        ctx.mix = dict(base, arrivals={"kind": "poisson", "rate_per_s": rate})
        ctx.spans = Spans()
        win = driver.measure(ctx, engine, cfg)
        due = sorted((f.due, f.times[0] - f.due if f.times else float("inf"))
                     for f in win.flights.values() if f.due is not None)
        due = due[: len(win.record["ttft_s"])]
        half = len(due) // 2
        first = [t for _, t in due[:half]] or [float("nan")]
        second = [t for _, t in due[half:]] or [float("nan")]
        print(json.dumps({
            "rate_per_s": rate, "due": len(due), "backlog_at_end": win.backlog,
            "ttft_ms_first_half": [percentile(first, 50) * 1e3,
                                   percentile(first, 95) * 1e3],
            "ttft_ms_second_half": [percentile(second, 50) * 1e3,
                                    percentile(second, 95) * 1e3],
            "tokens_per_s": win.record["tokens"] / win.record["window_s"],
            "admit_ms_p50": percentile(win.record["spans"].get("admit", [0]), 50) * 1e3,
            "tick_ms_p50": percentile(win.record["spans"].get("decode_tick", [0]), 50) * 1e3,
            "slots_busy_pages_live_p50_p95_max": occupancy(win.record),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
