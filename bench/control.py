"""Readings that set a cell's correctness limit, several seeds in one
process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 45 \
        [--plan '{"dtype": "bf16"}']

Without ``--plan`` it runs the program as the cell states it (the lower
reading of the limit: the largest number that sound runs give). With
``--plan '{"dtype": "bf16"}'`` it runs the control: the program's own
bf16 compute path, the nearest precision below the configuration's
fp32 (the upper reading: the smallest number the control gives). Each
seed is a whole run of the cell (set-up, window, check) and prints one
JSON line with its compared numbers. The benchmark's own runs
(``bench/run.py``) never run this.
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
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--plan", default="{}",
                    help="JSON overrides of the mix's plan")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.path.insert(0, str(ROOT / "bench"))
    import run as bench_run

    from bench.harness import registry
    from bench.harness.cell import run_cell
    from bench.harness.spans import occupancy, percentile

    bench = registry.benchmark(ROOT)
    bench_run.check_device(registry.cell(bench, args.workload)["chips"])
    bench_run.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        result, outcome = run_cell(
            args.workload, seed=seed, seconds=args.seconds, trace=False,
            t_start=t0, root=ROOT, bench=bench,
            plan_overrides=json.loads(args.plan),
        )
        tails = {
            k: [percentile(v, q) * 1e3 for q in (50, 90, 95, 99)]
            for k in ("itl_s", "ttft_s") if (v := outcome.record.get(k))
        }
        print(json.dumps({
            "seed": seed, "plan": json.loads(args.plan),
            "correct": result["correct"], "checks": result["checks"],
            "served_gaps": outcome.record.get("served_gaps"),
            "problems": outcome.problems, "metrics": result["metrics"],
            "tails_ms_p50_p90_p95_p99": tails,
            "memory_peak_bytes": result["device"]["memory_peak_bytes"],
            "slots_busy_pages_live_p50_p95_max": occupancy(outcome.record),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
