"""Chip benchmark of this repository's serving and training paths.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on, in
one process: refuses anything but a TPU of a kind in the benchmark's
peaks table (and fewer chips than the cell asks for), keeps JAX's
persistent compilation cache in ``.jax_cache`` of the checkout, makes
the weights from the seed, warms up, measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints
one JSON line last. With ``--trace 0`` its metrics are the cell's
end-to-end metrics; with ``--trace 1``, its per-layer metrics, read
from a profiler trace of part of the window and the benchmark's spans.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def check_device(chips: int) -> None:
    """Exit non-zero unless JAX sees at least ``chips`` TPUs of a kind
    the peaks table knows. The TPU runtime's logs go inside the
    checkout, not to its default fixed path under /tmp."""
    if "TPU_LOG_DIR" not in os.environ:
        os.environ["TPU_LOG_DIR"] = str(ROOT / ".tpu_logs")
        os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    import jax

    from bench.harness.cell import peaks_for

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX found platform {dev.platform!r});"
                         " this benchmark measures the chip only")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    if peaks_for(dev.device_kind) is None:
        raise SystemExit(f"bench: no published peaks for device kind "
                         f"{dev.device_kind!r} in bench/harness/peaks.json")


def enable_cache() -> None:
    """The persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says); every program is
    cached, however quick its compile."""
    import jax

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import registry
    from bench.harness.cell import report_checks, result_line, run_cell

    bench = registry.benchmark(ROOT)
    cell = registry.cell(bench, args.workload)
    check_device(cell["chips"])
    enable_cache()
    result, outcome = run_cell(
        args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, root=ROOT, bench=bench,
    )
    from bench.harness.spans import occupancy, percentile

    rec = outcome.record
    tails = {k: [round(percentile(rec[k], q) * 1e3, 3) for q in (50, 80, 90, 95, 99)]
             for k in ("itl_s", "ttft_s") if rec.get(k)}
    print(f"setup_s {rec['setup_s']:.3f}, window_s {rec['window_s']:.3f}, "
          f"attempted {result['attempted']}, ms at p50/80/90/95/99 {tails}, "
          f"slots busy and pages live at p50/p95/max {occupancy(rec)}",
          file=sys.stderr)
    report_checks(result, outcome)
    print(result_line(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
