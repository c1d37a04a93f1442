"""``bench/run.py`` refuses to run anywhere but on a TPU, and in a
checkout that holds only the benchmark's own files."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "qwen3-1.7b.decode-offline", "--seed", "2147483999",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_exits_nonzero_on_cpu():
    got = _run(ROOT)
    assert got.returncode != 0
    assert _no_result(got.stdout)
    assert "no TPU" in got.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    got = _run(tmp_path)
    assert got.returncode != 0
    assert _no_result(got.stdout)
