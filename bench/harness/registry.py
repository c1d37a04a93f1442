"""Find the items a cell names, by name: its configuration, traffic mix,
driver, correctness limits and metric readers.

    bench/configs/<config>.json     sizes of one model as it is run
    bench/traffic/<mix>.json        parameters of one traffic mix or job
    bench/drivers/<driver>.py       one entry point of the program
    bench/checks/<cell>.json        the limits `correct` is judged by
    bench/metrics/<metric>.py       one metric's reduction

A later cell or metric is added by adding files of these kinds and
entries in BENCHMARK.json; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def item(kind: str, name: str, *, bench_dir: pathlib.Path = BENCH) -> dict:
    """The JSON item ``bench/<kind>/<name>.json``."""
    path = bench_dir / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} item named {name!r} ({path})")
    return load_json(path)


def module(kind: str, name: str, *, bench_dir: pathlib.Path = BENCH):
    """The Python module ``bench/<kind>/<name>.py`` (names may hold dots,
    so it is loaded from its path, not imported by name)."""
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload named {name!r} in BENCHMARK.json "
                     f"(known: {[w['name'] for w in bench['workloads']]})")


def _listed(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(bench: dict, cell_name: str) -> list[dict]:
    """The end-to-end metrics this cell reports."""
    return [m for m in bench["end_to_end"] if _listed(m, cell_name)]


def per_layer(bench: dict, cell_name: str) -> list[dict]:
    """Per-layer metrics of this cell: those that list it, and those
    without a list whose end-to-end metric the cell reports."""
    reported = {m["name"] for m in end_to_end(bench, cell_name)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif m["moves"] in reported:
            out.append(m)
    return out
