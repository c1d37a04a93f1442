"""Find the items a cell names, by name: its configuration, traffic mix,
driver, correctness limits and metric readers.

    bench/configs/<config>.json     sizes of one model as it is run
    bench/configs/<reference>.py    plain reference of an architecture
    bench/configs/<converter>.py    its weights into the program's tree
    bench/traffic/<mix>.json        parameters of one traffic mix or job
    bench/drivers/<driver>.py       one entry point of the program
    bench/checks/<cell>.json        the limits `correct` is judged by
    bench/metrics/<metric>.py       one metric's reduction

A configuration file names its reference under ``reference.module`` and
its converter under ``program.convert``. What each exports:

- a reference (imports nothing of the program): ``sizes(conf)``, the
  sizes by its own names (a model that runs the program's attention
  kernels gives ``heads``, ``kv_heads`` and ``head_dim``);
  ``weight_key(seed)``; ``make_weights(conf, key_data)``, published-
  layout weights, traceable; ``as_served(w, planes, keep_bytes,
  rounding)``; ``Reference(conf)`` with ``.block`` and
  ``.served_gaps(w, prompt, served, pad_to=...)``; and the counts the
  metric readers use, of its sizes ``s``: ``matmul_params(s)``
  (parameters one token multiplies through: embedding out, head in,
  only the experts a token is routed to), ``plane_elements(s, leaves,
  min_elements)`` (elements of the leaves kept as byte planes: those
  listed in ``weight_planes`` with at least ``min_elements`` a layer)
  and ``attention_layers(s)`` (layers that run the paged and flash
  attention kernels);
- a converter (may import the program): ``program_config(conf, s)``,
  the program's ModelConfig, and ``to_program(conf, s, cfg, w)``, the
  reference's weights as the program's parameter tree, traceable. The
  driver passes both the reference's ``sizes(conf)``.

A reference or converter may build on another in its directory through
:func:`beside`. A later configuration of the dense decoder, or of
another architecture that the serving engine runs, is added as files
of these kinds and entries in BENCHMARK.json, and so is a later cell or
metric; the drivers, this harness and the readers stay as they are,
within two limits of the program that the serving driver cannot see
past:

- the warm-up prefills one prompt per page-size bucket of the mix's
  lengths, and the engine prefills expert and recurrent models at their
  exact length, so a mix for such a model uses prompt lengths that are
  page multiples, or it compiles inside the window; this holds until
  the engine tells its caller which lengths it compiles;
- the engine's expert dispatch drops tokens past its capacity (at
  least 8 per expert) and a plain reference drops none, so an expert
  cell keeps slots x top_k <= 8 and prompts short enough that no
  expert is offered more than its capacity, or waits for dropless
  routing in the program.

A new entry point of the program brings a driver of its own.
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


def beside(path: str, name: str):
    """The module ``<name>.py`` in the directory of the file ``path``:
    how a converter finds its reference, and a reference another it
    builds on, in whichever copy of ``bench/`` they were loaded from."""
    here = pathlib.Path(path).resolve().parent
    return module(here.name, name, bench_dir=here.parent)


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
