"""BENCHMARK.json keeps to the rules its format sets, and every item
it names is a file the harness can find."""
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = BENCH["workloads"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and ".." not in p and not p.startswith("/")


def test_full_check_fits_its_time():
    """2 + 14 x 24 runs of run_seconds + 60, 2 x 90 s of compile per
    cell, and 1200 s spare fit into 43200 s."""
    n = 24
    total = ((2 + 14 * n) * (BENCH["run_seconds"] + 60) + n * 2 * 90
             + 1200)
    assert total <= 43200


@pytest.mark.parametrize("m", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_names_units_and_reader(m):
    assert NAME.match(m["name"])
    assert UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    if m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    else:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def _reported(cell_name):
    return {m["name"] for m in BENCH["end_to_end"]
            if "workloads" not in m or cell_name in m["workloads"]}


@pytest.mark.parametrize("m", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_per_layer_moves_a_metric_each_listed_cell_reports(m):
    names = {c["name"] for c in CELLS}
    assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    for cell in m.get("workloads", names):
        assert cell in names
        assert m["moves"] in _reported(cell)


@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_cell_items_exist_and_report_enough(cell):
    assert NAME.match(cell["name"])
    assert cell["chips"] in (1, 4)
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    bench = ROOT / "bench"
    assert (bench / "traffic" / f"{cell['traffic']}.json").is_file()
    assert (bench / "checks" / f"{cell['name']}.json").is_file()
    mix = json.loads((bench / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (bench / "drivers" / f"{mix['driver']}.py").is_file()
    reported = _reported(cell["name"])
    assert "setup_s" in reported and len(reported) >= 2
    assert any(cell["name"] in m.get("workloads", [cell["name"]])
               for m in BENCH["per_layer"])


def test_cells_unique_and_at_most_half_on_four_chips():
    pairs = [(c["config"], c["traffic"]) for c in CELLS]
    assert len(set(pairs)) == len(pairs)
    assert len({c["name"] for c in CELLS}) == len(CELLS)
    assert sum(c["chips"] == 4 for c in CELLS) <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("c", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_config_file_states_what_it_changed(c):
    path = ROOT / c["file"]
    conf = json.loads(path.read_text())
    assert path.parent == ROOT / "bench" / "configs"
    assert conf["name"] == c["name"] and conf["source"] == c["source"]
    assert sorted(conf["reduced"]) == sorted(c["reduced"])
    assert set(conf["published"]) == set(c["reduced"])
    for key in c["reduced"]:
        assert NAME.match(key)
        assert not key.endswith(("_dim", "_rank", "_size"))
    assert {c["config"] for c in CELLS} >= {c["name"]}
