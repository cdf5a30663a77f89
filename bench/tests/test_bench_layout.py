"""Every cell of BENCHMARK.json resolves to its files by name, and every
name and unit keeps to the benchmark's character rules."""
import json
import re
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import run  # noqa: E402

BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def test_top_level_keys_and_paths():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["bench"]
    assert BENCHMARK["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    for c in BENCHMARK["configs"]:
        assert c["file"].startswith("bench/") and (REPO / c["file"]).is_file()


def test_names_and_units_use_allowed_characters():
    names = ([c["name"] for c in BENCHMARK["configs"]] + CELLS
             + [m["name"] for m in BENCHMARK["end_to_end"]]
             + [m["name"] for m in BENCHMARK["per_layer"]]
             + [w["traffic"] for w in BENCHMARK["workloads"]]
             + [k for c in BENCHMARK["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    spec = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    wl, cfg = run.load_cell(cell)
    assert wl["config"] == spec["config"] == cfg["name"]
    assert (REPO / "bench" / "traffic" / f"{spec['traffic']}.json").is_file()
    assert (REPO / "bench" / "entries" / f"{wl['entry']}.py").is_file()
    assert wl["chips"] == spec["chips"]
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    assert cell in e2e[wl["end_to_end"]["name"]]["workloads"]
    assert wl["end_to_end"]["unit"] == e2e[wl["end_to_end"]["name"]]["unit"]
    per_layer = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in wl["per_layer"]:
        reader = run.load_module("metrics", name)
        assert reader.UNIT == per_layer[name]["unit"]
        assert cell in per_layer[name]["workloads"]
        assert wl["end_to_end"]["name"] == per_layer[name]["moves"]
    assert wl["limits"] and all(v > 0 for v in wl["limits"].values())


def test_per_layer_metrics_list_only_cells_that_read_them():
    for m in BENCHMARK["per_layer"]:
        for cell in m["workloads"]:
            wl, _ = run.load_cell(cell)
            assert m["name"] in wl["per_layer"], (m["name"], cell)
