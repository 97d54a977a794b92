"""BENCHMARK.json and the files it names agree, and keep to the limits on
names, units and sizes the benchmark is held to."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_lines():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in SPEC[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for e in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_files_agree(cell):
    w = json.loads((BENCH / "workloads" / f"{cell['name']}.json").read_text())
    assert {k: w[k] for k in ("config", "traffic", "chips", "why")} == {
        k: cell[k] for k in ("config", "traffic", "chips", "why")}
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    assert (BENCH / "drivers" / f"{traffic['driver']}.py").exists()
    assert all(v is not None for v in traffic["limits"].values())
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                        .read_text())
    assert (BENCH / "references" / f"{config['reference']}.py").exists()


def test_configs():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert f["reduced"] == c["reduced"]


def test_every_cell_reports_enough():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in SPEC["end_to_end"]}
    assert e2e["setup_s"] == cells
    for cell in cells:
        assert any(cell in ws for n, ws in e2e.items() if n != "setup_s")
        assert any(cell in m["workloads"] for m in SPEC["per_layer"])
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= e2e[m["moves"]]


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"]: m["bound"] for m in SPEC["end_to_end"]}[
        "setup_s"] == 0.25


def test_chip_time_fits_with_24_cells():
    r = SPEC["run_seconds"]
    runs = 2 + 14 * 24
    assert runs * (r + 60) + 24 * 2 * 90 + 1200 <= 43200
