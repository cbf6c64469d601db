"""BENCHMARK.json and the files it names keep to the benchmark's format."""

import json
import re
import sys
from pathlib import Path

import pytest

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(CHECKOUT))

from bench import harness  # noqa: E402

SPEC = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LAYERS = {"scheduler", "model step", "compiler", "kernels", "device"}


def test_top_level_keys_and_budget():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1].startswith("bench/")
    s = SPEC["run_seconds"]
    # a full check of 24 cells must fit its 43200 s
    assert 2 + 14 * 24 <= 43200 and (2 + 14 * 24) * (s + 60) \
        + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_and_units(kind):
    names = [e["name"] for e in SPEC[kind]]
    assert len(names) == len(set(names))
    for e in SPEC[kind]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]


def test_configs_hold_what_is_run():
    for c in SPEC["configs"]:
        f = json.loads((CHECKOUT / c["file"]).read_text())
        assert c["file"].startswith("bench/configs/")
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size")), key
            assert f["published"][key] != f["model"][key]
        assert f["model"]["torch_dtype"] == "bfloat16"


def test_metrics_name_their_cells_and_layers():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    setup = next(m for m in SPEC["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["per_layer"]:
        assert m["layer"] in LAYERS and m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").exists()
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_reports_enough():
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.limits["widest_gap"] > 0
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        # each per-layer metric moves an end-to-end metric of the cell
        assert {m["moves"] for m in cell.per_layer} <= names
        assert w["chips"] == 1 and len(w["why"]) <= 200
