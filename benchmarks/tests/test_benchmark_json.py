"""BENCHMARK.json against the parts of the contract a test can hold it to."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TABLES = [ROOT / "BENCHMARK.json", ROOT / "benchmarks/tests/BENCHMARK.tiny.json"]


@pytest.fixture(params=TABLES, ids=lambda p: p.name)
def bench(request):
    return json.loads(request.param.read_text())


def cells_of(metric, bench):
    return metric.get("workloads") or [w["name"] for w in bench["workloads"]]


def test_keys_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = []
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)
        names.append(w["name"])
    assert len(names) == len(set(names))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_every_moves_points_at_an_end_to_end_metric_its_cells_report(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        assert set(cells_of(m, bench)) <= set(cells_of(e2e[m["moves"]], bench)), m
    for w in bench["workloads"]:
        assert sum(w["name"] in cells_of(m, bench) for m in bench["end_to_end"]) >= 2
        assert any(w["name"] in cells_of(m, bench) for m in bench["per_layer"])


def test_everything_a_cell_names_is_a_file_of_its_own(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for c in configs.values():
        assert (ROOT / c["file"]).is_file()
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    for w in bench["workloads"]:
        assert w["config"] in configs
        assert (ROOT / "benchmarks/traffic" / f"{w['traffic']}.json").is_file()
        mix = json.loads((ROOT / "benchmarks/traffic" / f"{w['traffic']}.json").read_text())
        load = json.loads((ROOT / "benchmarks/cells" / f"{w['name']}.json").read_text())
        assert load["clients" if mix["arrivals"] == "closed" else "rate"] > 0
        assert json.loads((ROOT / configs[w["config"]]["file"]).read_text())["bench"]["chips"] == w["chips"]
    for m in bench["per_layer"]:
        assert (ROOT / "benchmarks/layer_metrics" / f"{m['name']}.py").is_file()
    for m in bench["end_to_end"]:
        assert (ROOT / "benchmarks/end_to_end" / f"{m['name']}.py").is_file()
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word


def test_no_width_is_reduced(bench):
    width = re.compile(r"(_dim|_rank|_size)$|head|expert")
    for c in bench["configs"]:
        assert not any(width.search(k) for k in c["reduced"])
