"""BENCHMARK.json against the contract the harness relies on."""

import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MIXED = {"smollm-serial.oasst-poisson", "smollm-serial.oasst-burst"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def reports(metric, cell):
    return cell in metric.get("workloads", [cell])


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51


def test_names_units_and_text(bench):
    names = []
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    for e in bench["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for p in bench["per_layer"]:
        assert "\n" not in p["layer"] and 1 <= len(p["layer"]) <= 200
    for x in bench["workloads"] + bench["configs"]:
        assert NAME.match(x["name"]), x["name"]
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
        names.append(x["name"])
    assert len(names) == len(set(names))


def test_every_cell_has_its_files(bench):
    confs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
    pairs = set()
    for w in bench["workloads"]:
        assert w["config"] in confs
        assert w["chips"] == 1
        assert os.path.exists(os.path.join(HERE, "traffic",
                                           f"{w['traffic']}.json"))
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    used = {w["config"] for w in bench["workloads"]}
    assert used == set(confs)


def test_every_per_layer_metric_has_a_reader_and_its_target(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    for p in bench["per_layer"]:
        assert os.path.exists(os.path.join(HERE, "metrics",
                                           f"{p['name']}.py"))
        target = e2e[p["moves"]]
        for cell in cells:
            if reports(p, cell):
                assert reports(target, cell), (p["name"], cell)


def test_every_cell_reports_enough(bench):
    for w in bench["workloads"]:
        cell = w["name"]
        e2e = [m["name"] for m in bench["end_to_end"] if reports(m, cell)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(reports(p, cell) for p in bench["per_layer"])


def test_all_requests_ttft_tail_only_in_mixed_cells(bench):
    m = {m["name"]: m for m in bench["end_to_end"]}["ttft_p90_s"]
    cells = {w["name"] for w in bench["workloads"]}
    assert set(m["workloads"]) == MIXED & cells
