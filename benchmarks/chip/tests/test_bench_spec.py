"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
finds its file."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
CHIP = ROOT / "benchmarks" / "chip"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def _applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(SPEC["command"]) <= 32
    assert all(_text(w) for w in SPEC["command"])
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    for w in SPEC["command"][1:]:
        assert any(w.startswith(p + "/") for p in SPEC["paths"])
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_keys():
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _text(c["source"])
        assert _text(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        names.append(("config", c["name"]))
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and _text(w["why"])
        names.append(("cell", w["name"]))
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _text(m["layer"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(("metric", m["name"]))
    assert len(names) == len(set(names))
    metric_names = [n for k, n in names if k == "metric"]
    assert len(metric_names) == len(set(metric_names))


def test_pairs_configs_and_chips():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_every_cell_reports_what_its_metrics_move():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in SPEC["workloads"]:
        cell = w["name"]
        mine = [m for m in SPEC["end_to_end"] if _applies(m, cell)]
        assert len(mine) >= 2
        layers = [m for m in SPEC["per_layer"] if _applies(m, cell)]
        assert layers
        for m in layers:
            assert _applies(e2e[m["moves"]], cell), (m["name"], cell)


def test_files_found_by_name():
    for c in SPEC["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        assert (CHIP / "references" / f"{conf['reference']}.py").exists()
        assert conf["correct"]["max_logit_gap_sd"]["limit"] is not None
    for w in SPEC["workloads"]:
        assert (CHIP / "traffic" / f"{w['traffic']}.json").exists()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (CHIP / "metrics" / f"{m['name']}.py").exists()


@pytest.mark.parametrize("m", [m for m in SPEC["per_layer"]
                               if m["unit"] == "%"], ids=lambda m: m["name"])
def test_shares_are_named_by_kind(m):
    if m["source"] == "device_trace" and "gather" in m["name"]:
        assert m["name"].endswith("_roofline")
