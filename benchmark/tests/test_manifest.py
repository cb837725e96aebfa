"""BENCHMARK.json against the contract's limits, and against the files it names."""

import re

import pytest

from benchlib import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def m():
    return manifest.load()


def all_metrics(m):
    return m["end_to_end"] + m["per_layer"]


def test_keys_and_command(m):
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmark"]
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert all(not w.startswith("/") and ".." not in w for w in m["command"])


def test_names_and_units_use_only_the_allowed_characters(m):
    names = [x["name"] for x in m["configs"] + m["workloads"] + all_metrics(m)]
    names += [w["config"] for w in m["workloads"]] + [w["traffic"] for w in m["workloads"]]
    names += [k for c in m["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for metric in all_metrics(m):
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for group in (m["configs"], m["workloads"], all_metrics(m)):
        seen = [x["name"] for x in group]
        assert len(seen) == len(set(seen))


def test_entries_have_just_the_contract_keys(m):
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert e["source"] in ("host_clock", "device_trace") and 0 < e["bound"] <= 0.1
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}


def test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer_metric(m):
    for w in m["workloads"]:
        e2e = [e["name"] for e in manifest.wanted(m, w["name"], trace=False)]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert manifest.wanted(m, w["name"], trace=True), w["name"]


def test_a_metrics_cells_report_the_metric_it_moves(m):
    e2e = {e["name"]: e for e in m["end_to_end"]}
    cells = [w["name"] for w in m["workloads"]]
    for p in m["per_layer"]:
        assert p["moves"] in e2e, p
        for cell in p.get("workloads", cells):
            assert cell in cells
            assert manifest.reports(e2e[p["moves"]], cell), (p["name"], cell)


def test_files_named_by_the_manifest_exist(m):
    specs = manifest.metric_files()
    readers = manifest.plugins("readers")
    for p in m["per_layer"]:
        spec = specs[p["name"]]
        assert spec["reader"] in readers, p["name"]
        for key in ("unit", "better", "source", "layer", "moves", "workloads"):
            assert spec[key] == p[key], (p["name"], key)
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    for w in m["workloads"]:
        config = manifest.config_of(m, w)
        manifest.traffic_of(w)
        assert (manifest.BENCH / "drivers" / f"{config['driver']}.py").exists()
        assert (manifest.BENCH / "reference" / f"{config['reference']}.py").exists()
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files)) and all(f.startswith("benchmark/") for f in files)


def test_roofline_and_mfu_names(m):
    for p in m["per_layer"]:
        base = p["name"].split(".")[0]
        if base.endswith("_roofline") or "mfu" in base:
            assert p["unit"] == "%" and p["better"] == "higher" and p["source"] == "device_trace"
