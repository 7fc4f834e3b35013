"""BENCHMARK.json against the contract's rules that a test can hold, and the
files every name in it stands for."""

import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert spec["paths"] == ["benchmark"] and 1 <= spec["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lines(spec):
    names = [e["name"] for group in ("configs", "workloads", "end_to_end", "per_layer")
             for e in spec[group]]
    for n in names + [w[k] for w in spec["workloads"] for k in ("config", "traffic")] + \
            [r for c in spec["configs"] for r in c["reduced"]]:
        assert NAME.match(n), n
    for group in ("configs", "workloads"):
        assert len({e["name"] for e in spec[group]}) == len(spec[group])
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for text in [e["why"] for e in spec["configs"] + spec["workloads"]] + \
            [c["source"] for c in spec["configs"]] + [m["layer"] for m in spec["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text, text


def test_entry_keys(spec):
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)


def test_every_name_has_its_file(spec):
    for c in spec["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
        assert os.path.exists(os.path.join(BENCH, "generators", cfg["generator"] + ".py"))
        assert any(w["config"] == c["name"] for w in spec["workloads"])
    for w in spec["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert os.path.exists(os.path.join(BENCH, mix["kind"] + ".py"))
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py")), m["name"]


def test_every_cell_reports_what_its_metrics_move(spec):
    cells = {w["name"] for w in spec["workloads"]}
    reports = {c: {m["name"] for m in spec["end_to_end"] if c in m.get("workloads", cells)}
               for c in cells}
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in spec["end_to_end"])
    for c in cells:
        assert len(reports[c]) >= 2
        assert any(c in m.get("workloads", cells) for m in spec["per_layer"])
    for m in spec["per_layer"]:
        for c in m.get("workloads", cells):
            assert c in cells and m["moves"] in reports[c], (m["name"], c)


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    from benchmark import cells

    assert cells.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        cells.load_peaks("TPU v9 imaginary")
