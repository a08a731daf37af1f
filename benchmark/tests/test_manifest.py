"""BENCHMARK.json and every file it names, held to the driver's rules."""

import json
import os
import re

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 \
        and "\n" not in s and "\t" not in s


def test_top_level(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark"]
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    cells = len(manifest["workloads"])
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) + 24 * 180 \
        + 1200 <= 43200, "run_seconds too long for 24 cells"
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert 1 <= cells <= 24


def test_configs(manifest):
    names = [c["name"] for c in manifest["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmark/")
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"]
        assert set(body["reduced"]) == set(c["reduced"])
        for key in ("source", "guarantees", "assumed", "video", "graph",
                    "client"):
            assert key in body
        assert os.path.exists(os.path.join(
            BENCH, "reference", body["graph"]["op"] + ".py"))


def test_workloads(manifest):
    names = [w["name"] for w in manifest["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in manifest["configs"]}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs
        assert w["chips"] in (1, 4)
        assert line(w["why"])
        with open(os.path.join(BENCH, "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert set(traffic) - {"per_chip"} == {
            "tables", "resident_tables", "fill_corpus", "fill_bulk_tables",
            "streams", "shapes", "check"}
        assert all(s["sampler"] in ("All", "Range", "StridedRange", "Stride",
                                    "Gather") for s in traffic["shapes"])
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(names) // 4)


def test_metrics(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    assert 1 <= len(e2e) <= 16 and 1 <= len(manifest["per_layer"]) <= 128

    def reported_in(m):
        return set(m.get("workloads", cells))

    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert reported_in(m) <= cells
    for cell in cells:
        mine = [m for m in manifest["end_to_end"] if cell in reported_in(m)]
        assert {"setup_s"} < {m["name"] for m in mine}
        assert any(cell in reported_in(m) for m in manifest["per_layer"])
    layers = set()
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert NAME.match(m["layer"]), "a layer is one token"
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        # each of its cells reports the end-to-end metric it moves
        assert reported_in(m) <= reported_in(e2e[m["moves"]])
        if "roofline" in m["name"] or "mfu" in re.split(r"[._]", m["name"]):
            assert m["unit"] == "%"
        layers.add(m["layer"])
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            mdef = json.load(f)
        for key in ("name", "layer", "unit", "better", "source", "moves"):
            assert mdef[key] == m[key], (m["name"], key)
        assert os.path.exists(os.path.join(
            BENCH, "reducers", mdef["reducer"] + ".py"))
    assert layers == {"client", "decode", "staging", "evaluate", "kernels",
                      "device"}


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    ignored = ("__pycache__", ".pytest_cache")
    for base, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d not in ignored]
        for f in files:
            assert ok.match(os.path.relpath(os.path.join(base, f), ROOT))
