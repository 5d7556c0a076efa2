"""BENCHMARK.json against the contract's static rules, and against the data
files the harness finds by its names."""

import os
import re

import pytest

from benchmark import manifest as manifest_mod

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return manifest_mod.Manifest()


def test_keys_and_sizes(man):
    d = man.data
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(man.path) <= 64 * 1024
    assert 1 <= d["run_seconds"] <= 51
    assert 1 <= len(d["paths"]) <= 16 and all(PATH.match(p) for p in d["paths"])
    assert len(d["command"]) <= 32
    cells = len(d["workloads"])
    # a full check with the full 24 cells must fit: the limit later PRs inherit
    assert (2 + 14 * 24) * (d["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    four = sum(1 for w in d["workloads"] if w["chips"] == 4)
    assert four <= max(1, cells // 4)


def test_names_units_and_lines(man):
    d = man.data
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in d[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    metrics = d["end_to_end"] + d["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in d["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in d["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in d["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in d["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in d["workloads"])
        assert any(c["file"].startswith(p + "/") for p in d["paths"])
    assert len({c["file"] for c in d["configs"]}) == len(d["configs"])


def test_every_cell_reports_what_it_must(man):
    e2e = {m["name"] for m in man.data["end_to_end"]}
    assert "setup_s" in e2e
    for w in man.data["workloads"]:
        mine = {m["name"] for m in man.end_to_end(w["name"])}
        assert "setup_s" in mine and len(mine) >= 2, w["name"]
        layer = man.per_layer(w["name"])
        assert layer, w["name"]
        for m in layer:  # what it moves is reported where it is
            assert m["moves"] in mine, (w["name"], m["name"])


def test_files_found_by_name_and_agree(man):
    for w in man.data["workloads"]:
        cfg = man.config(w["config"])
        entry = next(c for c in man.data["configs"] if c["name"] == w["config"])
        assert cfg["source"] == entry["source"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        assert cfg["chips"] == w["chips"]
        for key in cfg["reduced"]:
            # never a width; a chip's share of the experts or of the
            # vocabulary is a count, which the contract lets a cut name
            assert key == "vocab_size" or not re.search(
                r"(_size|ffn|_dim|_rank|head|_per_tok|expand)", key), key
        traffic = man.traffic(w["traffic"])
        assert man.find("generators", traffic["kind"] + ".py")
        reports = set(traffic["reports"].values())
        assert reports == {m["name"] for m in man.end_to_end(w["name"])}
    for m in man.data["per_layer"]:
        spec = man.layer_metric(m["name"])
        for key in ("name", "layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert "workloads" not in spec   # BENCHMARK.json alone says which cells
        assert man.find("readers", spec["reader"] + ".py")


def test_file_names_use_name_characters(man):
    for base, _, files in os.walk(manifest_mod.HERE):
        if "__pycache__" in base or os.sep + "out" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), man.root)
            assert PATH.match(rel), rel
